"""Roofline terms from a compiled dry-run artifact (EXPERIMENTS.md §Roofline).

    compute term    = HLO_FLOPs  / (chips x 197e12)
    memory term     = HLO_bytes  / (chips x 819e9)
    collective term = coll_bytes / (chips x 50e9)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (whole-
program, all chips).  Collective bytes are NOT in cost_analysis: we parse
the post-SPMD optimized HLO (``compiled.as_text()``) and sum operand
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.  Byte conventions (ring algorithms on a per-chip
basis): all-reduce counts 2x its operand (reduce-scatter + all-gather
phases), all-gather counts its *result*, reduce-scatter and all-to-all
their operand, collective-permute its operand.  Collectives whose
replica_groups span pods are charged to DCN (reported separately).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.perfmodel.hw import TPU_V5E, HardwareSpec

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)")
_WHILE_RE = re.compile(
    r"while\([^)]*\),\s*condition=%([\w\.\-]+),\s*body=%([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count\\?":\{\\?"n\\?":\\?"(\d+)')
_CALL_RE = re.compile(
    r"(?:call|conditional)\([^)]*\).*?to_apply=%([\w\.\-]+)")


def _computations(hlo_text: str):
    """Split the module into {computation_name: body_text}.

    A computation definition is a top-level (unindented) line starting
    with '%name (' or 'ENTRY %name (' and ending with '{'; its body runs
    to the matching top-level '}'."""
    comps = {}
    name, buf = None, []
    for line in hlo_text.splitlines():
        starts_def = (not line.startswith(" ") and
                      line.rstrip().endswith("{") and "->" in line and
                      (line.startswith("%") or line.startswith("ENTRY")))
        if starts_def:
            m = _COMP_RE.match(line)
            if name is not None:
                comps[name] = "\n".join(buf)
            name, buf = (m.group(1) if m else None), []
        elif line.strip() == "}" and not line.startswith("  "):
            if name is not None:
                comps[name] = "\n".join(buf)
            name, buf = None, []
        elif name is not None:
            buf.append(line)
    if name is not None:
        comps[name] = "\n".join(buf)
    return comps


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum collective payload bytes by op kind from optimized HLO text.

    LOOP-AWARE: a collective inside a ``while`` body executes once per
    iteration; bodies are weighted by XLA's known_trip_count annotation
    (nested loops multiply).  Without this, scan-over-layers /
    grad-accumulation programs under-count collectives by 10-100x.
    """
    comps = _computations(hlo_text)
    # body -> trip count, and caller edges (which computation contains
    # the while/call that invokes each body)
    multiplier: Dict[str, float] = {}
    edges: Dict[str, list] = {}
    for cname, body in comps.items():
        for line in body.splitlines():
            wm = _WHILE_RE.search(line)
            if wm:
                tm = _TRIP_RE.search(line)
                trips = float(tm.group(1)) if tm else 1.0
                for callee in (wm.group(1), wm.group(2)):
                    edges.setdefault(cname, []).append((callee, trips))
            else:
                cm = _CALL_RE.search(line)
                if cm:
                    edges.setdefault(cname, []).append((cm.group(1), 1.0))

    # propagate multipliers from every root (computations nobody calls)
    called = {callee for lst in edges.values() for callee, _ in lst}
    roots = [c for c in comps if c not in called]
    mult: Dict[str, float] = {c: 0.0 for c in comps}
    stack = [(r, 1.0) for r in roots]
    seen_depth = 0
    while stack and seen_depth < 1_000_000:
        seen_depth += 1
        cname, m = stack.pop()
        if m <= mult.get(cname, 0.0) and mult.get(cname, 0.0) > 0:
            continue
        mult[cname] = max(mult.get(cname, 0.0), m)
        for callee, trips in edges.get(cname, []):
            stack.append((callee, m * trips))

    out: Dict[str, float] = {}
    for cname, body in comps.items():
        m = max(mult.get(cname, 1.0), 1.0)
        for line in body.splitlines():
            cm = _COLL_RE.search(line)
            if not cm:
                continue
            type_str, op = cm.group(1), cm.group(2)
            nbytes = _shape_bytes(type_str)
            if op == "all-reduce":
                nbytes *= 2                  # RS + AG phases of a ring AR
            out[op] = out.get(op, 0.0) + nbytes * m
    return out


def collective_report(hlo_text: str, top: int = 12):
    """Itemized (bytes x trips) collective list — the §Perf profiling
    view: which collective, in which loop, costs what."""
    comps = _computations(hlo_text)
    multiplier: Dict[str, float] = {}
    edges: Dict[str, list] = {}
    for cname, body in comps.items():
        for line in body.splitlines():
            wm = _WHILE_RE.search(line)
            if wm:
                tm = _TRIP_RE.search(line)
                trips = float(tm.group(1)) if tm else 1.0
                for callee in (wm.group(1), wm.group(2)):
                    edges.setdefault(cname, []).append((callee, trips))
            else:
                cm = _CALL_RE.search(line)
                if cm:
                    edges.setdefault(cname, []).append((cm.group(1), 1.0))
    called = {callee for lst in edges.values() for callee, _ in lst}
    mult: Dict[str, float] = {}
    stack = [(c, 1.0) for c in comps if c not in called]
    n = 0
    while stack and n < 1_000_000:
        n += 1
        cname, m = stack.pop()
        if m <= mult.get(cname, 0.0):
            continue
        mult[cname] = m
        for callee, trips in edges.get(cname, []):
            stack.append((callee, m * trips))
    items = []
    for cname, body in comps.items():
        m = max(mult.get(cname, 1.0), 1.0)
        for line in body.splitlines():
            cm = _COLL_RE.search(line)
            if not cm:
                continue
            b = _shape_bytes(cm.group(1))
            if cm.group(2) == "all-reduce":
                b *= 2
            items.append((b * m, cm.group(2), cm.group(1)[:50], m, cname[:40]))
    items.sort(key=lambda t: -t[0])
    return items[:top]


@dataclasses.dataclass
class RooflineTerms:
    """cost_analysis() on this backend reports PER-DEVICE flops/bytes
    (verified by a controlled sharded-matmul probe); fields below store
    per-device values, terms() therefore divides by per-chip peaks only.
    Collective bytes from the SPMD module are likewise per-chip."""
    arch: str
    shape: str
    mesh: str
    chips: int
    flops: float                        # per device
    hbm_bytes: float                    # per device
    coll_bytes: float                   # per device
    coll_by_op: Dict[str, float]
    model_flops: float                  # whole-model (all chips)
    peak_mem_per_chip: float = 0.0

    def terms(self, hw: HardwareSpec = TPU_V5E):
        t_compute = self.flops / hw.peak_flops
        t_mem = self.hbm_bytes / hw.hbm_bw
        t_coll = self.coll_bytes / hw.ici_bw
        return t_compute, t_mem, t_coll

    @property
    def total_flops(self) -> float:
        return self.flops * self.chips

    @property
    def bottleneck(self) -> str:
        tc, tm, tl = self.terms()
        return ["compute", "memory", "collective"][
            [tc, tm, tl].index(max(tc, tm, tl))]

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / compiled HLO FLOPs — remat/padding/redundancy."""
        return self.model_flops / self.total_flops if self.flops else 0.0

    def roofline_fraction(self, hw: HardwareSpec = TPU_V5E) -> float:
        """MFU-style: time the model's useful FLOPs would take at peak /
        the modeled step time.  For memory/collective-bound steps this is
        honestly low — §Perf tracks the dominant term separately."""
        tc, tm, tl = self.terms(hw)
        t_step = max(tc, tm) + tl
        t_bound = self.model_flops / (self.chips * hw.peak_flops)
        return min(1.0, t_bound / max(t_step, 1e-12))


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS baseline: 6*N_active*D trained tokens, or 2*N_active*D
    inferred tokens (+ attention context reads are not counted — this is
    the deliberately-conservative 'useful work' yardstick)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # one token per sequence


def analyze(compiled, cfg, shape, mesh_name: str, chips: int,
            arch: Optional[str] = None) -> RooflineTerms:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    mem = 0.0
    try:
        ma = compiled.memory_analysis()
        mem = float(getattr(ma, "temp_size_in_bytes", 0) +
                    getattr(ma, "argument_size_in_bytes", 0) +
                    getattr(ma, "output_size_in_bytes", 0) -
                    getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass
    return RooflineTerms(
        arch=arch or cfg.name, shape=shape.name, mesh=mesh_name,
        chips=chips, flops=flops, hbm_bytes=hbm,
        coll_bytes=sum(coll.values()), coll_by_op=coll,
        model_flops=model_flops_for(cfg, shape),
        peak_mem_per_chip=mem / max(chips, 1))
