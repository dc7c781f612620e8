"""The benchmark's run: set up the served stack, drive a cell's traffic
through it, reduce what was recorded to metrics, and check the answers.

Found by name, so that a later cell adds files and edits none:

* ``BENCHMARK.json``: the cells, and the metrics each reports;
* ``<bench>/configs/<config>.json``: the model (its published keys), the
  block the reference computes, the ``ServeConfig``, the router and the
  limits of the check;
* ``<bench>/traffic/<mix>.json``: arrivals and lengths (``traffic.py``);
* ``<bench>/metrics/<metric>.py``: ``reduce(run) -> float | None``.

``<bench>`` is this directory, or a directory given beside it whose
files are looked for first.

The served stack is the one users reach: ``POST /v1/generate`` on
``GatewayHTTPServer`` over a ``Gateway`` on the real-time clock, one
``rapid`` worker per chip, each with a ``DeviceExecutor`` whose weights
are replaced by ``bench/weights.py``'s from the run's seed.  The load
generator is a child process (``client.py``) that never imports JAX.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
from typing import Dict, List, Optional, Sequence

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class SetupError(RuntimeError):
    """The run cannot be made here (no chip, unknown device, bad files)."""


# -- files found by name -------------------------------------------------------

@dataclasses.dataclass
class Files:
    """Where a run finds its files: ``extra`` first, then this package."""
    benchmark: pathlib.Path = ROOT / "BENCHMARK.json"
    extra: Optional[pathlib.Path] = None

    def dirs(self) -> List[pathlib.Path]:
        return ([self.extra] if self.extra else []) + [HERE]

    def find(self, kind: str, name: str, suffix: str) -> pathlib.Path:
        for d in self.dirs():
            p = d / kind / f"{name}{suffix}"
            if p.is_file():
                return p
        raise SetupError(f"no {kind} file {name}{suffix} under "
                         f"{[str(d) for d in self.dirs()]}")

    def json(self, kind: str, name: str) -> dict:
        return json.loads(self.find(kind, name, ".json").read_text())

    def module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def cell_of(files: Files, workload: str):
    """(cell, metrics of this cell by mode)."""
    bench = json.loads(files.benchmark.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    metrics = {"e2e": [m for m in bench["end_to_end"] if mine(m)],
               "layer": [m for m in bench["per_layer"] if mine(m)]}
    return cell, metrics


def configure_jax() -> None:
    """The persistent compile cache at its one fixed path inside the
    checkout (the program takes it from ``JAX_COMPILATION_CACHE_DIR``),
    every program cached however quick its compile."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peaks_of(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# -- what the run records --------------------------------------------------------

@dataclasses.dataclass
class Step:
    """One ``execute`` call of one replica."""
    idx: int
    chip: int
    t0: float
    t1: float
    prefill: List[tuple]          # (rid, valid tokens) per prefill
    decode_rids: List[int]
    decode_lens: List[int]        # tokens each new token attends
    d_prefill: Optional[float]    # LaunchOutcome.duration_s
    d_decode: Optional[float]


@dataclasses.dataclass
class Run:
    """What a metric's ``reduce`` reads."""
    cell: dict
    model: dict
    dims: object                  # bench.reference.Dims
    peaks: dict
    chips: int
    seconds: float
    window: tuple                 # (t0, t1) on time.monotonic()
    requests: List[dict]          # the client's records
    steps: List[Step]
    setup_s: float
    replica_of: Dict[int, int]    # rid -> replica
    trace: object = None          # bench.trace.Trace (traced runs)
    # where host-side per-layer metrics are read: the window, or in a
    # traced run the part of it before the profiler started
    host_window: Optional[tuple] = None
    closed_loop: bool = False

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def due_in_window(self) -> List[dict]:
        return [r for r in self.requests if self.in_window(r["due"])]

    def host_span(self) -> tuple:
        return self.host_window or self.window

    def due_in_host_span(self) -> List[dict]:
        lo, hi = self.host_span()
        return [r for r in self.requests if lo <= r["due"] < hi]

    def steps_in_host_span(self) -> List[Step]:
        lo, hi = self.host_span()
        return [s for s in self.steps if s.t0 >= lo and s.t1 <= hi]


def percentile(vals: Sequence[float], q: float) -> Optional[float]:
    """numpy's default (linear) percentile."""
    a = sorted(vals)
    if not a:
        return None
    vi = (q / 100.0) * (len(a) - 1)
    lo = int(vi)
    hi = min(lo + 1, len(a) - 1)
    return a[lo] + (a[hi] - a[lo]) * (vi - lo)


# -- the served stack ------------------------------------------------------------

def check_registry(cfg, dims) -> None:
    """The program's registry entry must be the configuration file's."""
    have = {"d_model": cfg.d_model, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "num_layers": cfg.num_layers, "ffn_glu": cfg.ffn_glu,
            "qkv_bias": cfg.qkv_bias, "tie": cfg.tie_embeddings,
            "norm_eps": cfg.norm_eps, "rope_theta": float(cfg.rope_theta),
            "dtype": cfg.dtype, "act": cfg.act}
    want = {"d_model": dims.d, "num_heads": dims.heads,
            "num_kv_heads": dims.kv_heads, "head_dim": dims.head_dim,
            "d_ff": dims.ffn, "vocab_size": dims.vocab,
            "num_layers": dims.layers, "ffn_glu": dims.glu,
            "qkv_bias": dims.qkv_bias, "tie": dims.tied,
            "norm_eps": dims.eps, "rope_theta": dims.theta,
            "dtype": dims.dtype,
            "act": "silu" if dims.glu else "gelu"}
    diff = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if diff or cfg.sliding_window:
        raise SetupError(f"{cfg.name}: the program's config differs from "
                         f"the configuration file: {diff}")


def model_config(model: dict):
    from repro.config import get_config, get_reduced_config
    arch = model["arch"]
    return get_reduced_config(arch) if model.get("registry") == "reduced" \
        else get_config(arch)


def build_executors(cfg, serve, devices, seed: int, interpret: bool):
    from repro.core import DeviceExecutor
    make = functools.partial(DeviceExecutor, cfg, serve,
                             seed=seed % (2 ** 31 - 1), interpret=interpret)
    if len(devices) == 1:
        return [make(devices[0])]
    with concurrent.futures.ThreadPoolExecutor(len(devices)) as pool:
        return list(pool.map(make, devices))


def install_weights(ex, seed: int, dims) -> None:
    """Replace the executor's weights by the benchmark's, from ``seed``."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from bench import reference, weights
    names, leaves, _ = weights.paths(ex.params)
    want = reference.layout(dims)
    have = {n: (l.shape[1:] if n.startswith("layers/") else l.shape)
            for n, l in zip(names, leaves)}
    if have != {k: tuple(v) for k, v in want.items()}:
        raise SetupError(f"served weights {have} are not the layout the "
                         f"reference computes {want}")
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        ex.params)
    for leaf in leaves:
        leaf.delete()
    ex.params = None
    ex.params = weights.make_params(like, seed,
                                    SingleDeviceSharding(ex.device))


def instrument(ex, chip: int, steps: List[Step], tracing: bool) -> None:
    """Record every ``execute`` of ``ex`` (and, traced, annotate it)."""
    import jax
    inner = ex.execute

    def execute(plan, view):
        pre = [(r.rid, r.prompt_len + r.tokens_generated)
               for r in plan.prefill.batch] if plan.prefill else []
        batch = (list(view.running) + list(plan.decode.joins)) \
            if plan.decode else []
        idx = len(steps)
        t0 = time.monotonic()
        if tracing:
            with jax.profiler.TraceAnnotation("bench.execute", step=idx,
                                              chip=chip):
                out = inner(plan, view)
        else:
            out = inner(plan, view)
        t1 = time.monotonic()
        steps.append(Step(
            idx, chip, t0, t1, pre, [r.rid for r in batch],
            [r.prompt_len + r.tokens_generated for r in batch],
            out.prefill.duration_s if out.prefill else None,
            out.decode.duration_s if out.decode else None))
        return out

    ex.execute = execute


async def drive(gw, plan: dict, on_window=None) -> dict:
    """Serve ``plan`` through the gateway's HTTP server to the client."""
    from repro.serving import GatewayHTTPServer
    server = GatewayHTTPServer(gw, "127.0.0.1", 0)
    await server.start()
    try:
        plan = dict(plan, port=server._server.sockets[0].getsockname()[1])
        proc = await asyncio.create_subprocess_exec(
            sys.executable, str(HERE / "client.py"),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            limit=1 << 28)
        proc.stdin.write(json.dumps(plan).encode())
        proc.stdin.close()
        result = None
        try:
            while True:
                line = await proc.stdout.readline()
                if not line:
                    break
                if line.startswith(b"window ") and on_window:
                    t0, t1 = map(float, line.split()[1:3])
                    on_window(t0, t1)
                elif line.startswith(b"{"):
                    result = json.loads(line)
        finally:
            if proc.returncode is None:
                try:
                    await asyncio.wait_for(proc.wait(), 30)
                except asyncio.TimeoutError:
                    proc.kill()
                    await proc.wait()
        if proc.returncode != 0 or result is None:
            raise RuntimeError(f"load generator exited {proc.returncode}")
        return result
    finally:
        await server.close()


class Tracer:
    """Profiles the last ``span_s`` seconds of the window.  Stopping the
    profiler blocks the host for seconds while it writes the trace, so
    the slice ends with the window; ``t_start`` is when it began."""

    def __init__(self, out_dir: pathlib.Path, span_s: float, log):
        self.dir = out_dir
        self.span_s = span_s
        self.log = log
        self.ann = None
        self.active = False
        self.t_start = None

    def arm(self, t0: float, t1: float) -> None:
        loop = asyncio.get_running_loop()
        span = min(self.span_s, (t1 - t0) / 2)
        # loop.time() is time.monotonic() on the default event loop
        loop.call_at(t1 - span, self.start)
        loop.call_at(t1, self.stop)

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.t_start = time.monotonic()
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.active = True
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.log(f"profiler started in "
                 f"{time.monotonic() - self.t_start:.3f} s")

    def stop(self) -> None:
        import jax
        if self.active:
            t = time.monotonic()
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False
            self.log(f"profiler stopped in {time.monotonic() - t:.3f} s")

    def path(self) -> Optional[str]:
        found = sorted(self.dir.rglob("*.xplane.pb"))
        return str(found[-1]) if found else None


# -- the run -----------------------------------------------------------------------

@dataclasses.dataclass
class Stack:
    """The served stack of one cell, set up and warm."""
    cell: dict
    model: dict
    mix: dict
    metrics: dict
    dims: object
    peaks: dict
    devices: list
    executors: list
    gateway: object
    steps: List[Step]


def build_stack(workload: str, seed: int, trace: bool, *, files: Files,
                devices=None, interpret: bool = False) -> Stack:
    """Load the cell's files, check the chips, build one warm
    ``DeviceExecutor`` per chip with the benchmark's weights, and the
    gateway over them.  ``devices`` defaults to JAX's, which must be TPUs
    enough for the cell; tests pass CPU devices with ``interpret=True``."""
    import jax

    from bench import reference
    cell, metrics = cell_of(files, workload)
    model = files.json("configs", cell["config"])
    mix = files.json("traffic", cell["traffic"])
    chips = cell["chips"]
    if devices is None:
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise SetupError(f"no TPU: JAX's first device is a "
                             f"{devices[0].platform!r} device")
    if len(devices) < chips:
        raise SetupError(f"{workload} needs {chips} chips, JAX found "
                         f"{len(devices)}")
    devices = list(devices)[:chips]
    peaks = {} if interpret else peaks_of(devices[0].device_kind)

    from repro.config import ServeConfig
    from repro.serving import Gateway, RealTimeClock
    dims = reference.dims_of(model)
    cfg = model_config(model)
    check_registry(cfg, dims)
    serve = ServeConfig(mode="rapid", chips=1, **model["serve"])
    executors = build_executors(cfg, serve, devices, seed, interpret)
    steps: List[Step] = []
    for chip, ex in enumerate(executors):
        install_weights(ex, seed, dims)
        instrument(ex, chip, steps, trace)
    jax.block_until_ready([ex.params for ex in executors])
    gw = Gateway(cfg, serve, modes=(),
                 router=model.get("router", "least_loaded"),
                 clock=RealTimeClock())
    for ex in executors:
        gw.add_worker("rapid", executor=ex)
    if trace:
        inner_submit = gw.submit

        def submit(r, **kw):
            with jax.profiler.TraceAnnotation("bench.submit"):
                return inner_submit(r, **kw)
        gw.submit = submit
    return Stack(cell, model, mix, metrics, dims, peaks, devices, executors,
                 gw, steps)


def serve_plan(stack: Stack, plan: dict, tracer=None) -> dict:
    """Drive one plan through the stack; the client's result."""
    def on_window(t0, t1):
        if tracer:
            tracer.arm(t0, t1)

    try:
        return asyncio.run(drive(stack.gateway, dict(plan, drain_s=60.0),
                                 on_window))
    finally:
        if tracer:
            tracer.stop()


def replicas(stack: Stack) -> Dict[int, int]:
    """rid -> replica: the gateway's assignments still held (a cancelled
    request leaves them), then the replica whose executor prefilled it."""
    out = {r.rid: w.wid for w in stack.gateway.registry.workers.values()
           for r in w.replica.assigned}
    out.update((rid, s.chip) for s in stack.steps for rid, _ in s.prefill)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             files: Files = None, devices=None, interpret: bool = False,
             t_start: Optional[float] = None, control: bool = False,
             log=None) -> dict:
    """One run of one cell; returns the result line's object.
    ``control`` also reads the fp8 control on the same sample and
    reports its verdict under ``control``."""
    t_start = time.monotonic() if t_start is None else t_start
    files = files or Files()
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    from bench import check, traffic
    stack = build_stack(workload, seed, trace, files=files, devices=devices,
                        interpret=interpret)
    plan = traffic.build(stack.mix, seed, seconds, module=files.module)
    tracer = Tracer(ROOT / ".bench_trace" / workload, 4.0, log) \
        if trace else None
    result = serve_plan(stack, plan, tracer)
    t0, t1 = result["window"]
    log(f"load generator: {len(result['requests'])} requests, sends late "
        f"by {result['late_s']['mean'] * 1e3:.3f} ms on average, "
        f"{result['late_s']['max'] * 1e3:.3f} ms at most")
    devices = stack.devices
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    run = Run(cell=stack.cell, model=stack.model, dims=stack.dims,
              peaks=stack.peaks, chips=len(devices), seconds=seconds,
              window=(t0, t1), requests=result["requests"],
              steps=stack.steps, setup_s=t0 - t_start,
              replica_of=replicas(stack),
              closed_loop=plan["mode"] == "closed")
    if tracer and tracer.t_start is not None:
        run.host_window = (t0, tracer.t_start)
        from bench import trace as tracemod
        path = tracer.path()
        run.trace = tracemod.load(path) if path else None

    # the answers, then the program's state freed before the reference
    served = check.collect(run, stack.executors)
    metrics, model = stack.metrics, stack.model
    del stack
    gc.collect()
    verdict = check.judge(run, served, model, seed, devices[0], control)

    out_metrics = {}
    for m in (metrics["layer"] if trace else metrics["e2e"]):
        v = files.module("metrics", m["name"]).reduce(run)
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted, failed = check.counts(run)
    line = {"correct": verdict["correct"], "attempted": attempted,
            "failed": failed, "metrics": out_metrics,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices), "memory_peak_bytes": peak}}
    if trace and run.trace is not None:
        from bench import breakdown
        line["device"].update(breakdown.busy(run))
        line["breakdown"] = breakdown.breakdown(run)
    if control:
        line["control"] = verdict["control"]
        line["gaps"] = verdict["gaps"]
    line["checks"] = verdict["checks"]
    return line
