"""Record the span fixture of ``bench/test_program_spans.py`` on one TPU.

    python3 bench/testdata/record_spans.py [--out DIR] [--cpu]

Serves four short requests of the ``tiny`` cell (the registry's reduced
starcoder2-3b) through the benchmark's stack and profiles the half
second that holds them.  Writes ``spans.xplane.pb`` (the profile with
only the chip's plane and the ``bench.*`` and ``rapid.*`` host events
kept) and ``spans.json`` (the window, the wrapper's steps and the
program's spans of the same run) beside this file, or in ``--out``.
``--cpu`` rehearses on the CPU, kernels in interpret mode.
"""
import argparse
import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEED = 2 ** 31 + 77


def strip(raw: bytes) -> bytes:
    """The profile with only what the fixture's readers need, or as it
    is where the XPlane protobuf module cannot be found."""
    spec = importlib.util.find_spec("tensorflow")
    path = spec and pathlib.Path(spec.submodule_search_locations[0]) / \
        "tsl/profiler/protobuf/xplane_pb2.py"
    if not path or not path.is_file():
        return raw
    mspec = importlib.util.spec_from_file_location("xplane_pb2", path)
    pb = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(pb)
    space = pb.XSpace()
    space.ParseFromString(raw)
    keep = [p for p in space.planes
            if p.name.startswith("/device:TPU:") or p.name == "/host:CPU"]
    for p in keep:
        if p.name != "/host:CPU":
            continue
        named = {k for k, m in p.event_metadata.items()
                 if m.name.startswith(("rapid.", "bench."))}
        for line in p.lines:
            evs = [e for e in line.events if e.metadata_id in named]
            del line.events[:]
            line.events.extend(evs)
        lines = [ln for ln in p.lines if ln.events]
        del p.lines[:]
        p.lines.extend(lines)
        for k in [k for k in p.event_metadata if k not in named]:
            del p.event_metadata[k]
    del space.planes[:]
    space.planes.extend(keep)
    return space.SerializeToString()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=pathlib.Path, default=HERE)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    import jax

    from bench import harness
    from repro import obs
    files = harness.Files(benchmark=HERE / "BENCHMARK.json", extra=HERE)
    out = args.out
    out.mkdir(parents=True, exist_ok=True)
    devices = jax.devices("cpu") if args.cpu else None
    stack = harness.build_stack("tiny", SEED, True, files=files,
                                devices=devices, interpret=args.cpu)
    # four requests that overlap in the profiled second half: prefills
    # beside decode steps, slot inserts, a free slot or two
    plan = {"mode": "open", "seconds": 1.0, "lead_s": 0.0,
            "requests": [[0.55 + 0.03 * i, 20 + 30 * i, 12]
                         for i in range(4)]}
    tracer = harness.Tracer(ROOT / ".bench_trace" / "spans-fixture", 0.5,
                            lambda *a: print(*a, file=sys.stderr))
    result = harness.serve_plan(stack, plan, tracer)
    since = tracer.t_start - 0.1
    (out / "spans.xplane.pb").write_bytes(
        strip(pathlib.Path(tracer.path()).read_bytes()))
    rec = {"window": result["window"], "profile_start": tracer.t_start,
           "steps": [[s.idx, s.chip, s.t0, s.t1] for s in stack.steps
                     if s.t1 >= since],
           "spans": [[s.name, s.start, s.end, s.attrs] for s in obs.spans()
                     if s.end >= since * 1e9]}
    (out / "spans.json").write_text(json.dumps(rec) + "\n")
    print(f"{len(rec['steps'])} steps, {len(rec['spans'])} spans, "
          f"{(out / 'spans.xplane.pb').stat().st_size} bytes of profile, "
          f"counters {obs.counters()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
