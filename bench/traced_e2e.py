"""A traced run of one cell whose result line also carries the cell's
end-to-end metrics, to read what taking a profile costs them.

    python3 bench/traced_e2e.py --workload sc2-chat --seed 7 --seconds 51

The run is ``bench/run.py --trace 1``'s, with every end-to-end metric of
the cell reduced beside its per-layer metrics; compare its line with
``bench/run.py --trace 0`` on the same seed.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import tempfile  # noqa: E402

import syspath  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    syspath.setup()
    from bench.harness import ROOT, Files, configure_jax, run_cell
    configure_jax()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"] += bench["end_to_end"]
    with tempfile.TemporaryDirectory() as d:
        path = pathlib.Path(d) / "BENCHMARK.json"
        path.write_text(json.dumps(bench))
        line = run_cell(args.workload, args.seed, args.seconds, True,
                        files=Files(benchmark=path), t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
