"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload sc2-chat --seed 7 --seconds 30 --trace 0

Sets up the served stack for the cell named in ``BENCHMARK.json``, warms
it, drives the cell's traffic through ``POST /v1/generate`` for
``--seconds``, checks what was served against the plain reference, and
prints as the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profile of part
of the window), ``device`` and, traced, ``breakdown``; ``checks`` comes
last, each number compared with its limit, and the same numbers are the
last lines of standard error.

Exits non-zero with no result line when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from
``bench/peaks.json``, and outside a checkout of the repository.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import syspath  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    src = syspath.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    syspath.setup()
    from bench.harness import SetupError, configure_jax, run_cell
    configure_jax()
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START)
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
