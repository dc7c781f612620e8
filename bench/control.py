"""Readings behind a configuration's limit: the program's widest logit
gap on many seeds, and the fp8 control's on some, in one process.

    python3 bench/control.py --workload sc2-chat --seconds 51 \\
        --seeds 1,2,3,4 --control-seeds 1,2,3

Each seed is a whole run of the cell at its own load (``run_cell``) for
``--seconds``; on a control seed the same sample is also read through
the configuration's reference at fp8 (its ``forward(quant=True)``), as
the gap of the token the fp8 forward puts first, and judged by the same
limits as the program (``check.verdict``): ``control_correct`` has to come out
false.  One JSON line per seed.  The benchmark's own runs never run the
control.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402

import syspath  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    syspath.setup()
    from bench.harness import configure_jax
    configure_jax()
    from bench.harness import run_cell
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        line = run_cell(args.workload, seed, args.seconds, False,
                        control=seed in ctrl, log=lambda *a: None)
        control = line.get("control") or {}
        print(json.dumps({
            "seed": seed, "correct": line["correct"],
            "logit_gap": line["checks"]["logit_gap"]["value"],
            "tokens_compared": line["checks"]["tokens_compared"]["value"],
            "control_correct": control.get("correct"),
            "control_logit_gap": (control.get("checks") or {}).get(
                "logit_gap", {}).get("value"),
            "gaps": line.get("gaps"), "control_gaps": control.get("gaps"),
            "attempted": line["attempted"], "failed": line["failed"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "checks": line["checks"],
            "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
