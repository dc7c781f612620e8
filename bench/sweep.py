"""Find the highest rate an open-loop cell's stack sustains: one set-up,
then one window per rate and traffic seed, lowest rate first.

    python3 bench/sweep.py --workload sc2-chat --rates 4,5,6,7,8 \\
        --seeds 21,22 --seconds 40

Each window is reduced by the cell's own metric files (``bench/metrics``)
on a ``Run`` of the window, and of each half of it: the requests due and
failed (``check.counts``), ``ttft_p95_ms`` over the window and over each
half (a backlog that grows shows as a second half above the first),
``itl_p95_ms``, ``queue_wait_p95_ms``, and ``output_tok_s`` over the
second half beside the tokens/s offered (rate x mean output).  A window
keeps pace when nothing failed, the second half's TTFT p95 is within
1.25x the first's, and the second half delivers at least 0.9 of what is
offered.  The knee is the highest rate at which every seed keeps pace at
it and at every lower rate.  The cell's rate goes into its traffic file
by hand.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402

import syspath  # noqa: E402


def summarize(files, stack, result, plan, rate):
    from bench import check, harness
    t0, t1 = result["window"]
    mid = (t0 + t1) / 2
    run = harness.Run(cell=stack.cell, model=stack.model, dims=stack.dims,
                      peaks=stack.peaks, chips=len(stack.devices),
                      seconds=t1 - t0, window=(t0, t1),
                      requests=result["requests"], steps=stack.steps,
                      setup_s=0.0, replica_of=harness.replicas(stack))
    first = dataclasses.replace(run, window=(t0, mid))
    second = dataclasses.replace(run, window=(mid, t1))

    def metric(name, r):
        return files.module("metrics", name).reduce(r)

    offered = rate * sum(q[2] for q in plan["requests"]) / len(
        plan["requests"])
    due, failed = check.counts(run)
    p95_first, p95_second = (metric("ttft_p95_ms", first),
                             metric("ttft_p95_ms", second))
    tok_second = metric("output_tok_s", second)
    keeps = (failed == 0 and p95_first is not None and p95_second is not None
             and p95_second <= 1.25 * p95_first and tok_second >= 0.9 * offered)
    return {"rate": rate, "due": due, "failed": failed,
            "ttft_p95_ms": metric("ttft_p95_ms", run),
            "ttft_p95_first_half_ms": p95_first,
            "ttft_p95_second_half_ms": p95_second,
            "itl_p95_ms": metric("itl_p95_ms", run),
            "queue_wait_p95_ms": metric("queue_wait_p95_ms", run),
            "offered_tok_s": offered, "output_tok_s_second_half": tok_second,
            "keeps_pace": keeps}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args()
    syspath.setup()
    from bench.harness import configure_jax
    configure_jax()
    from bench import harness, traffic
    files = harness.Files()
    seeds = [int(s) for s in args.seeds.split(",")]
    stack = harness.build_stack(args.workload, seeds[0], False, files=files)
    print(f"set-up {time.monotonic() - T_START:.3f} s", flush=True)
    knee, failing = None, False
    for rate in sorted(float(r) for r in args.rates.split(",")):
        mix = dict(stack.mix, arrival=dict(stack.mix["arrival"],
                                           rate_per_s=rate))
        ok = True
        for seed in seeds:
            plan = traffic.build(mix, seed, args.seconds,
                                 module=files.module)
            res = harness.serve_plan(stack, plan)
            line = summarize(files, stack, res, plan, rate)
            print(json.dumps(dict(line, seed=seed)), flush=True)
            ok = ok and line["keeps_pace"]
        failing = failing or not ok
        if not failing:
            knee = rate
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
