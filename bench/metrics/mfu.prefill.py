"""Model FLOPs of the valid prompt tokens over the device time of the
prefill programs that computed them, as a share of peak bf16 FLOP/s."""
from bench import work
from bench.breakdown import program_ns, traced_steps


def reduce(run):
    if run.trace is None:
        return None
    flops = ns = 0.0
    for step, span in traced_steps(run):
        t = program_ns(run, step, span, "_prefill_step")
        if step.prefill and t > 0:
            flops += sum(work.prefill_flops(run.dims, n)
                         for _, n in step.prefill)
            ns += t
    if ns == 0:
        return None
    return flops / (ns * 1e-9 * run.peaks["bf16_flops_per_s"]) * 100
