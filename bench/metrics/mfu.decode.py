"""Model FLOPs of the live slots' tokens over the device time of the
decode programs, as a share of peak bf16 FLOP/s."""
from bench.breakdown import program_ns, traced_steps


def reduce(run):
    if run.trace is None:
        return None
    flops = ns = 0.0
    for step, span in traced_steps(run):
        t = program_ns(run, step, span, "_decode_step")
        if step.decode_lens and t > 0:
            flops += run.ref.decode_flops(run.dims, step.decode_lens)
            ns += t
    if ns == 0:
        return None
    return flops / (ns * 1e-9 * run.peaks["bf16_flops_per_s"]) * 100
