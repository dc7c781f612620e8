"""``flash_prefill``'s share of its roofline: the least time of the
causal attention over the valid prompt tokens (the larger of its FLOPs
over peak FLOP/s and its bytes over peak bandwidth) over the kernel's
device time in the trace."""
from bench import work
from bench.breakdown import kernel_ns, traced_steps


def reduce(run):
    if run.trace is None:
        return None
    least = ns = 0.0
    pk = run.peaks
    for step, span in traced_steps(run):
        t = kernel_ns(run, step, span, "flash_prefill")
        if step.prefill and t > 0:
            for _, n in step.prefill:
                least += max(
                    work.prefill_attention_flops(run.dims, n)
                    / pk["bf16_flops_per_s"],
                    work.flash_prefill_bytes(run.dims, n)
                    / pk["hbm_bytes_per_s"])
            ns += t
    return least / (ns * 1e-9) * 100 if ns else None
