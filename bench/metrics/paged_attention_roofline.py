"""``paged_attention``'s share of its roofline: the least time to read
the live KV of each decode step (sum of attended lengths times KV bytes
per token, plus q and out) at peak bandwidth, or its FLOPs at peak if
longer, over the kernel's device time in the trace."""
from bench.breakdown import kernel_ns, traced_steps


def reduce(run):
    if run.trace is None:
        return None
    least = ns = 0.0
    pk = run.peaks
    for step, span in traced_steps(run):
        t = kernel_ns(run, step, span, "paged_attention")
        if step.decode_lens and t > 0:
            least += max(
                run.ref.paged_attention_bytes(run.dims, step.decode_lens)
                / pk["hbm_bytes_per_s"],
                run.ref.attention_flops(run.dims, step.decode_lens)
                / pk["bf16_flops_per_s"])
            ns += t
    return least / (ns * 1e-9) * 100 if ns else None
