"""Kernel micro-bench: wall time per call in interpret mode (CPU) plus
the analytic TPU-v5e roofline estimate for the same shapes.  Interpret
wall-times validate nothing about TPU perf — the derived column is the
real deliverable; the CSV keeps both for regression tracking."""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.unified_pd import unified_pd
from repro.perfmodel.hw import TPU_V5E


def _t(fn, *a, n=3, **kw):
    fn(*a, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*a, **kw))
    return (time.perf_counter() - t0) / n * 1e6


def main():
    rng = jax.random.PRNGKey(0)
    rows = []
    # flash prefill, serving-ish shape (small for interpret mode)
    B, Hq, Hkv, S, D = 1, 4, 2, 512, 64
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    us = _t(flash_prefill, q, k, v, block_q=128, block_k=128,
            interpret=True, n=2)
    flops = 2 * 2 * B * Hq * S * S * D * 0.5
    est = flops / TPU_V5E.peak_flops * 1e6
    rows.append(("kernel_flash_prefill_us", f"{us:.0f}",
                 f"tpu_v5e_roofline_us={est:.1f}"))
    # paged attention decode
    N, page, mp, Bd = 64, 16, 16, 8
    kp = jax.random.normal(ks[0], (Hkv, N, D, page), jnp.float32)
    vp = jax.random.normal(ks[1], (Hkv, N, D, page), jnp.float32)
    qd = jax.random.normal(ks[2], (Bd, Hq, D), jnp.float32)
    tabs = jnp.tile(jnp.arange(mp, dtype=jnp.int32), (Bd, 1))
    lens = jnp.full((Bd,), mp * page, jnp.int32)
    us = _t(paged_attention, qd, kp, vp, tabs, lens, interpret=True, n=2)
    bytes_ = Bd * mp * page * Hkv * D * 2 * 4
    est = bytes_ / TPU_V5E.hbm_bw * 1e6
    rows.append(("kernel_paged_attention_us", f"{us:.0f}",
                 f"tpu_v5e_bw_bound_us={est:.2f}"))
    # unified P/D
    us = _t(unified_pd, q.transpose(0, 2, 1, 3)[:, :, :, :]
            if False else q, k, v, qd, kp.swapaxes(2, 3),
            vp.swapaxes(2, 3), tabs, lens,
            f_decode=0.5, block_q=128, block_k=128, interpret=True, n=1)
    rows.append(("kernel_unified_pd_us", f"{us:.0f}",
                 "fused P+D single launch"))
    # ssm scan
    Bm_, L, din, ds = 2, 256, 64, 16
    xs = jax.random.normal(ks[0], (Bm_, L, din), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bm_, L, din)))
    A = -jnp.exp(jax.random.normal(ks[2], (din, ds)) * 0.3)
    Bmat = jax.random.normal(ks[0], (Bm_, L, ds), jnp.float32)
    Cmat = jax.random.normal(ks[1], (Bm_, L, ds), jnp.float32)
    us = _t(ssm_scan, xs, dt, A, Bmat, Cmat, chunk=64, tile_d=64,
            interpret=True, n=2)
    rows.append(("kernel_ssm_scan_us", f"{us:.0f}",
                 "chunked selective scan"))
    emit(rows)
    return dict(rows=[r[:2] for r in rows])


if __name__ == "__main__":
    main()
