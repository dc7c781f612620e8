"""Shared benchmark machinery: engine sweeps + CSV emission.

Every benchmark prints ``name,value,derived`` CSV rows (one per paper
figure datapoint) and returns a dict for benchmarks.run aggregation.
Serving instances: 32 chips for LlaMA-3.1-70B-class models (TPU v5e has
16 GB/chip — the 8x MI300X node of the paper is ~1.5 TB HBM; 32 v5e =
512 GB holds weights + KV comfortably, DESIGN.md §6), disagg split 16P/16D.

Benchmarks are Serving API v2 consumers: ``run_point`` subscribes a
``StreamMetrics`` to the engine's event stream and summarizes from it —
no blocking ``run()`` / post-hoc ``records()``.
"""
from __future__ import annotations

import copy
from typing import Dict, List

from repro.config import SLOConfig, ServeConfig, get_config
from repro.core import make_engine
from repro.serving import TRACES, StreamMetrics, generate_trace

CHIPS = 32
MODELS = {
    "llama3-70b": dict(slo_itl_ms=100.0),
    "mixtral-8x7b": dict(slo_itl_ms=50.0),
}
QPS_SWEEP = (1.0, 2.0, 4.0, 8.0, 16.0, 24.0)
DURATION = 45.0


def serve_cfg(mode: str, slo_itl_ms: float, chunk: int = 512,
              async_sched: bool = True) -> ServeConfig:
    # token budget tracks the chunk knob (Sarathi semantics): decodes
    # always fit, prefill gets ~one chunk per iteration — this is what
    # the paper's "chunk size" sweep actually varies
    return ServeConfig(mode=mode, chips=CHIPS,
                       slo=SLOConfig(itl_ms=slo_itl_ms),
                       chunk_size=chunk, token_budget=chunk + 128,
                       disagg_split=(16, 16), max_batch_slots=128,
                       async_scheduling=async_sched)


def run_point(arch: str, mode: str, trace: str, qps: float,
              slo_itl_ms: float, chunk: int = 512, seed: int = 0,
              duration: float = DURATION) -> Dict[str, float]:
    cfg = get_config(arch)
    reqs = generate_trace(TRACES[trace], qps=qps, duration_s=duration,
                          seed=seed)
    eng = make_engine(mode, cfg, serve_cfg(mode, slo_itl_ms, chunk))
    metrics = StreamMetrics()
    eng.subscribe(metrics)
    eng.enqueue([copy.deepcopy(r) for r in reqs])
    eng.loop.run()
    span = eng.loop.now if eng.loop.now > 0 else 1.0
    out = metrics.summarize(SLOConfig(itl_ms=slo_itl_ms), span)
    out["kv_util"] = eng.kv_util_mean
    return out


def emit(rows: List[tuple]) -> None:
    for name, value, derived in rows:
        print(f"{name},{value},{derived}")
