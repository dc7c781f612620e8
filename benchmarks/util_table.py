"""Paper §5.4: resource utilization.  KV-pool (memory) utilization per
engine + the disagg memory imbalance; compute-utilization proxy from
the interference model's occupancy shares."""
import copy

from benchmarks.common import emit, serve_cfg
from repro.config import get_config
from repro.core import DisaggEngine, make_engine
from repro.serving import TRACES, generate_trace


def main():
    cfg = get_config("llama3-70b")
    reqs = generate_trace(TRACES["arxiv"], qps=8.0, duration_s=45, seed=0)
    rows = []
    utils = {}
    for mode in ("rapid", "hybrid", "disagg"):
        eng = make_engine(mode, cfg, serve_cfg(mode, 100.0))
        eng.enqueue([copy.deepcopy(r) for r in reqs])
        eng.loop.run()
        kv = eng.kv_util_mean
        utils[mode] = kv
        rows.append((f"util_{mode}_kv_pool", f"{kv:.3f}",
                     "mean fraction of KV pool live"))
        if isinstance(eng, DisaggEngine):
            # §3.2.2 imbalance: prefill-side pool holds KV only
            # transiently; report its mean occupancy too
            rows.append((f"util_{mode}_prefill_pool",
                         f"{eng.kv_p.utilization:.3f}",
                         "prefill-side residual occupancy"))
    if utils.get("disagg"):
        rows.append(("util_rapid_over_disagg_memory",
                     f"{utils['rapid'] / max(utils['disagg'], 1e-9):.2f}",
                     "paper: up to +37% memory utilization"))
    emit(rows)
    return utils


if __name__ == "__main__":
    main()
