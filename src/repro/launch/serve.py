"""Serving launcher: run RAPID / hybrid / disagg on a trace and report
throughput, goodput and tail latencies (the paper's §5 methodology).

    python -m repro.launch.serve --arch llama3-70b --trace lmsys \
        --qps 8 --duration 60 --mode rapid

Multi-replica cluster serving (shared virtual clock, pluggable router):

    python -m repro.launch.serve --arch llama3-70b --trace lmsys \
        --qps 24 --replicas 4 --router least_loaded --mode rapid

``--mix rapid,rapid,hybrid`` overrides ``--mode``/``--replicas`` with an
explicit per-replica engine list; heterogeneous fleets use
``mode:COUNTxCHIPS`` groups with the BucketServe-style router:

    python -m repro.launch.serve --arch llama3-70b --trace loogle \
        --qps 8 --mix rapid:2x16,rapid:1x32 --router bucketed \
        --admission --rebalance

``--admission`` enables KV-aware admission control (queue/redirect/
reject arrivals that would overflow a replica's block pool — for disagg
replicas the transient prefill pool is projected too);
``--rebalance`` enables the cross-replica preemption/migration tick.

``--scale-policy`` turns on SLO-driven autoscaling: ``reactive`` is the
trailing TTFT-attainment window, ``projection`` forecasts TTFT/ITL from
each replica's live load via the perfmodel and scales before violations
happen — including growing a disagg replica's prefill and decode chip
pools independently.  Per-pool fleet shapes use ``mode:COUNTxP+D``:

    python -m repro.launch.serve --arch llama3-70b --trace lmsys \
        --qps 16 --mix disagg:2x12+20 --scale-policy projection \
        --max-replicas 4

``--serve http`` starts the online gateway instead of replaying a
trace: an asyncio front-end with admission, routing, heartbeat health
checks and crash failover, streaming each request's typed event stream
as JSON lines (serving/gateway.py + serving/http.py):

    python -m repro.launch.serve --arch llama3-70b --mode rapid \
        --replicas 2 --serve http --port 8080
    curl -N -X POST http://127.0.0.1:8080/v1/generate \
        -d '{"prompt_len": 512, "max_new_tokens": 64}'

By default engine logic is real and step durations come from the
calibrated TPU-v5e perfmodel (``PerfModelExecutor``); no device runs.
``--executor device`` instead runs every step of each ``rapid`` replica
on its own TPU chip (``DeviceExecutor``: jitted prefill/decode programs
over the Pallas kernels, random weights from ``--seed``, greedy
sampling, measured step times), and refuses to start without a TPU:

    python -m repro.launch.serve --arch starcoder2-3b --mode rapid \
        --serve http --executor device --max-slots 8 --max-seq-len 2048

``--profiler-port N`` (with ``--serve http``) also starts JAX's profiler
server on port N, so a live trace can be captured from outside with the
program's ``rapid.*`` spans beside the device ops (README, "Tracing a
live server"); ``GET /metrics`` carries the span recorder's counters.
"""
from __future__ import annotations

import argparse
import copy
import json

from repro.config import SLOConfig, ServeConfig, get_config, list_archs
from repro.core import make_engine
from repro.serving import (ROUTERS, TRACES, AdmissionPolicy,
                           ProjectionPolicy, RebalancePolicy, ScalePolicy,
                           StreamMetrics, diurnal_rate, flash_crowd_rate,
                           generate_multiclass_trace, generate_trace,
                           parse_mix, run_fleet)


def _serve_config(mode: str, chips: int, slo: SLOConfig, chunk: int,
                  max_slots: int,
                  max_seq_len: int = ServeConfig.max_seq_len) -> ServeConfig:
    return ServeConfig(mode=mode, chips=chips, slo=slo,
                       chunk_size=chunk,
                       disagg_split=(chips // 2, chips // 2),
                       max_batch_slots=max_slots, max_seq_len=max_seq_len)


def _device_executors(p, args, cfg, serve, modes):
    """(devices, executor factory) for ``--executor device``: one
    ``DeviceExecutor`` per replica on ``jax.devices()[i]``."""
    import jax
    from repro.core import DeviceExecutor, configure_compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        p.error(f"--executor device needs a TPU; JAX's first device is "
                f"a {devices[0].platform!r} device")
    if set(modes) != {"rapid"}:
        p.error("--executor device serves rapid replicas only")
    configure_compile_cache()
    return devices, lambda d: DeviceExecutor(cfg, serve, d, seed=args.seed)


def run_one(arch: str, mode: str, trace: str, qps: float, duration: float,
            chips: int, slo_itl_ms: float, chunk: int = 512,
            seed: int = 0, max_slots: int = 128):
    cfg = get_config(arch)
    slo = SLOConfig(itl_ms=slo_itl_ms)
    serve = _serve_config(mode, chips, slo, chunk, max_slots)
    reqs = generate_trace(TRACES[trace], qps=qps, duration_s=duration,
                          seed=seed)
    eng = make_engine(mode, cfg, serve)
    # API v2: consume the event stream instead of scraping records()
    metrics = StreamMetrics()
    eng.subscribe(metrics)
    eng.enqueue([copy.deepcopy(r) for r in reqs])
    eng.loop.run()
    span = eng.loop.now if eng.loop.now > 0 else 1.0
    return metrics.summarize(slo, span)


def _workload_requests(workload: str, trace: str, qps: float,
                       duration: float, seed: int, arrival: str):
    """Single-class trace, or the multi-tenant mix (SLO classes +
    multi-turn sessions from serving/workloads.py), under a flat /
    diurnal / flash-crowd arrival process."""
    if workload == "trace":
        return generate_trace(TRACES[trace], qps=qps, duration_s=duration,
                              seed=seed)
    rate_fn = None
    if arrival == "diurnal":
        rate_fn = diurnal_rate(qps, amplitude=0.5, period_s=duration / 2)
    elif arrival == "flash":
        rate_fn = flash_crowd_rate(qps, 3.0 * qps, duration * 0.4,
                                   duration * 0.6)
    return generate_multiclass_trace(qps=qps, duration_s=duration,
                                     seed=seed, rate_fn=rate_fn)


def run_cluster(arch: str, modes, router: str, trace: str, qps: float,
                duration: float, chips: int, slo_itl_ms: float,
                chunk: int = 512, seed: int = 0, max_slots: int = 128,
                admission: AdmissionPolicy = None,
                rebalance: RebalancePolicy = None, scale=None,
                workload: str = "trace", arrival: str = "flat",
                session_affinity: bool = False):
    """Run a trace against an N-replica cluster; returns the fleet/per-
    replica summary dict from ``fleet_summarize`` plus the fleet span."""
    cfg = get_config(arch)
    slo = SLOConfig(itl_ms=slo_itl_ms)
    mode0 = modes[0] if isinstance(modes[0], str) else modes[0].mode
    serve = _serve_config(mode0, chips, slo, chunk, max_slots)
    reqs = _workload_requests(workload, trace, qps, duration, seed, arrival)
    out, cluster = run_fleet(cfg, serve, modes, router, reqs,
                             admission=admission, rebalance=rebalance,
                             scale=scale, session_affinity=session_affinity)
    out["router"] = router
    if scale is not None:
        out["scale_events"] = list(cluster._scale_events)
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3-70b", choices=list_archs())
    p.add_argument("--mode", default="rapid",
                   choices=["rapid", "hybrid", "disagg", "all"])
    p.add_argument("--trace", default="lmsys", choices=list(TRACES))
    p.add_argument("--qps", type=float, default=8.0)
    p.add_argument("--duration", type=float, default=60.0)
    p.add_argument("--chips", type=int, default=32,
                   help="chips per serving replica")
    p.add_argument("--slo-itl-ms", type=float, default=100.0)
    p.add_argument("--chunk", type=int, default=512)
    p.add_argument("--replicas", type=int, default=1)
    p.add_argument("--router", default="least_loaded",
                   choices=sorted(ROUTERS))
    p.add_argument("--mix", default=None,
                   help="comma-separated per-replica engine modes, e.g. "
                        "'rapid,rapid,hybrid', or heterogeneous "
                        "'mode:COUNTxCHIPS' groups like 'rapid:2x16,"
                        "hybrid:1x32' (overrides --mode/--replicas)")
    p.add_argument("--workload", default="trace",
                   choices=["trace", "multiclass"],
                   help="'multiclass' replaces the single-class --trace "
                        "with the multi-tenant mix (interactive sessions "
                        "+ batch + best_effort, serving/workloads.py)")
    p.add_argument("--arrival", default="flat",
                   choices=["flat", "diurnal", "flash"],
                   help="arrival process for --workload multiclass")
    p.add_argument("--session-affinity", action="store_true",
                   help="route a session's turns to the replica parking "
                        "its prefix KV (prefix-cache hits)")
    p.add_argument("--admission", action="store_true",
                   help="KV-aware admission control at the cluster")
    p.add_argument("--class-aware-admission", action="store_true",
                   help="class-ordered admission headroom: sheds "
                        "best_effort first, never interactive (implies "
                        "--admission)")
    p.add_argument("--kv-headroom", type=float, default=0.9,
                   help="admission: max projected pool occupancy")
    p.add_argument("--admission-max-wait", type=float, default=60.0,
                   help="admission: queueing deadline before rejection (s)")
    p.add_argument("--rebalance", action="store_true",
                   help="cross-replica preemption/migration tick")
    p.add_argument("--scale-policy", default=None,
                   choices=["reactive", "projection"],
                   help="SLO-driven autoscaling: 'reactive' trailing "
                        "TTFT-attainment window, 'projection' perfmodel "
                        "forecasts incl. independent disagg P/D pool "
                        "scaling")
    p.add_argument("--min-replicas", type=int, default=1)
    p.add_argument("--max-replicas", type=int, default=4)
    p.add_argument("--serve", default="offline",
                   choices=["offline", "http"],
                   help="'http' starts the online gateway (streaming "
                        "NDJSON API, heartbeats, crash failover) instead "
                        "of replaying a trace offline")
    p.add_argument("--checkpoint-interval", type=int, default=0,
                   help="gateway KV snapshot period in generated tokens "
                        "(0 disables; crash failover then re-prefills "
                        "from scratch)")
    p.add_argument("--max-retries", type=int, default=2,
                   help="failover re-dispatches per request before the "
                        "terminal worker_lost rejection")
    p.add_argument("--retry-backoff", type=float, default=0.05,
                   help="base seconds of the exponential failover "
                        "backoff (doubles per retry, capped at 2 s)")
    p.add_argument("--executor", default="perfmodel",
                   choices=["perfmodel", "device"],
                   help="'device' runs each replica's steps on its own "
                        "TPU chip (needs --serve http, --mode rapid); "
                        "'perfmodel' prices them")
    p.add_argument("--seed", type=int, default=0,
                   help="device executor: seed of the random weights and "
                        "of the prompts' token ids")
    p.add_argument("--max-slots", type=int, default=128,
                   help="decode batch slots per replica")
    p.add_argument("--max-seq-len", type=int,
                   default=ServeConfig.max_seq_len,
                   help="--serve http: longest prompt + output of one "
                        "request (device executor: the length of each KV "
                        "slot)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--profiler-port", type=int, default=None,
                   help="--serve http: start jax.profiler's server on "
                        "this port for live trace capture")
    p.add_argument("--json", default=None)
    args = p.parse_args(argv)
    if args.executor == "device" and args.serve != "http":
        p.error("--executor device serves online: add --serve http")
    if args.profiler_port is not None and args.serve != "http":
        p.error("--profiler-port profiles a live server: add --serve http")

    if args.serve == "http":
        from repro.serving import (Gateway, GatewayPolicy, RealTimeClock,
                                   RetryPolicy, run_http)
        if args.mode == "all" and not args.mix:
            p.error("--serve http needs a concrete fleet; use --mode or "
                    "--mix, not --mode all")
        mix = parse_mix(args.mix) if args.mix \
            else [args.mode] * args.replicas
        modes = [m if isinstance(m, str) else m.mode for m in mix]
        cfg = get_config(args.arch)
        slo = SLOConfig(itl_ms=args.slo_itl_ms)
        device = args.executor == "device"
        serve = _serve_config(modes[0], 1 if device else args.chips, slo,
                              args.chunk, args.max_slots, args.max_seq_len)
        devices, factory = _device_executors(p, args, cfg, serve, modes) \
            if device else ((), None)
        admission = AdmissionPolicy(
            kv_headroom=args.kv_headroom,
            max_wait_s=args.admission_max_wait,
            class_aware=args.class_aware_admission)
        gw = Gateway(cfg, serve, modes=modes, router=args.router,
                     clock=RealTimeClock(), admission=admission,
                     session_affinity=args.session_affinity,
                     policy=GatewayPolicy(
                         checkpoint_interval=args.checkpoint_interval,
                         max_retries=args.max_retries),
                     retry=RetryPolicy(
                         max_retries=args.max_retries,
                         backoff_base_s=args.retry_backoff),
                     devices=devices, executor_factory=factory)
        if args.profiler_port is not None:
            import jax
            jax.profiler.start_server(args.profiler_port)
            print(f"profiler server on port {args.profiler_port}")
        run_http(gw, host=args.host, port=args.port)
        return 0

    out = {}
    if args.mix or args.replicas > 1 or args.admission or \
            args.class_aware_admission or args.rebalance or \
            args.scale_policy or args.workload != "trace" or \
            args.session_affinity:
        if args.mode == "all" and not args.mix:
            p.error("--mode all cannot combine with --replicas; use "
                    "--mix rapid,hybrid,disagg to build a mixed fleet")
        mix = parse_mix(args.mix) if args.mix \
            else [args.mode] * args.replicas
        admission = AdmissionPolicy(
            kv_headroom=args.kv_headroom,
            max_wait_s=args.admission_max_wait,
            class_aware=args.class_aware_admission) \
            if args.admission or args.class_aware_admission else None
        rebalance = RebalancePolicy() if args.rebalance else None
        scale = None
        if args.scale_policy == "reactive":
            scale = ScalePolicy(min_replicas=args.min_replicas,
                                max_replicas=args.max_replicas)
        elif args.scale_policy == "projection":
            scale = ProjectionPolicy(min_replicas=args.min_replicas,
                                     max_replicas=args.max_replicas)
        res = run_cluster(args.arch, mix, args.router, args.trace,
                          args.qps, args.duration, args.chips,
                          args.slo_itl_ms, args.chunk,
                          admission=admission, rebalance=rebalance,
                          max_slots=args.max_slots,
                          scale=scale, workload=args.workload,
                          arrival=args.arrival,
                          session_affinity=args.session_affinity)
        out["cluster"] = res
        f = res["fleet"]
        names = [m if isinstance(m, str)
                 else (f"{m.mode}x{m.chips}" if m.chips else m.mode)
                 for m in mix]
        print(f"cluster[{'+'.join(names)} | {args.router}] "
              f"thpt={f['throughput_tok_s']:9.1f} tok/s  "
              f"goodput={f['goodput_req_s']:6.2f} req/s  "
              f"ttft_p99={f['ttft_p99_s']:7.2f}s  "
              f"slo_ok={f['slo_attainment'] * 100:5.1f}%  "
              f"rej={f['rejected']}  migr={f['migrations']}")
        if res.get("admission"):
            print(f"  admission: {res['admission']}")
        if res.get("scale_events"):
            ups = sum(1 for _, a, _ in res["scale_events"] if a == "up")
            pools = sum(1 for _, a, _ in res["scale_events"]
                        if a.startswith("pool_"))
            print(f"  scaling[{args.scale_policy}]: {ups} replica "
                  f"add(s), {pools} independent pool grow(s)")
        for name, s in res["per_replica"].items():
            print(f"  {name:10s} n={s['requests']:4d}  "
                  f"thpt={s['throughput_tok_s']:9.1f} tok/s  "
                  f"ttft_p95={s['ttft_p95_s']:7.2f}s")
        if args.workload == "multiclass":
            for name, s in res["per_class"].items():
                print(f"  class {name:12s} n={s['requests']:4d}  "
                      f"goodput={s['goodput_req_s']:6.2f} req/s  "
                      f"slo_ok={s['slo_attainment'] * 100:5.1f}%  "
                      f"rej={s['rejected']}")
    else:
        modes = (["rapid", "hybrid", "disagg"] if args.mode == "all"
                 else [args.mode])
        for mode in modes:
            s = run_one(args.arch, mode, args.trace, args.qps,
                        args.duration, args.chips, args.slo_itl_ms,
                        args.chunk, max_slots=args.max_slots)
            out[mode] = s
            print(f"{mode:7s} thpt={s['throughput_tok_s']:9.1f} tok/s  "
                  f"goodput={s['goodput_req_s']:6.2f} req/s  "
                  f"ttft_p95={s['ttft_p95_s']:7.2f}s  "
                  f"itl_p95={s['itl_p95_s'] * 1e3:6.0f}ms  "
                  f"slo_ok={s['slo_attainment'] * 100:5.1f}%")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
