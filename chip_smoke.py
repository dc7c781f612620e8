#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU: starcoder2-3b at full width.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four one-chip replicas

One chip: the gateway runs on the real-time clock with its HTTP server
on localhost and one ``rapid`` worker whose ``DeviceExecutor`` holds
random starcoder2-3b weights (from ``--seed``) on the first device.
Eight requests (prompts of 128-2016 tokens, 32 new tokens each) stream
through ``POST /v1/generate``; each must end with 32 tokens.  For the
two shortest, the served logits of every token (prefill's first token
and each decode step) are compared with a teacher-forced float32
``impl="ref"`` forward on the host CPU over prompt plus served tokens.

Four chips: four ``rapid`` replicas, one per device, behind the
``least_loaded`` router serve sixteen requests (prompts of 128-683
tokens, 64 new tokens each); every replica must serve
one and hold its weights on its own device.  The same requests are then
served by one replica on the first chip, and each request's first-token
logits must agree with the fleet's.

The logit error is max |served - reference| / std(reference) over the
vocabulary of each compared position; it must stay under ``TOL``.  The
last line of the output is a JSON object naming the device.  Without a
TPU, or outside a checkout of the repository, it exits non-zero.
"""
from __future__ import annotations

import argparse
import asyncio
import concurrent.futures
import dataclasses
import gc
import json
import os
import pathlib
import sys
import time
import weakref

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"
ARCH = "starcoder2-3b"
TOL = 0.25          # bf16 serving vs float32 reference, per position


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(1)


# -- HTTP client --------------------------------------------------------------

async def open_stream(port: int, prompt_len: int, max_new: int):
    """POST one request; return once the server answered with its head
    (the gateway has then assigned the request its id)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"prompt_len": prompt_len,
                       "max_new_tokens": max_new}).encode()
    t0 = time.perf_counter()
    writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: localhost\r\n"
                 b"Content-Length: " + str(len(body)).encode() +
                 b"\r\n\r\n" + body)
    await writer.drain()
    status = await reader.readline()
    if b" 200 " not in status:
        fail(f"POST /v1/generate answered {status!r}")
    while (await reader.readline()) not in (b"\r\n", b""):
        pass
    return reader, writer, t0


async def consume(reader, writer, t0: float) -> dict:
    """Read one NDJSON event stream to its terminal event."""
    out = {"rid": None, "token_t": [], "indices": [], "end": None}
    try:
        while True:
            line = await reader.readline()
            if not line:
                break
            ev = json.loads(line)
            out["rid"] = ev["rid"]
            if ev["type"] == "token":
                out["token_t"].append(time.perf_counter() - t0)
                out["indices"].append(ev["index"])
            elif ev["type"] in ("finished", "rejected", "cancelled"):
                out["end"] = ev
                break
    finally:
        writer.close()
        await writer.wait_closed()
    return out


async def serve_requests(gw, specs):
    """Start the gateway's HTTP server, stream every request, stop it."""
    from repro.serving import GatewayHTTPServer
    server = GatewayHTTPServer(gw, "127.0.0.1", 0)
    await server.start()
    try:
        port = server._server.sockets[0].getsockname()[1]
        readers = []                 # one request at a time: ids in order
        for p, n in specs:
            stream = await open_stream(port, p, n)
            readers.append(asyncio.create_task(consume(*stream)))
        return await asyncio.gather(*readers)
    finally:
        await server.close()


def check_streams(results, specs) -> None:
    for res, (plen, n) in zip(results, specs):
        end = res["end"]
        if end is None or end["type"] != "finished":
            fail(f"request {res['rid']} (prompt {plen}) ended with {end}")
        if res["indices"] != list(range(n)) or end["output_len"] != n:
            fail(f"request {res['rid']}: {len(res['indices'])} tokens "
                 f"streamed, {end['output_len']} reported, {n} asked")


def report(results, specs) -> None:
    for res, (plen, _) in zip(results, specs):
        ts = res["token_t"]
        itl = [b - a for a, b in zip(ts, ts[1:])]
        print(f"request {res['rid']:2d} prompt {plen:5d}  TTFT "
              f"{ts[0] * 1e3:9.3f} ms  ITL mean "
              f"{sum(itl) / len(itl) * 1e3:8.3f} ms  max "
              f"{max(itl) * 1e3:8.3f} ms")


def logit_error(served, ref) -> float:
    import numpy as np
    return float(np.max(np.abs(served - ref)) / np.std(ref))


# -- phases -------------------------------------------------------------------

def gateway_for(cfg, serve, executors):
    from repro.serving import Gateway, RealTimeClock
    gw = Gateway(cfg, serve, modes=(), router="least_loaded",
                 clock=RealTimeClock())
    for ex in executors:
        gw.add_worker("rapid", executor=ex)
    return gw


def reference_logits(ex, cfg, rids):
    """Teacher-forced float32 forward on the host CPU over prompt plus
    served tokens of ``rids`` (one padded batch, one compile)."""
    import jax
    import numpy as np
    from repro.models.transformer import forward
    cpu = jax.devices("cpu")[0]
    params32 = jax.tree.map(
        lambda a: jax.device_put(np.asarray(a).astype(np.float32), cpu),
        ex.params)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    seqs = [ex.token_ids(rid)[:-1] for rid in rids]
    S = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s           # right padding: causal, never read
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), toks.shape)
    fwd = jax.jit(lambda p, t, q: forward(p, cfg32, t, q, impl="ref"))
    out = fwd(params32, jax.device_put(toks, cpu), jax.device_put(pos, cpu))
    out = np.asarray(out, np.float32)[..., :cfg.vocab_size]
    return {rid: out[i] for i, rid in enumerate(rids)}


def one_chip(args, cfg, jax) -> None:
    from repro.config import ServeConfig
    from repro.core import DeviceExecutor
    dev = jax.devices()[0]
    serve = ServeConfig(mode="rapid", chips=1, max_batch_slots=8,
                        max_seq_len=2048, page_size=16)
    t0 = time.perf_counter()
    ex = DeviceExecutor(cfg, serve, dev, seed=args.seed, record_logits=True)
    print(f"compile+init {time.perf_counter() - t0:.3f} s "
          f"(executor warm-up {ex.compile_s:.3f} s, buckets {ex.buckets})")
    specs = [(p, 32) for p in (128, 2016, 640, 1024, 200, 1536, 384, 896)]
    gw = gateway_for(cfg, serve, [ex])
    t0 = time.perf_counter()
    results = asyncio.run(serve_requests(gw, specs))
    wall = time.perf_counter() - t0
    check_streams(results, specs)
    report(results, specs)
    n_tok = sum(n for _, n in specs)
    print(f"served {len(specs)} requests, {n_tok} tokens in {wall:.3f} s")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")

    by_len = sorted(zip(specs, results), key=lambda x: x[0][0])
    rids = [res["rid"] for _, res in by_len[:2]]
    t0 = time.perf_counter()
    ref = reference_logits(ex, cfg, rids)
    worst = 0.0
    for rid in rids:
        errs = [logit_error(row, ref[rid][p])
                for p, row in ex.logits[rid].items()]
        if len(errs) != 32:
            fail(f"request {rid}: {len(errs)} logit rows recorded, 32 "
                 f"expected")
        worst = max(worst, max(errs))
        print(f"request {rid}: max logit error {max(errs):.6f} over "
              f"{len(errs)} positions (first token {errs[0]:.6f})")
    print(f"largest logit error {worst:.6f} (tolerance {TOL}); float32 "
          f"reference on the host CPU took {time.perf_counter() - t0:.3f} s")
    if not worst < TOL:
        fail(f"logit error {worst} exceeds {TOL}")


def four_chips(args, cfg, jax) -> None:
    from repro.config import ServeConfig
    from repro.core import DeviceExecutor
    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(devs)}")
    devs = devs[:4]
    serve = ServeConfig(mode="rapid", chips=1, max_batch_slots=8,
                        max_seq_len=1024, page_size=16)

    def build(dev):
        return DeviceExecutor(cfg, serve, dev, seed=args.seed,
                              record_logits=True)

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        fleet = list(pool.map(build, devs))
    print(f"compile+init of 4 replicas {time.perf_counter() - t0:.3f} s")
    homes = [jax.tree.leaves(ex.params)[0].devices() for ex in fleet]
    if homes != [{d} for d in devs]:
        fail(f"replica weights are not one per device: {homes}")
    # 64 new tokens keep each replica busy while the next prompts arrive
    specs = [(128 + 37 * i, 64) for i in range(16)]
    results = asyncio.run(serve_requests(
        gateway_for(cfg, serve, fleet), specs))
    check_streams(results, specs)
    report(results, specs)
    per_replica = [sorted(ex.logits) for ex in fleet]
    print(f"requests per replica: {[len(r) for r in per_replica]}")
    if not all(per_replica):
        fail("a replica served no request")
    first = {}
    for ex in fleet:
        for rid, rows in ex.logits.items():
            first[rid] = rows[min(rows)]
    # chip 0 cannot hold a second replica: the fleet's buffers must go
    refs = [weakref.ref(ex) for ex in fleet]
    del fleet, ex
    gc.collect()
    if any(r() is not None for r in refs):
        fail("the fleet's executors are still referenced")

    single = build(devs[0])
    again = asyncio.run(serve_requests(
        gateway_for(cfg, serve, [single]), specs))
    check_streams(again, specs)
    worst = 0.0
    for res in again:
        rid = res["rid"]
        rows = single.logits[rid]
        worst = max(worst, logit_error(rows[min(rows)], first[rid]))
    print(f"largest first-token logit error, fleet vs one replica "
          f"{worst:.6f} (tolerance {TOL})")
    if not worst < TOL:
        fail(f"logit error {worst} exceeds {TOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve through four one-chip replicas and "
                         "compare with one replica")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"no repository at {ROOT}: run from a checkout")
    sys.path.insert(0, str(SRC))
    # the float32 reference runs on the host CPU next to the chip
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is a {dev.platform!r} "
             f"device")
    from repro.config import get_config
    from repro.core import configure_compile_cache
    print(f"compile cache {configure_compile_cache()}")
    cfg = get_config(ARCH)
    if args.four_chips:
        four_chips(args, cfg, jax)
    else:
        one_chip(args, cfg, jax)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
