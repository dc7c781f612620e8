"""Serving gateway: registry, routing, streaming, admission, metrics.

Everything runs on the simulated clock (serving/clock.py protocol), so
these are fully deterministic — no sockets, no sleeps.  Churn scenarios
(crash, drain, slow consumer) live in tests/test_gateway_churn.py.
"""
import pytest

from repro.config import SLOConfig, ServeConfig, get_config
from repro.core.events import (FinishedEvent, PhaseEvent, RejectedEvent,
                               TokenEvent)
from repro.core.request import Request
from repro.serving import Gateway, RequestChannel
from repro.serving.worker import WorkerState

CFG = get_config("llama3-70b")


def _serve(mode="rapid", chips=16, slots=64):
    return ServeConfig(mode=mode, chips=chips, slo=SLOConfig(itl_ms=100.0),
                       chunk_size=512, disagg_split=(chips // 2, chips // 2),
                       max_batch_slots=slots)


def _gateway(modes=("rapid", "rapid"), **kw):
    return Gateway(CFG, _serve(), modes=list(modes), **kw)


def _trace(n, max_new=16, prompt=256, gap=0.02, **kw):
    return [Request(rid=i, arrival=gap * i, prompt_len=prompt,
                    max_new_tokens=max_new, **kw) for i in range(n)]


# ---------------------------------------------------------------------------
# registry / workers
# ---------------------------------------------------------------------------

def test_registry_tracks_workers_and_replicas():
    gw = _gateway(modes=("rapid", "hybrid"))
    assert sorted(gw.registry.workers) == [0, 1]
    assert [rep.mode for rep in gw.registry.replicas] == ["rapid", "hybrid"]
    assert [w.name for w in gw.registry.healthy()] == ["rapid-0", "hybrid-1"]
    w = gw.add_worker("rapid")
    assert w.wid == 2 and len(gw.registry.replicas) == 3
    gw.registry.deregister(2)
    assert 2 not in gw.registry.workers and len(gw.registry.replicas) == 2


def test_heartbeat_timeout_declares_silent_worker_dead():
    gw = _gateway()
    r = _trace(1, max_new=600)[0]       # keep the gateway busy long enough
    gw._expected = 1
    gw.clock.at(0.0, lambda: gw.submit(r, consumer=lambda ev: None))
    gw.clock.at(0.2, lambda: gw.kill_worker(1))   # idle worker crashes
    states = []
    gw.clock.at(0.3, lambda: states.append(gw.registry.workers[1].state))
    gw.clock.run()
    # not yet detected right after the crash...
    assert states == [WorkerState.UP]
    # ...but the missing heartbeats eventually were
    assert gw.registry.workers[1].state is WorkerState.DEAD
    assert gw.registry.workers[1].replica not in gw.registry.replicas
    assert gw.health()["workers"]["rapid-1"] == "dead"


def test_healthy_workers_keep_beating_and_stay_up():
    gw = _gateway()
    recs, _ = gw.serve_trace(_trace(6))
    assert all(r.finish is not None for r in recs)
    assert all(w.state is WorkerState.UP
               for w in gw.registry.workers.values())


# ---------------------------------------------------------------------------
# streaming channels
# ---------------------------------------------------------------------------

def test_channel_dedupes_replayed_token_indices():
    got = []
    ch = RequestChannel(rid=1, consumer=got.append)
    assert ch.offer(TokenEvent(1, 0.1, 0))
    assert ch.offer(TokenEvent(1, 0.2, 1))
    assert not ch.offer(TokenEvent(1, 0.3, 0))    # failover replay
    assert not ch.offer(TokenEvent(1, 0.3, 1))
    assert ch.offer(TokenEvent(1, 0.4, 2))
    assert [e.index for e in got] == [0, 1, 2]
    assert ch.offer(FinishedEvent(1, 0.5, 0.0, 8, 3))
    assert ch.closed and ch.done
    assert not ch.offer(TokenEvent(1, 0.6, 3))    # closed -> dropped


def test_channel_pause_resume_watermarks():
    paused, resumed = [], []
    ch = RequestChannel(rid=1, capacity=4, resume_at=1,
                        on_pause=paused.append, on_resume=resumed.append)
    for i in range(4):
        ch.offer(TokenEvent(1, 0.1 * i, i))
    assert paused == [1] and ch.paused
    ch.offer(TokenEvent(1, 0.5, 4))               # buffered past capacity
    assert len(ch) == 5 and paused == [1]         # pause fires once
    while len(ch) > 1:
        ch.take()
    assert resumed == [1] and not ch.paused
    assert ch.drain()[0].index == 4


def test_streamed_events_reach_consumer_in_order():
    gw = _gateway(modes=("rapid",))
    evs = []
    r = Request(rid=0, arrival=0.0, prompt_len=128, max_new_tokens=12)
    gw._expected = 1
    gw.clock.at(0.0, lambda: gw.submit(r, consumer=evs.append))
    gw.clock.run()
    kinds = [type(e).__name__ for e in evs]
    assert kinds[0] == "PhaseEvent" and kinds[-1] == "FinishedEvent"
    idxs = [e.index for e in evs if isinstance(e, TokenEvent)]
    assert idxs == list(range(12))
    times = [e.t for e in evs]
    assert times == sorted(times)


# ---------------------------------------------------------------------------
# admission / routing
# ---------------------------------------------------------------------------

def test_oversized_prompt_rejected_through_channel():
    gw = _gateway(modes=("rapid",))
    evs = []
    r = Request(rid=0, arrival=0.0, prompt_len=10**7, max_new_tokens=4)
    gw._expected = 1
    gw.clock.at(0.0, lambda: gw.submit(r, consumer=evs.append))
    gw.clock.run()
    assert len(evs) == 1 and isinstance(evs[0], RejectedEvent)
    assert evs[0].reason == "never_fits"
    assert gw.metrics.records[0].rejected


def test_session_affinity_pins_turns_to_one_worker():
    gw = _gateway(router="round_robin", session_affinity=True)
    reqs = _trace(6, gap=2.0, session_id="s1")
    recs, _ = gw.serve_trace(reqs)
    assert all(r.finish is not None for r in recs)
    homes = {w.wid: len(w.replica.assigned)
             for w in gw.registry.workers.values()}
    assert sorted(homes.values()) == [0, 6]       # all turns on one worker


def test_truncated_band_request_finishes_with_flag():
    # build a prompt that fits the pool but whose prompt+output cannot:
    # engine admission caps max_new_tokens instead of stalling.  Gateway
    # admission is opened wide so the band request reaches the engine.
    from repro.serving import AdmissionPolicy
    gw = _gateway(modes=("rapid",),
                  admission=AdmissionPolicy(kv_headroom=1.0,
                                            projected_output_frac=0.0))
    eng = gw.registry.workers[0].engine
    pool_tokens = eng.kv.allocator.num_blocks * gw.serve.page_size
    r = Request(rid=0, arrival=0.0, prompt_len=pool_tokens - 3,
                max_new_tokens=64)
    gw._expected = 1
    evs = []
    gw.clock.at(0.0, lambda: gw.submit(r, consumer=evs.append))
    gw.clock.run()
    fin = evs[-1]
    assert isinstance(fin, FinishedEvent)
    assert fin.truncated and fin.output_len == 4
    rec = gw.metrics.records[0]
    assert rec.truncated and rec.output_len == 4


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_metrics_summary_carries_loop_stats_and_workers():
    gw = _gateway()
    gw.serve_trace(_trace(8))
    s = gw.metrics_summary()
    loop = s["fleet"]["loop"]
    assert set(loop) == {"dispatched", "clamped", "peak_heap"}
    assert loop["dispatched"] > 0 and loop["clamped"] == 0
    assert set(s["per_replica"]) == {"rapid-0", "rapid-1"}
    assert s["fleet"]["completed"] == 8
    assert s["fleet"]["retries"] == 0 and s["fleet"]["truncated"] == 0


def test_health_endpoint_shape():
    gw = _gateway()
    h = gw.health()
    assert h["status"] == "ok"
    assert h["workers"] == {"rapid-0": "up", "rapid-1": "up"}
    assert h["live_requests"] == 0 and h["paused_streams"] == 0


def test_summarize_gains_retry_truncation_counters():
    from repro.serving.metrics import RequestRecord, summarize
    recs = [RequestRecord(rid=0, arrival=0.0, prompt_len=8, output_len=4,
                          ttft=0.1, itl_p95=0.01, finish=1.0, retries=2,
                          truncated=True),
            RequestRecord(rid=1, arrival=0.0, prompt_len=8, output_len=0,
                          ttft=None, itl_p95=None, finish=None,
                          rejected=True, retries=1)]
    s = summarize(recs, SLOConfig(itl_ms=100.0), 1.0)
    assert s["retries"] == 3          # rejected requests' retries count too
    assert s["truncated"] == 1


def test_run_fleet_summary_includes_loop_stats():
    from repro.serving import run_fleet
    serve = _serve()
    out, cluster = run_fleet(CFG, serve, ["rapid", "rapid"], "round_robin",
                             _trace(6))
    loop = out["fleet"]["loop"]
    assert loop == cluster.loop.stats.as_dict()
    assert loop["dispatched"] > 0


# ---------------------------------------------------------------------------
# HTTP surface: one real-socket end-to-end pass (skipped if the sandbox
# forbids binding localhost)
# ---------------------------------------------------------------------------

def test_http_generate_healthz_metrics():
    import asyncio
    import json as _json

    from repro.core.events import event_from_json
    from repro.serving import GatewayHTTPServer, RealTimeClock

    async def scenario():
        gw = Gateway(CFG, _serve(), modes=["rapid"], clock=RealTimeClock())
        server = GatewayHTTPServer(gw, host="127.0.0.1", port=0)
        try:
            await server.start()
        except OSError as e:
            pytest.skip(f"cannot bind localhost: {e}")
        port = server._server.sockets[0].getsockname()[1]

        async def call(method, path, body=b""):
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            head = (f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n").encode()
            writer.write(head + body)
            await writer.drain()
            raw = await reader.read()
            writer.close()
            await writer.wait_closed()
            header, _, payload = raw.partition(b"\r\n\r\n")
            status = int(header.split()[1])
            return status, payload

        status, payload = await call("GET", "/healthz")
        assert status == 200
        assert _json.loads(payload)["status"] == "ok"

        body = _json.dumps({"prompt_len": 64,
                            "max_new_tokens": 5}).encode()
        status, payload = await call("POST", "/v1/generate", body)
        assert status == 200
        events = [event_from_json(line)
                  for line in payload.decode().splitlines()]
        assert isinstance(events[-1], FinishedEvent)
        assert [e.index for e in events
                if isinstance(e, TokenEvent)] == list(range(5))

        status, payload = await call("GET", "/metrics")
        assert status == 200
        m = _json.loads(payload)
        assert m["fleet"]["completed"] == 1
        assert "loop" in m["fleet"]
        # the span recorder's counters (repro.obs) ride along
        assert {"steps", "compiles", "prefill_tokens_valid",
                "decode_slot_steps_live"} <= set(m["counters"])

        status, _ = await call("GET", "/nope")
        assert status == 404
        status, _ = await call("POST", "/v1/generate", b"{bad json")
        assert status == 400
        await server.close()

    asyncio.run(asyncio.wait_for(scenario(), timeout=60))


def test_http_malformed_inputs_get_400_and_server_survives():
    """Robustness contract: junk bodies, junk headers and junk request
    lines are client errors (400/405), never an exception escaping the
    handler — the server keeps answering afterwards."""
    import asyncio
    import json as _json

    from repro.serving import GatewayHTTPServer, RealTimeClock

    async def scenario():
        gw = Gateway(CFG, _serve(), modes=["rapid"], clock=RealTimeClock())
        server = GatewayHTTPServer(gw, host="127.0.0.1", port=0)
        try:
            await server.start()
        except OSError as e:
            pytest.skip(f"cannot bind localhost: {e}")
        port = server._server.sockets[0].getsockname()[1]

        async def raw(payload: bytes):
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            writer.write(payload)
            await writer.drain()
            data = await reader.read()
            writer.close()
            await writer.wait_closed()
            header, _, body = data.partition(b"\r\n\r\n")
            return int(header.split()[1]), body

        async def call(method, path, body=b""):
            head = (f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n").encode()
            return await raw(head + body)

        bad_bodies = [
            b"{bad json",                                  # not JSON
            b"[1, 2, 3]",                                  # not an object
            b'{"max_new_tokens": 4}',                      # missing field
            b'{"prompt_len": "x", "max_new_tokens": 4}',   # wrong type
            b'{"prompt_len": 0, "max_new_tokens": 4}',     # out of range
            b'{"prompt_len": 8, "max_new_tokens": 4, "cached_prefix_len": -1}',
            b'{"prompt_len": 8, "max_new_tokens": 4, "session_id": 5}',
        ]
        for body in bad_bodies:
            status, _ = await call("POST", "/v1/generate", body)
            assert status == 400, body
        for body in [b"notjson", b'{"rid": "x"}', b"{}"]:
            status, _ = await call("POST", "/v1/cancel", body)
            assert status == 400, body
        # cancel of an unknown rid is a clean "no"
        status, payload = await call("POST", "/v1/cancel", b'{"rid": 99}')
        assert status == 200
        assert _json.loads(payload) == {"rid": 99, "cancelled": False}
        # junk request line / headers
        status, _ = await raw(b"GARBAGE\r\n\r\n")
        assert status == 400
        status, _ = await raw(b"POST /v1/generate HTTP/1.1\r\n"
                              b"Content-Length: -5\r\n\r\n")
        assert status == 400
        status, _ = await raw(b"POST /v1/generate HTTP/1.1\r\n"
                              b"Content-Length: 9999999\r\n\r\n")
        assert status == 400
        status, _ = await call("GET", "/v1/generate")
        assert status == 405
        # the server is still healthy after all of that
        status, payload = await call("GET", "/healthz")
        assert status == 200
        assert _json.loads(payload)["status"] == "ok"
        await server.close()

    asyncio.run(asyncio.wait_for(scenario(), timeout=60))


def test_http_cancel_route_and_midstream_disconnect():
    """Streaming cancellation end to end: POST /v1/cancel terminates a
    live stream with a typed ``cancelled`` NDJSON line, and a client
    that disconnects mid-stream gets its request cancelled server-side
    (engine slot freed) instead of decoding into a dead socket."""
    import asyncio
    import json as _json

    from repro.core.events import CancelledEvent, event_from_json
    from repro.serving import GatewayHTTPServer, RealTimeClock

    async def scenario():
        gw = Gateway(CFG, _serve(), modes=["rapid"], clock=RealTimeClock())
        server = GatewayHTTPServer(gw, host="127.0.0.1", port=0)
        try:
            await server.start()
        except OSError as e:
            pytest.skip(f"cannot bind localhost: {e}")
        port = server._server.sockets[0].getsockname()[1]

        async def post(path, body):
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            head = (f"POST {path} HTTP/1.1\r\nHost: x\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n").encode()
            writer.write(head + body)
            await writer.drain()
            await reader.readuntil(b"\r\n\r\n")      # response headers
            return reader, writer

        # -- explicit cancel via the API (request rid 0) --------------
        body = _json.dumps({"prompt_len": 64,
                            "max_new_tokens": 4000}).encode()
        reader, writer = await post("/v1/generate", body)
        for _ in range(3):                           # stream is live
            await reader.readline()
        c_reader, c_writer = await post("/v1/cancel",
                                        _json.dumps({"rid": 0}).encode())
        resp = _json.loads(await c_reader.read())
        assert resp == {"rid": 0, "cancelled": True}
        c_writer.close()
        await c_writer.wait_closed()
        tail = await asyncio.wait_for(reader.read(), timeout=30)
        last = tail.decode().splitlines()[-1]
        term = event_from_json(last)
        assert isinstance(term, CancelledEvent)
        assert term.reason == "client_cancel" and term.rid == 0
        writer.close()
        await writer.wait_closed()

        # -- abrupt disconnect mid-stream (request rid 1) -------------
        reader, writer = await post("/v1/generate", body)
        for _ in range(3):
            await reader.readline()
        writer.transport.abort()                     # RST, no goodbye
        for _ in range(400):                         # server notices on
            if gw.cancellations >= 2:                # its next write
                break
            await asyncio.sleep(0.05)
        assert gw.cancellations == 2
        recs = {r.rid: r for r in gw.metrics.records}
        assert recs[0].cancelled and recs[1].cancelled
        assert gw.health()["live_requests"] == 0
        s = gw.metrics_summary()["fleet"]
        assert s["cancelled"] == 2
        await server.close()

    asyncio.run(asyncio.wait_for(scenario(), timeout=60))


def test_http_worker_lost_streams_partial_then_typed_reject():
    """Crash round-trip over the wire: the NDJSON stream carries the
    partial tokens generated before the crash, then the terminal
    ``rejected`` line with reason=worker_lost and the partial
    ``output_len`` — never a hung socket or a bare EOF."""
    import asyncio
    import json as _json

    from repro.core.events import event_from_json
    from repro.serving import (GatewayHTTPServer, GatewayPolicy,
                               RealTimeClock)

    async def scenario():
        # fast heartbeats so death detection fits in test time
        policy = GatewayPolicy(heartbeat_s=0.05, heartbeat_timeout_s=0.2,
                               health_check_s=0.05)
        gw = Gateway(CFG, _serve(), modes=["rapid"], clock=RealTimeClock(),
                     policy=policy)
        server = GatewayHTTPServer(gw, host="127.0.0.1", port=0)
        try:
            await server.start()
        except OSError as e:
            pytest.skip(f"cannot bind localhost: {e}")
        port = server._server.sockets[0].getsockname()[1]

        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = _json.dumps({"prompt_len": 64,
                            "max_new_tokens": 4000}).encode()
        head = (f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        writer.write(head + body)
        await writer.drain()
        await reader.readuntil(b"\r\n\r\n")

        events, killed = [], False
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.strip():
                continue
            ev = event_from_json(line.decode())
            events.append(ev)
            if (not killed and isinstance(ev, TokenEvent)
                    and ev.index >= 3):
                killed = True
                gw.kill_worker(0)        # sole worker: no failover target
        writer.close()
        await writer.wait_closed()
        await server.close()

        assert killed, "stream never produced tokens"
        term = events[-1]
        assert isinstance(term, RejectedEvent)
        assert term.reason == "worker_lost"
        assert term.retries == 1             # one failover attempt made
        toks = [e for e in events if isinstance(e, TokenEvent)]
        assert [e.index for e in toks] == list(range(len(toks)))
        assert term.output_len == len(toks)  # partial progress reported

    asyncio.run(asyncio.wait_for(scenario(), timeout=60))


# ---------------------------------------------------------------------------
# real-time clock (no asyncio loop started; just the adapter contract)
# ---------------------------------------------------------------------------

def test_realtime_clock_contract():
    from repro.serving import RealTimeClock
    c = RealTimeClock()
    assert c.virtual is False and c.now == 0.0

    class _FakeLoop:
        def __init__(self):
            self.t = 100.0
            self.calls = []

        def time(self):
            return self.t

        def call_at(self, t, fn):
            self.calls.append(("at", t, fn))

        def call_later(self, dt, fn):
            self.calls.append(("later", dt, fn))

    # pre-bind schedules queue up and flush as delays at bind time
    c.after(0.25, lambda: None)
    loop = _FakeLoop()
    c.bind(loop)
    # the timebase rebases to bind: pre-bind timestamps (last_beat=0.0
    # at registration) stay comparable instead of jumping to loop.time()
    assert c.now == 0.0
    assert loop.calls == [("later", 0.25, loop.calls[0][2])]
    c.at(2.0, lambda: None)           # future: loop sees t0-offset time
    assert loop.calls[1][1] == 102.0 and c.stats.clamped == 0
    loop.t = 103.0                    # 3s of serving elapse
    assert c.now == 3.0
    c.at(2.5, lambda: None)           # past-due -> clamped to now
    assert loop.calls[2][1] == 103.0 and c.stats.clamped == 1
    c.after(-1.0, lambda: None)
    assert loop.calls[3][1] == 0.0 and c.stats.clamped == 2
    c.after(0.5, lambda: None)
    assert loop.calls[4][1] == 0.5 and c.stats.dispatched == 5
