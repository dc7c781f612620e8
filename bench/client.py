"""Load generator: a child process that never imports JAX.

    python3 bench/client.py < plan.json

Reads the plan (``bench/traffic.py``'s ``build`` plus ``port`` and
``drain_s``) on standard input, sends every request over
``POST /v1/generate`` on localhost with a stdlib asyncio client, and
prints on standard output first ``window <t0> <t1>`` as soon as the
window is fixed, then one JSON object: the window, how late the sends
ran, and each request's times on ``time.monotonic()`` (the clock the
server's process reads too).

Open loop: each request is sent at its due time, ``t0 + offset``, and
timed from then.  After the window closes no request is sent; the
client waits up to ``drain_s`` for every request due in the window to
get its first token, then disconnects the rest (the server cancels
them).  Closed loop: ``clients`` callers each send their next request
when the last one ends; the window opens when ``fill`` requests have a
first token and are still streaming, and at its close the callers stop
and disconnect.  A request stopped so ends ``cut`` if it was streaming
and ``unserved`` if no token had come.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time

now = time.monotonic


async def _post(port: int, prompt: int, max_new: int):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"prompt_len": prompt,
                       "max_new_tokens": max_new}).encode()
    writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: localhost\r\n"
                 b"Content-Length: " + str(len(body)).encode() +
                 b"\r\n\r\n" + body)
    await writer.drain()
    status = await reader.readline()
    if b" 200 " not in status:
        writer.close()
        raise ConnectionError(f"answered {status!r}")
    while (await reader.readline()) not in (b"\r\n", b""):
        pass
    return reader, writer


class Client:
    def __init__(self, plan: dict):
        self.plan = plan
        self.port = plan["port"]
        self.records = []
        self.writers = set()
        self.window = None
        self.window_set = asyncio.Event()
        self.streaming = 0           # requests with a first token, open

    def _open_window(self, t0: float) -> None:
        self.window = (t0, t0 + self.plan["seconds"])
        print(f"window {self.window[0]!r} {self.window[1]!r}", flush=True)
        self.window_set.set()

    async def request(self, i: int, due: float, prompt: int,
                      max_new: int) -> dict:
        rec = {"i": i, "rid": None, "due": due, "sent": now(),
               "prompt_len": prompt, "max_new": max_new, "tokens": [],
               "end": None, "end_t": None, "output_len": None}
        self.records.append(rec)
        writer = None
        first = False
        try:
            reader, writer = await _post(self.port, prompt, max_new)
            self.writers.add(writer)
            while True:
                line = await reader.readline()
                if not line:
                    break
                ev = json.loads(line)
                rec["rid"] = ev["rid"]
                if ev["type"] == "token":
                    rec["tokens"].append(now())
                    if ev["index"] != len(rec["tokens"]) - 1:
                        rec["end"] = "out_of_order"
                    if not first:
                        first = True
                        self.streaming += 1
                        self._on_first()
                elif ev["type"] in ("finished", "rejected", "cancelled"):
                    if rec["end"] is None:
                        rec["end"] = ev["type"]
                    rec["output_len"] = ev.get("output_len")
                    break
        except (ConnectionError, OSError, ValueError) as e:
            rec["end"] = rec["end"] or f"error: {e}"
        except asyncio.CancelledError:
            # stopped by the client at the close: "cut" while streaming,
            # "unserved" if no token had come
            rec["end"] = rec["end"] or ("cut" if first else "unserved")
        finally:
            rec["end_t"] = now()
            if first:
                self.streaming -= 1
            if writer is not None:
                self.writers.discard(writer)
                writer.close()
        return rec

    def _on_first(self) -> None:
        fill = self.plan.get("fill")
        if fill and self.window is None and self.streaming >= fill:
            self._open_window(now())

    async def run_open(self) -> None:
        reqs = self.plan["requests"]
        t0 = now() + 0.2 + self.plan.get("lead_s", 0.0)
        self._open_window(t0)
        tasks = []
        for i, (off, p, g) in enumerate(reqs):
            due = t0 + off
            wait = due - now()
            if wait > 0:
                await asyncio.sleep(wait)
            tasks.append(asyncio.create_task(self.request(i, due, p, g)))
        t1 = self.window[1]
        if now() < t1:
            await asyncio.sleep(t1 - now())
        deadline = now() + self.plan.get("drain_s", 60.0)
        while now() < deadline and any(
                not r["tokens"] and r["end"] is None
                for r in self.records if r["due"] >= t0):
            await asyncio.sleep(0.05)
        await self._stop(tasks)

    async def run_closed(self) -> None:
        reqs = list(enumerate(self.plan["requests"]))
        pending = iter(reqs)
        stop = asyncio.Event()

        async def caller():
            for i, (_, p, g) in pending:
                if stop.is_set():
                    return
                await self.request(i, now(), p, g)

        tasks = [asyncio.create_task(caller())
                 for _ in range(self.plan["clients"])]
        await asyncio.wait_for(self.window_set.wait(),
                               self.plan.get("fill_timeout_s", 300.0))
        t1 = self.window[1]
        if now() < t1:
            await asyncio.sleep(t1 - now())
        stop.set()
        await self._stop(tasks)

    async def _stop(self, tasks) -> None:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for w in list(self.writers):
            w.close()


def main() -> int:
    plan = json.loads(sys.stdin.read())
    client = Client(plan)
    run = client.run_open if plan["mode"] == "open" else client.run_closed
    asyncio.run(run())
    late = [r["sent"] - r["due"] for r in client.records]
    print(json.dumps({"window": client.window, "requests": client.records,
                      "late_s": {"max": max(late, default=0.0),
                                 "mean": sum(late) / max(len(late), 1)}}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
