"""Whether what the timed path served is right, and the request counts.

After the window closes, a sample drawn from the seed of the requests
the program served (finished, or cut at the close with the tokens
streamed so far; the longest among them; in a fleet, the longest of each
replica and at least one more of each) is run through the plain
reference the configuration names (``run.ref``, ``references/``) over
its prompt and served tokens, teacher forced.  At each served token the
number compared is the gap by which the reference's logit of that token
lies below the reference's best logit there.  The first served token
comes from the prefill program (``flash_prefill``), the rest from decode
steps through the slot cache (``paged_attention``) after the slot
insert, all sampled greedily, so every layer the cells name is covered.

``control`` reads the fp8 control on the same rows, the gap of the token
that the fp8 forward puts first, and judges it by the same limits: a
sound control run comes out not correct.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

ROWS = 8          # rows of the reference batch: fixed, so it compiles once


def counts(run) -> tuple:
    """(attempted, failed).  Open loop: the requests due in the window.
    Closed loop: those served at some time in the window (a caller's
    request still waiting for a slot when the callers stop was never
    served nor refused, and is not counted).  A request fails when it
    was refused, errored, or got no token at all; one still streaming
    when the run ends has not failed."""
    if run.closed_loop:
        t0, t1 = run.window
        due = [r for r in run.requests if r["end"] != "unserved"
               and r["due"] < t1 and r["end_t"] >= t0]
    else:
        due = run.due_in_window()
    bad = [r for r in due if not r["tokens"] or r["end"] not in
           ("finished", "cut")]
    return len(due), len(bad)


def collect(run, executors) -> Dict[int, dict]:
    """Prompt and served ids of each request that finished or was cut
    while streaming, from the executor that served it, with the count
    streamed to the client beside them: ``output_len`` is what the
    program reported finished, or for a cut request what was streamed."""
    out = {}
    by_rid = {r["rid"]: r for r in run.requests
              if r["end"] in ("finished", "cut") and r["rid"] is not None
              and r["tokens"]}
    for rid, rec in by_rid.items():
        rep = run.replica_of.get(rid)
        ids = None
        if rep is not None:
            try:
                ids = executors[rep].token_ids(rid)
            except KeyError:
                ids = None
        streamed = len(rec["tokens"])
        out[rid] = {"replica": rep, "prompt_len": rec["prompt_len"],
                    "streamed": streamed,
                    "output_len": rec["output_len"] if rec["end"] ==
                    "finished" else streamed, "ids": ids}
    return out


def sample(served: Dict[int, dict], seed: int) -> List[int]:
    """``ROWS`` served requests: the longest of each replica first,
    then others drawn from the seed, spread over the replicas."""
    rng = np.random.default_rng([seed, 0xC4EC])
    ok = {rid: s for rid, s in served.items() if s["ids"] is not None}
    by_rep: Dict[int, List[int]] = {}
    for rid in sorted(ok):
        by_rep.setdefault(ok[rid]["replica"], []).append(rid)
    picked = []
    for rep in sorted(by_rep):
        longest = max(by_rep[rep], key=lambda r: (ok[r]["output_len"],
                                                  ok[r]["prompt_len"], r))
        picked.append(longest)
    pools = {rep: [r for r in rng.permutation(rids) if r not in picked]
             for rep, rids in by_rep.items()}
    while len(picked) < ROWS and any(pools.values()):
        for rep in sorted(pools):
            if pools[rep] and len(picked) < ROWS:
                picked.append(int(pools[rep].pop(0)))
    return picked[:ROWS]


def verdict(lim: dict, chips: int, gap: float, compared: int,
            replicas: int, mismatch: int, lost: int) -> dict:
    """Each number compared beside its limit, and whether all hold."""
    checks = {
        "logit_gap": (gap, lim["max_logit_gap"], gap <= lim["max_logit_gap"]),
        "tokens_compared": (compared, lim["min_tokens"],
                            compared >= lim["min_tokens"]),
        "replicas_compared": (replicas, chips, replicas >= chips),
        "stream_mismatch": (mismatch, 0, mismatch == 0),
        "lost_requests": (lost, 0, lost == 0),
    }
    return {"correct": all(ok for _, _, ok in checks.values()),
            "checks": {k: {"value": v, "limit": lm}
                       for k, (v, lm, _) in checks.items()}}


def judge(run, served: Dict[int, dict], model: dict, seed: int, device,
          control: bool) -> dict:
    """The run's verdict; with ``control``, the fp8 control's beside it
    under ``control``, read on the same rows and held to the same
    limits."""
    mismatch = sum(1 for s in served.values()
                   if s["ids"] is None or s["streamed"] != s["output_len"]
                   or len(s["ids"]) < s["prompt_len"] + s["output_len"])
    # an answer that never came (open loop: none within the drain) or
    # came wrong; a refusal is a failure, not a wrong answer
    lost = sum(1 for r in run.due_in_window()
               if (r["end"] or "").startswith(("error", "out_of_order"))
               or (r["end"] == "unserved" and not run.closed_loop))
    rows = sample(served, seed)
    S = model["serve"]["max_seq_len"]
    toks = np.zeros((ROWS, S), np.int32)
    spans = []
    for i, rid in enumerate(rows):
        s = served[rid]
        n = s["prompt_len"] + s["output_len"]
        toks[i, :n] = s["ids"][:n]
        spans.append((i, s["prompt_len"] - 1, n - 1))   # predicting positions
    gap = ctrl_gap = 0.0
    gaps, ctrl_gaps = [], []
    compared = 0
    if rows:
        alt = None
        if control:
            alt = run.ref.forward(model, seed, toks, quant=True,
                                  device=device).argmax
        ref = run.ref.forward(model, seed, toks, alt=alt, device=device)
        for i, a, b in spans:
            gaps.append(ref.best[i, a:b] - ref.at_next[i, a:b])
            compared += b - a
            if control:
                ctrl_gaps.append(ref.best[i, a:b] - ref.at_alt[i, a:b])
        gap = float(max(g.max() for g in gaps))
        if control:
            ctrl_gap = float(max(g.max() for g in ctrl_gaps))
    replicas = len({served[r]["replica"] for r in rows})
    nums = dict(compared=compared, replicas=replicas, mismatch=mismatch,
                lost=lost)
    out = verdict(model["check"], run.chips, gap, **nums)
    out["gaps"] = _spread(gaps)
    if control:
        out["control"] = verdict(model["check"], run.chips, ctrl_gap, **nums)
        out["control"]["gaps"] = _spread(ctrl_gaps)
    return out


def _spread(gaps) -> dict:
    """How the gaps behind a widest one lie: their mean, their 99th
    percentile and the share of tokens not the reference's first choice
    (readings for the limit's derivation, not compared)."""
    if not gaps:
        return {}
    g = np.concatenate(gaps)
    return {"mean": float(g.mean()), "p99": float(np.percentile(g, 99)),
            "not_first": float((g > 0).mean())}
