"""Step execution backends for the generic serving engine (API v2).

An ``Executor`` runs (or prices) the steps a ``Scheduler`` decided to
launch: it turns a ``StepPlan`` into per-lane durations (and the
``StepCost`` objects the interference model needs for overlapped steps).

  * ``PerfModelExecutor`` wraps ``perfmodel.costs`` + ``perfmodel.
    interference`` — engine control flow is real, only durations are
    modelled.  Simulation tools and the golden parity tests use it.
  * ``DeviceExecutor`` runs every step on one JAX device — jitted
    prefill and decode programs over the Pallas kernels and a slot KV
    cache in device memory — samples greedily and reports the measured
    wall time of each step.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import pathlib
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.core.scheduler import SchedView, StepPlan
from repro.kernels import ops
from repro.perfmodel import batch as B
from repro.perfmodel import costs as C
from repro.perfmodel import interference as I
from repro.models.transformer import (decode_forward, forward, init_cache,
                                      init_model, write_prefill_to_cache)
from repro.perfmodel.hw import TPU_V5E, HardwareSpec


@dataclasses.dataclass(frozen=True)
class LaunchOutcome:
    """One priced lane step: wall-clock duration (host overhead included,
    Fig 6) plus the device cost the interference model consumes while
    the step is in flight."""
    duration_s: float
    cost: C.StepCost


@dataclasses.dataclass(frozen=True)
class StepOutputs:
    """Durations for every launch in a ``StepPlan`` (None = not in plan)."""
    prefill: Optional[LaunchOutcome] = None
    decode: Optional[LaunchOutcome] = None
    hybrid: Optional[LaunchOutcome] = None


class Executor:
    """Protocol: run or price a StepPlan.  Implementations must handle
    launches in plan order — prefill before decode — so a decode
    launched in the same plan sees the new prefill in flight (colocated
    interference).  Each ``LaunchOutcome.duration_s`` counts from the
    moment ``execute`` was called.

    ``max_context`` bounds prompt + output of one request (None: only
    the KV pool bounds it); ``pool_blocks`` sizes the engine's decode KV
    pool from what the executor holds (None: the scheduler's HBM model).
    ``worker`` is the replica id its spans carry.
    """

    max_context: Optional[int] = None
    worker: int = 0

    def pool_blocks(self) -> Optional[int]:
        return None

    def execute(self, plan: StepPlan, view: SchedView) -> StepOutputs:
        raise NotImplementedError

    def price_batch(self, plans: Sequence[StepPlan],
                    views: Sequence[SchedView]) -> "list[StepOutputs]":
        """Price many (plan, view) pairs in one call.  The pairs must be
        causally independent (different replicas, or speculative what-if
        pricing) — implementations may reorder the underlying cost
        evaluations.  Default: the sequential scalar path."""
        return [self.execute(p, v) for p, v in zip(plans, views)]

    def transfer_seconds(self, r, serve) -> float:
        """Disagg KV-transfer time for one request (ICI on the critical
        path, §3.2.1)."""
        raise NotImplementedError


class PerfModelExecutor(Executor):
    """Default executor: calibrated TPU-v5e perfmodel durations.

    ``colocated`` selects the paper's intra-GPU interference coupling:
    when prefill and decode share chips, an overlapped step's duration
    comes from ``interference.overlapped_times`` under the decode lane's
    resource split ``f_decode``; split-pool (disagg) lanes run at their
    own ``phase_time``.
    """

    def __init__(self, cfg, hw: HardwareSpec = TPU_V5E,
                 colocated: bool = True,
                 lane_chips: Optional[Dict[str, int]] = None):
        self.cfg = cfg
        self.hw = hw
        self.colocated = colocated
        self.lane_chips = lane_chips or {}

    def _chips(self, lane: str, serve) -> int:
        return self.lane_chips.get(lane, serve.chips)

    # -- host-side scheduling overhead (Fig 6a vs 6b) -----------------------
    def _step_time(self, device_s: float, serve) -> float:
        cpu = serve.scheduler_overhead_ms / 1e3
        if serve.async_scheduling:
            return max(device_s, cpu)
        return device_s + cpu

    def execute(self, plan: StepPlan, view: SchedView) -> StepOutputs:
        return self._assemble(plan, view, C.prefill_cost,
                              C.chunk_prefill_cost, C.decode_cost)

    def price_batch(self, plans: Sequence[StepPlan],
                    views: Sequence[SchedView]) -> "list[StepOutputs]":
        """Batched pricing: every cost any plan needs is collected,
        deduplicated by operating point, and priced through the
        ``perfmodel.batch`` array layer in one call per cost kind — the
        per-call ``lru_cache`` memoization of the scalar path becomes
        vectorized key dedup here.  Control flow is ``_assemble`` both
        times (a recording pass, then a lookup pass), so the batched and
        scalar paths cannot drift; the costs themselves are bit-identical
        by the batch layer's contract."""
        pre_k: dict = {}
        chk_k: dict = {}
        dec_k: dict = {}

        def rec_pre(cfg, seq_lens, tp):
            pre_k[(tuple(seq_lens), tp)] = None
            return C.ZERO_COST

        def rec_chk(cfg, chunk_tokens, ctx_so_far, tp):
            chk_k[(chunk_tokens, ctx_so_far, tp)] = None
            return C.ZERO_COST

        def rec_dec(cfg, bs, ctx_total, tp):
            dec_k[(bs, ctx_total, tp)] = None
            return C.ZERO_COST

        for p, v in zip(plans, views):
            self._assemble(p, v, rec_pre, rec_chk, rec_dec)

        if pre_k:
            ks = list(pre_k)
            got = B.prefill_cost(self.cfg, [k[0] for k in ks],
                                 np.array([k[1] for k in ks]))
            for i, k in enumerate(ks):
                pre_k[k] = got.item(i) if any(k[0]) else C.ZERO_COST
        if chk_k:
            ks = list(chk_k)
            got = B.chunk_prefill_cost(
                self.cfg, [k[0] for k in ks], [k[1] for k in ks],
                np.array([k[2] for k in ks]))
            for i, k in enumerate(ks):
                chk_k[k] = got.item(i)
        if dec_k:
            ks = list(dec_k)
            got = B.decode_cost(self.cfg, [k[0] for k in ks],
                                [k[1] for k in ks],
                                np.array([k[2] for k in ks]))
            for i, k in enumerate(ks):
                dec_k[k] = got.item(i) if k[0] else C.ZERO_COST

        def use_pre(cfg, seq_lens, tp):
            return pre_k[(tuple(seq_lens), tp)]

        def use_chk(cfg, chunk_tokens, ctx_so_far, tp):
            return chk_k[(chunk_tokens, ctx_so_far, tp)]

        def use_dec(cfg, bs, ctx_total, tp):
            return dec_k[(bs, ctx_total, tp)]

        return [self._assemble(p, v, use_pre, use_chk, use_dec)
                for p, v in zip(plans, views)]

    def _assemble(self, plan: StepPlan, view: SchedView, prefill_cost,
                  chunk_prefill_cost, decode_cost) -> StepOutputs:
        """The one pricing control flow: which costs a plan needs and how
        they couple through the interference model.  ``execute`` injects
        the memoized scalar pricers; ``price_batch`` injects recorders,
        then lookups into the batched results."""
        serve = view.serve
        p_out = d_out = h_out = None
        if plan.prefill is not None:
            chips = self._chips("prefill", serve)
            batch = plan.prefill.batch
            if any(r.cached_prefix_len for r in batch):
                # session prefix skip: each request only prefills its new
                # suffix, attending over the cached prefix as context
                cost = C.ZERO_COST
                for r in batch:
                    cost = cost + chunk_prefill_cost(
                        self.cfg, r.prefill_tokens_needed,
                        r.cached_prefix_len, chips)
            else:
                cost = prefill_cost(
                    self.cfg, [r.prompt_len for r in batch], chips)
            dlane = view.lanes.get("decode", None)
            if self.colocated and dlane is not None and dlane.busy and \
                    dlane.cost is not None:
                dur = I.overlapped_times(cost, dlane.cost, self.hw, chips,
                                         f_decode=dlane.f_decode).t_prefill
            else:
                dur = I.phase_time(cost, self.hw, chips)
            p_out = LaunchOutcome(self._step_time(dur, serve), cost)
        if plan.decode is not None:
            chips = self._chips("decode", serve)
            # running batch context from the queue's incremental counter
            # (identical integer sum, without the O(batch) walk)
            bs = len(view.running) + len(plan.decode.joins)
            ctx_total = float(view.running.ctx_tokens +
                              sum(r.context_len for r in plan.decode.joins))
            cost = decode_cost(self.cfg, bs, ctx_total, chips)
            if p_out is not None:
                p_cost = p_out.cost          # launched in this same plan
            else:
                plane = view.lanes.get("prefill", None)
                p_cost = plane.cost if plane is not None and plane.busy \
                    else None
            if self.colocated and p_cost is not None:
                dur = I.overlapped_times(p_cost, cost, self.hw, chips,
                                         f_decode=plan.decode.f_decode
                                         ).t_decode
            else:
                dur = I.phase_time(cost, self.hw, chips)
            d_out = LaunchOutcome(self._step_time(dur, serve), cost)
        if plan.hybrid is not None:
            chips = self._chips("step", serve)
            cost = C.ZERO_COST
            for r, take in plan.hybrid.chunks:
                cost = cost + chunk_prefill_cost(
                    self.cfg, take,
                    r.cached_prefix_len + r.prefill_tokens_done, chips)
            bs = len(view.running)
            if bs:
                ctx_total = float(view.running.ctx_tokens)
                cost = cost + decode_cost(self.cfg, bs, ctx_total, chips)
            dur = I.phase_time(cost, self.hw, chips)
            h_out = LaunchOutcome(self._step_time(dur, serve), cost)
        return StepOutputs(prefill=p_out, decode=d_out, hybrid=h_out)

    def transfer_seconds(self, r, serve) -> float:
        return C.kv_transfer_bytes(self.cfg, r.prompt_len) / \
            (serve.kv_transfer_gbps * 1e9)



# ---------------------------------------------------------------------------
# Device execution
# ---------------------------------------------------------------------------

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Persistent compile cache for the chip entry points.  JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself when it is set; otherwise the
    cache lives at one fixed path inside the checkout (git ignores it)
    so that a later process on the same checkout finds it again.
    Returns the directory in use."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def prefill_buckets(max_seq_len: int, smallest: int = 128) -> List[int]:
    """Padded prefill lengths: powers of two from ``smallest`` below
    ``max_seq_len``, then ``max_seq_len`` itself."""
    out, b = [], smallest
    while b < max_seq_len:
        out.append(b)
        b *= 2
    return out + [max_seq_len]


def prompt_ids(seed: int, rid: int, n: int, vocab: int) -> np.ndarray:
    """The token ids of request ``rid``'s prompt: drawn from ``(seed,
    rid)``, so every executor with the same seed serves the same prompt
    for the same request id."""
    return np.random.default_rng([seed, rid]).integers(
        0, vocab, size=n, dtype=np.int32)


def param_key(seed: int):
    """The key random weights are drawn from.  The ``rbg`` generator
    compiles a full-width init in a fraction of threefry's time."""
    return jax.random.key(seed, impl="rbg")


def _init_params(key, *, cfg):
    return init_model(key, cfg)[0]


def _stack_routes(routes) -> tuple:
    """Held experts' pair counts ``{"pos<i>": (P, Eh)}`` as a 1-tuple of
    one (L, Eh) array in layer order; () for a model with no held share,
    whose step programs keep their three outputs."""
    if not routes:
        return ()
    keys = sorted(routes, key=lambda k: int(k[3:]))
    return (jnp.stack([routes[k] for k in keys], 1).reshape(
        -1, routes[keys[0]].shape[-1]),)


@functools.partial(jax.jit, static_argnames=("cfg", "impl"))
def _prefill_step(params, tokens, length, *, cfg, impl):
    """tokens (1, Lb) right-padded to a bucket; length (1,) valid tokens.
    Returns (next token (1,), its logits (1, V), the prompt's KV as a
    one-slot cache of Lb positions (a window layer's ring holds the last
    window of the valid tokens)), then, for a model that holds a share
    of its experts, their pair counts (L, Eh)."""
    Lb = tokens.shape[1]
    pos = jnp.arange(Lb, dtype=jnp.int32)[None]
    logits, aux = forward(params, cfg, tokens, pos, impl=impl,
                          return_aux=True, last_only=True, lengths=length)
    kv = write_prefill_to_cache(cfg, init_cache(cfg, 1, Lb), aux, Lb,
                                lengths=length)
    logits = logits[:, 0, :cfg.vocab_size]
    return (jnp.argmax(logits, -1).astype(jnp.int32), logits, kv) + \
        _stack_routes(aux.get("route"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_slot(cache, kv, row):
    """Write a one-slot cache (leaves (P, H, 1, D, Lb), or a window
    layer's ring) into slot ``row`` of the slot cache (leaves (P, H, B,
    D, Sc)) in place."""
    return jax.tree.map(
        lambda c, x: jax.lax.dynamic_update_slice(
            c, x.astype(c.dtype), (0, 0, row, 0, 0)), cache, kv)


@functools.partial(jax.jit, static_argnames=("cfg", "impl"),
                   donate_argnums=(1,))
def _decode_step(params, cache, tokens, lens, *, cfg, impl):
    """One token for every slot: tokens (B,) at positions ``lens`` (B,).
    Returns (next tokens (B,), logits (B, V), updated cache), then the
    held experts' pair counts (L, Eh) as ``_prefill_step`` does."""
    logits, cache, routes = decode_forward(
        params, cfg, tokens[:, None], lens[:, None], cache, lens, impl=impl,
        return_routing=True)
    logits = logits[:, 0, :cfg.vocab_size]
    return (jnp.argmax(logits, -1).astype(jnp.int32), logits, cache) + \
        _stack_routes(routes)


class DeviceExecutor(Executor):
    """Runs the rapid scheduler's steps on one JAX device.

    Construction places ``init_model(param_key(seed))`` params on
    ``device``, allocates the slot cache (``serve.max_batch_slots`` slots
    of ``serve.max_seq_len`` tokens) and compiles and runs every program
    the serving loop will call — one prefill per bucket length, the slot
    insert per bucket, the decode step — so serving never compiles.

    A prefill runs each request alone, right-padded to its bucket, and
    keeps the prompt's KV on the device until the request joins the
    decode batch; a join copies it into a free slot.  Layers may mix
    full and window attention: a window layer's slot is a ring of its
    window, written at ``position % window``.  An expert layer may hold
    one chip's share of the experts (``MoEConfig.held``).  Decode runs
    every slot each step (free slots compute and are ignored).  Sampling
    is greedy.  Every step blocks until the device is done and reports
    the wall time since ``execute`` was called.

    Spans (``repro.obs``): ``rapid.execute`` around each call that
    launches work, ``rapid.queue`` from a request's engine queue entry to
    its ``rapid.prefill``, ``rapid.insert`` per slot insert,
    ``rapid.decode`` per decode step, and inside prefill and decode
    ``rapid.put`` (inputs to the device) and ``rapid.sync`` (the wait
    and the read-back of the next tokens).  Counters: ``steps``, valid
    and padded prefill tokens, live and free decode slot-steps, and the
    KV blocks decode attention fetches and spans (``ops.dense_blocks``;
    the fetched ones are also ``rapid.decode``'s ``kv_blocks_read``).  With a
    held share of experts the routing is read back with the tokens:
    ``rapid.prefill`` and ``rapid.decode`` carry its attributes
    (``_routed``) and the counters ``moe_pairs_routed`` (tokens x top_k x layers) and
    ``moe_pairs_held`` grow.

    Prompts are token ids drawn from ``(seed, rid)`` (``prompt_ids``);
    ``token_ids(rid)`` returns prompt plus generated ids.  With
    ``record_logits`` the served logits are kept by input position:
    ``logits[rid][p]`` predicted the token at ``p + 1``.

    ``interpret=True`` runs the kernels in Pallas interpret mode, for
    tests on the CPU; without it a device that is not a TPU is refused.
    """

    def __init__(self, cfg, serve, device, *, seed: int = 0,
                 interpret: bool = False, record_logits: bool = False):
        if device.platform != "tpu" and not interpret:
            raise RuntimeError(
                f"DeviceExecutor compiles its kernels for a TPU, but "
                f"{device} is a {device.platform!r} device (tests on the "
                f"CPU pass interpret=True)")
        if serve.mode != "rapid":
            raise ValueError(
                f"DeviceExecutor runs the rapid scheduler only, not "
                f"{serve.mode!r}")
        refused = [f"{m} mixers" for m in sorted(
            {cfg.mixer_at(i) for i in range(cfg.period)} - {"attn"})]
        if cfg.frontend != "token":
            refused.append(f"the {cfg.frontend!r} frontend")
        if refused:
            raise ValueError(
                f"{cfg.name}: DeviceExecutor serves token-input models "
                f"whose layers are full or window attention, not "
                f"{', '.join(refused)}")
        if serve.max_seq_len % serve.page_size:
            raise ValueError("serve.max_seq_len must be a multiple of "
                             "serve.page_size")
        self.cfg = cfg
        self.serve = serve
        self.device = device
        self.seed = seed
        self.impl = "interpret" if interpret else "pallas"
        self.max_context = serve.max_seq_len
        self.buckets = prefill_buckets(serve.max_seq_len)
        self.logits: Optional[Dict[int, Dict[int, np.ndarray]]] = \
            {} if record_logits else None
        B = serve.max_batch_slots
        self._row_rid: List[Optional[int]] = [None] * B
        self._tokens = np.zeros(B, np.int32)
        self._lens = np.zeros(B, np.int32)
        self._ids: Dict[int, List[int]] = {}
        self._pending: Dict[int, tuple] = {}     # rid -> (kv, length)
        self._calls = 0

        t0 = time.perf_counter()
        on_device = SingleDeviceSharding(device)
        self.params = jax.jit(functools.partial(_init_params, cfg=cfg),
                              out_shardings=on_device)(param_key(seed))
        self.cache = jax.jit(
            functools.partial(init_cache, cfg, B, serve.max_seq_len),
            out_shardings=on_device)()
        self._warm_up()
        self.compile_s = time.perf_counter() - t0

    def _put(self, x):
        return jax.device_put(x, self.device)

    def _warm_up(self) -> None:
        """Compile and run every program serving will call."""
        for Lb in self.buckets:
            kv = _prefill_step(
                self.params, self._put(np.zeros((1, Lb), np.int32)),
                self._put(np.ones(1, np.int32)), cfg=self.cfg,
                impl=self.impl)[2]
            self.cache = _insert_slot(self.cache, kv, self._put(np.int32(0)))
        out = _decode_step(self.params, self.cache, self._put(self._tokens),
                           self._put(self._lens), cfg=self.cfg,
                           impl=self.impl)
        self.cache = out[2]
        jax.block_until_ready(out)

    # -- Executor protocol ---------------------------------------------------
    def pool_blocks(self) -> int:
        s = self.serve
        return s.max_batch_slots * s.max_seq_len // s.page_size

    def execute(self, plan: StepPlan, view: SchedView) -> StepOutputs:
        if plan.hybrid is not None or (
                plan.prefill is not None and plan.prefill.pool is not None):
            raise ValueError("DeviceExecutor runs the rapid scheduler "
                             "only (no hybrid or split-pool steps)")
        t0 = time.perf_counter()
        self._calls += 1
        if plan.prefill is None and plan.decode is None:
            return StepOutputs()
        obs.count("steps")
        p_out = d_out = None
        with obs.span("rapid.execute", worker=self.worker, step=self._calls):
            if plan.prefill is not None:
                # the previous prefill has completed: every prompt KV
                # still held is for a request in pending_join, or dead
                live = {r.rid for r in view.queues["pending_join"]}
                for rid in [k for k in self._pending if k not in live]:
                    del self._pending[rid]
                for r in plan.prefill.batch:
                    self._prefill(r)
                p_out = LaunchOutcome(time.perf_counter() - t0, C.ZERO_COST)
            if plan.decode is not None:
                self._decode(list(view.running) + list(plan.decode.joins))
                d_out = LaunchOutcome(time.perf_counter() - t0, C.ZERO_COST)
        return StepOutputs(prefill=p_out, decode=d_out)

    # -- steps ---------------------------------------------------------------
    def _routed(self, counts: np.ndarray, tokens: int) -> dict:
        """Count one step's routing, its held experts' pairs (L, Eh) for
        ``tokens`` computed, and return its span attributes:
        ``expert_pairs`` in all, ``expert_max`` the sum over layers of
        each layer's most loaded held expert, ``experts_live`` the
        (layer, held expert) groups that got a pair."""
        obs.count("moe_pairs_routed",
                  tokens * self.cfg.moe.top_k * counts.shape[0])
        obs.count("moe_pairs_held", int(counts.sum()))
        return {"expert_pairs": int(counts.sum()),
                "expert_max": int(counts.max(axis=1).sum()),
                "experts_live": int((counts > 0).sum())}

    def token_ids(self, rid: int) -> List[int]:
        return list(self._ids[rid])

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"{n} context tokens exceed max_seq_len "
                         f"{self.max_context}")

    def _prefill(self, r) -> None:
        # a preempted request re-prefills prompt + what it has emitted; a
        # token computed for a step whose emission was preempted is dropped
        n = r.prompt_len + r.tokens_generated
        ids = self._ids.get(r.rid)
        if ids is None:
            ids = self._ids[r.rid] = list(
                prompt_ids(self.seed, r.rid, n, self.cfg.vocab_size))
        if len(ids) < n:
            raise RuntimeError(f"request {r.rid}: {n} context tokens "
                               f"needed, {len(ids)} known")
        del ids[n:]
        Lb = self._bucket(n)
        obs.count("prefill_tokens_valid", n)
        obs.count("prefill_tokens_padded", Lb - n)
        w = self.worker
        with obs.span("rapid.prefill", worker=w, rid=r.rid, valid=n,
                      bucket=Lb) as sp:
            if r.t_queued_ns is not None:
                obs.record("rapid.queue", r.t_queued_ns, sp.start, worker=w,
                           rid=r.rid, preempted=int(r.preemptions > 0))
            with obs.span("rapid.put", worker=w):
                toks = np.zeros((1, Lb), np.int32)
                toks[0, :n] = ids
                toks = self._put(toks)
                length = self._put(np.array([n], np.int32))
            tok, logits, kv, *route = _prefill_step(
                self.params, toks, length, cfg=self.cfg, impl=self.impl)
            with obs.span("rapid.sync", worker=w):
                jax.block_until_ready((tok, kv))
                # read on the host: indexing the device array would compile
                ids.append(int(np.asarray(tok)[0]))
                if route:
                    sp.set(**self._routed(np.asarray(route[0]), n))
        self._pending[r.rid] = (kv, n)
        if self.logits is not None:
            self.logits.setdefault(r.rid, {})[n - 1] = \
                np.asarray(logits[0], np.float32)

    def _decode(self, batch) -> None:
        want = {r.rid for r in batch}
        for i, rid in enumerate(self._row_rid):
            if rid is not None and rid not in want:    # finished/preempted
                self._row_rid[i] = None
                self._tokens[i] = self._lens[i] = 0
        held = set(self._row_rid)
        for r in batch:
            if r.rid in held:
                continue
            if r.rid not in self._pending:
                raise RuntimeError(f"request {r.rid} joins decode without "
                                   f"a prefilled KV")
            if None not in self._row_rid:
                raise RuntimeError("decode batch exceeds max_batch_slots")
            i = self._row_rid.index(None)
            kv, n = self._pending.pop(r.rid)
            with obs.span("rapid.insert", worker=self.worker, rid=r.rid,
                          slot=i):
                self.cache = _insert_slot(self.cache, kv,
                                          self._put(np.int32(i)))
            self._row_rid[i] = r.rid
            self._lens[i] = n
            self._tokens[i] = self._ids[r.rid][-1]
        if self._lens.max() >= self.max_context:
            raise RuntimeError("a decode step would write past its slot")
        slots = len(self._row_rid)
        live = slots - self._row_rid.count(None)
        obs.count("decode_slot_steps_live", live)
        obs.count("decode_slot_steps_free", slots - live)
        read = grid = 0
        for pos in range(self.cfg.period):
            r, g = ops.dense_blocks(self.cache[f"pos{pos}"]["k"],
                                    self._lens + 1, self.cfg.window_at(pos))
            read, grid = read + r, grid + g
        obs.count("decode_kv_blocks_read", read)
        obs.count("decode_kv_blocks_grid", grid)
        w = self.worker
        with obs.span("rapid.decode", worker=w, live=live, slots=slots,
                      kv_blocks_read=read) as sp:
            with obs.span("rapid.put", worker=w):
                tokens = self._put(self._tokens)
                lens = self._put(self._lens)
            nxt, logits, self.cache, *route = _decode_step(
                self.params, self.cache, tokens, lens, cfg=self.cfg,
                impl=self.impl)
            with obs.span("rapid.sync", worker=w):
                jax.block_until_ready((nxt, self.cache))
                nxt = np.asarray(nxt)
                rec = np.asarray(logits, np.float32) \
                    if self.logits is not None else None
                if route:
                    sp.set(**self._routed(np.asarray(route[0]), slots))
        for i, rid in enumerate(self._row_rid):
            if rid is None:
                continue
            if rec is not None:
                self.logits.setdefault(rid, {})[int(self._lens[i])] = rec[i]
            self._ids[rid].append(int(nxt[i]))
            self._tokens[i] = nxt[i]
            self._lens[i] += 1
