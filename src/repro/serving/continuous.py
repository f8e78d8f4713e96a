"""Continuous-batching serve engine with in-flight fault recovery.

``ServeEngine`` (engine.py) is a *static*-batch engine: it pads requests
into lockstep batches, prefills each batch from scratch, and the whole
batch finishes together — so a short request queued behind a long one
pays the long one's decode tail (head-of-line blocking), and the tested
``WidthSwapper.reshape_states`` never runs against live state because
every boundary starts from a fresh prefill.  This module is the step
from that batch demo toward a loaded server:

  * **Slot-based continuous batching** — the engine owns ``batch_slots``
    decode slots over one shared KV cache; requests *join in flight*
    (a one-request prefill written into a free slot at its own
    position — the ragged-decode path in ``models.transformer`` scatters
    cache writes per slot) and *leave in flight* the moment they finish,
    freeing the slot for the next queued request.  No request ever waits
    for an unrelated request's tail.
  * **Admission + watchdogs** — joins go through the existing
    :class:`~repro.serving.engine.AdmissionControl` (deadline projection
    against an EWMA of per-request service times); once decoding, a
    per-request watchdog sheds any request that exceeds its deadline
    *during* decode (partial tokens returned, ``deadline_missed=True``)
    instead of letting a doomed request occupy a slot.
  * **Recoverable boundary transactions** — at a width-plan boundary the
    engine swaps params through ``WidthSwapper.apply_guarded`` and
    carries every live KV cache across via ``reshape_states`` (exact
    when the plan shrinks heads).  The crossing is a transaction: if the
    swap rolls back or the KV reshape faults
    (``serving.chaos.ReshapeFailureInjector``), the engine restores the
    canonical tree + fresh state and *requeues* every in-flight request
    with its already-generated tokens intact — bounded retries
    (``max_retries``), never a silent drop.  ``Result.retries`` counts
    requeues and ``Result.recovered`` marks requests that survived one.
    A boundary that would *grow* KV heads requeues live requests the
    same way (their history re-prefills at the new width) rather than
    decoding against zero-history head slots.
  * **Graceful drain** — :meth:`ContinuousServeEngine.drain` stops
    admitting, sheds the waiting queue, finishes (or sheds, on budget
    exhaustion) the in-flight slots, and returns a :class:`Ledger` in
    which every submitted request is accounted for as
    finished / shed / failed — the sums are exact by construction.
  * **Open-loop load** — :class:`Arrival` timestamps requests on the
    engine clock; ``serving.chaos.open_loop_arrivals`` generates
    Poisson/burst traffic per class on a ``VirtualClock`` so tail
    percentiles (p50/p99/p99.9 via ``chaos.TailReport``) are exactly
    reproducible from a seed.
  * **Spans** — with ``spans`` on, every phase of :meth:`step` opens a
    profiler span (``serving.spans``): ``engine.step`` around the body;
    inside it ``engine.deliver``, ``engine.boundary``, ``engine.admit``,
    ``engine.prefill`` (holding ``engine.chunk`` per chunk call and
    ``engine.commit``), ``engine.inputs`` (host to device),
    ``engine.decode``, ``engine.sample``, ``engine.sync`` (device to
    host) and ``engine.retire``; ``engine.write_slot`` wherever a slot is
    written.  Off (the default) they cost one function call each.

Determinism contract: with a ``VirtualClock`` + ``batch_cost_fn`` every
join, shed, boundary crossing, and requeue is a pure function of the
seeds — the chaos tier asserts exact ledgers, not statistics.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.serving.engine import Request, Result, WidthPlan
from repro.serving.spans import span


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One open-loop arrival: a request hitting the server at time ``t``
    (engine-clock seconds), tagged with its traffic class for per-class
    tail reporting."""

    t: float
    request: Request
    klass: str = ""


@dataclasses.dataclass(frozen=True)
class BoundaryEvent:
    """One width-plan boundary crossing attempt, in ``boundary_log``."""

    step: int                 # engine step index at the crossing
    plan_name: str            # traffic class of the target plan
    outcome: str              # "ok" | "swap_rolled_back" |
    #                           "reshape_failed" | "requeued_grow"
    requeued: int             # in-flight requests sent back to the queue
    error: str = ""           # repr of the mid-boundary exception, if any
    carried: int = 0          # in-flight requests whose KV crossed live


@dataclasses.dataclass(frozen=True)
class ChunkEvent:
    """One chunked-prefill fault, in ``chunk_log``: the request requeued
    holding ``committed`` prefilled tokens — its recovery checkpoint."""

    step: int                 # engine step index at the fault
    rid: int                  # faulted request
    committed: int            # prefill tokens surviving as checkpoint
    error: str = ""           # repr of the chunk exception


@dataclasses.dataclass(frozen=True)
class Ledger:
    """Complete accounting of a serve run: every submitted request ends
    in exactly one terminal state.  ``evicted`` counts requests handed
    off to another replica by ``evict_in_flight`` — terminal *on this
    engine* (the router re-submits them elsewhere), so they count toward
    ``accounted`` here and exactly one engine ultimately finishes,
    sheds, or fails each logical request."""

    submitted: int
    finished: int
    shed: int
    failed: int
    in_flight: int            # non-terminal (0 after drain())
    queued: int               # non-terminal (0 after drain())
    evicted: int = 0          # migrated off this engine (router failover)

    @property
    def accounted(self) -> int:
        return self.finished + self.shed + self.failed + self.evicted

    @property
    def complete(self) -> bool:
        """True when every submitted request reached a terminal state."""
        return self.accounted == self.submitted \
            and self.in_flight == 0 and self.queued == 0


@dataclasses.dataclass
class _Tracked:
    """Engine-internal per-request bookkeeping.

    The three ``chunk_*`` fields are the chunked-prefill checkpoint: a
    slot-local decode pytree holding every committed chunk's KV rows,
    plus the shape/effective head vectors it was built under.  The
    checkpoint travels with the request through requeues and replica
    migrations; it is resumable exactly when both head vectors still
    match the engine's active ones (otherwise the prefill restarts —
    never silently decodes against stale-width rows)."""

    rid: int
    request: Request
    klass: str
    arrival_t: float
    generated: List[int] = dataclasses.field(default_factory=list)
    retries: int = 0
    join_t: float = 0.0
    prefill_done: int = 0                       # committed prefill tokens
    chunk_state: Optional[dict] = None          # batch-1 decode pytree
    chunk_heads: Optional[np.ndarray] = None    # KV *shape* heads of it
    chunk_eff: Optional[np.ndarray] = None      # effective heads of it


class ContinuousServeEngine:
    """Requests join and leave the running decode batch in flight.

    The engine owns one decode-state pytree shaped ``(batch_slots,
    max_len, ...)`` (``models.transformer.init_decode_state`` layout) and
    a per-slot position vector; decode steps run all occupied slots in
    one ragged ``decode_step`` call (vector ``pos``).  Joining writes a
    single-request prefill into a free slot's rows; leaving just frees
    the slot.  Width-plan boundaries re-shape the *live* cache through
    ``WidthSwapper.reshape_states`` — see the module docstring for the
    transaction/recovery semantics.

    Decoder-only models only (``cfg.is_encdec`` is rejected): cross
    -attention caches have no slot-local rewrite path.
    """

    def __init__(self, params, cfg: ModelConfig, *, max_len: int = 512,
                 batch_slots: int = 4, rng_seed: int = 0,
                 planner=None, swapper=None, admission=None, degrader=None,
                 clock: Callable[[], float] = time.monotonic,
                 batch_cost_fn=None, max_retries: int = 2,
                 boundary_every: int = 4, boundary_cooldown: int = 8,
                 compile_cache=None,
                 prefill_bucketing: Optional[bool] = None,
                 prefill_bucket_min: int = 8,
                 prefill_chunk: Optional[int] = None,
                 step_token_budget: Optional[int] = None,
                 chunk_fault_hook: Optional[Callable[[], None]] = None,
                 spans=False):
        if cfg.is_encdec:
            raise ValueError("continuous batching supports decoder-only "
                             "models (no cross-attention cache rewrite)")
        if degrader is not None and admission is None:
            raise ValueError(
                "a degradation controller needs an AdmissionControl as "
                "its overload-signal source; pass admission= too")
        self.cfg = cfg
        self.max_len = int(max_len)
        self.slots = int(batch_slots)
        self.rng = jax.random.PRNGKey(rng_seed)
        self.planner = planner
        self.swapper = swapper
        self.admission = admission
        self.degrader = degrader
        self.clock = clock
        # profiler spans of the step's phases (serving.spans.span): off,
        # on, or a span factory; the caller may switch it at any time
        self.spans = spans
        self.batch_cost_fn = batch_cost_fn
        self.max_retries = max(int(max_retries), 0)
        # Plan boundaries are only *considered* every `boundary_every`
        # engine steps (a continuous engine has no natural batch edge),
        # and after a failed crossing the engine serves `boundary_cooldown`
        # steps on the canonical tree before retrying — so a crash-looping
        # swap cannot starve the requeued requests out of their retries.
        self.boundary_every = max(int(boundary_every), 1)
        self.boundary_cooldown = max(int(boundary_cooldown), 0)

        # Active serving state: params + the realized widths they carry.
        self.params_active = params
        self._canonical = params if swapper is None else swapper.full_params
        n_refs = len(tfm.decoder_layer_refs(cfg))
        self._full_heads = np.full(n_refs, cfg.n_heads, dtype=np.int64)
        self._heads_active = self._full_heads.copy()
        # Head counts defining the KV-cache SHAPES, which differ from
        # `_heads_active` (the effective head values) exactly when the
        # active plan is realized as a zero-mask: masked params keep
        # canonical shapes, so reshape_states must source from the shape
        # vector while grow-detection compares effective values.
        self._shape_heads = self._full_heads.copy()
        self._masked_active = False
        self._plan_active: Optional[WidthPlan] = None
        self._key_active: Optional[tuple] = None

        # Prefill length bucketing: pow2-pad join prefills so the number
        # of distinct prefill shapes (jit traces / AOT executables) is
        # bounded by log2(max_len), not one per distinct prompt length.
        # Exact only for pure global-causal-attention dense stacks:
        # local-attention ring caches rotate by the *total* prefill
        # length and recurrent/MoE-capacity layers see the padded rows,
        # so bucketing is refused there.  Default: on when a compile
        # cache is attached (the cache is why bucket count matters).
        bucket_ok = not cfg.moe and all(
            kind == "attn" for kind, _ in tfm.layer_plan(cfg))
        if prefill_bucketing is None:
            self.prefill_bucketing = compile_cache is not None and bucket_ok
        elif prefill_bucketing and not bucket_ok:
            raise ValueError(
                "prefill_bucketing requires a pure global-attention "
                "dense decoder (local/recurrent layers and MoE capacity "
                "are length-sensitive)")
        else:
            self.prefill_bucketing = bool(prefill_bucketing)
        self.prefill_bucket_min = max(int(prefill_bucket_min), 1)

        # Chunked prefill: joins seat a request in a "prefilling" slot
        # and its prompt runs `prefill_chunk` tokens at a time from each
        # step's token budget, interleaved with the decode steps of the
        # other slots — a long prompt can no longer stall every decode
        # slot for its whole length, and each committed chunk is a
        # recovery checkpoint.  Same eligibility as bucketing: chunks
        # replay against a KV cache, which only global causal attention
        # supports.
        if prefill_chunk is not None:
            if not bucket_ok:
                raise ValueError(
                    "chunked prefill requires a pure global-attention "
                    "dense decoder (local/recurrent layers and MoE "
                    "capacity cannot replay a chunk against a cache)")
            if int(prefill_chunk) < 1:
                raise ValueError("prefill_chunk must be >= 1")
        self.prefill_chunk = None if prefill_chunk is None \
            else int(prefill_chunk)
        self.step_token_budget = None if step_token_budget is None \
            else max(int(step_token_budget), 1)
        self.chunk_fault_hook = chunk_fault_hook

        # Slot state: one shared decode pytree + per-slot positions.
        self.states = tfm.init_decode_state(cfg, self.slots, self.max_len)
        self.pos = np.zeros(self.slots, dtype=np.int64)
        self._slots: List[Optional[_Tracked]] = [None] * self.slots
        self._last_tok = np.zeros(self.slots, dtype=np.int32)

        # Queues: pending (future arrivals, by time), waiting (delivered,
        # not yet admitted), retry (admitted work evicted by a boundary
        # failure — rejoins ahead of the queue, without re-admission).
        self._pending: deque = deque()
        self._queue: deque = deque()
        self._retry: deque = deque()
        self.draining = False

        # Accounting.
        self._next_rid = 0
        self._results: dict[int, Result] = {}
        self._submitted = 0
        self._finished = 0
        self._shed = 0
        self._failed = 0
        self._evicted = 0
        self.steps = 0
        self._last_boundary_fail = -(10 ** 9)
        self.plan_log: List[WidthPlan] = []
        self.swap_log: List = []
        self.boundary_log: List[BoundaryEvent] = []
        self.chunk_log: List[ChunkEvent] = []
        self.join_count = 0
        self.chunk_steps = 0        # successful prefill chunks executed

        # AOT width-variant executables (serving/compile_cache.py): the
        # cache's prefill/decode entry points are lookup-or-traced
        # -fallback, so a cold cache behaves exactly like the historical
        # jit lambdas; warm_compile() makes boundary crossings traceless.
        self.compile_cache = compile_cache
        if compile_cache is not None:
            if compile_cache.cfg is not cfg and compile_cache.cfg != cfg:
                raise ValueError("compile_cache was built for a different "
                                 "ModelConfig than this engine")
            self._decode = compile_cache.decode
            self._prefill = compile_cache.prefill
            self._chunk = compile_cache.chunk
        else:
            self._decode = jax.jit(
                lambda p, t, pos, st: tfm.decode_step(p, cfg, t, pos, st))
            self._prefill = jax.jit(
                lambda p, toks: tfm.forward(p, cfg, tokens=toks,
                                            mode="prefill"))
            self._chunk = jax.jit(
                lambda p, toks, pos, st: tfm.prefill_chunk(p, cfg, toks,
                                                           pos, st))

    def _prefill_len(self, plen: int) -> int:
        """Padded prefill length for a ``plen``-token join."""
        from repro.serving.compile_cache import pow2_bucket
        if not self.prefill_bucketing:
            return plen
        return min(pow2_bucket(plen, self.prefill_bucket_min),
                   max(self.max_len, plen))

    def warm_compile(self, plans: Sequence[WidthPlan],
                     prefill_lengths: Sequence[int] = ()) -> int:
        """Plan-time AOT compilation: compile the ragged decode
        executable (and bucketed single-request prefill executables for
        ``prefill_lengths``) for every plan — plus the full-width
        baseline — so boundary crossings and joins are table lookups.
        With chunked prefill the chunk executables are warmed instead of
        whole-prompt prefill: the full chunk plus the pow2 buckets of
        each prompt's final partial chunk.  Masked-crossover plans warm
        the full-width key.  Returns the number of executables warmed;
        only faults the cache's hook injects are absorbed (the serve
        path then traces)."""
        if self.compile_cache is None:
            return 0
        from repro.serving.compile_cache import (
            decode_state_struct, realized_exec_key)
        cache = self.compile_cache
        prev_key = cache.active_key
        if self.prefill_chunk is None:
            buckets = sorted({self._prefill_len(int(l))
                              for l in prefill_lengths})
            chunk_buckets: list = []
        else:
            # Chunked joins never call the whole-prompt prefill: the
            # shape set is the chunk itself plus the pow2 buckets of
            # each prompt's final partial chunk (capped at the chunk).
            c = self.prefill_chunk
            shapes = {c}
            for plen in prefill_lengths:
                tail = int(plen) % c or c
                shapes.add(min(self._prefill_len(tail), c))
            chunk_buckets = sorted(shapes)
            buckets = []
        n = 0
        todo = ([None] if self.swapper is None else list(plans) + [None])
        for plan in todo:
            if plan is None:
                key = cache.full_key
                params = self._canonical
                heads = None
            else:
                masked = bool(plan.widths) \
                    and cache.decide(plan) == "masked"
                params, event = self.swapper.apply_guarded(
                    plan, masked=masked)
                if event.outcome != "ok":
                    continue
                mlp_w, heads_to = self.swapper.realize_plan(plan)
                if masked:
                    key, heads = cache.full_key, None
                else:
                    key = realized_exec_key(mlp_w, heads_to)
                    heads = heads_to
            cache.set_active(key)
            st = decode_state_struct(self.cfg, self.slots, self.max_len,
                                     swapper=self.swapper, heads=heads)
            cur = jnp.zeros((self.slots,), jnp.int32)
            posv = jnp.zeros((self.slots,), jnp.int32)
            n += cache.precompile("decode", key, (self.slots,),
                                  (params, cur, posv, st))
            for plen in buckets:
                toks = jnp.zeros((1, plen), jnp.int32)
                n += cache.precompile("prefill", key, (1, plen),
                                      (params, toks))
            if chunk_buckets:
                st1 = decode_state_struct(self.cfg, 1, self.max_len,
                                          swapper=self.swapper, heads=heads)
                off = jnp.zeros((), jnp.int32)
                for clen in chunk_buckets:
                    toks = jnp.zeros((1, clen), jnp.int32)
                    n += cache.precompile("chunk", key, (1, clen),
                                          (params, toks, off, st1))
            if plan is not None:
                cache.mark_plan_warm(plan)
        cache.set_active(prev_key)
        return n

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(self, request: Request, *, arrival_t: Optional[float] = None,
               klass: str = "") -> int:
        """Register one request; returns its id.  Arrivals in the future
        (``arrival_t`` > now) are delivered when the clock reaches them.
        A draining engine sheds immediately — it no longer admits."""
        rid = self._next_rid
        self._next_rid += 1
        self._submitted += 1
        t = self.clock() if arrival_t is None else float(arrival_t)
        tr = _Tracked(rid=rid, request=request, klass=klass, arrival_t=t)
        if self.draining:
            self._terminal(tr, shed=True)
            return rid
        self._pending.append(tr)
        return rid

    def result(self, rid: int) -> Optional[Result]:
        return self._results.get(rid)

    def cancel(self, rid: int) -> bool:
        """Cancel one in-flight or queued request *slot-exactly*: only
        the named request's slot is freed (every other slot keeps
        decoding undisturbed) and it resolves as shed with
        ``cancelled=True``.  The hedging layer calls this on the losing
        leg of a resolved hedge pair.  Returns False for unknown or
        already-terminal ids."""
        for i, tr in enumerate(self._slots):
            if tr is not None and tr.rid == rid:
                self._slots[i] = None
                self.pos[i] = 0
                self._last_tok[i] = 0
                self._terminal(tr, shed=True, cancelled=True)
                return True
        for q in (self._retry, self._queue, self._pending):
            for tr in q:
                if tr.rid == rid:
                    q.remove(tr)
                    self._terminal(tr, shed=True, cancelled=True)
                    return True
        return False

    # ------------------------------------------------------------------
    # replica failover surface (used by serving.router)
    # ------------------------------------------------------------------
    def evict_in_flight(self) -> List[_Tracked]:
        """Strip every non-terminal request off this engine — slots,
        retry, waiting and pending queues — and return the trackers with
        generated tokens and chunk checkpoints intact.  No Results are
        written here: the requests are terminal *on this engine* only
        (``Ledger.evicted``); the router re-submits them elsewhere via
        :meth:`adopt`."""
        out: List[_Tracked] = []
        for i, tr in enumerate(self._slots):
            if tr is not None:
                self._slots[i] = None
                self.pos[i] = 0
                self._last_tok[i] = 0
                out.append(tr)
        out.extend(self._retry)
        self._retry.clear()
        out.extend(self._queue)
        self._queue.clear()
        out.extend(self._pending)
        self._pending.clear()
        self._evicted += len(out)
        return out

    def adopt(self, tr: _Tracked, *,
              arrival_t: Optional[float] = None) -> int:
        """Accept a request evicted from another replica: a fresh local
        rid, original arrival time (so deadlines and latency keep
        counting from the true arrival), generated tokens and chunk
        checkpoint carried over.  Checkpoint head vectors revalidate at
        join time against *this* engine's widths."""
        rid = self._next_rid
        self._next_rid += 1
        self._submitted += 1
        t = tr.arrival_t if arrival_t is None else float(arrival_t)
        adopted = _Tracked(
            rid=rid, request=tr.request, klass=tr.klass, arrival_t=t,
            generated=list(tr.generated), retries=tr.retries,
            prefill_done=tr.prefill_done, chunk_state=tr.chunk_state,
            chunk_heads=tr.chunk_heads, chunk_eff=tr.chunk_eff)
        if self.draining:
            self._terminal(adopted, shed=True)
            return rid
        self._pending.append(adopted)
        return rid

    def ledger(self) -> Ledger:
        return Ledger(
            submitted=self._submitted, finished=self._finished,
            shed=self._shed, failed=self._failed,
            in_flight=sum(tr is not None for tr in self._slots)
            + len(self._retry),
            queued=len(self._queue) + len(self._pending),
            evicted=self._evicted)

    # ------------------------------------------------------------------
    # terminal states
    # ------------------------------------------------------------------
    def _terminal(self, tr: _Tracked, *, shed: bool = False,
                  failed: bool = False, cancelled: bool = False) -> Result:
        now = self.clock()
        lat = now - tr.arrival_t
        d = tr.request.deadline_s
        res = Result(
            tokens=np.asarray(tr.generated, dtype=np.int32),
            steps=len(tr.generated), shed=shed,
            deadline_missed=(d is not None and lat > d
                             and (shed or not failed) and not cancelled
                             and bool(tr.generated or not shed)),
            latency_s=lat, retries=tr.retries, failed=failed,
            recovered=(tr.retries > 0 and not shed and not failed),
            cancelled=cancelled)
        self._results[tr.rid] = res
        if failed:
            self._failed += 1
        elif shed:
            self._shed += 1
        else:
            self._finished += 1
        return res

    def _finish(self, tr: _Tracked) -> None:
        res = self._terminal(tr)
        if self.admission is not None:
            self.admission.observe(self.clock() - tr.join_t)
        if self.planner is not None:
            name = (self._plan_active.traffic.name
                    if self._plan_active is not None else tr.klass)
            self.planner.record(name or "default", res.latency_s)

    # ------------------------------------------------------------------
    # queue movement
    # ------------------------------------------------------------------
    def _deliver(self) -> None:
        """Move pending arrivals whose time has come into the queue."""
        now = self.clock()
        ready = [tr for tr in self._pending if tr.arrival_t <= now]
        if ready:
            self._pending = deque(
                tr for tr in self._pending if tr.arrival_t > now)
            ready.sort(key=lambda tr: (tr.arrival_t, tr.rid))
            self._queue.extend(ready)

    def _free_slot(self) -> Optional[int]:
        for i, tr in enumerate(self._slots):
            if tr is None:
                return i
        return None

    def _join_waiting(self) -> int:
        """Fill free slots from the retry queue (pre-admitted) then the
        waiting queue (through admission).  Returns prefill token count
        for this step's cost accounting."""
        tokens = 0
        while True:
            i = self._free_slot()
            if i is None:
                break
            if self._retry:
                tr = self._retry.popleft()
            elif self._queue:
                tr = self._queue.popleft()
                if self.admission is not None and not self.admission.admit(
                        tr.request, now=self.clock(),
                        arrival=tr.arrival_t,
                        backlog_batches=len(self._queue) // self.slots):
                    self._terminal(tr, shed=True)
                    continue
            else:
                break
            tokens += self._join(i, tr)
        return tokens

    def _join(self, i: int, tr: _Tracked) -> int:
        """Prefill ``tr``'s prompt (plus any tokens generated before a
        requeue) into slot ``i``.  Returns the prefill token count."""
        prompt = np.concatenate(
            [np.asarray(tr.request.prompt, dtype=np.int32),
             np.asarray(tr.generated, dtype=np.int32)])
        remaining = tr.request.max_new_tokens - len(tr.generated)
        if remaining <= 0:          # requeued after its last token
            tr.join_t = self.clock()
            self._finish(tr)
            return 0
        if len(prompt) + remaining > self.max_len:
            self._terminal(tr, failed=True)
            return 0
        tr.join_t = self.clock()
        if self.prefill_chunk is not None:
            return self._join_chunked(i, tr)
        plen = len(prompt)
        padded = self._prefill_len(plen)
        if padded > plen:
            # pow2 bucket: right-pad so the prefill shape is one of
            # log2(max_len) buckets.  Exact for global causal attention
            # (rows < plen never attend the pad rows; _write_slot only
            # commits the first plen KV rows; logits read at plen-1).
            prompt_in = np.zeros(padded, np.int32)
            prompt_in[:plen] = prompt
        else:
            prompt_in = prompt
        with span(self.spans, "engine.prefill"):
            logits, states, _ = self._prefill(self.params_active,
                                              prompt_in[None])
        self._write_slot(i, states, plen)
        last = logits[0, plen - 1, :self.cfg.vocab_size]
        first = int(jnp.argmax(last))
        tr.generated.append(first)
        self._slots[i] = tr
        self.pos[i] = len(prompt)
        self._last_tok[i] = first
        self.join_count += 1
        if self._done(tr):
            self._release(i)
        return len(prompt)

    def _join_chunked(self, i: int, tr: _Tracked) -> int:
        """Seat ``tr`` in slot ``i`` as a *prefilling* request: no model
        call happens at join time — :meth:`_advance_prefills` runs its
        prompt ``prefill_chunk`` tokens per step from the step token
        budget.  A checkpoint built under the engine's current head
        vectors resumes from its committed tokens; anything else (stale
        widths, or a requeue that shrank the target, which cannot happen
        but is guarded anyway) restarts from token zero."""
        plen = len(tr.request.prompt) + len(tr.generated)
        resumable = (
            tr.chunk_state is not None
            and tr.chunk_heads is not None and tr.chunk_eff is not None
            and tr.chunk_heads.shape == self._shape_heads.shape
            and (tr.chunk_heads == self._shape_heads).all()
            and (tr.chunk_eff == self._heads_active).all()
            and 0 < tr.prefill_done <= plen)
        if not resumable:
            tr.chunk_state = self._fresh_states(self._shape_heads, batch=1)
            tr.chunk_heads = self._shape_heads.copy()
            tr.chunk_eff = self._heads_active.copy()
            tr.prefill_done = 0
        self._slots[i] = tr
        self.pos[i] = 0
        self._last_tok[i] = 0
        self.join_count += 1
        return 0

    def _advance_prefills(self, budget: Optional[int]) -> int:
        """Run at most one prefill chunk per prefilling slot (round-robin,
        repeated until the budget is spent or no slot can advance).
        Returns padded chunk tokens executed, for step cost accounting.
        The first chunk of a pass always runs even over budget — a chunk
        larger than the budget must still make progress."""
        spent = 0
        progressed = True
        while progressed:
            progressed = False
            for i, tr in enumerate(self._slots):
                if tr is None or tr.chunk_state is None:
                    continue
                target = len(tr.request.prompt) + len(tr.generated)
                clen = min(self.prefill_chunk, target - tr.prefill_done)
                if clen <= 0:       # fully committed last pass
                    continue
                padded = min(self._prefill_len(clen), self.prefill_chunk)
                if budget is not None and spent > 0 \
                        and spent + padded > budget:
                    return spent
                prompt = np.concatenate(
                    [np.asarray(tr.request.prompt, dtype=np.int32),
                     np.asarray(tr.generated, dtype=np.int32)])
                buf = np.zeros(padded, np.int32)
                buf[:clen] = prompt[tr.prefill_done:tr.prefill_done + clen]
                if self.chunk_fault_hook is not None:
                    try:
                        self.chunk_fault_hook()
                    except Exception as e:  # noqa: BLE001 — injected
                        self._chunk_fault(i, tr, e)
                        continue
                # an error from the executable itself is not a chunk
                # fault to restart from: it propagates
                off = jnp.asarray(tr.prefill_done, jnp.int32)
                with span(self.spans, "engine.chunk", tr.rid):
                    logits, tr.chunk_state = self._chunk(
                        self.params_active, buf[None], off, tr.chunk_state)
                tr.prefill_done += clen
                spent += padded
                self.chunk_steps += 1
                progressed = True
                if tr.prefill_done >= target:
                    self._commit_prefill(i, tr, logits, target, clen)
        return spent

    def _commit_prefill(self, i: int, tr: _Tracked, logits, plen: int,
                        clen: int) -> None:
        """Final chunk committed: write the checkpoint pytree into the
        shared slot cache, sample the first token from the last real
        row's logits, and switch the slot to decoding."""
        with span(self.spans, "engine.commit", tr.rid):
            self._write_slot(i, tr.chunk_state, plen)
            tr.chunk_state = None
            tr.chunk_heads = None
            tr.chunk_eff = None
            tr.prefill_done = 0
            first = int(jnp.argmax(
                logits[0, clen - 1, :self.cfg.vocab_size]))
            tr.generated.append(first)
            self.pos[i] = plen
            self._last_tok[i] = first
            if self._done(tr):
                self._release(i)

    def _chunk_fault(self, i: int, tr: _Tracked, e: Exception) -> None:
        """The chunk fault hook fired: free the slot and requeue the
        request *keeping its checkpoint* — recovery resumes from the last
        committed chunk, not token zero.  Past ``max_retries`` the
        request fails terminally (checkpoint dropped)."""
        self._slots[i] = None
        self.pos[i] = 0
        self._last_tok[i] = 0
        tr.retries += 1
        self.chunk_log.append(ChunkEvent(
            step=self.steps, rid=tr.rid, committed=tr.prefill_done,
            error=f"{type(e).__name__}: {e}"))
        if tr.retries > self.max_retries:
            tr.chunk_state = None
            tr.chunk_heads = None
            tr.chunk_eff = None
            self._terminal(tr, failed=True)
        else:
            self._retry.append(tr)

    def _done(self, tr: _Tracked) -> bool:
        if len(tr.generated) >= tr.request.max_new_tokens:
            return True
        return tr.request.eos_id >= 0 \
            and tr.generated[-1] == tr.request.eos_id

    def _release(self, i: int) -> None:
        tr = self._slots[i]
        self._slots[i] = None
        self.pos[i] = 0
        self._last_tok[i] = 0
        if tr is not None:
            self._finish(tr)

    # ------------------------------------------------------------------
    # slot cache writes
    # ------------------------------------------------------------------
    def _write_slot(self, i: int, prefill_states: dict, plen: int) -> None:
        """Write a one-request prefill's layer states into slot ``i`` of
        the shared decode pytree.  K/V caches land in rows ``0..plen`` of
        the slot's sequence axis; recurrent states replace the slot's
        row wholesale."""

        def write_group(gst: dict, lst: dict, stacked: bool) -> dict:
            out = dict(gst)
            for key, lv in lst.items():
                gv = gst[key]
                if key in ("k", "v"):
                    # (B, S, KV, dh) / stacked (U, B, S, KV, dh).  Only
                    # the first `plen` source rows are committed: a
                    # bucketed prefill carries junk KV in its pad rows
                    # (local-window ring caches may also carry fewer
                    # rows than plen — take what the source has).
                    s = min(plen, lv.shape[2 if stacked else 1])
                    if stacked:
                        upd = gv.at[:, i, :s] if s < gv.shape[2] \
                            else gv.at[:, i]
                        out[key] = upd.set(
                            lv[:, 0, :s].astype(gv.dtype))
                    else:
                        upd = gv.at[i, :s] if s < gv.shape[1] else gv.at[i]
                        out[key] = upd.set(lv[0, :s].astype(gv.dtype))
                else:
                    # per-slot state without a sequence axis (recurrent)
                    out[key] = (gv.at[:, i].set(lv[:, 0].astype(gv.dtype))
                                if stacked
                                else gv.at[i].set(lv[0].astype(gv.dtype)))
            return out

        with span(self.spans, "engine.write_slot"):
            st = dict(self.states)
            if "stack" in prefill_states:
                stack = dict(st["stack"])
                for key, lst in prefill_states["stack"].items():
                    stack[key] = write_group(stack[key], lst, stacked=True)
                st["stack"] = stack
            if "extra" in prefill_states:
                extra = dict(st.get("extra", {}))
                for key, lst in prefill_states["extra"].items():
                    extra[key] = write_group(extra[key], lst, stacked=False)
                st["extra"] = extra
            self.states = st

    def _fresh_states(self, heads, batch: Optional[int] = None) -> dict:
        """A fresh (empty) decode pytree shaped for realized ``heads`` —
        canonical shapes re-sliced through the swapper, no fault hook in
        the path (recovery must not be injectable).  ``batch`` overrides
        the slot count (chunk checkpoints are batch-1 pytrees)."""
        b = self.slots if batch is None else int(batch)
        st = tfm.init_decode_state(self.cfg, b, self.max_len)
        if self.swapper is None or (heads == self._full_heads).all():
            return st
        hook, self.swapper.reshape_fault_hook = \
            self.swapper.reshape_fault_hook, None
        try:
            return self.swapper.reshape_states(st, self._full_heads, heads)
        finally:
            self.swapper.reshape_fault_hook = hook

    # ------------------------------------------------------------------
    # boundary transactions
    # ------------------------------------------------------------------
    def _live_tokens(self) -> int:
        live = int(sum(self.pos[i] + (tr.prefill_done
                                      if tr.chunk_state is not None else 0)
                       for i, tr in enumerate(self._slots)
                       if tr is not None))
        return max(live, 1)

    def _requeue_in_flight(self) -> int:
        """Evict every occupied slot back to the retry queue, generated
        tokens intact.  Requests out of retries become terminal failures
        — accounted, never silently dropped."""
        n = 0
        for i, tr in enumerate(self._slots):
            if tr is None:
                continue
            self._slots[i] = None
            self.pos[i] = 0
            self._last_tok[i] = 0
            tr.retries += 1
            if tr.retries > self.max_retries:
                self._terminal(tr, failed=True)
            else:
                self._retry.append(tr)
            n += 1
        return n

    def _abort_boundary(self, outcome: str, plan, error: str) -> None:
        """Transaction rollback: restore the canonical tree + fresh
        canonical-shape state, requeue live work."""
        requeued = self._requeue_in_flight()
        self.params_active = self._canonical
        self._heads_active = self._full_heads.copy()
        self._shape_heads = self._full_heads.copy()
        self._masked_active = False
        self._plan_active = None
        self._key_active = None
        if self.compile_cache is not None:
            self.compile_cache.set_active(None)
        self.states = tfm.init_decode_state(self.cfg, self.slots,
                                            self.max_len)
        self._last_boundary_fail = self.steps
        self.boundary_log.append(BoundaryEvent(
            step=self.steps, plan_name=plan.traffic.name,
            outcome=outcome, requeued=requeued, error=error))

    def _maybe_cross_boundary(self) -> None:
        if self.swapper is None:
            return
        if self.degrader is not None:
            plan = self.degrader.select(self._live_tokens())
        elif self.planner is not None:
            plan = self.planner.select(self._live_tokens())
        else:
            return
        if self.steps - self._last_boundary_fail < self.boundary_cooldown:
            return                      # cooling down after a failure
        mlp_t, heads_to = self.swapper.realize_plan(plan)
        masked = (self.compile_cache is not None
                  and bool(getattr(plan, "widths", None))
                  and self.compile_cache.decide(plan) == "masked")
        key = (tuple(mlp_t.tolist()), tuple(heads_to.tolist()))
        if (key == self._key_active
                and masked == self._masked_active) or (
                self._key_active is None
                and (mlp_t == self.cfg.d_ff).all()
                and (heads_to == self.cfg.n_heads).all()):
            return                      # same realized widths: no boundary
        params_new, event = self.swapper.apply_guarded(plan, masked=masked)
        self.swap_log.append(event)
        if event.outcome != "ok":
            self._abort_boundary("swap_rolled_back", plan, event.error)
            return
        g = self.cfg.n_heads // max(self.cfg.n_kv_heads, 1)
        kv_from = np.maximum(self._heads_active // g, 1)
        kv_to = np.maximum(heads_to // g, 1)
        n_live = sum(tr is not None for tr in self._slots)
        live = n_live > 0
        shape_to = self._full_heads.copy() if masked else heads_to
        if live and (kv_to > kv_from).any():
            # Growing KV heads cannot restore sliced-away history:
            # requeue the live requests so their tokens re-prefill at the
            # new width, then adopt the plan on a fresh cache.  (A masked
            # grow requeues too — the re-grown heads' history rows hold
            # zeros written while they were masked.)
            requeued = self._requeue_in_flight()
            self.states = self._fresh_states(shape_to)
            outcome = "requeued_grow"
            n_live = 0
        elif masked and (shape_to == self._shape_heads).all():
            # Masked realization on already-canonical shapes: the
            # dropped heads are zero-weighted on both the q and output
            # projections, so stale KV rows in them are unreadable — no
            # state op needed.  (Every other boundary goes through
            # reshape_states, preserving its transactional fault
            # surface even for value-only changes.)
            requeued = 0
            outcome = "ok"
        else:
            try:
                self.states = self.swapper.reshape_states(
                    self.states, self._shape_heads, shape_to)
                # Live chunk checkpoints cross the boundary with the
                # shared cache (same transaction: a fault here aborts the
                # whole crossing and the requeued checkpoints revalidate
                # against whatever widths the engine recovers to).
                for ctr in self._slots:
                    if ctr is not None and ctr.chunk_state is not None:
                        ctr.chunk_state = self.swapper.reshape_states(
                            ctr.chunk_state, self._shape_heads, shape_to)
                        ctr.chunk_heads = np.asarray(shape_to).copy()
                        ctr.chunk_eff = heads_to.copy()
                requeued = 0
                outcome = "ok"
            except Exception as e:  # noqa: BLE001 — the guard IS the point
                self._abort_boundary("reshape_failed", plan,
                                     f"{type(e).__name__}: {e}")
                return
        self.params_active = params_new
        self._heads_active = heads_to
        self._shape_heads = shape_to
        self._masked_active = masked
        self._plan_active = plan
        self._key_active = key
        if self.compile_cache is not None:
            from repro.serving.compile_cache import realized_exec_key
            self.compile_cache.set_active(
                None if masked else realized_exec_key(mlp_t, heads_to))
        self.plan_log.append(plan)
        self.boundary_log.append(BoundaryEvent(
            step=self.steps, plan_name=plan.traffic.name,
            outcome=outcome, requeued=requeued, carried=n_live))

    # ------------------------------------------------------------------
    # the engine step
    # ------------------------------------------------------------------
    def _watchdog(self) -> None:
        """Shed any decoding request past its deadline — enforcement
        *during* decode, not only at admission."""
        now = self.clock()
        for i, tr in enumerate(self._slots):
            if tr is None or tr.request.deadline_s is None:
                continue
            if now - tr.arrival_t > tr.request.deadline_s:
                self._slots[i] = None
                self.pos[i] = 0
                self._last_tok[i] = 0
                self._terminal(tr, shed=True)

    def step(self) -> bool:
        """One engine step: deliver arrivals, join free slots, decode one
        token for every occupied slot, account time, enforce watchdogs,
        consider a plan boundary.  Returns True while work remains."""
        sp = self.spans
        with span(sp, "engine.step"):
            self.steps += 1
            with span(sp, "engine.deliver"):
                self._deliver()
            if self.steps % self.boundary_every == 0:
                with span(sp, "engine.boundary"):
                    self._maybe_cross_boundary()
            with span(sp, "engine.admit"):
                prefill_tokens = self._join_waiting()
            chunk_tokens = 0
            if self.prefill_chunk is not None:
                # Chunk budget: whatever the step token budget leaves
                # after one decode token per decoding slot.  Budget-less
                # engines run every prefilling slot one chunk per step.
                n_decoding = sum(tr is not None and tr.chunk_state is None
                                 for tr in self._slots)
                cbudget = None if self.step_token_budget is None \
                    else max(self.step_token_budget - n_decoding, 0)
                with span(sp, "engine.prefill"):
                    chunk_tokens = self._advance_prefills(cbudget)
            active = [i for i, tr in enumerate(self._slots)
                      if tr is not None and tr.chunk_state is None]
            if not active and prefill_tokens == 0 and chunk_tokens == 0:
                if not (self._queue or self._retry) and self._pending:
                    # idle until the next arrival: fast-forward a virtual
                    # clock; a wall clock delivers immediately (open-loop
                    # arrival times in the past).
                    nxt = min(tr.arrival_t for tr in self._pending)
                    advance = getattr(self.clock, "advance", None)
                    if advance is not None and nxt > self.clock():
                        advance(nxt - self.clock())
                    else:
                        self._queue.extend(
                            sorted(self._pending,
                                   key=lambda tr: (tr.arrival_t, tr.rid)))
                        self._pending.clear()
                    return self._outstanding()
                return self._outstanding()

            if active:
                with span(sp, "engine.inputs"):
                    toks = jnp.asarray(self._last_tok)
                    posv = jnp.asarray(self.pos)
                with span(sp, "engine.decode"):
                    logits, self.states = self._decode(
                        self.params_active, toks, posv, self.states)
                logits = logits[:, :self.cfg.vocab_size]
                cur = self._sample(logits, active)
                with span(sp, "engine.sync"):
                    host = np.asarray(cur)
            with span(sp, "engine.retire"):
                decoded = 0
                for i in active:
                    tr = self._slots[i]
                    tr.generated.append(int(host[i]))
                    self.pos[i] += 1
                    self._last_tok[i] = int(host[i])
                    decoded += 1
                    if self._done(tr):
                        self._release(i)

                # time accounting: modeled (virtual clock) or measured
                step_tokens = decoded + prefill_tokens + chunk_tokens
                if self.batch_cost_fn is not None and step_tokens:
                    dt = self.batch_cost_fn(self._plan_active, step_tokens)
                    advance = getattr(self.clock, "advance", None)
                    if advance is not None:
                        advance(dt)
                self._watchdog()
                if self.admission is not None and self.degrader is not None:
                    qb = (len(self._queue) + len(self._retry)
                          + self.slots - 1) // self.slots
                    self.degrader.observe(self.admission.signal(qb))
            return self._outstanding()

    def _sample(self, logits, active):
        with span(self.spans, "engine.sample"):
            temps = [self._slots[i].request.temperature for i in active]
            if not any(t > 0 for t in temps):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            temp = np.ones(self.slots, np.float32)
            use = np.zeros(self.slots, bool)
            for i in active:
                t = self._slots[i].request.temperature
                if t > 0:
                    temp[i] = max(t, 1e-6)
                    use[i] = True
            self.rng, sub = jax.random.split(self.rng)
            nxt = jax.random.categorical(
                sub, logits / jnp.asarray(temp)[:, None], axis=-1)
            greedy = jnp.argmax(logits, axis=-1)
            return jnp.where(jnp.asarray(use), nxt,
                             greedy).astype(jnp.int32)

    def _outstanding(self) -> bool:
        return (bool(self._pending) or bool(self._queue)
                or bool(self._retry)
                or any(tr is not None for tr in self._slots))

    # ------------------------------------------------------------------
    # front doors
    # ------------------------------------------------------------------
    def run(self, arrivals: Sequence, *, max_steps: int = 1_000_000
            ) -> List[Result]:
        """Serve an open-loop workload (``Arrival``s or bare ``Request``s,
        which arrive immediately) to completion; results align with the
        input order."""
        rids = []
        for a in arrivals:
            if isinstance(a, Arrival):
                rids.append(self.submit(a.request, arrival_t=a.t,
                                        klass=a.klass))
            else:
                rids.append(self.submit(a))
        steps = 0
        while self._outstanding():
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"run exceeded {max_steps} steps")
            self.step()
        return [self._results[r] for r in rids]

    def drain(self, *, max_steps: int = 100_000) -> Ledger:
        """Stop admitting, shed the waiting queue, finish (or shed, once
        ``max_steps`` is spent) the in-flight work, and return a complete
        ledger."""
        self.draining = True
        self._deliver()
        for tr in list(self._pending) + list(self._queue):
            self._terminal(tr, shed=True)
        self._pending.clear()
        self._queue.clear()
        if not self._retry and all(tr is None for tr in self._slots):
            # Nothing in flight (including the zero-submission case):
            # return the — possibly empty — ledger without stepping the
            # engine at all.
            led = self.ledger()
            assert led.complete, f"drain ledger does not sum: {led}"
            return led
        steps = 0
        while self._retry or any(tr is not None for tr in self._slots):
            steps += 1
            if steps > max_steps:
                for i, tr in enumerate(self._slots):
                    if tr is not None:
                        self._slots[i] = None
                        self.pos[i] = 0
                        self._terminal(tr, shed=True)
                while self._retry:
                    self._terminal(self._retry.popleft(), shed=True)
                break
            self.step()
        led = self.ledger()
        assert led.complete, f"drain ledger does not sum: {led}"
        return led
