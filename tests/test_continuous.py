"""Continuous-batching engine: in-flight joins, boundary transactions,
fault recovery, drain ledgers.

The model-backed scenarios run on the same tiny reduced config as the
chaos tier, a virtual clock, and seeded injectors — every assertion is
exact (ledger sums, who recovered, run-twice equality), not statistical.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced_config
from repro.core import TPU_V5E as HW
from repro.models import init_params
from repro.models import transformer as tfm
from repro.serving import (
    AdmissionControl, Arrival, ContinuousServeEngine,
    DegradationController, DegradationLadder, Ledger, Request, ServeEngine,
    ServingWidthPlanner, TrafficClass, WidthPlan, WidthSwapper,
    WidthVariantCompileCache, serving_templates,
)
from repro.serving.chaos import (
    CompileFailureInjector, InjectedFault, ReshapeFailureInjector,
    SwapFailureInjector, TailReport, TrafficLoad, VirtualClock,
    class_tail_reports, modeled_batch_cost, open_loop_arrivals,
)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config(get_config("qwen1.5-0.5b"), d_model=128,
                         n_layers=2, d_ff=576)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def reqs_for(cfg, lens, *, max_new=6, seed=0, deadline_s=None):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=(pl,))
                    .astype(np.int32), max_new_tokens=max_new,
                    deadline_s=deadline_s) for pl in lens]


# ---------------------------------------------------------------------------
# ragged decode: the mechanism continuous batching stands on
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestRaggedDecode:
    def test_vector_pos_matches_scalar_pos(self, setup):
        """decode_step with a uniform (B,) pos vector must bit-match the
        scalar-pos path — same math, different indexing."""
        cfg, params = setup
        B, plen = 3, 7
        rng = np.random.default_rng(1)
        prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                           size=(B, plen)).astype(np.int32))
        _, st, _ = tfm.forward(params, cfg, tokens=prompts, mode="prefill")
        st = jax.tree_util.tree_map(
            lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 3)
                              + [(0, 32 - x.shape[-3]), (0, 0), (0, 0)]),
            st)
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B,))
                          .astype(np.int32))
        scalar_logits, scalar_st = tfm.decode_step(
            params, cfg, tok, jnp.asarray(plen, jnp.int32), st)
        vec_logits, vec_st = tfm.decode_step(
            params, cfg, tok, jnp.full((B,), plen, jnp.int32), st)
        np.testing.assert_allclose(np.asarray(scalar_logits),
                                   np.asarray(vec_logits),
                                   rtol=1e-4, atol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(scalar_st),
                        jax.tree_util.tree_leaves(vec_st)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_ragged_rows_match_independent_runs(self, setup):
        """Each slot at its own position must decode exactly what that
        request would decode alone — no cross-slot leakage."""
        cfg, params = setup
        lens = (5, 9, 3)
        eng = ContinuousServeEngine(params, cfg, max_len=32, batch_slots=3)
        results = eng.run(reqs_for(cfg, lens, max_new=5, seed=2))
        solo = ServeEngine(params, cfg, max_len=32, batch_slots=1)
        expected = solo.generate(reqs_for(cfg, lens, max_new=5, seed=2))
        for got, want in zip(results, expected):
            assert np.array_equal(got.tokens, want.tokens)


# ---------------------------------------------------------------------------
# the engine: joins, leaves, ledgers
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestContinuousEngine:
    def test_requests_join_in_flight(self, setup):
        """More requests than slots: later requests join as earlier ones
        leave — no batch barrier, every submission accounted for."""
        cfg, params = setup
        eng = ContinuousServeEngine(params, cfg, max_len=32, batch_slots=2)
        results = eng.run(reqs_for(cfg, (4, 8, 5, 6, 3), max_new=4))
        assert eng.join_count == 5
        assert all(len(r.tokens) == 4 for r in results)
        led = eng.ledger()
        assert led.complete and led.finished == 5

    def test_short_request_not_blocked_by_long(self, setup):
        """Head-of-line: a 2-token request next to a 16-token request
        finishes first on the engine clock — the static engine's batch
        barrier would hold it until the long tail completes."""
        cfg, params = setup
        clock = VirtualClock()
        eng = ContinuousServeEngine(
            params, cfg, max_len=48, batch_slots=2, clock=clock,
            batch_cost_fn=modeled_batch_cost(1e-3))
        rng = np.random.default_rng(3)
        long = Request(prompt=rng.integers(0, cfg.vocab_size, size=(6,))
                       .astype(np.int32), max_new_tokens=16)
        short = Request(prompt=rng.integers(0, cfg.vocab_size, size=(6,))
                        .astype(np.int32), max_new_tokens=2)
        r_long, r_short = eng.run([long, short])
        assert r_short.latency_s < r_long.latency_s
        assert len(r_short.tokens) == 2 and len(r_long.tokens) == 16

    def test_arrivals_respect_virtual_time(self, setup):
        cfg, params = setup
        clock = VirtualClock()
        eng = ContinuousServeEngine(
            params, cfg, max_len=32, batch_slots=2, clock=clock,
            batch_cost_fn=modeled_batch_cost(1e-3))
        [req] = reqs_for(cfg, (4,), max_new=2)
        [res] = eng.run([Arrival(t=5.0, request=req)])
        # the engine fast-forwarded to the arrival; latency excludes the
        # idle wait before t=5
        assert clock() >= 5.0
        assert res.latency_s < 5.0

    def test_oversized_request_fails_not_hangs(self, setup):
        cfg, params = setup
        eng = ContinuousServeEngine(params, cfg, max_len=16, batch_slots=2)
        big = reqs_for(cfg, (14,), max_new=8)[0]     # 14 + 8 > 16
        ok = reqs_for(cfg, (4,), max_new=2, seed=5)[0]
        r_big, r_ok = eng.run([big, ok])
        assert r_big.failed and not r_ok.failed
        led = eng.ledger()
        assert led.complete and led.failed == 1 and led.finished == 1

    def test_watchdog_sheds_mid_decode(self, setup):
        """Deadline enforcement *during* decode: a request whose budget
        expires mid-stream is shed with its partial tokens."""
        cfg, params = setup
        clock = VirtualClock()
        eng = ContinuousServeEngine(
            params, cfg, max_len=48, batch_slots=2, clock=clock,
            batch_cost_fn=modeled_batch_cost(0.01))
        doomed = reqs_for(cfg, (6,), max_new=16, deadline_s=0.25)[0]
        fine = reqs_for(cfg, (6,), max_new=16, seed=7)[0]
        r_doomed, r_fine = eng.run([doomed, fine])
        assert r_doomed.shed and r_doomed.deadline_missed
        assert 0 < len(r_doomed.tokens) < 16      # partial, not dropped
        assert not r_fine.shed and len(r_fine.tokens) == 16
        assert eng.ledger().complete

    def test_admission_sheds_on_queue_cap(self, setup):
        cfg, params = setup
        clock = VirtualClock()
        eng = ContinuousServeEngine(
            params, cfg, max_len=32, batch_slots=2, clock=clock,
            admission=AdmissionControl(max_queue_batches=1),
            batch_cost_fn=modeled_batch_cost(1e-3))
        results = eng.run(reqs_for(cfg, (4,) * 12, max_new=8))
        led = eng.ledger()
        assert led.complete
        assert led.shed > 0 and led.finished > 0
        assert led.shed == sum(r.shed for r in results)

    def test_drain_ledger_is_complete(self, setup):
        """drain(): queue shed, in-flight finished, nothing unaccounted,
        and post-drain submissions are refused (shed)."""
        cfg, params = setup
        clock = VirtualClock()
        eng = ContinuousServeEngine(
            params, cfg, max_len=32, batch_slots=2, clock=clock,
            batch_cost_fn=modeled_batch_cost(1e-3))
        for r in reqs_for(cfg, (4,) * 6, max_new=8):
            eng.submit(r)
        eng.step()                 # some joined, some still queued
        led = eng.drain()
        assert led.complete and led.submitted == 6
        assert led.shed == 4       # 2 slots in flight, 4 queued -> shed
        assert led.finished == 2
        rid = eng.submit(reqs_for(cfg, (4,), seed=9)[0])
        assert eng.result(rid).shed
        assert eng.ledger().complete


# ---------------------------------------------------------------------------
# boundary transactions + recovery
# ---------------------------------------------------------------------------
def make_serving_stack(cfg, params, *, sites=("mlp",), deltas=(0.8, 0.6),
                       tokens=96):
    templates, modules = serving_templates(cfg, HW, tokens=tokens,
                                           sites=sites)
    planner = ServingWidthPlanner(HW, templates, modules=modules)
    traffic = [TrafficClass("burst", tokens)]
    planner.plan(traffic)
    ladder = DegradationLadder.build(planner, traffic, deltas=deltas)
    return planner, ladder


class _ScriptedSelector:
    """Deterministic stand-in for a DegradationController: returns the
    scripted plans in order, then holds the last one."""

    def __init__(self, plans):
        self.plans = list(plans)

    def select(self, tokens):
        plan = self.plans[0]
        if len(self.plans) > 1:
            self.plans.pop(0)
        return plan

    def observe(self, signal):
        return 0


@pytest.mark.slow
@pytest.mark.chaos
class TestBoundaryRecovery:
    def _narrow_and_full(self, cfg, planner, *, sites):
        narrow = planner.select(96)
        assert narrow.widths, "planner produced no narrowed plan"
        full = WidthPlan(traffic=narrow.traffic, widths={}, latency_s=0.0,
                         baseline_latency_s=0.0, satisfied=True,
                         modules=planner.modules)
        return narrow, full

    def test_reshape_fault_requeues_without_loss(self, setup):
        """A KV-reshape fault mid-boundary aborts the transaction: the
        canonical tree is restored, every in-flight request is requeued
        with its tokens intact, and the run finishes with zero lost."""
        cfg, params = setup
        planner, _ = make_serving_stack(cfg, params)
        narrow, _ = self._narrow_and_full(cfg, planner, sites=("mlp",))
        inj = ReshapeFailureInjector(1.0, seed=0)        # first boundary dies
        swapper = WidthSwapper(params, cfg, reshape_fault_hook=inj)
        clock = VirtualClock()
        eng = ContinuousServeEngine(
            params, cfg, max_len=48, batch_slots=2, clock=clock,
            planner=planner, swapper=swapper,
            batch_cost_fn=modeled_batch_cost(1e-3),
            max_retries=3, boundary_every=2, boundary_cooldown=1000)
        eng.planner = None
        eng.degrader = _ScriptedSelector([narrow])
        eng.admission = AdmissionControl(max_queue_batches=100)
        results = eng.run(reqs_for(cfg, (6, 6), max_new=8))
        assert inj.injected == 1
        [ev] = [b for b in eng.boundary_log if b.outcome == "reshape_failed"]
        assert ev.requeued == 2 and "InjectedFault" in ev.error
        # canonical-tree consistency after the abort: the cooldown keeps
        # the engine on the rolled-back state for the rest of the run
        assert eng.params_active is swapper.full_params
        led = eng.ledger()
        assert led.complete and led.finished == 2 and led.failed == 0
        for r in results:
            assert r.recovered and r.retries == 1
            assert len(r.tokens) == 8                    # nothing lost

    def test_swap_rollback_requeues_without_loss(self, setup):
        cfg, params = setup
        planner, _ = make_serving_stack(cfg, params)
        narrow, _ = self._narrow_and_full(cfg, planner, sites=("mlp",))
        inj = SwapFailureInjector(1.0, seed=0, steps=("materialize",))
        swapper = WidthSwapper(params, cfg, fault_hook=inj)
        clock = VirtualClock()
        eng = ContinuousServeEngine(
            params, cfg, max_len=48, batch_slots=2, clock=clock,
            planner=planner, swapper=swapper,
            batch_cost_fn=modeled_batch_cost(1e-3),
            max_retries=3, boundary_every=2, boundary_cooldown=1000)
        eng.planner = None
        eng.degrader = _ScriptedSelector([narrow])
        eng.admission = AdmissionControl(max_queue_batches=100)
        results = eng.run(reqs_for(cfg, (6, 6), max_new=8))
        assert eng.swap_log[0].outcome == "rolled_back"
        [ev] = [b for b in eng.boundary_log
                if b.outcome == "swap_rolled_back"]
        assert ev.requeued == 2
        assert eng.params_active is swapper.full_params
        assert eng.ledger().complete
        assert all(r.recovered and len(r.tokens) == 8 for r in results)

    def test_retry_budget_exhaustion_fails_loudly(self, setup):
        """Every boundary attempt fails and retries run out: requests end
        *failed*, in the ledger — never silently dropped."""
        cfg, params = setup
        planner, _ = make_serving_stack(cfg, params)
        narrow, _ = self._narrow_and_full(cfg, planner, sites=("mlp",))
        inj = ReshapeFailureInjector(1.0, seed=0)
        swapper = WidthSwapper(params, cfg, reshape_fault_hook=inj)
        eng = ContinuousServeEngine(
            params, cfg, max_len=48, batch_slots=2, clock=VirtualClock(),
            planner=planner, swapper=swapper,
            batch_cost_fn=modeled_batch_cost(1e-3),
            max_retries=1, boundary_every=2, boundary_cooldown=0)
        eng.planner = None
        eng.degrader = _ScriptedSelector([narrow])
        eng.admission = AdmissionControl(max_queue_batches=100)
        results = eng.run(reqs_for(cfg, (6, 6), max_new=8))
        led = eng.ledger()
        assert led.complete
        assert led.failed == 2 and led.finished == 0
        assert all(r.failed and r.retries == 2 for r in results)

    def _narrow_attn(self, cfg, planner):
        """A hand-built half-heads plan: the tiny reduced config is too
        small for Algorithm 2 to *choose* to narrow attention, but the
        boundary mechanics are what's under test."""
        base = planner.select(96)
        g = cfg.n_heads // max(cfg.n_kv_heads, 1)
        w = max(cfg.n_heads // 2, g) * cfg.head_dim
        return dataclasses.replace(
            base, widths={n: w for n in planner.modules})

    def test_shrink_boundary_carries_live_kv(self, setup):
        """An attention-narrowing boundary reshapes the live cache and
        decoding continues — no requeue, tokens keep flowing."""
        cfg, params = setup
        planner, _ = make_serving_stack(cfg, params, sites=("attn",))
        narrow = self._narrow_attn(cfg, planner)
        swapper = WidthSwapper(params, cfg)
        eng = ContinuousServeEngine(
            params, cfg, max_len=64, batch_slots=2, clock=VirtualClock(),
            planner=planner, swapper=swapper,
            batch_cost_fn=modeled_batch_cost(1e-3),
            boundary_every=3)
        eng.planner = None
        eng.degrader = _ScriptedSelector([narrow])
        eng.admission = AdmissionControl(max_queue_batches=100)
        results = eng.run(reqs_for(cfg, (6, 6), max_new=12))
        oks = [b for b in eng.boundary_log if b.outcome == "ok"]
        assert oks and all(b.requeued == 0 for b in oks)
        assert eng.ledger().complete
        assert all(not r.retries and len(r.tokens) == 12 for r in results)

    def test_grow_boundary_requeues_instead_of_zero_history(self, setup):
        """Shrink then grow with requests in flight: the grow crossing
        must requeue (re-prefill at the new width), never decode against
        zero-history head slots."""
        cfg, params = setup
        planner, _ = make_serving_stack(cfg, params, sites=("attn",))
        narrow = self._narrow_attn(cfg, planner)
        full = dataclasses.replace(narrow, widths={})
        swapper = WidthSwapper(params, cfg)
        eng = ContinuousServeEngine(
            params, cfg, max_len=64, batch_slots=2, clock=VirtualClock(),
            planner=planner, swapper=swapper,
            batch_cost_fn=modeled_batch_cost(1e-3),
            boundary_every=3)
        eng.planner = None
        eng.degrader = _ScriptedSelector([narrow, narrow, full])
        eng.admission = AdmissionControl(max_queue_batches=100)
        results = eng.run(reqs_for(cfg, (6, 6), max_new=16))
        grows = [b for b in eng.boundary_log if b.outcome == "requeued_grow"]
        assert grows and grows[0].requeued > 0
        led = eng.ledger()
        assert led.complete and led.failed == 0
        assert all(len(r.tokens) == 16 for r in results)
        assert any(r.recovered for r in results)


# ---------------------------------------------------------------------------
# load generation + tail reports (no model)
# ---------------------------------------------------------------------------
class TestOpenLoopLoad:
    LOADS = [TrafficLoad("steady", rate_rps=50.0, duration_s=2.0),
             TrafficLoad("spike", rate_rps=0.0, duration_s=2.0,
                         burst_at=0.5, burst_n=32)]

    def test_arrivals_are_seed_deterministic(self):
        a = open_loop_arrivals(self.LOADS, 256, seed=3)
        b = open_loop_arrivals(self.LOADS, 256, seed=3)
        assert [x.t for x in a] == [x.t for x in b]
        assert all(np.array_equal(x.request.prompt, y.request.prompt)
                   for x, y in zip(a, b))
        c = open_loop_arrivals(self.LOADS, 256, seed=4)
        assert [x.t for x in a] != [x.t for x in c]

    def test_arrivals_sorted_and_classed(self):
        arrivals = open_loop_arrivals(self.LOADS, 256, seed=0)
        ts = [a.t for a in arrivals]
        assert ts == sorted(ts)
        assert sum(a.klass == "spike" for a in arrivals) == 32
        assert all(a.t == 0.5 for a in arrivals if a.klass == "spike")
        assert all(0 < a.t < 2.0 for a in arrivals)

    def test_burst_outside_window_rejected(self):
        """A burst past its load's duration silently extended the run —
        now a loud schedule error."""
        bad = [TrafficLoad("late", rate_rps=1.0, duration_s=1.0,
                           burst_at=1.5, burst_n=4)]
        with pytest.raises(ValueError, match="outside its"):
            open_loop_arrivals(bad, 256, seed=0)

    def test_overlapping_spike_schedules_rejected(self):
        """Two classes spiking at the same instant interleave by list
        order, not by seed — refused so determinism can't silently
        depend on load declaration order."""
        bad = [TrafficLoad("a", rate_rps=0.0, duration_s=2.0,
                           burst_at=0.5, burst_n=8),
               TrafficLoad("b", rate_rps=0.0, duration_s=2.0,
                           burst_at=0.5, burst_n=8)]
        with pytest.raises(ValueError, match="overlapping spike"):
            open_loop_arrivals(bad, 256, seed=0)

    def test_tail_report_percentiles(self):
        from repro.serving import Result

        results = [Result(tokens=np.zeros(1, np.int32), steps=1,
                          latency_s=float(i)) for i in range(1, 1001)]
        results.append(Result(tokens=np.zeros(0, np.int32), steps=0,
                              shed=True))
        results.append(Result(tokens=np.zeros(0, np.int32), steps=0,
                              failed=True))
        rep = TailReport.build("t", results)
        assert rep.completed == 1000 and rep.shed == 1 and rep.failed == 1
        assert rep.p50_s == pytest.approx(500.5)
        assert rep.p99_s == pytest.approx(990.01)
        assert rep.p999_s == pytest.approx(999.001)
        empty = TailReport.build("e", [])
        assert np.isnan(empty.p50_s)

    def test_reshape_injector_seeded(self):
        def trace(seed):
            inj = ReshapeFailureInjector(0.4, seed=seed)
            out = []
            for _ in range(64):
                try:
                    inj()
                    out.append(False)
                except InjectedFault:
                    out.append(True)
            return out

        assert trace(2) == trace(2)
        assert trace(2) != trace(3)
        never = ReshapeFailureInjector(0.0)
        for _ in range(16):
            never()
        assert never.injected == 0 and never.calls == 16


# ---------------------------------------------------------------------------
# acceptance: 4x burst + both injectors, exact ledger, run-twice identical
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.chaos
class TestContinuousChaosScenario:
    @pytest.fixture(scope="class")
    def stack(self, setup):
        cfg, params = setup
        planner, ladder = make_serving_stack(cfg, params)
        return cfg, params, planner, ladder

    LOADS = [TrafficLoad("steady", rate_rps=40.0, duration_s=1.0,
                         prompt_len=8, max_new_tokens=8, deadline_s=2.0),
             TrafficLoad("spike", rate_rps=0.0, duration_s=1.0,
                         prompt_len=8, max_new_tokens=8, deadline_s=2.0,
                         burst_at=0.3, burst_n=48)]   # ~4x the steady rate

    def _run(self, stack):
        cfg, params, planner, ladder = stack
        swap_inj = SwapFailureInjector(0.3, seed=1, steps=("begin",))
        resh_inj = ReshapeFailureInjector(0.3, seed=2)
        swapper = WidthSwapper(params, cfg, fault_hook=swap_inj,
                               reshape_fault_hook=resh_inj)
        admission = AdmissionControl(max_queue_batches=3,
                                     target_batch_s=0.25,
                                     ewma_alpha=0.5, headroom=2.0)
        degrader = DegradationController(
            ladder, down_threshold=1.0, up_threshold=0.5,
            down_patience=4, up_patience=8, observe_every=4)
        eng = ContinuousServeEngine(
            params, cfg, max_len=48, batch_slots=4, planner=planner,
            swapper=swapper, admission=admission, degrader=degrader,
            clock=VirtualClock(),
            batch_cost_fn=modeled_batch_cost(1e-3, overhead_s=0.002),
            max_retries=3, boundary_every=4, boundary_cooldown=8)
        arrivals = open_loop_arrivals(self.LOADS, cfg.vocab_size, seed=5)
        results = eng.run(arrivals)
        ledger = eng.drain()
        return eng, swap_inj, resh_inj, arrivals, results, ledger

    def test_faults_fire_and_nothing_is_lost(self, stack):
        eng, swap_inj, resh_inj, arrivals, results, ledger = self._run(stack)
        assert swap_inj.injected >= 1 and resh_inj.injected >= 1
        aborted = [b for b in eng.boundary_log
                   if b.outcome in ("swap_rolled_back", "reshape_failed")]
        assert aborted and any(b.requeued > 0 for b in aborted)
        # the resilience claim: ledger sums exactly, zero silently lost
        assert ledger.complete
        assert ledger.submitted == len(arrivals)
        assert ledger.failed == 0
        assert sum(r.recovered for r in results) > 0
        # recovered requests still produced their full token budget
        for r in results:
            if r.recovered:
                assert len(r.tokens) == 8

    def test_degradation_engages_under_burst(self, stack):
        eng, *_ = self._run(stack)
        downs = [s for s in eng.degrader.shift_log
                 if s.direction == "down"]
        assert downs, "controller never downshifted under a 4x burst"
        assert any(b.outcome == "ok" for b in eng.boundary_log)

    def test_scenario_run_twice_is_identical(self, stack):
        def signature():
            eng, swap_inj, resh_inj, arrivals, results, ledger = \
                self._run(stack)
            reports = class_tail_reports(arrivals, results)
            return (
                [(r.shed, r.failed, r.retries, r.latency_s,
                  r.tokens.tolist()) for r in results],
                [b.outcome for b in eng.boundary_log],
                [s.direction for s in eng.degrader.shift_log],
                ledger,
                {k: dataclasses.astuple(v) for k, v in reports.items()},
            )

        assert signature() == signature()


# ---------------------------------------------------------------------------
# AOT compile cache in the serving hot path
# ---------------------------------------------------------------------------
def make_cached_engine(cfg, params, plan, *, cache=None, lens=(6, 6),
                       max_new=8):
    """Continuous engine + compile cache + scripted narrow plan — the
    shared rig for the AOT-serving scenarios."""
    cache = cache if cache is not None else WidthVariantCompileCache(cfg)
    swapper = WidthSwapper(params, cfg)
    eng = ContinuousServeEngine(
        params, cfg, max_len=48, batch_slots=2, clock=VirtualClock(),
        swapper=swapper, compile_cache=cache,
        batch_cost_fn=modeled_batch_cost(1e-3),
        max_retries=3, boundary_every=2, boundary_cooldown=1000)
    eng.planner = None
    eng.degrader = _ScriptedSelector([plan])
    eng.admission = AdmissionControl(max_queue_batches=100)
    return eng, cache, swapper


@pytest.mark.slow
@pytest.mark.chaos
class TestCompileCacheServing:
    def _narrow(self, cfg, params, *, sliced):
        """A planner-produced mlp-narrowing plan with its economics
        pinned: ``sliced=True`` makes the modeled saving dwarf one AOT
        compile (own executable), ``False`` makes it negligible (the
        zero-mask crossover)."""
        planner, _ = make_serving_stack(cfg, params)
        narrow = planner.select(96)
        assert narrow.widths
        if sliced:
            return dataclasses.replace(narrow, latency_s=0.5,
                                       baseline_latency_s=1.0)
        return dataclasses.replace(narrow, latency_s=0.999,
                                   baseline_latency_s=1.0)

    def test_warm_boundary_crossing_traces_nothing(self, setup):
        """The acceptance contract: after warm_compile, a serve run that
        crosses a width boundary performs zero jit traces — every
        prefill/decode is an AOT executable hit."""
        cfg, params = setup
        narrow = self._narrow(cfg, params, sliced=True)
        eng, cache, _ = make_cached_engine(cfg, params, narrow)
        warmed = eng.warm_compile([narrow], prefill_lengths=(6,))
        assert warmed > 0
        traced_at_warm = cache.tracer.count
        results = eng.run(reqs_for(cfg, (6, 6), max_new=8))
        assert cache.tracer.count == traced_at_warm   # ZERO new traces
        assert cache.stats["hits"] > 0
        assert any(b.outcome == "ok" for b in eng.boundary_log)
        assert eng.ledger().complete
        assert all(len(r.tokens) == 8 for r in results)

    def test_masked_crossover_runs_on_full_width_executable(self, setup):
        """An uneconomic plan realizes as zero-masked full-shape params:
        the boundary commits, but the cache stays addressed at the
        full-width key — no narrow executable is ever built."""
        cfg, params = setup
        narrow = self._narrow(cfg, params, sliced=False)
        eng, cache, _ = make_cached_engine(cfg, params, narrow)
        assert cache.decide(narrow) == "masked"
        eng.warm_compile([narrow], prefill_lengths=(6,))
        traced_at_warm = cache.tracer.count
        results = eng.run(reqs_for(cfg, (6, 6), max_new=8))
        assert cache.tracer.count == traced_at_warm
        assert any(b.outcome == "ok" for b in eng.boundary_log)
        assert eng._masked_active
        assert cache.active_key == cache.full_key
        # full-shape params throughout: the masked tree mirrors canonical
        canon = {tuple(x.shape)
                 for x in jax.tree_util.tree_leaves(params)}
        active = {tuple(x.shape)
                  for x in jax.tree_util.tree_leaves(eng.params_active)}
        assert active == canon
        assert eng.ledger().complete
        assert all(len(r.tokens) == 8 for r in results)

    def test_lookup_fault_serves_traced_with_zero_lost(self, setup):
        """Chaos: every serve-time executable fetch faults.  The engine
        must fall back to the traced path and finish every request with
        its full token budget — an AOT fault is never a lost request."""
        cfg, params = setup
        narrow = self._narrow(cfg, params, sliced=True)
        inj = CompileFailureInjector(1.0, steps=("lookup",))
        cache = WidthVariantCompileCache(cfg, fault_hook=inj)
        eng, cache, _ = make_cached_engine(cfg, params, narrow,
                                           cache=cache)
        eng.warm_compile([narrow], prefill_lengths=(6,))
        results = eng.run(reqs_for(cfg, (6, 6), max_new=8))
        assert inj.injected >= 1
        assert cache.stats["fallbacks"] >= 1
        assert cache.stats["hits"] == 0       # warm entries unreachable
        led = eng.ledger()
        assert led.complete and led.failed == 0
        assert all(len(r.tokens) == 8 for r in results)

    def test_compile_fault_serves_traced_with_zero_lost(self, setup):
        """Chaos: plan-time AOT compilation faults, so nothing is ever
        warm — the run degrades to the historical traced behavior."""
        cfg, params = setup
        narrow = self._narrow(cfg, params, sliced=True)
        inj = CompileFailureInjector(1.0, steps=("compile",))
        cache = WidthVariantCompileCache(cfg, fault_hook=inj)
        eng, cache, _ = make_cached_engine(cfg, params, narrow,
                                           cache=cache)
        assert eng.warm_compile([narrow], prefill_lengths=(6,)) == 0
        assert inj.injected >= 1 and len(cache) == 0
        results = eng.run(reqs_for(cfg, (6, 6), max_new=8))
        assert cache.stats["fallbacks"] >= 1
        led = eng.ledger()
        assert led.complete and led.failed == 0
        assert all(len(r.tokens) == 8 for r in results)


# ---------------------------------------------------------------------------
# pow2 prefill buckets: bounded trace count, unchanged tokens
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestPrefillBucketing:
    LENS = (3, 5, 6, 7, 9, 12)

    def _run(self, cfg, params, *, cache):
        eng = ContinuousServeEngine(
            params, cfg, max_len=48, batch_slots=2, clock=VirtualClock(),
            compile_cache=cache,
            batch_cost_fn=modeled_batch_cost(1e-3))
        results = eng.run(reqs_for(cfg, self.LENS, max_new=6))
        assert eng.ledger().complete
        return eng, [r.tokens.tolist() for r in results]

    def test_buckets_bound_traces(self, setup):
        """Six distinct prompt lengths land in two pow2 buckets {8, 16}:
        exactly 2 prefill traces + 1 decode trace, instead of one trace
        per distinct length — the grow-boundary retrace fix, pinned."""
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        eng, _ = self._run(cfg, params, cache=cache)
        assert eng.prefill_bucketing          # default ON with a cache
        assert {eng._prefill_len(l) for l in self.LENS} == {8, 16}
        assert cache.tracer.count == 3        # 2 buckets + 1 decode shape

    def test_bucketed_tokens_match_unbucketed(self, setup):
        """Right-padded pow2 prefill is exact for global causal
        attention: the generated tokens are identical to the unbucketed
        engine's."""
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        _, bucketed = self._run(cfg, params, cache=cache)
        _, plain = self._run(cfg, params, cache=None)
        assert bucketed == plain

    def test_explicit_bucketing_on_ineligible_config_raises(self, setup):
        cfg, params = setup
        local_cfg = dataclasses.replace(cfg, block_pattern=("local",),
                                        window=8)
        local_params = init_params(jax.random.PRNGKey(0), local_cfg)
        with pytest.raises(ValueError, match="prefill_bucketing"):
            ContinuousServeEngine(local_params, local_cfg, max_len=48,
                                  prefill_bucketing=True)


# ---------------------------------------------------------------------------
# chunked prefill: interleaving, checkpoints, recovery
# ---------------------------------------------------------------------------
class _OneShotChunkFault:
    """Raise InjectedFault on exactly the n-th chunk execution."""

    def __init__(self, at):
        self.at = int(at)
        self.calls = 0
        self.injected = 0

    def __call__(self):
        self.calls += 1
        if self.calls == self.at:
            self.injected += 1
            raise InjectedFault(f"injected chunk fault at call {self.at}")


@pytest.mark.slow
class TestChunkedPrefill:
    LENS = (5, 13, 27, 3, 21)

    def _run(self, cfg, params, *, chunk, budget=None, hook=None,
             max_retries=2):
        eng = ContinuousServeEngine(
            params, cfg, max_len=64, batch_slots=2, prefill_chunk=chunk,
            step_token_budget=budget, chunk_fault_hook=hook,
            max_retries=max_retries)
        results = eng.run(reqs_for(cfg, self.LENS, max_new=8))
        return eng, results

    def test_chunked_tokens_match_whole_prefill(self, setup):
        """Chunk-at-a-time prefill against the growing cache is exact
        for greedy decoding: every request's tokens match the
        whole-prompt prefill engine's, and the chunk count is exactly
        sum(ceil(plen / chunk))."""
        cfg, params = setup
        _, plain = self._run(cfg, params, chunk=None)
        eng, chunked = self._run(cfg, params, chunk=4, budget=8)
        for a, b in zip(plain, chunked):
            assert np.array_equal(a.tokens, b.tokens)
        assert eng.chunk_steps == sum(-(-l // 4) for l in self.LENS)
        assert eng.ledger().complete

    def test_chunk_fault_resumes_from_checkpoint(self, setup):
        """A fault mid-prefill requeues at the last committed chunk, not
        token zero: the total successful chunk count stays exactly
        sum(ceil(plen / chunk)) — no chunk re-executed — and the request
        finishes with identical tokens, marked recovered."""
        cfg, params = setup
        _, plain = self._run(cfg, params, chunk=None)
        hook = _OneShotChunkFault(4)      # mid-prefill of an early prompt
        eng, results = self._run(cfg, params, chunk=4, hook=hook)
        assert hook.injected == 1
        assert eng.chunk_log and eng.chunk_log[0].committed > 0
        assert eng.chunk_steps == sum(-(-l // 4) for l in self.LENS)
        for a, b in zip(plain, results):
            assert np.array_equal(a.tokens, b.tokens)
        assert sum(r.recovered for r in results) == 1
        assert eng.ledger().complete and eng.ledger().failed == 0

    def test_chunk_retry_budget_exhaustion_fails_loudly(self, setup):
        """Every chunk faulting forever: the request fails terminally
        after max_retries, accounted in the ledger — never a hang."""
        cfg, params = setup

        def always():
            raise InjectedFault("permanent chunk fault")

        eng = ContinuousServeEngine(
            params, cfg, max_len=64, batch_slots=2, prefill_chunk=4,
            chunk_fault_hook=always, max_retries=1)
        results = eng.run(reqs_for(cfg, (9,), max_new=4))
        assert results[0].failed and results[0].retries == 2
        led = eng.ledger()
        assert led.complete and led.failed == 1

    def test_compile_cache_serves_the_same_tokens(self, setup):
        """The serving shape of the chip benchmark (chunked joins beside
        ragged decode, every slot busy) gives the same tokens through
        the warm AOT executables as through the engine's own jits."""
        cfg, params = setup

        def serve(cache):
            eng = ContinuousServeEngine(
                params, cfg, max_len=64, batch_slots=2, prefill_chunk=4,
                prefill_bucketing=True, compile_cache=cache)
            if cache is not None:
                eng.warm_compile([])
            return eng.run(reqs_for(cfg, self.LENS, max_new=8))

        cache = WidthVariantCompileCache(cfg)
        for a, b in zip(serve(None), serve(cache)):
            assert np.array_equal(a.tokens, b.tokens)
        assert cache.stats["hits"] > 0 and cache.stats["misses"] == 0

    def test_chunk_on_ineligible_config_raises(self, setup):
        cfg, params = setup
        local_cfg = dataclasses.replace(cfg, block_pattern=("local",),
                                        window=8)
        local_params = init_params(jax.random.PRNGKey(0), local_cfg)
        with pytest.raises(ValueError, match="chunked prefill"):
            ContinuousServeEngine(local_params, local_cfg, max_len=48,
                                  prefill_chunk=4)

    def test_chunk_shapes_are_bounded_with_cache(self, setup):
        """With a compile cache the chunk executable shape set is the
        chunk plus pow2 tail buckets — bounded, AOT-warmable."""
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        eng = ContinuousServeEngine(
            params, cfg, max_len=64, batch_slots=2, prefill_chunk=8,
            compile_cache=cache)
        eng.warm_compile([], prefill_lengths=self.LENS)
        traced_before = cache.tracer.count
        results = eng.run(reqs_for(cfg, self.LENS, max_new=4))
        assert eng.ledger().complete
        # decode is the only trace the serve loop should add on top of
        # the warmed chunk executables
        assert cache.tracer.count - traced_before <= 1
        assert all(len(r.tokens) == 4 for r in results)


class TestDrainFastPath:
    def test_drain_on_zero_submitted_engine(self, setup):
        """drain() before any submission returns the empty-but-complete
        ledger without stepping the engine at all — pinned (the guard
        keeps the zero-work drain from ever touching the model)."""
        cfg, params = setup
        eng = ContinuousServeEngine(params, cfg, max_len=32)
        led = eng.drain()
        assert led == Ledger(submitted=0, finished=0, shed=0, failed=0,
                             in_flight=0, queued=0, evicted=0)
        assert led.complete and eng.steps == 0

    def test_drain_after_completion_is_also_stepless(self, setup):
        cfg, params = setup
        eng = ContinuousServeEngine(params, cfg, max_len=32)
        eng.run(reqs_for(cfg, (4,), max_new=2))
        steps = eng.steps
        led = eng.drain()
        assert led.complete and led.finished == 1
        assert eng.steps == steps
