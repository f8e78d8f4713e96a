"""Width-variant AOT compile cache: keys, crossover, trace accounting,
fault fallback, and the autotuned-tile numerics contract.

The model-backed scenarios reuse the reduced serving config; every
assertion is exact (trace counts, stats dicts, bitwise logits), not
statistical.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced_config
from repro.core import TPU_V5E as HW
from repro.core.plan_address import plan_key
from repro.kernels import ops
from repro.models import init_params
from repro.models import transformer as tfm
from repro.serving import (
    TraceCounter, TrafficClass, WidthPlan, WidthSwapper,
    WidthVariantCompileCache, pow2_bucket, realized_exec_key,
    serving_templates,
)
from repro.serving.chaos import CompileFailureInjector, InjectedFault
from repro.serving.compile_cache import decode_state_struct


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config(get_config("qwen1.5-0.5b"), d_model=128,
                         n_layers=2, d_ff=576)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_plan(widths, modules, *, tokens=96, latency_s=1.0,
              baseline_latency_s=2.0, name="t"):
    return WidthPlan(traffic=TrafficClass(name, tokens), widths=widths,
                     latency_s=latency_s,
                     baseline_latency_s=baseline_latency_s,
                     satisfied=True, modules=modules)


# ---------------------------------------------------------------------------
# pure units: buckets, trace counting, keys, crossover
# ---------------------------------------------------------------------------
class TestUnits:
    def test_pow2_bucket(self):
        assert pow2_bucket(1) == 8          # lo floor
        assert pow2_bucket(8) == 8
        assert pow2_bucket(9) == 16
        assert pow2_bucket(16) == 16
        assert pow2_bucket(17) == 32
        assert pow2_bucket(3, lo=1) == 4
        assert pow2_bucket(1000) == 1024

    def test_trace_counter_counts_traces_not_calls(self):
        tracer = TraceCounter()
        f = jax.jit(tracer.wrap(lambda x: x * 2))
        f(jnp.zeros((3,)))
        f(jnp.ones((3,)))                   # jit-cache hit: no trace
        assert tracer.count == 1
        f(jnp.zeros((4,)))                  # new shape: one more trace
        assert tracer.count == 2

    def test_realized_exec_key_distinct(self, setup):
        cfg, _ = setup
        cache = WidthVariantCompileCache(cfg)
        full = realized_exec_key(
            np.full(cfg.n_layers, cfg.d_ff),
            np.full(cfg.n_layers, cfg.n_heads))
        assert full == cache.full_key
        narrow = realized_exec_key(
            np.full(cfg.n_layers, 256), np.full(cfg.n_layers, cfg.n_heads))
        assert narrow != full
        # set_active(None) resets to the canonical full key
        cache.set_active(narrow)
        assert cache.active_key == narrow
        cache.set_active(None)
        assert cache.active_key == cache.full_key

    def test_decide_crossover(self, setup):
        cfg, _ = setup
        cache = WidthVariantCompileCache(cfg, compile_cost_s=0.25,
                                         horizon_batches=32)
        # saving over the horizon dwarfs one compile -> own executable
        big = make_plan({"mlp0": 256}, {}, latency_s=1.0,
                        baseline_latency_s=2.0)
        assert cache.decide(big) == "sliced"
        # saving (1 ms * 32) < 0.25 s -> masked onto the warm full path
        small = make_plan({"mlp0": 256}, {}, latency_s=0.999,
                          baseline_latency_s=1.0)
        assert cache.decide(small) == "masked"
        # the full-width plan has nothing to mask
        full = make_plan({}, {})
        assert cache.decide(full) == "sliced"

    def test_warm_plan_registry(self, setup):
        cfg, _ = setup
        cache = WidthVariantCompileCache(cfg)
        p = make_plan({"mlp0": 256}, {})
        q = make_plan({"mlp0": 384}, {})
        assert not cache.plan_is_warm(p)
        cache.mark_plan_warm(p)
        assert cache.plan_is_warm(p)
        assert not cache.plan_is_warm(q)
        assert plan_key(p.widths) != plan_key(q.widths)


# ---------------------------------------------------------------------------
# AOT executables: zero-trace warm path, traced fallback, faults
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestExecutables:
    def test_warm_prefill_zero_traces_and_matches_traced(self, setup):
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(1, 8)).astype(np.int32))
        assert cache.precompile("prefill", cache.full_key, (1, 8),
                                (params, toks))
        assert cache.stats["aot_compiles"] == 1
        # the executable is named for its kind (HLO and profiler traces)
        assert cache.executable("prefill", cache.full_key, (1, 8)) \
            .as_text().startswith("HloModule jit_prefill,")
        traced_after_warm = cache.tracer.count   # lower() traced once
        out = cache.prefill(params, toks)
        out2 = cache.prefill(params, toks)
        assert cache.tracer.count == traced_after_warm  # zero new traces
        assert cache.stats["hits"] == 2
        ref_logits, _, _ = tfm.forward(params, cfg, tokens=toks,
                                       mode="prefill")
        np.testing.assert_array_equal(
            np.asarray(out[0].astype(jnp.float32)),
            np.asarray(ref_logits.astype(jnp.float32)))
        del out2

    def test_cold_lookup_falls_back_to_traced(self, setup):
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        logits, _, _ = cache.prefill(params, toks)
        assert logits.shape[:2] == (1, 8)
        assert cache.stats["misses"] == 1
        assert cache.tracer.count == 1           # the fallback traced

    def test_warm_decode_zero_traces(self, setup):
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        b, max_len = 2, 32
        struct = decode_state_struct(cfg, b, max_len)
        tok = jnp.zeros((b,), jnp.int32)
        pos = jnp.zeros((), jnp.int32)
        assert cache.precompile("decode", cache.full_key, (b,),
                                (params, tok, pos, struct))
        assert cache.executable("decode", cache.full_key, (b,)) \
            .as_text().startswith("HloModule jit_decode,")
        traced = cache.tracer.count
        states = tfm.init_decode_state(cfg, b, max_len)
        logits, new_states = cache.decode(params, tok, pos, states)
        assert cache.tracer.count == traced
        assert cache.stats["hits"] == 1
        assert logits.shape[0] == b
        jax.tree_util.tree_map(lambda a, s: None, new_states, states)

    def test_decode_executable_moves_no_cache_slab(self, setup):
        """Decode reads the stacked KV cache and writes only the new
        rows: the compiled program builds no broadcast or copy of the
        whole stack, and no copy or scatter of one layer's slab (the
        per-layer slab relay it replaced had both)."""
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        b, max_len = 4, 32
        struct = decode_state_struct(cfg, b, max_len)
        tok = jnp.zeros((b,), jnp.int32)
        assert cache.precompile("decode", cache.full_key, (b,),
                                (params, tok, tok, struct))
        text = cache.executable("decode", cache.full_key, (b,)).as_text()
        stack = struct["stack"]["u0"]["k"].shape
        slabs = {stack[1:], (1,) + stack[1:]}
        ops_by_shape = [
            (op, tuple(int(d) for d in dims.split(",")))
            for dims, op in re.findall(
                r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(", text)]
        assert not [(op, sh) for op, sh in ops_by_shape
                    if (sh == stack and op in ("broadcast", "copy"))
                    or (sh in slabs
                        and op in ("broadcast", "copy", "scatter"))]
        assert ("dynamic-update-slice", stack) in ops_by_shape

    def test_compile_fault_absorbed_and_served_traced(self, setup):
        cfg, params = setup
        inj = CompileFailureInjector(1.0, steps=("compile",))
        cache = WidthVariantCompileCache(cfg, fault_hook=inj)
        toks = jnp.zeros((1, 8), jnp.int32)
        assert not cache.precompile("prefill", cache.full_key, (1, 8),
                                    (params, toks))
        assert inj.injected >= 1
        assert cache.stats["fallbacks"] == 1
        assert len(cache) == 0
        assert cache.events[-1].outcome == "fault"
        logits, _, _ = cache.prefill(params, toks)   # traced path serves
        assert np.isfinite(
            np.asarray(logits.astype(jnp.float32))).all()

    def test_lookup_fault_absorbed_and_served_traced(self, setup):
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        assert cache.precompile("prefill", cache.full_key, (1, 8),
                                (params, toks))
        cache.fault_hook = CompileFailureInjector(1.0, steps=("lookup",))
        logits, _, _ = cache.prefill(params, toks)
        assert logits.shape[:2] == (1, 8)
        assert cache.stats["fallbacks"] == 1
        assert cache.stats["hits"] == 0

    def test_real_compile_error_raises(self, setup):
        """A lowering the hook did not inject fails the warm-up instead
        of being absorbed into a serve-time retrace."""
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        bad = jnp.zeros((1, 8), jnp.float32)        # tokens must be ints
        with pytest.raises(Exception):
            cache.precompile("prefill", cache.full_key, (1, 8),
                             (params, bad))
        assert cache.stats["fallbacks"] == 0 and len(cache) == 0
        assert not any(e.outcome == "fault" for e in cache.events)

    def test_executable_error_raises_not_retraced(self, setup):
        """An AOT executable fed arguments it was not compiled for raises;
        the traced jit does not silently take over."""
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        assert cache.precompile("prefill", cache.full_key, (1, 8),
                                (params, toks))
        traced = cache.tracer.count
        wrong = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        with pytest.raises(Exception):
            cache.prefill(wrong, toks)
        assert cache.tracer.count == traced
        assert cache.stats["fallbacks"] == 0

    def test_lru_bounds_executables(self, setup):
        cfg, params = setup
        cache = WidthVariantCompileCache(cfg, max_entries=1)
        t8 = jnp.zeros((1, 8), jnp.int32)
        t16 = jnp.zeros((1, 16), jnp.int32)
        cache.precompile("prefill", cache.full_key, (1, 8), (params, t8))
        cache.precompile("prefill", cache.full_key, (1, 16), (params, t16))
        assert len(cache) == 1               # oldest evicted


# ---------------------------------------------------------------------------
# masked realization: full-shape zero-masked params, distinct cache key
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestMaskedRealization:
    def test_masked_apply_keeps_canonical_shapes(self, setup):
        cfg, params = setup
        _, modules = serving_templates(cfg, HW, tokens=96, sites=("mlp",))
        swapper = WidthSwapper(params, cfg)
        plan = make_plan({f"mlp{i}": 256 for i in range(cfg.n_layers)},
                         modules)
        sliced, ev_s = swapper.apply(plan)
        masked, ev_m = swapper.apply(plan, masked=True)
        assert not ev_s.masked and ev_m.masked
        s_shapes = {tuple(x.shape)
                    for x in jax.tree_util.tree_leaves(sliced)}
        m_shapes = [tuple(x.shape)
                    for x in jax.tree_util.tree_leaves(masked)]
        f_shapes = [tuple(x.shape)
                    for x in jax.tree_util.tree_leaves(params)]
        assert m_shapes == f_shapes          # canonical shapes throughout
        assert s_shapes != set(m_shapes)     # the sliced tree is smaller
        # dropped channels really are zero: a masked forward cannot read
        # them even through a stale optimizer state
        w_up = masked["decoder"]["stack"]["u0"]["mlp"]["w_up"]
        assert not np.asarray(w_up[..., 256:]).any()
        assert np.asarray(w_up[..., :256]).any()

    def test_masked_and_sliced_use_distinct_swap_cache_keys(self, setup):
        cfg, params = setup
        _, modules = serving_templates(cfg, HW, tokens=96, sites=("mlp",))
        swapper = WidthSwapper(params, cfg)
        plan = make_plan({f"mlp{i}": 256 for i in range(cfg.n_layers)},
                         modules)
        a, _ = swapper.apply(plan, masked=True)
        b, _ = swapper.apply(plan)
        c, _ = swapper.apply(plan, masked=True)
        assert a is c                        # masked entry cached
        assert a is not b                    # and distinct from sliced

    def test_full_width_plan_ignores_masked_flag(self, setup):
        cfg, params = setup
        _, modules = serving_templates(cfg, HW, tokens=96, sites=("mlp",))
        swapper = WidthSwapper(params, cfg)
        plan = make_plan({}, modules)
        p, ev = swapper.apply(plan, masked=True)
        assert not ev.masked                 # nothing to mask at full width
        assert p is swapper.full_params


# ---------------------------------------------------------------------------
# autotuned tiles: sliced forward bit-for-bit vs default-tile forward
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.kernels
class TestAutotunedTileNumerics:
    def test_sliced_forward_bitwise_default_vs_autotuned(self, setup):
        """The acceptance contract for threading ``ops.*(hw=...)`` tiles
        through the model: on shapes where the contraction blocking
        coincides (single k-step, single kv-chunk), the autotuned-tile
        forward must be bit-for-bit with the default-tile forward —
        tiling the independent output axes differently is free."""
        cfg, params = setup
        _, modules = serving_templates(cfg, HW, tokens=96, sites=("mlp",))
        swapper = WidthSwapper(params, cfg)
        plan = make_plan({f"mlp{i}": 128 for i in range(cfg.n_layers)},
                         modules)
        sliced, _ = swapper.apply(plan)
        toks = jnp.asarray(np.random.default_rng(3).integers(
            0, cfg.vocab_size, size=(2, 16)).astype(np.int32))
        with ops.kernel_context(force="pallas_interpret"):
            base, _, _ = tfm.forward(sliced, cfg, tokens=toks,
                                     mode="prefill")
        with ops.kernel_context(hw=HW, force="pallas_interpret"):
            tuned, _, _ = tfm.forward(sliced, cfg, tokens=toks,
                                      mode="prefill")
        np.testing.assert_array_equal(
            np.asarray(base.astype(jnp.float32)),
            np.asarray(tuned.astype(jnp.float32)))

    def test_kernel_context_inert_in_ref_mode(self, setup):
        """Without a force override off-TPU, the context must not change
        numerics: the routed path is only taken when a kernel mode is
        actually active."""
        cfg, params = setup
        toks = jnp.asarray(np.random.default_rng(4).integers(
            0, cfg.vocab_size, size=(1, 8)).astype(np.int32))
        with jax.disable_jit():
            plain, _, _ = tfm.forward(params, cfg, tokens=toks,
                                      mode="prefill")
            with ops.kernel_context(hw=HW, force="ref"):
                ctxd, _, _ = tfm.forward(params, cfg, tokens=toks,
                                         mode="prefill")
        np.testing.assert_array_equal(
            np.asarray(plain.astype(jnp.float32)),
            np.asarray(ctxd.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# chaos injector unit
# ---------------------------------------------------------------------------
class TestCompileFailureInjector:
    def test_rate_one_raises_on_matching_step(self):
        inj = CompileFailureInjector(1.0, steps=("lookup",))
        inj("compile")                       # non-matching step: no-op
        with pytest.raises(InjectedFault):
            inj("lookup")
        assert inj.calls == 1 and inj.injected == 1  # only matching steps

    def test_rate_zero_never_raises(self):
        inj = CompileFailureInjector(0.0)
        for _ in range(20):
            inj("lookup")
        assert inj.injected == 0

    def test_unknown_step_rejected(self):
        with pytest.raises(ValueError):
            CompileFailureInjector(1.0, steps=("frobnicate",))
