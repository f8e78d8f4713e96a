"""Width-variant executable cache: AOT-compiled prefill/decode per plan.

Every distinct realized ``WidthPlan`` changes the param (and KV) shapes
the serving engines feed ``models.transformer``, and a fresh shape costs
a full jit trace + XLA compile (~hundreds of ms) at its first boundary
crossing — exactly the latency spike a width *optimizer* exists to
remove.  This module makes the executable itself a planned, cached
artifact, the same way ``core.table_cache.ProfileTableCache`` makes the
staircase tables one:

  * :class:`WidthVariantCompileCache` AOT-compiles (``jax.jit(...)
    .lower(...).compile()``) the prefill and decode functions for every
    plan-realizable width at *plan time* (``ServeEngine.warm_compile`` /
    ``ContinuousServeEngine.warm_compile``), keyed on
    ``(hardware fingerprint, kind, realized plan key, shape bucket)``.
    A warm boundary crossing is then a dict lookup — never a trace.
  * Serve-time entry points (:meth:`prefill` / :meth:`decode` /
    :meth:`chunk`) take an ordinary traced ``jax.jit`` path on a miss,
    so a cold cache serves like the historical jit lambdas.  A fault
    that ``fault_hook`` injects (``serving.chaos.CompileFailureInjector``)
    takes the same traced path and is counted in ``stats["fallbacks"]``.
    A real lowering, compile or execution error raises: the device is
    never hidden behind a silent retrace.
  * :meth:`decide` is the **cost crossover**: when a plan's modeled
    saving over the engine's horizon is smaller than one AOT compile,
    the plan should be realized as *zero-masked full-shape params*
    (``WidthSwapper.apply(plan, masked=True)``) running on the already
    -warm full-width executable — trading the plan's FLOP saving for a
    guaranteed-warm boundary.
  * :class:`TraceCounter` is the observability hook the acceptance
    assertions hang off: it wraps the Python callables handed to
    ``jax.jit``, so ``tracer.count`` increments exactly when XLA
    (re-)traces — a warm crossing leaves it unchanged.

The model functions are traced inside ``kernels.ops.kernel_context``
(``hw=`` the cache's hardware spec), so on a Pallas backend every
compiled variant runs on the wave-aligned tiles ``kernels.autotune``
picks; off-TPU the context is inert and the reference path is used
unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.plan_address import plan_key
from repro.kernels import ops
from repro.models import transformer as tfm

# Fault-hook checkpoints, mirroring width_swap.SWAP_STEPS: "lower" and
# "compile" fire during plan-time AOT compilation, "lookup" on every
# serve-time executable fetch.  A hook raising at any of them must leave
# the engine on the traced fallback path with zero lost requests.
COMPILE_STEPS = ("lower", "compile", "lookup")


def pow2_bucket(n: int, lo: int = 8) -> int:
    """Smallest power of two >= n (and >= lo) — the prefill length
    bucket.  Bucketing bounds the number of distinct prefill shapes (and
    therefore traces/executables) at log2(max_len) instead of one per
    distinct prompt length."""
    n = max(int(n), 1)
    b = max(int(lo), 1)
    while b < n:
        b *= 2
    return b


class TraceCounter:
    """Counts jit traces by counting Python-body executions.

    ``jax.jit`` only runs the wrapped Python callable on a trace-cache
    miss, so incrementing inside the body counts traces exactly: AOT
    ``lower()`` calls count (they trace once, at plan time), warm
    executable calls and jit-cache hits do not.  The wrapper keeps
    ``fn``'s name, so the executable ``jax.jit`` builds from it is named
    after ``fn`` (``jit_decode``, ...) in HLO and in profiler traces."""

    def __init__(self) -> None:
        self.count = 0

    def wrap(self, fn: Callable) -> Callable:
        @functools.wraps(fn)
        def counted(*args, **kwargs):
            self.count += 1
            return fn(*args, **kwargs)
        return counted


@dataclasses.dataclass(frozen=True)
class CompileEvent:
    """One cache interaction, appended to ``events``."""

    kind: str           # "prefill" | "decode" | "chunk"
    key: tuple          # full executable key (fingerprint/kind/plan/shape)
    outcome: str        # "compiled" | "hit" | "miss" | "fault"
    wall_s: float = 0.0
    error: str = ""


def realized_exec_key(mlp_w, heads) -> tuple:
    """Executable key for a realized width assignment: the per-layer
    (mlp widths, head counts) the param/KV *shapes* follow.  Masked
    realizations keep canonical shapes and therefore use the cache's
    ``full_key`` instead."""
    return (tuple(int(x) for x in np.asarray(mlp_w).ravel()),
            tuple(int(x) for x in np.asarray(heads).ravel()))


class WidthVariantCompileCache:
    """AOT executable table for one model config.

    One instance per engine (``cfg`` must match the engine's): the
    engines route every prefill/decode through :meth:`prefill` /
    :meth:`decode`, and call ``set_active`` with the realized executable
    key at each boundary so lookups address the right variant.
    """

    def __init__(self, cfg: ModelConfig, *, hw=None, tile_cache=None,
                 compile_cost_s: float = 0.25, horizon_batches: int = 32,
                 fault_hook: "Callable[[str], None] | None" = None,
                 max_entries: int = 64):
        self.cfg = cfg
        self.hw = hw
        self.tile_cache = tile_cache
        if hw is not None:
            from repro.core.table_cache import hardware_fingerprint
            self.fingerprint = hardware_fingerprint(hw)
        else:
            self.fingerprint = ""
        self.compile_cost_s = float(compile_cost_s)
        self.horizon_batches = max(int(horizon_batches), 1)
        self.fault_hook = fault_hook
        self.max_entries = max(int(max_entries), 1)
        self._exec: "OrderedDict[tuple, Any]" = OrderedDict()
        self._warm_plans: set = set()
        self.events: List[CompileEvent] = []
        self.stats = {"aot_compiles": 0, "hits": 0, "misses": 0,
                      "fallbacks": 0}
        self.tracer = TraceCounter()

        n_refs = len(tfm.decoder_layer_refs(cfg))
        # Canonical full-width executable key — what masked realizations
        # and the engine's initial (unswapped) state resolve to.
        self.full_key = ((cfg.d_ff,) * n_refs, (cfg.n_heads,) * n_refs)
        self._active_key: tuple = self.full_key

        # The single pair of jit wrappers used for BOTH plan-time AOT
        # lowering and the serve-time traced fallback; their bodies run
        # under the kernel context so Pallas backends get autotuned
        # tiles (inert in ref mode — numerics unchanged).  Each function
        # is named for its kind: the executables are jit_prefill,
        # jit_decode and jit_chunk.
        def prefill(p, toks):
            with ops.kernel_context(hw=self.hw, cache=self.tile_cache):
                return tfm.forward(p, cfg, tokens=toks, mode="prefill")

        def decode(p, t, pos, st):
            with ops.kernel_context(hw=self.hw, cache=self.tile_cache):
                return tfm.decode_step(p, cfg, t, pos, st)

        def chunk(p, toks, pos, st):
            with ops.kernel_context(hw=self.hw, cache=self.tile_cache):
                return tfm.prefill_chunk(p, cfg, toks, pos, st)

        self._jit = {
            "prefill": jax.jit(self.tracer.wrap(prefill)),
            "decode": jax.jit(self.tracer.wrap(decode)),
            "chunk": jax.jit(self.tracer.wrap(chunk)),
        }

    # ------------------------------------------------------------------
    # keys
    # ------------------------------------------------------------------
    def set_active(self, key: "tuple | None") -> None:
        """Point serve-time lookups at a realized executable key (the
        boundary-time switch).  ``None`` resets to full width."""
        self._active_key = self.full_key if key is None else tuple(key)

    @property
    def active_key(self) -> tuple:
        return self._active_key

    def _entry_key(self, kind: str, key: tuple, shape_key: tuple) -> tuple:
        return (self.fingerprint, kind, key, tuple(shape_key))

    def __len__(self) -> int:
        return len(self._exec)

    # ------------------------------------------------------------------
    # warm-plan registry (planner preference signal)
    # ------------------------------------------------------------------
    def mark_plan_warm(self, plan) -> None:
        self._warm_plans.add(plan_key(plan.widths))

    def plan_is_warm(self, plan) -> bool:
        return plan_key(plan.widths) in self._warm_plans

    # ------------------------------------------------------------------
    # cost crossover
    # ------------------------------------------------------------------
    def decide(self, plan) -> str:
        """``"sliced"`` | ``"masked"``: realize the plan with genuinely
        smaller shapes (own executable) or as zero-masked full-shape
        params on the warm full-width executable.

        The crossover prices one AOT compile against the plan's modeled
        saving over ``horizon_batches`` served batches: recompilation
        that costs more wall time than the FLOPs it saves is realized as
        a mask instead."""
        widths = getattr(plan, "widths", None)
        if not widths:
            return "sliced"     # full width: nothing to mask
        saved_per_batch = max(
            float(plan.baseline_latency_s) - float(plan.latency_s), 0.0)
        saved = saved_per_batch * self.horizon_batches
        return "sliced" if saved >= self.compile_cost_s else "masked"

    # ------------------------------------------------------------------
    # plan-time AOT compilation
    # ------------------------------------------------------------------
    def _injected(self, step: str, ek: tuple, t0: "float | None" = None
                  ) -> bool:
        """Run the fault hook at ``step``.  True when it raised: the
        fault is recorded and the caller takes the traced path.  Only
        the hook's own faults are absorbed this way."""
        if self.fault_hook is None:
            return False
        try:
            self.fault_hook(step)
        except Exception as e:  # noqa: BLE001 — an injected fault
            self.stats["fallbacks"] += 1
            self.events.append(CompileEvent(
                kind=ek[1], key=ek, outcome="fault",
                wall_s=0.0 if t0 is None else time.perf_counter() - t0,
                error=f"{type(e).__name__}: {e}"))
            return True
        return False

    def precompile(self, kind: str, key: tuple, shape_key: tuple,
                   example_args: tuple) -> bool:
        """AOT-compile one (kind, realized key, shape) executable from
        example args (arrays or ShapeDtypeStructs).  Returns True when
        the entry is warm afterwards, False when the fault hook refused
        it (the serve path then traces).  A real lowering or compile
        error raises: a kernel the compiler refuses must stop the run."""
        if kind not in self._jit:
            raise ValueError(f"unknown kind {kind!r}")
        ek = self._entry_key(kind, key, shape_key)
        if ek in self._exec:
            return True
        t0 = time.perf_counter()
        if self._injected("lower", ek, t0):
            return False
        lowered = self._jit[kind].lower(*example_args)
        if self._injected("compile", ek, t0):
            return False
        compiled = lowered.compile()
        self._exec[ek] = compiled
        while len(self._exec) > self.max_entries:
            self._exec.popitem(last=False)
        self.stats["aot_compiles"] += 1
        self.events.append(CompileEvent(
            kind=kind, key=ek, outcome="compiled",
            wall_s=time.perf_counter() - t0))
        return True

    def executable(self, kind: str, key: tuple, shape_key: tuple):
        """The warm AOT executable for an entry, or None."""
        return self._exec.get(self._entry_key(kind, key, shape_key))

    # ------------------------------------------------------------------
    # serve-time entry points
    # ------------------------------------------------------------------
    def _get(self, kind: str, shape_key: tuple):
        ek = self._entry_key(kind, self._active_key, shape_key)
        if self._injected("lookup", ek):
            return None
        exe = self._exec.get(ek)
        if exe is None:
            self.stats["misses"] += 1
            self.events.append(CompileEvent(kind=kind, key=ek,
                                            outcome="miss"))
            return None
        self._exec.move_to_end(ek)
        self.stats["hits"] += 1
        return exe

    def _call(self, kind: str, shape_key: tuple, *args):
        """AOT hit, else the traced jit (a miss, or a lookup fault the
        hook injected).  Errors raised by the executable propagate."""
        exe = self._get(kind, shape_key)
        if exe is None:
            return self._jit[kind](*args)
        return exe(*args)

    def prefill(self, params, toks):
        """Whole-prompt prefill.  Same signature and return value as the
        engines' historical jit lambda."""
        return self._call("prefill", tuple(int(d) for d in toks.shape),
                          params, toks)

    def decode(self, params, toks, pos, states):
        """One decode step."""
        return self._call("decode", tuple(int(d) for d in toks.shape),
                          params, toks, pos, states)

    def chunk(self, params, toks, pos, states):
        """One prefill chunk (``tfm.prefill_chunk``).  The chunk offset
        ``pos`` is a traced argument, so one executable per chunk *shape*
        serves every chunk position — the chunked-prefill shape set is
        {(1, chunk)} plus the pow2 tail buckets, bounded exactly like
        bucketed whole-prompt prefill."""
        return self._call("chunk", tuple(int(d) for d in toks.shape),
                          params, toks, pos, states)


def decode_state_struct(cfg: ModelConfig, b: int, max_len: int, *,
                        swapper=None, heads=None):
    """Shape/dtype pytree of the decode state for AOT lowering — built
    under ``jax.eval_shape`` so nothing is allocated.  With a swapper +
    realized ``heads``, the canonical state is re-sliced to the plan's
    KV shapes (fault hook disabled: this is shape inference, not a
    swap)."""
    def build():
        st = tfm.init_decode_state(cfg, b, max_len)
        if swapper is not None and heads is not None:
            full = np.full(len(swapper.refs), cfg.n_heads, dtype=np.int64)
            if (np.asarray(heads) != full).any():
                st = swapper.reshape_states(st, full, np.asarray(heads))
        return st

    if swapper is not None:
        hook, swapper.reshape_fault_hook = swapper.reshape_fault_hook, None
        try:
            return jax.eval_shape(build)
        finally:
            swapper.reshape_fault_hook = hook
    return jax.eval_shape(build)
