"""Jit'd dispatch wrappers: Pallas kernel on TPU, reference path elsewhere.

The model code calls these; on the CPU dry-run they lower the memory-safe
jnp reference (real HLO, real cost analysis), on TPU runtime they hit the
Pallas kernels, and with ``force='pallas_interpret'`` they execute the
kernel bodies in Python for correctness tests.

Tile selection: every kernel wrapper takes either explicit block args or
``hw=`` (a ``HardwareSpec``), in which case blocks come from the
tail-aware autotuner (``repro.kernels.autotune`` — roofline + Eq. 3
grid-wave scoring, memoized per hardware/shape and optionally persisted
via ``cache=``).  With neither, the historical fixed defaults apply.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_lib
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul_tiled import matmul_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.rglru import rglru_pallas
from repro.kernels.rwkv6 import rwkv6_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(force: Optional[str]) -> str:
    if force:
        return force
    return "pallas" if _on_tpu() else "ref"


@dataclasses.dataclass(frozen=True)
class KernelContext:
    """Ambient tile-selection state for model code that cannot thread
    ``hw=``/``cache=``/``force=`` through every call site (e.g. the
    transformer forward traced inside a serving jit).  Installed with
    :func:`kernel_context`; the dispatch wrappers below fall back to it
    whenever their own hw/cache/force arguments are left unset."""

    hw: Any = None
    cache: Any = None
    force: Optional[str] = None


_KERNEL_CTX: Optional[KernelContext] = None


def get_kernel_context() -> Optional[KernelContext]:
    return _KERNEL_CTX


def kernel_routing_active() -> bool:
    """True when an installed kernel context would actually reach a
    kernel backend.  In ref mode the wrappers route to the jnp reference
    paths, whose numerics differ from the models' native einsum code —
    callers must keep their historical path then, so a context on a
    CPU-only run is inert by construction."""
    ctx = _KERNEL_CTX
    return ctx is not None and _mode(ctx.force) != "ref"


@contextlib.contextmanager
def kernel_context(hw=None, cache=None, force: Optional[str] = None):
    """Install a :class:`KernelContext` for the duration of the block.
    Trace-time scoping: model code traced under this context bakes the
    context's tile choices into the jaxpr, so an AOT-compiled executable
    keeps its autotuned blocks forever.  Fields left unset inherit from
    an enclosing context, so an outer ``force=`` reaches the contexts
    the serving compile cache installs."""
    global _KERNEL_CTX
    prev = _KERNEL_CTX
    _KERNEL_CTX = KernelContext(*_ctx_fallback(hw, cache, force))
    try:
        yield _KERNEL_CTX
    finally:
        _KERNEL_CTX = prev


def _ctx_fallback(hw, cache, force):
    """Fill unset hw/cache/force from the ambient context, if any."""
    ctx = _KERNEL_CTX
    if ctx is None:
        return hw, cache, force
    return (hw if hw is not None else ctx.hw,
            cache if cache is not None else ctx.cache,
            force if force is not None else ctx.force)


def _dtype_bits(x) -> int:
    return jnp.asarray(x).dtype.itemsize * 8


def matmul(x, w, *, block_m: Optional[int] = None,
           block_n: Optional[int] = None, block_k: Optional[int] = None,
           hw=None, cache=None, force: Optional[str] = None):
    """Tile-quantized matmul.  Pads M/N/K up to block multiples — the pad
    FLOPs are the tail the width optimizer removes by resizing N."""
    hw, cache, force = _ctx_fallback(hw, cache, force)
    mode = _mode(force)
    if mode == "ref":
        return ref_lib.matmul_ref(x, w)
    m, k = x.shape
    _, n = w.shape
    if hw is not None and block_m is None and block_n is None \
            and block_k is None:
        from repro.kernels.autotune import autotune_matmul
        cfg = autotune_matmul(hw, m, n, k, dtype_bits=_dtype_bits(x),
                              cache=cache)
        block_m, block_n, block_k = cfg.blocks
    block_m = 256 if block_m is None else block_m
    block_n = 256 if block_n is None else block_n
    block_k = 512 if block_k is None else block_k
    pad = lambda d, b: (-d) % b
    pm = pad(m, min(block_m, m))
    pn = pad(n, min(block_n, n))
    pk = pad(k, min(block_k, k))
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    wp = jnp.pad(w, ((0, pk), (0, pn)))
    out = matmul_pallas(xp, wp, block_m=block_m, block_n=block_n,
                        block_k=block_k,
                        interpret=(mode == "pallas_interpret"))
    return out[:m, :n]


def flash_attention(q, k, v, *, mask_kind: str = "causal", window: int = 0,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    hw=None, cache=None, force: Optional[str] = None):
    """Flash attention.  Non-divisible sequences are zero-padded for
    causal/local masks (trailing padded kv positions are masked out by
    position, padded q rows are sliced off — exact); an unmasked
    attention cannot pad kv, so non-divisible Skv raises there."""
    hw, cache, force = _ctx_fallback(hw, cache, force)
    mode = _mode(force)
    if mode == "ref":
        from repro.models.attention import chunked_attention
        return chunked_attention(q, k, v, mask_kind=mask_kind,
                                 window=window)
    b, sq, h, dh = q.shape
    _, skv, kv_heads, _ = k.shape
    if hw is not None and block_q is None and block_kv is None:
        from repro.kernels.autotune import autotune_flash_attention
        cfg = autotune_flash_attention(hw, b, sq, skv, h, kv_heads, dh,
                                       dtype_bits=_dtype_bits(q),
                                       cache=cache)
        block_q, block_kv = cfg.blocks
    block_q = 512 if block_q is None else block_q
    block_kv = 512 if block_kv is None else block_kv
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    pq, pkv = (-sq) % bq, (-skv) % bkv
    if pq or pkv:
        if pkv and mask_kind not in ("causal", "local"):
            raise ValueError(
                f"flash_attention: Skv={skv} is not divisible by "
                f"block_kv={bkv} and mask_kind={mask_kind!r} attends all "
                f"positions, so kv padding would change the output. Use a "
                f"divisor block_kv (hw= autotuning picks one) or pad kv "
                f"yourself with an explicit mask.")
        if pkv and skv < sq:
            raise ValueError(
                f"flash_attention: cannot pad kv for Skv={skv} < Sq={sq} "
                f"— padded kv positions would be attendable by trailing "
                f"query rows under mask_kind={mask_kind!r}.")
        qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        out = flash_attention_pallas(
            qp, kp, vp, mask_kind=mask_kind, window=window, block_q=bq,
            block_kv=bkv, interpret=(mode == "pallas_interpret"))
        return out[:, :sq]
    return flash_attention_pallas(
        q, k, v, mask_kind=mask_kind, window=window, block_q=block_q,
        block_kv=block_kv, interpret=(mode == "pallas_interpret"))


def rglru_scan(a, b, h0, *, force: Optional[str] = None):
    mode = _mode(force)
    if mode == "ref":
        return ref_lib.rglru_ref(a, b, h0)
    return rglru_pallas(a, b, h0,
                        interpret=(mode == "pallas_interpret"))


def rwkv6(r, k, v, log_w, u, *, chunk: int = 32,
          force: Optional[str] = None):
    mode = _mode(force)
    if mode == "ref":
        return ref_lib.rwkv6_ref(r, k, v, log_w, u)
    return rwkv6_pallas(r, k, v, log_w, u, chunk=chunk,
                        interpret=(mode == "pallas_interpret"))


def moe_gmm(x, w, *, block_c: Optional[int] = None,
            block_f: Optional[int] = None, block_d: Optional[int] = None,
            hw=None, cache=None, force: Optional[str] = None):
    """Grouped expert matmul.  Pads C/F/D up to block multiples (padded
    rows/cols are sliced off; padded D lanes contribute exact zeros)."""
    hw, cache, force = _ctx_fallback(hw, cache, force)
    mode = _mode(force)
    if mode == "ref":
        return ref_lib.moe_gmm_ref(x, w)
    e, c, d = x.shape
    _, _, f = w.shape
    if hw is not None and block_c is None and block_f is None \
            and block_d is None:
        from repro.kernels.autotune import autotune_moe_gmm
        cfg = autotune_moe_gmm(hw, e, c, d, f, dtype_bits=_dtype_bits(x),
                               cache=cache)
        block_c, block_f, block_d = cfg.blocks
    block_c = 128 if block_c is None else block_c
    block_f = 256 if block_f is None else block_f
    block_d = 256 if block_d is None else block_d
    pad = lambda dim, blk: (-dim) % min(blk, dim)
    pc, pf, pd = pad(c, block_c), pad(f, block_f), pad(d, block_d)
    if pc or pf or pd:
        xp = jnp.pad(x, ((0, 0), (0, pc), (0, pd)))
        wp = jnp.pad(w, ((0, 0), (0, pd), (0, pf)))
        out = moe_gmm_pallas(xp, wp, block_c=block_c, block_f=block_f,
                             block_d=block_d,
                             interpret=(mode == "pallas_interpret"))
        return out[:, :c, :f]
    return moe_gmm_pallas(x, w, block_c=block_c, block_f=block_f,
                          block_d=block_d,
                          interpret=(mode == "pallas_interpret"))


def staircase_latency(widths, shard_out, ca, mb, mc, *, lane: int,
                      force: Optional[str] = None):
    """Fused staircase sweep (see ``kernels.staircase_fused``): a (L, C)
    width matrix + per-row affine coefficients -> (latency, waves,
    occupancy).  Pallas kernel on TPU (or under ``pallas_interpret``),
    fp64 NumPy fused reference elsewhere."""
    from repro.kernels.staircase_fused import (
        fused_staircase_reference, staircase_fused_pallas)
    mode = _mode(force)
    if mode == "ref":
        return fused_staircase_reference(widths, shard_out, ca, mb, mc,
                                         lane=lane)
    return staircase_fused_pallas(widths, shard_out, ca, mb, mc, lane=lane,
                                  interpret=(mode == "pallas_interpret"))
