"""Flash attention (causal / local / full) as a Pallas TPU kernel.

Grid: (batch*kv_heads*q_per_kv, q_blocks, kv_blocks) with kv innermost; the
online-softmax stats (m, l) and the output accumulator live in VMEM scratch
and persist across the kv-block iterations of one q block (TPU pallas grids
execute sequentially per core, so scratch carries state).

VMEM working set per cell: (bq, dh) q + (bkv, dh) k,v + (bq, bkv) scores +
(bq, dh) acc — with bq=bkv=512, dh=128 that is ~1.5 MiB << VMEM.

The kv grid dimension is NOT truncated for causal masking (every kv block is
visited, fully-masked ones contribute zeros) — this mirrors the XLA
reference path and keeps the kernel simple; the block-triangle skip is a
recorded perf iteration (EXPERIMENTS.md section Perf).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, n_kv: int, bq: int, bkv: int,
                  mask_kind: str, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, dh)
    k = k_ref[0].astype(jnp.float32)          # (bkv, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    if mask_kind in ("causal", "local"):
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = kpos <= qpos
        if mask_kind == "local" and window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           mask_kind: str = "causal", window: int = 0,
                           block_q: int = 512, block_kv: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, dh); k/v: (B, Skv, KV, dh) with H % KV == 0.

    Returns (B, Sq, H, dh).  Sq % block_q == 0 and Skv % block_kv == 0.
    """
    b, sq, h, dh = q.shape
    _, skv, kv, _ = k.shape
    g = h // kv
    scale = 1.0 / math.sqrt(dh)
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    if sq % bq or skv % bkv:
        raise ValueError(
            f"flash_attention_pallas needs block-divisible sequences: "
            f"(Sq, Skv)=({sq}, {skv}) is not divisible by blocks "
            f"({bq}, {bkv}) (requested ({block_q}, {block_kv}), clamped to"
            f" the dims). Pad the sequences up to block multiples — "
            f"ops.flash_attention pads causal/local shapes automatically.")

    # layout: fold heads into batch; kv heads repeat via index mapping
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, dh)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kv, skv, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kv, skv, dh)

    grid = (b * h, sq // bq, skv // bkv)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, n_kv=skv // bkv,
                          bq=bq, bkv=bkv, mask_kind=mask_kind,
                          window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bkv, dh), lambda bh, qi, ki: (bh // g, ki, 0)),
            pl.BlockSpec((1, bkv, dh), lambda bh, qi, ki: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)
