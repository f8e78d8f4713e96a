"""Attention: GQA, causal/local/bidirectional/cross, prefill + decode.

Three execution paths:
  * ``chunked_attention`` — flash-style online-softmax over KV chunks in pure
    jnp (lax.scan).  Memory-safe at 32k context; the dry-run lowers this.
    On TPU runtime, ops.py dispatches to the Pallas flash kernel instead.
  * ``decode_attention`` — single-token attention against a full cache
    (single-device / replicated path).
  * ``flash_decode_sharded`` — sequence-parallel decode: the KV cache is
    sharded along *sequence* over the ``model`` mesh axis; each shard
    computes partial softmax stats over its chunk and the result is combined
    with pmax/psum (flash-decoding), inside ``shard_map``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.models.layers import COMPUTE_DTYPE, PARAM_DTYPE, cast, dense_init
from repro.parallel.sharding import (
    shard, current_mesh, logical_to_pspec, batch_axes,
)

NEG_INF = -1e30


def _vmem_scope(name, fn):
    """Tag a region whose intermediates are VMEM-resident in the Pallas
    kernel (ops.py) — the loop-aware byte model skips their HBM traffic."""
    from functools import wraps

    @wraps(fn)
    def wrapped(*a, **k):
        with jax.named_scope(name):
            return fn(*a, **k)
    return wrapped


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   bias: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads, head_dim),
                         in_axis_size=d_model),
        "wk": dense_init(ks[1], (d_model, n_kv, head_dim),
                         in_axis_size=d_model),
        "wv": dense_init(ks[2], (d_model, n_kv, head_dim),
                         in_axis_size=d_model),
        "wo": dense_init(ks[3], (n_heads, head_dim, d_model),
                         in_axis_size=n_heads * head_dim),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), PARAM_DTYPE)
        p["bk"] = jnp.zeros((n_kv, head_dim), PARAM_DTYPE)
        p["bv"] = jnp.zeros((n_kv, head_dim), PARAM_DTYPE)
    return p


def qkv_proj(p: dict, x: jax.Array):
    q = jnp.einsum("...d,dhk->...hk", x, cast(p["wq"]))
    k = jnp.einsum("...d,dhk->...hk", x, cast(p["wk"]))
    v = jnp.einsum("...d,dhk->...hk", x, cast(p["wv"]))
    if "bq" in p:
        q = q + cast(p["bq"])
        k = k + cast(p["bk"])
        v = v + cast(p["bv"])
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def kv_proj(p: dict, x: jax.Array):
    k = jnp.einsum("...d,dhk->...hk", x, cast(p["wk"]))
    v = jnp.einsum("...d,dhk->...hk", x, cast(p["wv"]))
    if "bk" in p:
        k = k + cast(p["bk"])
        v = v + cast(p["bv"])
    return k, v


def out_proj(p: dict, o: jax.Array) -> jax.Array:
    out = jnp.einsum("...hk,hkd->...d", o, cast(p["wo"]))
    return shard(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# chunked flash attention (reference path; lowered in the dry-run)
# ---------------------------------------------------------------------------
def _chunk_sizes(sq: int, skv: int, q_chunk: int, kv_chunk: int):
    qc = min(q_chunk, sq)
    while sq % qc:
        qc //= 2
    kc = min(kv_chunk, skv)
    while skv % kc:
        kc //= 2
    return max(qc, 1), max(kc, 1)


def chunked_attention(
    q: jax.Array,            # (B, Sq, H, dh)
    k: jax.Array,            # (B, Skv, KV, dh)
    v: jax.Array,            # (B, Skv, KV, dh)
    *,
    mask_kind: str = "causal",     # causal | local | none
    window: int = 0,
    q_offset: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    kv_len: Optional[jax.Array] = None,   # valid kv length (ragged masking)
) -> jax.Array:
    """Online-softmax attention over KV chunks; returns (B, Sq, H, dh)."""
    b, sq, h, dh = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(dh)
    qc, kc = _chunk_sizes(sq, skv, q_chunk, kv_chunk)
    nq, nk = sq // qc, skv // kc

    qr = cast(q.reshape(b, nq, qc, kv, g, dh))
    kr = cast(k.reshape(b, nk, kc, kv, dh))
    vr = cast(v.reshape(b, nk, kc, kv, dh))

    q_pos_base = q_offset + jnp.arange(nq) * qc            # (nq,)
    k_pos_base = jnp.arange(nk) * kc                       # (nk,)

    @jax.checkpoint
    @partial(_vmem_scope, "vmem_resident_flash")
    def q_step(_, qi):
        # Rematted: the backward pass recomputes per-chunk probabilities
        # from the (tiny) chunk inputs instead of saving the (qc, kc)
        # score/probability blocks of every chunk pair — this is what makes
        # the pure-jnp path flash-like in memory, not just compute.
        qblk, qpos0 = qi                                   # (b,qc,kv,g,dh)
        qpos = qpos0 + jnp.arange(qc)                      # (qc,)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kpos0 = ki
            kpos = kpos0 + jnp.arange(kc)                  # (kc,)
            s = jnp.einsum("bqkgd,bckd->bkgqc", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qc, kc), bool)
            if mask_kind in ("causal", "local"):
                mask &= kpos[None, :] <= qpos[:, None]
            if mask_kind == "local" and window > 0:
                mask &= kpos[None, :] > qpos[:, None] - window
            if kv_len is not None:
                mask &= kpos[None, :] < kv_len
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))    # (b,kv,g,qc)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bckd->bkgqd",
                            cast(p), vblk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kv, g, qc, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kr.transpose(1, 0, 2, 3, 4), vr.transpose(1, 0, 2, 3, 4),
             k_pos_base))
        o = acc / jnp.maximum(l, 1e-30)[..., None]         # (b,kv,g,qc,dh)
        return None, o

    _, outs = jax.lax.scan(q_step, None,
                           (qr.transpose(1, 0, 2, 3, 4, 5), q_pos_base))
    # outs: (nq, b, kv, g, qc, dh) -> (b, sq, h, dh)
    o = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, dh)
    return cast(o)


def prefill_attention(q, k, v, *, mask_kind: str = "causal",
                      window: int = 0) -> jax.Array:
    """Prefill attention through the ambient kernel context.

    When a ``kernels.ops.kernel_context`` is installed and would reach a
    kernel backend (TPU or ``force='pallas_interpret'``), causal prefill
    routes through ``ops.flash_attention`` so it runs on the autotuned
    wave-aligned tiles of the context's hardware spec.  Otherwise — the
    historical CPU/ref path — this is exactly ``chunked_attention``."""
    from repro.kernels import ops
    if mask_kind == "causal" and ops.kernel_routing_active():
        return ops.flash_attention(q, k, v, mask_kind="causal",
                                   window=window)
    return chunked_attention(q, k, v, mask_kind=mask_kind, window=window)


def local_attention_prefill(q, k, v, *, window: int, q_offset: int = 0,
                            q_chunk: int = 1024) -> jax.Array:
    """Sliding-window attention that only touches the window's KV chunks.

    For each query chunk we slice a (window + q_chunk) KV strip — total work
    O(S * window) rather than O(S^2) — the sub-quadratic path that makes
    long_500k viable for recurrentgemma.
    """
    b, sq, h, dh = q.shape
    skv, kv = k.shape[1], k.shape[2]
    qc, _ = _chunk_sizes(sq, skv, q_chunk, q_chunk)
    strip = min(skv, window + qc)
    if strip >= skv:
        return chunked_attention(q, k, v, mask_kind="local", window=window,
                                 q_offset=q_offset)
    nq = sq // qc
    qr = q.reshape(b, nq, qc, h, dh)

    @partial(_vmem_scope, "vmem_resident_flash_local")
    def q_step(_, qi):
        qblk, idx = qi
        qpos0 = q_offset + idx * qc
        start = jnp.clip(qpos0 + qc - strip, 0, skv - strip)
        ks = jax.lax.dynamic_slice_in_dim(k, start, strip, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, start, strip, axis=1)
        g = h // kv
        scale = 1.0 / math.sqrt(dh)
        s = jnp.einsum("bqkgd,bckd->bkgqc",
                       qblk.reshape(b, qc, kv, g, dh).astype(COMPUTE_DTYPE),
                       ks.astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32) * scale
        qpos = qpos0 + jnp.arange(qc)[:, None]
        kpos = start + jnp.arange(strip)[None, :]
        mask = (kpos <= qpos) & (kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqc,bckd->bqkgd", p.astype(COMPUTE_DTYPE), vs,
                       preferred_element_type=jnp.float32)
        return None, o.reshape(b, qc, h, dh).astype(COMPUTE_DTYPE)

    _, outs = jax.lax.scan(q_step, None,
                           (qr.transpose(1, 0, 2, 3, 4), jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dh)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def decode_attention(q, k_cache, v_cache, cache_len, k_new=None,
                     v_new=None) -> jax.Array:
    """Single-token attention, replicated cache.  q: (B, H, dh).

    ``cache_len`` is the valid cache length — a scalar (lockstep decode)
    or a (B,) vector (ragged decode: each slot of a continuous batch at
    its own position).  With ``k_new``/``v_new`` (B, KV, dh) the step's
    own row is not in the cache: the query attends over the cache's
    first ``cache_len`` rows and that row, so the cache is only read."""
    b, h, dh = q.shape
    kv = k_cache.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, kv, g, dh).astype(COMPUTE_DTYPE)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k_cache.shape[1])
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 1:                # per-slot valid lengths
        cache_len = cache_len[:, None, None, None]
    s = jnp.where(pos[None, None, None, :] < cache_len, s, NEG_INF)
    if k_new is None:
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(COMPUTE_DTYPE),
                       v_cache.astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32)
        return o.reshape(b, h, dh).astype(COMPUTE_DTYPE)
    # softmax over the cache's rows and the new row, taken apart
    s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new.astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32)[..., None] * scale
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    e, e_new = jnp.exp(s - m), jnp.exp(s_new - m)
    den = jnp.sum(e, axis=-1, keepdims=True) + e_new
    o = jnp.einsum("bkgs,bskd->bkgd", (e / den).astype(COMPUTE_DTYPE),
                   v_cache.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32)
    o = o + ((e_new / den).astype(COMPUTE_DTYPE).astype(jnp.float32)
             * v_new.astype(COMPUTE_DTYPE).astype(jnp.float32)[:, :, None])
    return o.reshape(b, h, dh).astype(COMPUTE_DTYPE)


def chunk_prefill_attention(q, k_cache, v_cache, offset) -> jax.Array:
    """Chunked-prefill attention: a (B, C, H, dh) query chunk whose rows
    sit at absolute positions ``offset .. offset + C`` attends causally
    over a full-capacity cache (B, S, KV, dh) that already holds every
    previously committed chunk's K/V *and* this chunk's own rows
    (written at ``[offset, offset + C)`` before the call).

    Row ``i`` of the chunk sees exactly keys ``0 .. offset + i`` — the
    same key set a whole-prompt causal prefill gives it — so chunked and
    whole-prompt prefill agree.  Rows past the real chunk length (a
    pow2-bucketed final chunk) compute garbage that the caller never
    commits, exactly like bucketed prefill pad rows.  ``offset`` may be
    a traced scalar: one executable serves every chunk position."""
    b, c, h, dh = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(dh)
    sc = jnp.einsum("bqkgd,bskd->bkgqs",
                    q.reshape(b, c, kv, g, dh).astype(COMPUTE_DTYPE),
                    k_cache.astype(COMPUTE_DTYPE),
                    preferred_element_type=jnp.float32) * scale
    qpos = offset + jnp.arange(c)
    kpos = jnp.arange(s)
    mask = kpos[None, :] <= qpos[:, None]              # (c, s)
    sc = jnp.where(mask[None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(COMPUTE_DTYPE),
                   v_cache.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, c, h, dh).astype(COMPUTE_DTYPE)


def _dp_axes(mesh: Mesh):
    return batch_axes(mesh)


def flash_decode_sharded(q, k_cache, v_cache, cache_len, mesh: Mesh,
                         seq_axis: str = "model") -> jax.Array:
    """Sequence-parallel decode attention (flash-decoding on the mesh).

    q:        (B, H, dh)      — batch over data axes, replicated over model
    caches:   (B, S, KV, dh)  — batch over data axes, S sharded over `model`
    Each model-shard computes partial (m, l, o) over its local S chunk; the
    global softmax is reconstructed with pmax/psum.
    """
    if seq_axis not in mesh.axis_names:
        return decode_attention(q, k_cache, v_cache, cache_len)
    n_shards = mesh.shape[seq_axis]
    s_total = k_cache.shape[1]
    s_loc = s_total // n_shards
    dp = _dp_axes(mesh)

    def f(qb, kb, vb, clen):
        b, h, dh = qb.shape
        kv = kb.shape[2]
        g = h // kv
        scale = 1.0 / math.sqrt(dh)
        off = jax.lax.axis_index(seq_axis) * s_loc
        pos = off + jnp.arange(s_loc)
        valid = pos < clen
        s = jnp.einsum("bkgd,bskd->bkgs",
                       qb.reshape(b, kv, g, dh).astype(COMPUTE_DTYPE),
                       kb.astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        m_loc = jnp.maximum(jnp.max(s, axis=-1), NEG_INF)   # (b,kv,g)
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(valid[None, None, None, :], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bkgs,bskd->bkgd", p.astype(COMPUTE_DTYPE),
                           vb.astype(COMPUTE_DTYPE),
                           preferred_element_type=jnp.float32)
        m_glob = jax.lax.pmax(m_loc, seq_axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * corr, seq_axis)
        o = jax.lax.psum(o_loc * corr[..., None], seq_axis)
        o = o / jnp.maximum(l_glob, 1e-30)[..., None]
        return o.reshape(b, h, dh).astype(COMPUTE_DTYPE)

    return shard_map(
        f, mesh=mesh,
        in_specs=(P(dp, None, None), P(dp, seq_axis, None, None),
                  P(dp, seq_axis, None, None), P()),
        out_specs=P(dp, None, None),
    )(q, k_cache, v_cache, cache_len)


def write_rows(cache, rows, pos):
    """Write one new K or V row per slot into ``cache`` (..., B, S, KV, dh)
    at sequence position ``pos``: a scalar (every slot) or a (B,) vector
    (ragged decode).  ``rows`` is (..., B, KV, dh), with the same leading
    axes as the cache (a layer stack).  Written as dynamic-update-slices,
    which keep the cache's device layout: on a donated cache the write is
    in place."""
    lead = (0,) * (cache.ndim - 4)
    rows = rows.astype(cache.dtype)[..., None, :, :]
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(cache, rows, lead + (0, pos, 0, 0))
    for b in range(rows.shape[-4]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[..., b:b + 1, :, :, :], lead + (b, pos[b], 0, 0))
    return cache


def update_cache_sharded(cache, new, pos, mesh: Optional[Mesh],
                         seq_axis: str = "model"):
    """Write (B, KV, dh) `new` at sequence position `pos` of a seq-sharded
    cache (B, S, KV, dh).  Only the owning shard commits the write."""
    if mesh is None or seq_axis not in mesh.axis_names:
        return write_rows(cache, new, pos)
    n_shards = mesh.shape[seq_axis]
    s_loc = cache.shape[1] // n_shards
    dp = _dp_axes(mesh)

    def f(c, n, p):
        off = jax.lax.axis_index(seq_axis) * s_loc
        i = p - off
        inb = (i >= 0) & (i < s_loc)
        i_c = jnp.clip(i, 0, s_loc - 1)
        upd = jax.lax.dynamic_update_slice(
            c, n[:, None].astype(c.dtype), (0, i_c, 0, 0))
        return jnp.where(inb, upd, c)

    return shard_map(
        f, mesh=mesh,
        in_specs=(P(dp, seq_axis, None, None), P(dp, None, None), P()),
        out_specs=P(dp, seq_axis, None, None),
    )(cache, new, pos)
