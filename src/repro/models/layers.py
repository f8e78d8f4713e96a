"""Basic building blocks: norms, dense/embedding, rotary (incl. M-RoPE).

All layers are pure functions over explicit param dicts.  Compute dtype is
bf16, master params fp32 (cast at use).  Activation sharding is annotated
with logical axes via ``repro.parallel.shard``.
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


def cast(x):
    return x.astype(COMPUTE_DTYPE)


@contextlib.contextmanager
def compute_dtype(dtype):
    """Trace model code computing in ``dtype`` instead of bfloat16 for
    the duration of the block (trace-time, like ``kernel_context``): the
    float32 reference forward is the only caller."""
    global COMPUTE_DTYPE
    prev, COMPUTE_DTYPE = COMPUTE_DTYPE, dtype
    try:
        yield
    finally:
        COMPUTE_DTYPE = prev


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, in_axis_size: Optional[int] = None):
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        PARAM_DTYPE)


def embed_init(key, shape):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
        PARAM_DTYPE)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_norm(kind: str, d: int) -> dict:
    p = {"scale": jnp.ones((d,), PARAM_DTYPE)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), PARAM_DTYPE)
    return p


def apply_norm(p: dict, x: jax.Array, kind: str, eps: float = 1e-6
               ) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        raise ValueError(kind)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float
               ) -> jax.Array:
    """x: (..., S, H, dh); positions: (..., S) int32."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, dh/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., None, :]                             # broadcast over heads
    cos = cos[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: tuple) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions3: (..., S, 3) int32 — (t, h, w) position per token; the
    frequency bands of the half-dim are split across the three sections.
    """
    dh = x.shape[-1]
    half = dh // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(dh, theta)                       # (half,)
    sec_ids = jnp.repeat(jnp.arange(3), jnp.array(sections),
                         total_repeat_length=half)      # (half,)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(sec_ids, positions3.shape[:-1] + (half,)).astype(
            jnp.int32),
        axis=-1)                                        # (..., S, half)
    ang = pos * freqs
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU or plain GeLU)
# ---------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, gated: bool, bias: bool = False
             ) -> dict:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d_model, d_ff)),
         "w_down": dense_init(ks[1], (d_ff, d_model), in_axis_size=d_ff)}
    if gated:
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff))
    if bias:
        p["b_up"] = jnp.zeros((d_ff,), PARAM_DTYPE)
        p["b_down"] = jnp.zeros((d_model,), PARAM_DTYPE)
    return p


def apply_mlp(p: dict, x: jax.Array, gated: bool) -> jax.Array:
    from repro.kernels import ops
    if ops.kernel_routing_active():
        return _apply_mlp_kernels(p, x, gated)
    up = jnp.einsum("...d,df->...f", x, cast(p["w_up"]))
    if "b_up" in p:
        up = up + cast(p["b_up"])
    if gated:
        g = jnp.einsum("...d,df->...f", x, cast(p["w_gate"]))
        h = jax.nn.silu(g) * up
    else:
        h = jax.nn.gelu(up)
    h = shard(h, "batch", "seq", "mlp")
    out = jnp.einsum("...f,fd->...d", h, cast(p["w_down"]))
    if "b_down" in p:
        out = out + cast(p["b_down"])
    return shard(out, "batch", "seq", "embed")


def _apply_mlp_kernels(p: dict, x: jax.Array, gated: bool) -> jax.Array:
    """MLP on the tiled matmul kernel (ambient kernel context active):
    the token axes flatten to M so every projection runs on the
    autotuned wave-aligned (block_m, block_n, block_k) grid."""
    from repro.kernels import ops
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    up = ops.matmul(x2, cast(p["w_up"]))
    if "b_up" in p:
        up = up + cast(p["b_up"])
    if gated:
        g = ops.matmul(x2, cast(p["w_gate"]))
        h = jax.nn.silu(g) * up
    else:
        h = jax.nn.gelu(up)
    out = ops.matmul(h.astype(x.dtype), cast(p["w_down"]))
    if "b_down" in p:
        out = out + cast(p["b_down"])
    return shard(out.reshape(*lead, out.shape[-1]),
                 "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------
def init_embeddings(key, vocab: int, d_model: int, tie: bool) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"tok_emb": embed_init(k1, (vocab, d_model))}
    if not tie:
        p["out_emb"] = dense_init(k2, (d_model, vocab))
    return p


def embed_tokens(p: dict, tokens: jax.Array, d_model: int) -> jax.Array:
    x = cast(p["tok_emb"])[tokens]
    x = x * jnp.asarray(math.sqrt(d_model), COMPUTE_DTYPE)
    return shard(x, "batch", "seq", "embed")


def unembed(p: dict, x: jax.Array, tie: bool, softcap: float = 0.0
            ) -> jax.Array:
    if tie:
        logits = jnp.einsum("...d,vd->...v", x, cast(p["tok_emb"]))
    else:
        logits = jnp.einsum("...d,dv->...v", x, cast(p["out_emb"]))
    logits = shard(logits, "batch", "seq", "vocab")
    if softcap > 0.0:
        logits = (jnp.tanh(logits.astype(jnp.float32) / softcap) * softcap
                  ).astype(logits.dtype)
    return logits
