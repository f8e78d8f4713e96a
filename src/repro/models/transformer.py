"""Unified config-driven model: dense/GQA/MoE/RG-LRU/RWKV6/enc-dec.

Layers are grouped into repeating *pattern units* (e.g. recurrentgemma's
(rglru, rglru, local), llama4's (attn+dense, attn+moe)); units are stacked
and applied with ``lax.scan`` so the lowered HLO contains each unique layer
body exactly once regardless of depth.  Leftover layers (depth not divisible
by the cycle) are unrolled.

Public API (pure functions over a param pytree):
  init_params(key, cfg)
  forward(params, cfg, tokens=..., embeds=..., mode="train"|"prefill", ...)
  train_loss(params, batch, cfg)
  init_decode_state(cfg, batch, max_len)
  decode_step(params, cfg, tokens, pos, state, ...)
  count_params_analytic(cfg)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import recurrent as rec_lib
from repro.models.layers import (
    COMPUTE_DTYPE, PARAM_DTYPE, apply_mlp, apply_mrope, apply_norm,
    apply_rope, cast, compute_dtype, embed_tokens, init_embeddings,
    init_mlp, init_norm, unembed,
)
from repro.parallel.sharding import current_mesh, shard

ZERO_AUX = {"moe_lb_loss": jnp.zeros((), jnp.float32),
            "moe_z_loss": jnp.zeros((), jnp.float32)}

VOCAB_QUANTUM = 128   # lane quantum: embeddings padded to eliminate the
                      # vocab tail (ragged vocab can't shard over TP and
                      # pads every MXU tile — the paper's Eq. 8b move)


def padded_vocab(cfg: ModelConfig) -> int:
    v = cfg.vocab_size
    return ((v + VOCAB_QUANTUM - 1) // VOCAB_QUANTUM) * VOCAB_QUANTUM


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------
def layer_plan(cfg: ModelConfig, encoder: bool = False) -> list:
    """[(kind, mlp_kind)] per layer.  Encoder layers are always attn+dense."""
    n = cfg.encoder_layers if encoder else cfg.n_layers
    out = []
    for i in range(n):
        kind = "attn" if encoder else cfg.block_kind(i)
        if kind == "rwkv":
            mlp_kind = "cmix"
        elif (not encoder and cfg.moe
              and (i + 1) % max(cfg.moe_interleave, 1) == 0):
            mlp_kind = "moe"
        else:
            mlp_kind = "dense"
        out.append((kind, mlp_kind))
    return out


def unit_cycle(cfg: ModelConfig, encoder: bool = False) -> int:
    if encoder:
        return 1
    c = len(cfg.block_pattern)
    if cfg.moe:
        c = math.lcm(c, max(cfg.moe_interleave, 1))
    return c


def decoder_layer_refs(cfg: ModelConfig) -> list:
    """Pytree address of every decoder layer, in layer order.

    Each entry is a dict: ``kind``/``mlp_kind`` from :func:`layer_plan`,
    plus where the layer's params live under ``params["decoder"]``:
    ``group`` is ``"stack"`` (scanned units; ``key`` names the unit slot
    ``u{j}`` and ``index`` the position along the stacked leading axis)
    or ``"extra"`` (unrolled leftovers; ``key`` is ``x{j}``, ``index``
    None).  ``init_decode_state`` lays decode states out identically, so
    the same addresses locate a layer's KV cache.
    """
    plan = layer_plan(cfg, encoder=False)
    cycle = unit_cycle(cfg)
    n_units = len(plan) // cycle
    refs = []
    for i, (kind, mlpk) in enumerate(plan):
        u, j = divmod(i, cycle)
        if u < n_units:
            refs.append({"kind": kind, "mlp_kind": mlpk, "group": "stack",
                         "key": f"u{j}", "index": u})
        else:
            refs.append({"kind": kind, "mlp_kind": mlpk, "group": "extra",
                         "key": f"x{i - n_units * cycle}", "index": None})
    return refs


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------
def init_layer(key, cfg: ModelConfig, kind: str, mlp_kind: str,
               cross: bool) -> dict:
    ks = jax.random.split(key, 8)
    p: dict = {"norm1": init_norm(cfg.norm, cfg.d_model)}
    if kind in ("attn", "local"):
        p["attn"] = attn_lib.init_attention(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            bias=cfg.qkv_bias)
    elif kind == "rglru":
        p["rglru"] = rec_lib.init_rglru(ks[0], cfg.d_model)
    elif kind == "rwkv":
        rw = rec_lib.init_rwkv(ks[0], cfg.d_model, cfg.n_heads,
                               cfg.rwkv_head_dim, cfg.d_ff)
        p["rwkv"] = rw["rwkv"]
        p["cmix"] = rw["cmix"]
        p["norm2"] = init_norm(cfg.norm, cfg.d_model)
        return p
    else:
        raise ValueError(kind)

    if cross:
        p["norm_cross"] = init_norm(cfg.norm, cfg.d_model)
        p["cross"] = {"attn": attn_lib.init_attention(
            ks[1], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            bias=cfg.qkv_bias)}

    if not cfg.parallel_block:
        p["norm2"] = init_norm(cfg.norm, cfg.d_model)
    if mlp_kind == "dense":
        p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_gated)
    elif mlp_kind == "moe":
        p["moe"] = moe_lib.init_moe(ks[2], cfg.d_model, cfg.n_experts,
                                    cfg.moe_d_ff, cfg.shared_expert,
                                    cfg.d_ff)
    return p


def _rope(cfg: ModelConfig, q, k, positions):
    if cfg.rope_kind == "none":
        return q, k
    if cfg.rope_kind == "mrope":
        return (apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections),
                apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections))
    return (apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k, positions, cfg.rope_theta))


def _default_positions(cfg: ModelConfig, b: int, s: int, offset=0):
    pos = offset + jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.rope_kind == "mrope":
        return jnp.broadcast_to(pos[..., None], (b, s, 3))
    return pos


def _decode_appends_rows(kind: str, mode: str, pos) -> bool:
    """A decode layer of global attention only reads its cache and
    returns its new K/V rows; ``apply_stack`` writes them after the layer
    scan (``attn_lib.write_rows``).  Writing inside the scan makes XLA
    move the cache into another device layout and back every step.  The
    lockstep decode of a sequence-sharded cache (a ``model`` mesh axis)
    keeps its own per-layer write."""
    mesh = current_mesh()
    return (mode == "decode" and kind == "attn"
            and (jnp.ndim(pos) == 1 or mesh is None
                 or "model" not in mesh.axis_names))


def _self_attention(p, x, cfg: ModelConfig, kind: str, mode: str,
                    cache, positions, pos, causal: bool):
    """Self-attention for train / prefill / decode.  Returns (y, cache);
    under ``_decode_appends_rows``, (y, new rows) with the rows (B, KV,
    dh) in place of the cache."""
    b = x.shape[0]
    if mode == "decode":
        # Ragged decode (continuous batching): `pos` may be a (B,) vector
        # of per-slot write positions — each slot of the batch sits at its
        # own sequence offset, so cache writes scatter per row and the
        # attention mask uses per-row valid lengths.
        ragged = jnp.ndim(pos) == 1
        q, k, v = attn_lib.qkv_proj(p, x)                 # (B,1,H,dh)
        rp = positions if positions is not None else (
            _default_positions(cfg, b, 1, pos[:, None] if ragged else pos))
        q, k = _rope(cfg, q, k, rp)
        if _decode_appends_rows(kind, mode, pos):
            rows = {"k": k[:, 0].astype(cache["k"].dtype),
                    "v": v[:, 0].astype(cache["v"].dtype)}
            o = attn_lib.decode_attention(q[:, 0], cache["k"], cache["v"],
                                          pos, rows["k"], rows["v"])
            return attn_lib.out_proj(p, o[:, None]), rows
        if kind == "local":
            w = cfg.window
            slot = pos % w
            if ragged:
                b_idx = jnp.arange(b)
                kc = cache["k"].at[b_idx, slot].set(
                    k[:, 0].astype(cache["k"].dtype))
                vc = cache["v"].at[b_idx, slot].set(
                    v[:, 0].astype(cache["v"].dtype))
            else:
                kc = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
            o = attn_lib.decode_attention(q[:, 0], kc, vc,
                                          jnp.minimum(pos + 1, w))
        else:
            mesh = current_mesh()
            kc = attn_lib.update_cache_sharded(cache["k"], k[:, 0], pos, mesh)
            vc = attn_lib.update_cache_sharded(cache["v"], v[:, 0], pos, mesh)
            o = attn_lib.flash_decode_sharded(q[:, 0], kc, vc, pos + 1, mesh)
        y = attn_lib.out_proj(p, o[:, None])
        return y, {"k": kc, "v": vc}

    if mode == "chunk":
        # Chunked prefill: x is a (B, C, d) chunk whose rows sit at
        # absolute positions pos..pos+C of a request already holding
        # `pos` committed rows in `cache`.  The chunk's K/V land in
        # cache rows [pos, pos+C) and every chunk row attends causally
        # over the full cache — so chunk-by-chunk prefill reproduces the
        # whole-prompt prefill exactly (global attention only: local
        # ring caches rotate by total length and cannot be grown
        # incrementally).
        if kind != "attn":
            raise ValueError(
                "chunked prefill requires global attention layers")
        s = x.shape[1]
        q, k, v = attn_lib.qkv_proj(p, x)
        rp = positions if positions is not None else _default_positions(
            cfg, b, s, pos)
        q, k = _rope(cfg, q, k, rp)
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        o = attn_lib.chunk_prefill_attention(q, kc, vc, pos)
        y = attn_lib.out_proj(p, o)
        return y, {"k": kc, "v": vc}

    # train / prefill
    s = x.shape[1]
    q, k, v = attn_lib.qkv_proj(p, x)
    rp = positions if positions is not None else _default_positions(cfg, b, s)
    q, k = _rope(cfg, q, k, rp)
    if kind == "local":
        o = attn_lib.local_attention_prefill(q, k, v, window=cfg.window)
    elif causal:
        # Routes through ops.flash_attention (autotuned wave-aligned
        # tiles) when a kernels.ops.kernel_context is active; plain
        # chunked_attention otherwise.
        o = attn_lib.prefill_attention(q, k, v, mask_kind="causal")
    else:
        o = attn_lib.chunked_attention(q, k, v, mask_kind="none")
    y = attn_lib.out_proj(p, o)
    new_cache = None
    if mode == "prefill":
        if kind == "local":
            w = cfg.window
            pad = max(w - s, 0)
            kw = k[:, -w:] if s >= w else jnp.pad(k, ((0, 0), (0, pad),
                                                      (0, 0), (0, 0)))
            vw = v[:, -w:] if s >= w else jnp.pad(v, ((0, 0), (0, pad),
                                                      (0, 0), (0, 0)))
            # ring-buffer order: rotate so slot (s % w) is next write
            if s >= w:
                shift = s % w
                kw = jnp.roll(kw, shift, axis=1)
                vw = jnp.roll(vw, shift, axis=1)
            new_cache = {"k": kw.astype(COMPUTE_DTYPE),
                         "v": vw.astype(COMPUTE_DTYPE)}
        else:
            # Reshard to the decode layout: KV sequence over `model`
            # (sequence-parallel cache).  Without this the returned caches
            # are only batch-sharded — 16x over HBM budget at 32k.
            new_cache = {
                "k": shard(k.astype(COMPUTE_DTYPE),
                           "batch", "kv_seq", None, None),
                "v": shard(v.astype(COMPUTE_DTYPE),
                           "batch", "kv_seq", None, None),
            }
    return y, new_cache


def _cross_attention(p, x, cfg: ModelConfig, mode: str, cache, enc_out):
    """Cross-attention onto encoder output (no rope)."""
    if mode == "decode":
        q = jnp.einsum("...d,dhk->...hk", x, cast(p["attn"]["wq"]))
        if "bq" in p["attn"]:
            q = q + cast(p["attn"]["bq"])
        mesh = current_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            o = attn_lib.flash_decode_sharded(q[:, 0], cache["ck"],
                                              cache["cv"], cache["clen"],
                                              mesh)
        else:
            o = attn_lib.decode_attention(q[:, 0], cache["ck"], cache["cv"],
                                          cache["clen"])
        return attn_lib.out_proj(p["attn"], o[:, None]), cache
    q = jnp.einsum("...d,dhk->...hk", x, cast(p["attn"]["wq"]))
    if "bq" in p["attn"]:
        q = q + cast(p["attn"]["bq"])
    k, v = attn_lib.kv_proj(p["attn"], enc_out)
    o = attn_lib.chunked_attention(q, k, v, mask_kind="none")
    y = attn_lib.out_proj(p["attn"], o)
    new_cache = None
    if mode == "prefill":
        new_cache = {"ck": k.astype(COMPUTE_DTYPE),
                     "cv": v.astype(COMPUTE_DTYPE),
                     "clen": jnp.asarray(enc_out.shape[1], jnp.int32)}
    return y, new_cache


def apply_layer(p: dict, x: jax.Array, cfg: ModelConfig, kind: str,
                mlp_kind: str, *, mode: str, state, enc_out, positions,
                pos, causal: bool, moe_strategy: str):
    """Returns (x, new_state, aux)."""
    aux = dict(ZERO_AUX)
    new_state: dict = {}

    if mode == "chunk" and kind != "attn":
        # recurrent layers carry a running state, not a cache: a chunk
        # cannot be replayed against them without decoding every token
        raise ValueError(
            f"chunked prefill supports global-attention layers only "
            f"(got {kind!r})")

    if kind == "rwkv":
        h = apply_norm(p["norm1"], x, cfg.norm)
        tm_state = ({"shift": state["shift"], "s": state["s"]}
                    if state else None)
        y, tm_new = rec_lib.apply_rwkv_timemix(
            p["rwkv"], h, state=tm_state, decode=(mode == "decode"))
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm)
        cm_state = state["cmix_shift"] if state else None
        y, cm_new = rec_lib.apply_rwkv_channelmix(p["cmix"], h, cm_state)
        x = x + y
        if mode != "train":
            new_state = {"shift": tm_new["shift"], "s": tm_new["s"],
                         "cmix_shift": cm_new}
        return x, new_state, aux

    h = apply_norm(p["norm1"], x, cfg.norm)

    if kind == "rglru":
        st = state if state else None
        y, rg_new = rec_lib.apply_rglru_block(p["rglru"], h, state=st,
                                              decode=(mode == "decode"))
        if mode != "train":
            new_state = rg_new
    else:
        sa_cache = ({"k": state["k"], "v": state["v"]} if state else None)
        y, sa_new = _self_attention(p["attn"], h, cfg, kind, mode, sa_cache,
                                    positions, pos, causal)
        if sa_new is not None:
            new_state.update(sa_new)

    if cfg.parallel_block and mlp_kind == "dense":
        # cohere: out = x + attn(norm(x)) + mlp(norm(x))
        y2 = apply_mlp(p["mlp"], h, cfg.mlp_gated)
        x = x + y + y2
        return x, new_state, aux

    x = x + y

    if "cross" in p:
        h = apply_norm(p["norm_cross"], x, cfg.norm)
        cr_cache = ({"ck": state["ck"], "cv": state["cv"],
                     "clen": state["clen"]} if state and "ck" in state
                    else None)
        y, cr_new = _cross_attention(p["cross"], h, cfg, mode, cr_cache,
                                     enc_out)
        x = x + y
        if cr_new is not None:
            new_state.update(cr_new)

    h = apply_norm(p["norm2"], x, cfg.norm)
    if mlp_kind == "dense":
        y = apply_mlp(p["mlp"], h, cfg.mlp_gated)
    elif mlp_kind == "moe":
        y, aux_m = moe_lib.apply_moe(p["moe"], h, cfg.experts_per_token,
                                     cfg.capacity_factor,
                                     strategy=moe_strategy,
                                     mesh=current_mesh())
        aux = {k: aux[k] + aux_m[k] for k in aux}
    else:
        raise ValueError(mlp_kind)
    x = x + y
    return x, new_state, aux


# ---------------------------------------------------------------------------
# stack init / apply
# ---------------------------------------------------------------------------
def _stack_trees(trees: list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _sqrt_divisor(n: int) -> int:
    """Divisor of n nearest to sqrt(n) (group size for sqrt remat)."""
    best, target = 1, math.sqrt(n)
    for d in range(1, n + 1):
        if n % d == 0 and abs(d - target) < abs(best - target):
            best = d
    return max(best, 1)


def init_stack(key, cfg: ModelConfig, encoder: bool, cross: bool) -> dict:
    plan = layer_plan(cfg, encoder)
    cycle = unit_cycle(cfg, encoder)
    n_units = len(plan) // cycle
    leftover = len(plan) % cycle

    units = []
    for u in range(n_units):
        unit = {}
        for j in range(cycle):
            i = u * cycle + j
            kind, mlpk = plan[i]
            unit[f"u{j}"] = init_layer(jax.random.fold_in(key, i), cfg,
                                       kind, mlpk, cross)
        units.append(unit)
    out: dict = {}
    if units:
        out["stack"] = _stack_trees(units)
    extra = {}
    for j in range(leftover):
        i = n_units * cycle + j
        kind, mlpk = plan[i]
        extra[f"x{j}"] = init_layer(jax.random.fold_in(key, i), cfg,
                                    kind, mlpk, cross)
    if extra:
        out["extra"] = extra
    return out


def apply_stack(stack_p: dict, x: jax.Array, cfg: ModelConfig, *,
                encoder: bool, mode: str, states: Optional[dict],
                enc_out, positions, pos, moe_strategy: str,
                remat: str = "none"):
    """Returns (x, new_states, aux_sum)."""
    plan = layer_plan(cfg, encoder)
    cycle = unit_cycle(cfg, encoder)
    n_units = len(plan) // cycle
    causal = not encoder
    unit_plan = plan[:cycle]

    def unit_body(x, uparams, ustates):
        new_states = {}
        aux_sum = dict(ZERO_AUX)
        for j, (kind, mlpk) in enumerate(unit_plan):
            st = ustates[f"u{j}"] if ustates is not None else None
            x, ns, aux = apply_layer(
                uparams[f"u{j}"], x, cfg, kind, mlpk, mode=mode, state=st,
                enc_out=enc_out, positions=positions, pos=pos, causal=causal,
                moe_strategy=moe_strategy)
            new_states[f"u{j}"] = ns
            aux_sum = {k: aux_sum[k] + aux[k] for k in aux_sum}
        if cfg.seq_parallel_acts and mode != "decode":
            # Megatron-SP: park the residual stream sequence-sharded over
            # `model` between blocks — norms/elementwise run sharded and
            # the 16x-replicated (B, S, D) transients disappear.
            x = shard(x, "batch", "act_seq", "embed")
        return x, new_states, aux_sum

    if remat != "none":
        policy = None
        if remat == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots
        # 'sqrt' keeps the unit-level checkpoint AND adds a group-level one
        # below — nested checkpointing, live set O(n/g + g) unit carries.
        unit_body = jax.checkpoint(unit_body, policy=policy,
                                   static_argnums=())

    aux_total = dict(ZERO_AUX)
    new_states_out: dict = {}

    if n_units:
        has_states = states is not None and "stack" in states

        def scan_fn(carry, xs):
            x = carry
            uparams = xs[0]
            ustates = xs[1] if has_states else None
            x, ns, aux = unit_body(x, uparams, ustates)
            return x, (ns, aux)

        if remat == "sqrt" and not has_states and n_units >= 4:
            # sqrt-schedule checkpointing: outer scan over groups of g
            # units (group body rematted), inner scan over units.  Live
            # activations: n_units/g saved carries + g transient carries,
            # instead of n_units — the difference between fitting
            # command-r-plus on v5e HBM and not.
            g = _sqrt_divisor(n_units)
            grouped = jax.tree.map(
                lambda a: a.reshape(n_units // g, g, *a.shape[1:]),
                stack_p["stack"])

            @jax.checkpoint
            def group_body(x, gparams):
                x, (_, aux) = jax.lax.scan(
                    lambda c, xs: scan_fn(c, (xs,)), x, gparams)
                return x, aux

            def outer(x, gparams):
                return group_body(x, gparams)

            x, aux_stacked = jax.lax.scan(outer, x, grouped)
            aux_total = {k: aux_total[k] + jnp.sum(aux_stacked[k])
                         for k in aux_total}
        else:
            xs = (stack_p["stack"], states["stack"]) if has_states \
                else (stack_p["stack"],)
            x, (ns_stacked, aux_stacked) = jax.lax.scan(scan_fn, x, xs)
            if mode != "train":
                for j, (kind, _) in enumerate(unit_plan):
                    if has_states and _decode_appends_rows(kind, mode, pos):
                        ns, st = ns_stacked[f"u{j}"], states["stack"][f"u{j}"]
                        for n in ("k", "v"):
                            ns[n] = attn_lib.write_rows(st[n], ns[n], pos)
                new_states_out["stack"] = ns_stacked
            aux_total = {k: aux_total[k] + jnp.sum(aux_stacked[k])
                         for k in aux_total}

    if "extra" in stack_p:
        leftover_plan = plan[n_units * cycle:]
        for j, (kind, mlpk) in enumerate(leftover_plan):
            st = (states["extra"][f"x{j}"]
                  if states is not None and "extra" in states else None)
            x, ns, aux = apply_layer(
                stack_p["extra"][f"x{j}"], x, cfg, kind, mlpk, mode=mode,
                state=st, enc_out=enc_out, positions=positions, pos=pos,
                causal=causal, moe_strategy=moe_strategy)
            if st is not None and _decode_appends_rows(kind, mode, pos):
                for n in ("k", "v"):
                    ns[n] = attn_lib.write_rows(st[n], ns[n], pos)
            if mode != "train":
                new_states_out.setdefault("extra", {})[f"x{j}"] = ns
            aux_total = {k: aux_total[k] + aux[k] for k in aux_total}

    return x, (new_states_out if mode != "train" else None), aux_total


# ---------------------------------------------------------------------------
# model API
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig) -> dict:
    k_embed, k_enc, k_dec = jax.random.split(key, 3)
    params = {
        "embed": init_embeddings(k_embed, padded_vocab(cfg), cfg.d_model,
                                 cfg.tie_embeddings),
        "final_norm": init_norm(cfg.norm, cfg.d_model),
        "decoder": init_stack(k_dec, cfg, encoder=False,
                              cross=cfg.is_encdec),
    }
    if cfg.is_encdec:
        params["encoder"] = init_stack(k_enc, cfg, encoder=True, cross=False)
        params["enc_norm"] = init_norm(cfg.norm, cfg.d_model)
    return params


def encode(params, cfg: ModelConfig, src_embeds: jax.Array,
           moe_strategy: str = "auto", remat: str = "none") -> jax.Array:
    x = shard(src_embeds.astype(COMPUTE_DTYPE), "batch", "seq", "embed")
    x, _, _ = apply_stack(params["encoder"], x, cfg, encoder=True,
                          mode="train", states=None, enc_out=None,
                          positions=None, pos=None,
                          moe_strategy=moe_strategy, remat=remat)
    return apply_norm(params["enc_norm"], x, cfg.norm)


def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            src_embeds=None, positions=None, mode: str = "train",
            states=None, moe_strategy: str = "auto", remat: str = "none"):
    """Full-sequence forward.  Returns (logits, new_states, aux)."""
    enc_out = None
    if cfg.is_encdec:
        assert src_embeds is not None
        enc_out = encode(params, cfg, src_embeds, moe_strategy, remat)
    if embeds is not None:
        x = shard(embeds.astype(COMPUTE_DTYPE), "batch", "seq", "embed")
    else:
        x = embed_tokens(params["embed"], tokens, cfg.d_model)
    x, new_states, aux = apply_stack(
        params["decoder"], x, cfg, encoder=False, mode=mode, states=states,
        enc_out=enc_out, positions=positions, pos=None,
        moe_strategy=moe_strategy, remat=remat)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = unembed(params["embed"], x, cfg.tie_embeddings,
                     cfg.logit_softcap)
    logits = _mask_vocab_pad(logits, cfg)
    return logits, new_states, aux


def reference_logits(params, cfg: ModelConfig, tokens) -> jax.Array:
    """The plain reference the serving path is checked against: the
    uncached full forward over ``tokens`` computed in float32 at the
    highest matmul precision (a TPU runs float32 matmuls in bfloat16
    passes otherwise).  Call it outside any ``kernels.ops.kernel_context``
    so no kernel is on its path."""
    with compute_dtype(jnp.float32), jax.default_matmul_precision("highest"):
        # a fresh jit per call: the compute dtype is trace-time state
        # that jax's trace cache does not key on
        logits, _, _ = jax.jit(
            lambda p, t: forward(p, cfg, tokens=t))(params, tokens)
    return logits


def _mask_vocab_pad(logits, cfg: ModelConfig):
    vp = padded_vocab(cfg)
    if vp == cfg.vocab_size:
        return logits
    idx = jnp.arange(vp)
    return jnp.where(idx < cfg.vocab_size, logits,
                     jnp.asarray(-1e9, logits.dtype))


def train_loss(params, batch: dict, cfg: ModelConfig,
               moe_strategy: str = "auto", remat: str = "none",
               aux_weight: float = 0.01, z_weight: float = 1e-3):
    logits, _, aux = forward(
        params, cfg,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        src_embeds=batch.get("src_embeds"),
        positions=batch.get("positions"),
        mode="train", moe_strategy=moe_strategy, remat=remat)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
    else:
        denom = nll.size
    loss = jnp.sum(nll) / denom
    total = loss + aux_weight * aux["moe_lb_loss"] \
        + z_weight * aux["moe_z_loss"]
    metrics = {"loss": loss, "moe_lb_loss": aux["moe_lb_loss"],
               "moe_z_loss": aux["moe_z_loss"],
               "logz_mean": jnp.mean(logz)}
    return total, metrics


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------
def _layer_state_shape(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                       enc_len: int, cross: bool) -> dict:
    st: dict = {}
    if kind in ("attn", "local"):
        s = min(cfg.window, max_len) if kind == "local" else max_len
        st["k"] = jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim),
                            COMPUTE_DTYPE)
        st["v"] = jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim),
                            COMPUTE_DTYPE)
        if cross:
            st["ck"] = jnp.zeros((batch, enc_len, cfg.n_kv_heads,
                                  cfg.head_dim), COMPUTE_DTYPE)
            st["cv"] = jnp.zeros((batch, enc_len, cfg.n_kv_heads,
                                  cfg.head_dim), COMPUTE_DTYPE)
            st["clen"] = jnp.zeros((), jnp.int32)
    elif kind == "rglru":
        st.update(rec_lib.rglru_init_state(batch, cfg.d_model))
    elif kind == "rwkv":
        st.update(rec_lib.rwkv_init_state(batch, cfg.d_model, cfg.n_heads,
                                          cfg.rwkv_head_dim))
    return st


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      enc_len: int = 0) -> dict:
    plan = layer_plan(cfg, encoder=False)
    cycle = unit_cycle(cfg)
    n_units = len(plan) // cycle
    cross = cfg.is_encdec
    out: dict = {}
    if n_units:
        units = []
        for u in range(n_units):
            unit = {}
            for j in range(cycle):
                kind, _ = plan[u * cycle + j]
                unit[f"u{j}"] = _layer_state_shape(cfg, kind, batch, max_len,
                                                   enc_len, cross)
            units.append(unit)
        out["stack"] = _stack_trees(units)
    leftover = len(plan) % cycle
    if leftover:
        extra = {}
        for j in range(leftover):
            kind, _ = plan[n_units * cycle + j]
            extra[f"x{j}"] = _layer_state_shape(cfg, kind, batch, max_len,
                                                enc_len, cross)
        out["extra"] = extra
    return out


def decode_step(params, cfg: ModelConfig, tokens: jax.Array, pos: jax.Array,
                states: dict, positions=None, moe_strategy: str = "auto"):
    """One token: tokens (B,) int32, pos scalar int32.  Returns
    (logits (B, V), new_states)."""
    x = embed_tokens(params["embed"], tokens[:, None], cfg.d_model)
    x, new_states, _ = apply_stack(
        params["decoder"], x, cfg, encoder=False, mode="decode",
        states=states, enc_out=None, positions=positions, pos=pos,
        moe_strategy=moe_strategy)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = unembed(params["embed"], x, cfg.tie_embeddings,
                     cfg.logit_softcap)
    logits = _mask_vocab_pad(logits, cfg)
    return logits[:, 0], new_states


def prefill_chunk(params, cfg: ModelConfig, tokens: jax.Array,
                  pos: jax.Array, states: dict, positions=None,
                  moe_strategy: str = "auto"):
    """One prefill chunk: tokens (B, C) int32 at absolute positions
    ``[pos, pos + C)``, written into (and attending over) the decode
    -state caches in ``states``.  Returns (logits (B, C, V), new_states).

    This is the incremental counterpart of ``mode="prefill"``: calling
    it chunk-by-chunk over a prompt leaves the caches and logits a
    whole-prompt prefill would produce, but no single call ever costs
    more than one chunk — the serving engine interleaves these calls
    with decode steps so a long prompt cannot stall the decode slots,
    and each committed chunk is a recovery checkpoint.  Decoder-only,
    pure global-attention dense stacks (same eligibility as prefill
    bucketing); ``pos`` may be traced, so one executable per chunk
    *shape* serves every chunk position."""
    if cfg.is_encdec:
        raise ValueError("chunked prefill supports decoder-only models")
    x = embed_tokens(params["embed"], tokens, cfg.d_model)
    x, new_states, _ = apply_stack(
        params["decoder"], x, cfg, encoder=False, mode="chunk",
        states=states, enc_out=None, positions=positions, pos=pos,
        moe_strategy=moe_strategy)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = unembed(params["embed"], x, cfg.tie_embeddings,
                     cfg.logit_softcap)
    logits = _mask_vocab_pad(logits, cfg)
    return logits, new_states


# ---------------------------------------------------------------------------
# analytic parameter counts
# ---------------------------------------------------------------------------
def count_params_analytic(cfg: ModelConfig, active_only: bool = False,
                          include_embeddings: bool = True) -> int:
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    nrm = d if cfg.norm == "rmsnorm" else 2 * d   # layernorm has a bias
    total = 0
    if include_embeddings:
        total += v * d
        if not cfg.tie_embeddings:
            total += d * v

    def attn_params():
        p = d * h * dh + 2 * d * kv * dh + h * dh * d
        if cfg.qkv_bias:
            p += h * dh + 2 * kv * dh
        return p

    def mlp_params():
        return (3 if cfg.mlp_gated else 2) * d * f

    def moe_params(active: bool):
        k = cfg.experts_per_token
        e = k if active else cfg.n_experts
        p = d * cfg.n_experts + e * 3 * d * cfg.moe_d_ff
        if cfg.shared_expert:
            p += 3 * d * f
        return p

    def rglru_params():
        w = d
        return 2 * d * w + w * d + rec_lib.CONV_K * w + 6 * w

    def rwkv_params():
        lora = 64
        tm = 4 * d * h * cfg.rwkv_head_dim + h * cfg.rwkv_head_dim * d \
            + d * lora + lora * h * cfg.rwkv_head_dim \
            + 2 * h * cfg.rwkv_head_dim + 5 * d + 2 * d
        cm = d * f + f * d + d * d + 2 * d
        return tm + cm

    for encoder in ([True] if cfg.is_encdec else []) + [False]:
        for kind, mlpk in layer_plan(cfg, encoder):
            total += nrm  # norm1
            if kind in ("attn", "local"):
                total += attn_params()
            elif kind == "rglru":
                total += rglru_params()
            elif kind == "rwkv":
                total += rwkv_params() + nrm
                continue
            if not encoder and cfg.is_encdec:
                total += attn_params() + nrm      # cross + its norm
            if not cfg.parallel_block:
                total += nrm                      # norm2
            if mlpk == "dense":
                total += mlp_params()
            elif mlpk == "moe":
                total += moe_params(active_only)
    total += nrm  # final norm
    if cfg.is_encdec:
        total += nrm
    return int(total)
