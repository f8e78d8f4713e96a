"""Per-arch smoke tests: reduced configs of all 10 assigned architectures —
one forward/train step on CPU asserting shapes + no NaNs, plus decode, and
the analytic parameter count against the real initialized tree."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs, reduced_config
from repro.models import (
    count_params_analytic, decode_step, init_decode_state, init_params,
    layer_plan, train_loss,
)
from repro.models import attention as attn
from repro.models import transformer as tfm
from repro.models.transformer import forward, padded_vocab

# full XLA compiles: quick tier skips with -m "not slow"
pytestmark = pytest.mark.slow

ARCHS = list_archs()
B, S = 2, 32


def make_batch(cfg, key=0):
    k = jax.random.PRNGKey(key)
    batch = {
        "tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
    }
    if cfg.is_encdec:
        batch["src_embeds"] = jax.random.normal(k, (B, S, cfg.d_model))
    if cfg.rope_kind == "mrope":
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, :, None], (B, S, 3))
    return batch


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch in ARCHS:
        cfg = reduced_config(get_config(arch))
        out[arch] = (cfg, init_params(jax.random.PRNGKey(0), cfg))
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(models, arch):
    cfg, params = models[arch]
    loss, metrics = train_loss(params, make_batch(cfg), cfg,
                               moe_strategy="dense")
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    assert np.isfinite(float(metrics["logz_mean"]))


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes(models, arch):
    cfg, params = models[arch]
    b = make_batch(cfg)
    logits, _, _ = forward(params, cfg, tokens=b["tokens"],
                           src_embeds=b.get("src_embeds"),
                           positions=b.get("positions"),
                           moe_strategy="dense")
    assert logits.shape == (B, S, padded_vocab(cfg))
    assert not np.isnan(np.asarray(logits, np.float32)).any()


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_smoke(models, arch):
    cfg, params = models[arch]
    enc_len = S if cfg.is_encdec else 0
    st = init_decode_state(cfg, B, 64, enc_len=enc_len)
    pos3 = (jnp.zeros((B, 1, 3), jnp.int32)
            if cfg.rope_kind == "mrope" else None)
    tok = jnp.zeros((B,), jnp.int32)
    logits, st2 = decode_step(params, cfg, tok, jnp.asarray(0), st,
                              positions=pos3)
    assert logits.shape == (B, padded_vocab(cfg))
    assert not np.isnan(np.asarray(logits, np.float32)).any()
    # states preserved structure
    assert jax.tree.structure(st) == jax.tree.structure(st2)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_analytic(models, arch):
    """count_params_analytic must equal the initialized tree exactly,
    modulo vocab padding (the deliberate tail-elimination pad)."""
    cfg, params = models[arch]
    actual = sum(x.size for x in jax.tree.leaves(params))
    expected = count_params_analytic(cfg)
    pad_rows = padded_vocab(cfg) - cfg.vocab_size
    pad = pad_rows * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    assert actual == expected + pad, (arch, actual, expected, pad)


@pytest.mark.parametrize("arch", ARCHS)
def test_layer_plan_covers_depth(arch):
    cfg = get_config(arch)
    plan = layer_plan(cfg)
    assert len(plan) == cfg.n_layers
    kinds = {k for k, _ in plan}
    if cfg.family == "hybrid":
        assert kinds == {"rglru", "local"}
    if cfg.family == "ssm":
        assert kinds == {"rwkv"}
    if cfg.moe:
        assert any(m == "moe" for _, m in plan)


def test_prefill_decode_consistency():
    """Greedy decode continuing a prefix == teacher-forced forward."""
    cfg = reduced_config(get_config("qwen1.5-0.5b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                              cfg.vocab_size)
    full_logits, _, _ = forward(params, cfg, tokens=toks)
    # decode token-by-token with a cache
    st = init_decode_state(cfg, 1, 16)
    outs = []
    for t in range(16):
        lg, st = decode_step(params, cfg, toks[:, t], jnp.asarray(t), st)
        outs.append(lg)
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(full_logits, np.float32), rtol=3e-2, atol=3e-2)


def test_recurrent_prefill_decode_consistency():
    cfg = reduced_config(get_config("recurrentgemma-2b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0,
                              cfg.vocab_size)
    full_logits, _, _ = forward(params, cfg, tokens=toks)
    st = init_decode_state(cfg, 1, 12)
    outs = []
    for t in range(12):
        lg, st = decode_step(params, cfg, toks[:, t], jnp.asarray(t), st)
        outs.append(lg)
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(full_logits, np.float32), rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# decode reads the cache and writes the new rows after the layer scan
# ---------------------------------------------------------------------------
def _per_slab_attention(p, x, cfg, kind, mode, cache, positions, pos,
                        causal):
    """Reference: a global-attention decode layer writes its new row into
    its own cache slab, then attends over the slab."""
    if mode != "decode" or kind != "attn":
        return _SELF_ATTENTION(p, x, cfg, kind, mode, cache, positions, pos,
                               causal)
    ragged = jnp.ndim(pos) == 1
    q, k, v = attn.qkv_proj(p, x)
    q, k = tfm._rope(cfg, q, k, tfm._default_positions(
        cfg, x.shape[0], 1, pos[:, None] if ragged else pos))

    def put(c, new):
        new = new.astype(c.dtype)
        if ragged:
            return c.at[jnp.arange(x.shape[0]), pos].set(new[:, 0])
        return jax.lax.dynamic_update_slice(c, new, (0, pos, 0, 0))

    kc, vc = put(cache["k"], k), put(cache["v"], v)
    o = attn.decode_attention(q[:, 0], kc, vc, pos + 1)
    return attn.out_proj(p, o[:, None]), {"k": kc, "v": vc}


_SELF_ATTENTION = tfm._self_attention
MAX_LEN = 32


def _mixed_config():
    """Global attention, RG-LRU and local attention in one unit; seven
    layers: two scanned units and one unrolled global-attention layer."""
    cfg = reduced_config(get_config("recurrentgemma-2b"), n_layers=7)
    return dataclasses.replace(cfg, block_pattern=("attn", "rglru", "local"))


@pytest.mark.parametrize("name,ragged", [
    ("qwen1.5-0.5b", True), ("qwen1.5-0.5b", False),
    ("mixed", True), ("mixed", False),
    ("seamless-m4t-medium", False),
])
def test_decode_matches_per_slab_decode(name, ragged, monkeypatch):
    cfg = (_mixed_config() if name == "mixed"
           else reduced_config(get_config(name)))
    b = 4
    params = init_params(jax.random.PRNGKey(0), cfg)
    st = init_decode_state(cfg, b, MAX_LEN, enc_len=8 if cfg.is_encdec
                           else 0)
    # a cache full of history, so every row the mask admits matters
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    st = jax.tree.map(
        lambda a: (jnp.full_like(a, 8) if a.dtype == jnp.int32
                   else jax.random.normal(next(keys), a.shape,
                                          jnp.float32).astype(a.dtype)), st)
    tok = jnp.arange(3, 3 + b, dtype=jnp.int32)
    pos = (jnp.asarray([0, 13, MAX_LEN - 1, 7], jnp.int32) if ragged
           else jnp.asarray(11, jnp.int32))

    logits, new = decode_step(params, cfg, tok, pos, st)
    with monkeypatch.context() as m:
        m.setattr(tfm, "_decode_appends_rows", lambda *a: False)
        m.setattr(tfm, "_self_attention", _per_slab_attention)
        ref_logits, ref = decode_step(params, cfg, tok, pos, st)

    # the same states, leaf for leaf: recurrent, local and cross states
    # come back where they were
    assert jax.tree.structure(new) == jax.tree.structure(ref)
    for a, r in zip(jax.tree.leaves(new), jax.tree.leaves(ref)):
        assert a.shape == r.shape and a.dtype == r.dtype
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=1e-2, atol=1e-2)
    written = np.zeros((b, MAX_LEN), bool)
    written[np.arange(b), np.asarray(pos)] = True
    for path, a in jax.tree_util.tree_leaves_with_path(new):
        a, r = (np.asarray(_leaf(t, path), np.float32) for t in (new, ref))
        np.testing.assert_allclose(a, r, rtol=1e-2, atol=1e-2)
        if path[-1].key in ("k", "v") and a.shape[-3] == MAX_LEN:
            # every cache row but the new one is left as it was, exactly
            old = np.asarray(_leaf(st, path), np.float32)
            np.testing.assert_array_equal(a[..., ~written, :, :],
                                          old[..., ~written, :, :])
            np.testing.assert_array_equal(r[..., ~written, :, :],
                                          old[..., ~written, :, :])


def _leaf(tree, path):
    for k in path:
        tree = tree[k.key]
    return tree
