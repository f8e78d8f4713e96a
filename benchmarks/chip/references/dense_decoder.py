"""Plain reference for the dense decoders served here (qwen1.5, deepseek-llm):
the uncached forward pass in float32 at the highest matmul precision, in
straightforward ``jax.numpy``, with no kernel, cache or batching.

It imports nothing of the program.  It reads the weight arrays the
benchmark made (``weights.py``) by their names, its sizes from the
configuration file, and the widths of the served width plan (query heads
and FFN channels kept in each layer; the plan keeps the leading heads and
channels and drops the rest).

The architecture, layer by layer (pre-norm, as the program defines it):

    x  = E[tokens] * sqrt(d_model)
    h  = rmsnorm(x) * g1                       (eps 1e-6)
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv  (biases where the model has them)
    q, k = rope(q), rope(k)                    (split-half rotation, theta)
    a  = softmax(q k^T / sqrt(dh), causal) v   (heads past the plan's count
                                                contribute nothing)
    x += a Wo
    h  = rmsnorm(x) * g2
    x += (silu(h Wg) * (h Wu)) Wd              (the plan's leading channels)
    logits = (rmsnorm(x) * gf) E^T  or  (...) Wout

Departure from the published models, kept because the program serves it:
the embedding is scaled by sqrt(d_model) (Qwen1.5 and DeepSeek-LLM do not
scale).

``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 with one scale per tensor, accumulated in float32.
"""

from __future__ import annotations

import functools
import math

import numpy as np

EPS = 1e-6
FP8_MAX = 448.0


def accepts(cfg, conf: dict) -> None:
    """Raise unless the program's ``cfg`` is the plain dense decoder this
    module computes: RMSNorm, gated FFN, global attention in every layer,
    standard RoPE, sequential blocks, no soft-capped logits, no encoder."""
    plain = (cfg.norm == "rmsnorm" and cfg.mlp_gated and not cfg.moe
             and tuple(cfg.block_pattern) == ("attn",)
             and cfg.rope_kind == "standard" and not cfg.parallel_block
             and cfg.logit_softcap == 0.0 and not cfg.is_encdec)
    if not plain:
        raise ValueError(f"{cfg.name}: not the dense decoder the "
                         f"reference computes")


def _layout(weights):
    dec = weights["decoder"]
    if set(dec) != {"stack"} or set(dec["stack"]) != {"u0"}:
        raise KeyError("the reference reads one stacked unit of decoder "
                       f"layers; the tree holds {sorted(dec)}")
    return dec["stack"]["u0"]


def _q8(x):
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.lru_cache(maxsize=None)
def _forward_fn(n_layers, n_heads, n_kv, dh, theta, vocab, tied, precision):
    import jax
    import jax.numpy as jnp

    q8 = _q8 if precision == "fp8" else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    def rms(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g

    def rope(x, pos):
        freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32)
                                 / dh))
        ang = pos[:, None].astype(jnp.float32) * freqs      # (S, dh/2)
        sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               -1)

    def fwd(w, heads, ffn, tokens):
        s = tokens.shape[0]
        pos = jnp.arange(s)
        emb = w["embed"]["tok_emb"]
        x = emb[tokens] * math.sqrt(emb.shape[1])
        causal = pos[None, :] <= pos[:, None]
        g = n_heads // n_kv

        def layer(x, lw):
            p, h_keep, f_keep = lw
            a = p["attn"]
            h = rms(x, p["norm1"]["scale"])
            q = mm("sd,dhk->shk", h, a["wq"])
            k = mm("sd,dhk->shk", h, a["wk"])
            v = mm("sd,dhk->shk", h, a["wv"])
            if "bq" in a:
                q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
            q, k = rope(q, pos), rope(k, pos)
            k = jnp.repeat(k, g, axis=1)
            v = jnp.repeat(v, g, axis=1)
            sc = mm("qhk,shk->hqs", q, k) / math.sqrt(dh)
            sc = jnp.where(causal[None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            o = mm("hqs,shk->qhk", pr, v)
            o = jnp.where((jnp.arange(n_heads) < h_keep)[None, :, None],
                          o, 0.0)
            x = x + mm("qhk,hkd->qd", o, a["wo"])
            m = p["mlp"]
            h = rms(x, p["norm2"]["scale"])
            keep = jnp.arange(m["w_up"].shape[-1]) < f_keep
            up = mm("sd,df->sf", h, m["w_up"])
            gate = mm("sd,df->sf", h, m["w_gate"])
            act = jnp.where(keep, jax.nn.silu(gate) * up, 0.0)
            x = x + mm("sf,fd->sd", act, m["w_down"])
            return x, None

        x, _ = jax.lax.scan(layer, x, (_layout(w), heads, ffn))
        x = rms(x, w["final_norm"]["scale"])
        if tied:
            logits = mm("sd,vd->sv", x, emb)
        else:
            logits = mm("sd,dv->sv", x, w["embed"]["out_emb"])
        return logits[:, :vocab]

    def gaps(w, heads, ffn, tokens, targets):
        """Per row: how far the target's logit lies below the row's best,
        and the row's best token."""
        logits = fwd(w, heads, ffn, tokens)
        best = jnp.max(logits, -1)
        tgt = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return best - tgt, jnp.argmax(logits, -1).astype(jnp.int32), logits

    return jax.jit(gaps)


class Reference:
    """The reference for one configuration file and one served plan."""

    def __init__(self, conf: dict, heads, ffn, pad_len: int):
        self.conf = conf
        self.heads = np.asarray(heads, np.int32)
        self.ffn = np.asarray(ffn, np.int32)
        self.pad_len = int(pad_len)
        n = int(conf["num_hidden_layers"])
        if self.heads.shape != (n,) or self.ffn.shape != (n,):
            raise ValueError("plan widths do not give one value per layer")

    def _fn(self, precision: str):
        c = self.conf
        nh = int(c["num_attention_heads"])
        return _forward_fn(
            int(c["num_hidden_layers"]), nh, int(c["num_key_value_heads"]),
            int(c.get("head_dim", int(c["hidden_size"]) // nh)),
            float(c["rope_theta"]), int(c["vocab_size"]),
            bool(c["tie_word_embeddings"]), precision)

    def _inputs(self, full: np.ndarray):
        n = len(full) - 1
        if n > self.pad_len:
            raise ValueError(f"sequence of {n} past the reference's "
                             f"{self.pad_len} rows")
        toks = np.zeros(self.pad_len, np.int32)
        tgt = np.zeros(self.pad_len, np.int32)
        toks[:n] = full[:-1]
        tgt[:n] = full[1:]
        return toks, tgt

    def served_gaps(self, weights, prompt, served) -> np.ndarray:
        """For each served token: the reference's best logit at that
        position minus the logit of the token served."""
        import jax
        import jax.numpy as jnp
        full = np.concatenate([prompt, served]).astype(np.int32)
        toks, tgt = self._inputs(full)
        with jax.default_matmul_precision("highest"):
            gap, _, _ = self._fn("f32")(weights, jnp.asarray(self.heads),
                                        jnp.asarray(self.ffn),
                                        jnp.asarray(toks), jnp.asarray(tgt))
        p = len(prompt)
        return np.asarray(gap[p - 1:p - 1 + len(served)], np.float64)

    def control_gaps(self, weights, prompt, served) -> np.ndarray:
        """The control: at the same positions, the gap of the token that
        the fp8 computation puts first, read on the float32 logits."""
        import jax
        import jax.numpy as jnp
        full = np.concatenate([prompt, served]).astype(np.int32)
        toks, tgt = self._inputs(full)
        args = (weights, jnp.asarray(self.heads), jnp.asarray(self.ffn),
                jnp.asarray(toks), jnp.asarray(tgt))
        with jax.default_matmul_precision("highest"):
            _, top8, _ = self._fn("fp8")(*args)
            _, _, logits = self._fn("f32")(*args)
            best = jnp.max(logits, -1)
            gap = best - jnp.take_along_axis(logits, top8[:, None], -1)[:, 0]
        p = len(prompt)
        return np.asarray(gap[p - 1:p - 1 + len(served)], np.float64)
