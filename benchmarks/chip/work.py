"""Algorithmic work: the operations and bytes the processed tokens need.

Counted at the active width plan (query heads and FFN channels kept per
layer) and at the live context lengths.  The program's padded or wasted
work is not counted: not the full-capacity attention over empty cache
rows, not the pad rows of a bucketed chunk, not logits for chunk rows
whose token nobody reads.  So a kernel's roofline reads the same work
whatever implements it.

Each layer is counted by its kind (``ModelShape``, from the published
keys): a sliding-window layer attends at most its window of keys; a
sparse layer's FFN is its router and the k experts each token is routed
to, at the expert width (``expert_layer_cost``), not the plan's FFN.

A matmul (M, K) x (K, N) needs 2*M*K*N operations and, at the operand
type's width, M*K + K*N + M*N elements moved.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

BF16 = 2


ATTN_KINDS = ("full_attention", "sliding_attention")
MLP_KINDS = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class ModelShape:
    """Sizes the work counts need, and each layer's kind.  Empty
    ``layer_types`` means global attention in every layer, empty
    ``mlp_layer_types`` a dense FFN in every layer."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    gated: bool = True
    layer_types: tuple = ()          # ATTN_KINDS, one per layer
    sliding_window: int = 0          # keys a sliding layer attends
    mlp_layer_types: tuple = ()      # MLP_KINDS, one per layer
    n_experts: int = 0               # routed experts (the router's width)
    experts_per_token: int = 0
    expert_ffn: int = 0              # one routed expert's FFN width
    shared_ffn: int = 0              # the shared expert's FFN width, or 0

    @classmethod
    def from_conf(cls, conf: dict) -> "ModelShape":
        """From the published keys of a configuration file: ``layer_types``
        and ``sliding_window``; ``mlp_layer_types``, else the leading
        ``first_k_dense_replace`` (or ``num_dense_layers``) dense layers
        and routed experts in the rest; ``num_experts``, ``num_experts_per_tok``,
        ``moe_intermediate_size``; the shared expert at
        ``shared_expert_intermediate_size``, or ``n_shared_experts``
        times ``moe_intermediate_size``."""
        nh = int(conf["num_attention_heads"])
        d = int(conf["hidden_size"])
        n = int(conf["num_hidden_layers"])
        k = int(conf.get("num_experts_per_tok", 0))
        f_e = int(conf.get("moe_intermediate_size", 0))
        attn = _per_layer(conf, "layer_types", n, ATTN_KINDS)
        if "mlp_layer_types" in conf:
            mlp = _per_layer(conf, "mlp_layer_types", n, MLP_KINDS)
        elif k:
            n_dense = int(conf.get("first_k_dense_replace",
                                   conf.get("num_dense_layers", 0)))
            mlp = ("dense",) * min(n_dense, n) \
                + ("sparse",) * max(n - n_dense, 0)
        else:
            mlp = ()
        shared = int(conf.get("shared_expert_intermediate_size", 0)
                     or int(conf.get("n_shared_experts", 0)) * f_e)
        shape = cls(d_model=d, n_heads=nh,
                    n_kv_heads=int(conf["num_key_value_heads"]),
                    head_dim=int(conf.get("head_dim", d // nh)),
                    vocab=int(conf["vocab_size"]),
                    gated=conf.get("hidden_act", "silu") == "silu",
                    layer_types=attn,
                    sliding_window=int(conf.get("sliding_window", 0)),
                    mlp_layer_types=mlp,
                    n_experts=int(conf.get("num_experts", 0)),
                    experts_per_token=k, expert_ffn=f_e,
                    shared_ffn=shared)
        if "sliding_attention" in attn and shape.sliding_window <= 0:
            raise ValueError("sliding layers need a sliding_window")
        if "sparse" in mlp and not (shape.n_experts and k and f_e):
            raise ValueError("sparse layers need num_experts, "
                             "num_experts_per_tok and moe_intermediate_size")
        return shape

    def keys_attended(self, layer: int,
                      contexts: Sequence[int]) -> Sequence[int]:
        """Keys each token attends in ``layer``, given the keys it would
        attend globally (itself included)."""
        if self.layer_types \
                and self.layer_types[layer] == "sliding_attention":
            return [min(c, self.sliding_window) for c in contexts]
        return contexts

    def sparse(self, layer: int) -> bool:
        """The layer's FFN is routed experts (else a dense FFN)."""
        return bool(self.mlp_layer_types) \
            and self.mlp_layer_types[layer] == "sparse"


def _per_layer(conf: dict, key: str, n: int, kinds: tuple) -> tuple:
    """The first ``n`` entries of the per-layer list ``conf[key]`` (a
    depth cut keeps the leading layers), or () where the file has none."""
    if key not in conf:
        return ()
    out = tuple(conf[key][:n])
    if len(out) != n:
        raise ValueError(f"{key} has {len(conf[key])} entries for {n} layers")
    bad = sorted(set(out) - set(kinds))
    if bad:
        raise ValueError(f"{key}: no work count for {bad}")
    return out


def matmul_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def matmul_bytes(m: int, k: int, n: int, itemsize: int = BF16) -> float:
    return float(itemsize) * (m * k + k * n + m * n)


def mlp_matmuls(m: int, d: int, f: int, gated: bool = True) -> list:
    """(M, K, N) of the FFN projections of ``m`` tokens at width ``f``."""
    mm = [(m, d, f)]
    if gated:
        mm.append((m, d, f))
    mm.append((m, f, d))
    return mm


def mlp_cost(m: int, d: int, f: int, gated: bool = True,
             itemsize: int = BF16) -> tuple:
    """(operations, bytes) of one layer's FFN projections."""
    mms = mlp_matmuls(m, d, f, gated)
    return (sum(matmul_flops(*x) for x in mms),
            sum(matmul_bytes(*x, itemsize) for x in mms))


def attn_proj_flops(m: int, s: ModelShape, heads: int) -> float:
    """Q, K, V and output projections of ``m`` tokens, ``heads`` query
    heads kept (KV heads at the model's GQA ratio)."""
    g = s.n_heads // s.n_kv_heads
    kv = max(heads // g, 1)
    return 2.0 * m * s.d_model * (heads + 2 * kv) * s.head_dim \
        + 2.0 * m * heads * s.head_dim * s.d_model


def attn_core_flops(contexts: Iterable[int], s: ModelShape,
                    heads: int) -> float:
    """Scores and weighted values: a token attending ``c`` keys costs
    4 * heads * head_dim * c."""
    return 4.0 * heads * s.head_dim * float(sum(contexts))


def head_flops(rows: int, s: ModelShape) -> float:
    """LM head for the rows whose next token is read."""
    return 2.0 * rows * s.d_model * s.vocab


def expert_layer_cost(m: int, s: ModelShape, experts_hit=None,
                      itemsize: int = BF16) -> tuple:
    """(operations, bytes) of one sparse layer's FFN for ``m`` tokens: the
    router (m, d) x (d, E), each token's k experts at the expert width,
    and the shared expert where the model has one.  Each expert that a
    token hits reads its weights once; ``experts_hit`` is that count,
    by default the expectation under uniform routing,
    E * (1 - (1 - k/E)^m).  Activations move once per token and expert."""
    d, e, k, f = s.d_model, s.n_experts, s.experts_per_token, s.expert_ffn
    if experts_hit is None:
        experts_hit = e * (1.0 - (1.0 - k / e) ** m)
    mats = 3 if s.gated else 2
    rows = m * k
    flops = matmul_flops(m, d, e) + mats * matmul_flops(rows, d, f)
    nbytes = matmul_bytes(m, d, e, itemsize) \
        + float(itemsize) * mats * (experts_hit * d * f + rows * (d + f))
    if s.shared_ffn:
        sf, sb = mlp_cost(m, d, s.shared_ffn, s.gated, itemsize)
        flops, nbytes = flops + sf, nbytes + sb
    return flops, nbytes


def ffn_cost(m: int, s: ModelShape, layer: int, f: int) -> tuple:
    """(operations, bytes) of ``layer``'s FFN for ``m`` tokens: a dense FFN
    at the plan's width ``f``, or the experts whatever ``f`` says."""
    if s.sparse(layer):
        return expert_layer_cost(m, s)
    return mlp_cost(m, s.d_model, f, s.gated)


def step_flops(s: ModelShape, heads: Sequence[int], ffn: Sequence[int],
               contexts: Sequence[int], logit_rows: int) -> float:
    """Model operations of one call: one token per entry of ``contexts``
    (the keys it would attend globally, itself included), every layer at
    its plan width and of its kind, and the head for ``logit_rows``
    rows."""
    m = len(contexts)
    total = head_flops(logit_rows, s)
    for i, (h, f) in enumerate(zip(heads, ffn)):
        total += attn_proj_flops(m, s, int(h))
        total += attn_core_flops(s.keys_attended(i, contexts), s, int(h))
        total += ffn_cost(m, s, i, int(f))[0]
    return total


def chunk_contexts(offset: int, length: int) -> list:
    """Keys each row of a prefill chunk attends: row i at absolute
    position offset + i attends offset + i + 1 keys."""
    return [offset + i + 1 for i in range(length)]


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """Least time on the chip, and which bound sets it."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
