"""Algorithmic work: the operations and bytes the processed tokens need.

Counted at the active width plan (query heads and FFN channels kept per
layer) and at the live context lengths.  The program's padded or wasted
work is not counted: not the full-capacity attention over empty cache
rows, not the pad rows of a bucketed chunk, not logits for chunk rows
whose token nobody reads.  So a kernel's roofline reads the same work
whatever implements it.

A matmul (M, K) x (K, N) needs 2*M*K*N operations and, at the operand
type's width, M*K + K*N + M*N elements moved.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

BF16 = 2


@dataclasses.dataclass(frozen=True)
class ModelShape:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    gated: bool = True

    @classmethod
    def from_conf(cls, conf: dict) -> "ModelShape":
        nh = int(conf["num_attention_heads"])
        d = int(conf["hidden_size"])
        return cls(d_model=d, n_heads=nh,
                   n_kv_heads=int(conf["num_key_value_heads"]),
                   head_dim=int(conf.get("head_dim", d // nh)),
                   vocab=int(conf["vocab_size"]),
                   gated=conf.get("hidden_act", "silu") == "silu")


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


def step_flops(s: ModelShape, heads: Sequence[int], ffn: Sequence[int],
               contexts: Sequence[int], logit_rows: int) -> float:
    """Model operations of one call: one token per entry of ``contexts``
    (the keys it attends, itself included), every layer at its plan
    width, and the head for ``logit_rows`` rows."""
    m = len(contexts)
    total = head_flops(logit_rows, s)
    for h, f in zip(heads, ffn):
        total += attn_proj_flops(m, s, int(h))
        total += attn_core_flops(contexts, s, int(h))
        total += mlp_cost(m, s.d_model, int(f), s.gated)[0]
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
