"""Tile-quantized matmul Pallas kernel — the paper's mechanism made visible.

The grid is ceil(M/bm) x ceil(N/bn) "thread blocks" (paper Fig. 4); each cell
runs a bk-stepped VMEM-resident accumulation on the MXU.  The cell count is
exactly the ``B`` of paper Eq. 3 — ``GridWaveModel`` predicts latency from it
and ``benchmarks/wave_verification.py`` checks the staircase against this
kernel's grid.

Block shapes are BlockSpec'd to VMEM: (bm, bk) + (bk, bn) + (bm, bn) tiles
must fit the ~128 MiB VMEM budget; defaults are MXU-aligned (multiples of
128) — a deliberately misaligned N exposes the tail as padded lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output tile; grid = (gm, gn, gk), k innermost."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(x: jax.Array, w: jax.Array, *, block_m: int = 256,
                  block_n: int = 256, block_k: int = 512,
                  interpret: bool = False) -> jax.Array:
    """x: (M, K) @ w: (K, N) -> (M, N).  Requires dims divisible by blocks
    (callers pad — that padding IS the tail effect; see ops.py)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"matmul_pallas needs block-divisible dims: (M, N, K)="
            f"({m}, {n}, {k}) is not divisible by blocks ({bm}, {bn}, {bk})"
            f" (requested ({block_m}, {block_n}, {block_k}), clamped to the"
            f" dims). Pad M/N/K up to block multiples and slice the output"
            f" — ops.matmul does this automatically.")
    gm, gn, gk = m // bm, n // bn, k // bk

    return pl.pallas_call(
        functools.partial(matmul_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="matmul_tiled",
    )(x, w)


def grid_blocks(m: int, n: int, k: int, block_m: int = 256,
                block_n: int = 256, block_k: int = 512) -> int:
    """B of paper Eq. 3 for this kernel (used by the wave benchmarks)."""
    ceil = lambda a, b: -(-a // b)
    return ceil(m, block_m) * ceil(n, block_n) * ceil(k, block_k)
