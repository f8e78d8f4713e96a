"""Tail-aware tile autotuning: pick kernel blocks from the staircase model.

The analytic staircase (``core.tail_model``) assumes ideal wave packing,
while the Pallas kernels in this package run whatever fixed tiles their
callers pass — so a width the optimizer put on a full-wave boundary of
the *model* can still land mid-wave on the *kernel's* grid.  This module
closes that gap: block sizes for ``matmul_tiled`` / ``flash_attention`` /
``moe_gmm`` are chosen by evaluating each candidate tiling through the
roofline and paper Eq. 3's grid-wave model (``GridWaveModel``), so the
realized grid lands on full-wave boundaries whenever one exists within
the VMEM budget.

Selection rule
--------------
For each candidate block tuple the cost model computes

    B         = grid cells     (matmul: ceil(M/bm) * ceil(N/bn) * ceil(K/bk)
                                — ``matmul_tiled.grid_blocks``)
    W         = ceil(B / S)    (Eq. 3 waves, S = hw.cores_per_chip)
    compute_s = dL * W         (dL = per-cell FLOPs / peak — Eq. 3's
                                L = dL * ceil(B / S))
    memory_s  = padded HBM traffic / bandwidth   (roofline)
    latency_s = max(compute_s, memory_s)
    tail_free = every dim divides its block  AND  B % S == 0

i.e. no padded tile lanes and no partial last wave.  Candidates whose
VMEM working set (operand and output blocks double-buffered + fp32
scratch) exceeds the budget are discarded.  The budget is three quarters
of the scoped VMEM the compiler grants one kernel by default
(``KERNEL_VMEM_BYTES``, capped by ``hw.vmem_bytes``), not the chip's
whole VMEM: the compiler keeps its own scratch in the same space, and on
v5e it refused a bf16 (1024, 1024, 1024)-block matmul whose working set
is 16 MiB.  Among survivors, tail-free configs are preferred when any
exist; ties break by (latency_s, padded_flops, grid_blocks, blocks) — a
pure function of (hardware, shape, dtype), so selection is deterministic
per ``HardwareSpec``.

Worked Eq. 3 example (TPU_LITE, S = cores_per_chip for the example's
sake; take S = 4): a (512, 512, 512) matmul at the fixed default blocks
(256, 256, 512) has B = 2*2*1 = 4 cells -> W = ceil(4/4) = 1 full wave,
tail-free.  The same matmul at (256, 256, 256) has B = 2*2*2 = 8 ->
W = 2, still tail-free; but at (192, 256, 512) B = ceil(512/192)*2*1 =
6 -> W = ceil(6/4) = 2 waves with the second wave only half occupied
AND 64 padded rows per m-tile — the tail the autotuner rejects: its
latency is 2*dL with dL inflated by padding, versus 1*dL for the
(256, 256, 512) choice.

Configs are memoized in-process per (hardware fingerprint, kernel,
shape, dtype) and optionally persisted through ``ProfileTableCache``
(``get_tiles``/``put_tiles``), so a serving process re-resolves tiles
from disk instead of re-enumerating candidates.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from repro.core.hardware import HardwareSpec
from repro.core.tail_model import GridWaveModel, ceil_div
from repro.core.table_cache import ProfileTableCache, hardware_fingerprint

__all__ = [
    "TileConfig", "autotune_matmul", "autotune_flash_attention",
    "autotune_moe_gmm", "clear_memo", "memo_stats",
]

# Candidate block edges. Multiples of the MXU/VPU tiles (8 sublanes x 128
# lanes); the selection cost model prunes what VMEM can't hold.
_M_EDGES = (8, 16, 32, 64, 128, 256, 512, 1024)
_LANE_EDGES = (128, 256, 512, 1024)

# Scoped VMEM the TPU compiler grants one Pallas kernel by default.
KERNEL_VMEM_BYTES = 16 * 1024**2


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One scored tiling of one kernel invocation shape."""

    kernel: str                 # "matmul" | "flash_attention" | "moe_gmm"
    blocks: tuple[int, ...]     # kernel block args, kernel-specific order
    grid: tuple[int, ...]       # resulting pallas grid
    grid_blocks: int            # B of Eq. 3 (product of grid)
    waves: int                  # W = ceil(B / cores_per_chip)
    tail_free: bool             # no padded lanes, no partial last wave
    latency_s: float            # max(Eq. 3 compute, roofline memory)
    padded_flops: float         # FLOPs actually executed incl. padding
    vmem_bytes: int             # per-core working set of this tiling


# In-process memo: (hw fingerprint, kernel, shape, dtype_bits) -> TileConfig.
_MEMO: dict = {}


def clear_memo() -> None:
    _MEMO.clear()


def memo_stats() -> dict:
    """Observability for the in-process memo: entry counts per kernel
    and how many memoized grids are tail-free.  The serving layer (width
    planner tail-preference, compile-cache smoke) reports these to show
    the autotuner is being consulted, not re-run."""
    per_kernel: dict[str, int] = {}
    tail_free = 0
    for (_, kernel, _, _), cfg in _MEMO.items():
        per_kernel[kernel] = per_kernel.get(kernel, 0) + 1
        tail_free += bool(cfg.tail_free)
    return {"entries": len(_MEMO), "tail_free": tail_free,
            "per_kernel": per_kernel}


def _vmem_budget(hw: HardwareSpec) -> int:
    """Bytes of VMEM a candidate tiling may hold (module docstring)."""
    return min(hw.vmem_bytes, KERNEL_VMEM_BYTES) * 3 // 4


def _select(cands: Sequence[TileConfig]) -> TileConfig:
    """Prefer tail-free tilings when any exist; break ties
    deterministically (latency, padded work, grid size, block tuple)."""
    pool = [c for c in cands if c.tail_free] or list(cands)
    return min(pool, key=lambda c: (c.latency_s, c.padded_flops,
                                    c.grid_blocks, c.blocks))


def _edge_candidates(dim: int, edges: Sequence[int]) -> list[int]:
    """Block candidates for one padded dim: every edge not uselessly
    larger than the dim (one block covering the dim is kept once)."""
    out = [e for e in edges if e < 2 * dim or e == edges[0]]
    return out or [edges[0]]


def _divisor_candidates(dim: int, edges: Sequence[int],
                        cap: int) -> list[int]:
    """Block candidates for a dim the kernel requires to divide evenly:
    the edges that divide ``dim``, plus ``dim`` itself when small."""
    out = [e for e in edges if dim % e == 0]
    if dim <= cap and dim not in out:
        out.append(dim)
    return out


# ---- per-kernel cost models ---------------------------------------------

def _matmul_config(hw: HardwareSpec, m: int, n: int, k: int,
                   bm: int, bn: int, bk: int,
                   dtype_bits: int) -> TileConfig:
    bpe = dtype_bits // 8
    vmem = 2 * (bm * bk + bk * bn + bm * bn) * bpe + bm * bn * 4
    gm, gn, gk = ceil_div(m, bm), ceil_div(n, bn), ceil_div(k, bk)
    blocks = gm * gn * gk
    cell_flops = 2.0 * bm * bn * bk
    wave = GridWaveModel(hw, cell_flops).evaluate(blocks)
    # Padded HBM traffic: each x tile is read once per n-block, each w
    # tile once per m-block, the output written once.
    total_bytes = ((gm * bm) * (gk * bk) * gn
                   + (gk * bk) * (gn * bn) * gm
                   + (gm * bm) * (gn * bn)) * bpe
    latency = max(wave.latency_s, total_bytes / hw.hbm_bandwidth)
    tail_free = (m % bm == 0 and n % bn == 0 and k % bk == 0
                 and blocks % hw.cores_per_chip == 0)
    return TileConfig(
        kernel="matmul", blocks=(bm, bn, bk), grid=(gm, gn, gk),
        grid_blocks=blocks, waves=wave.waves, tail_free=tail_free,
        latency_s=latency, padded_flops=cell_flops * blocks,
        vmem_bytes=vmem)


def _matmul_candidates(hw: HardwareSpec, shape, dtype_bits: int):
    m, n, k = shape
    out = []
    for bm in _edge_candidates(m, _M_EDGES):
        for bn in _edge_candidates(n, _LANE_EDGES):
            for bk in _edge_candidates(k, _LANE_EDGES):
                cfg = _matmul_config(hw, m, n, k, bm, bn, bk, dtype_bits)
                if cfg.vmem_bytes <= _vmem_budget(hw):
                    out.append(cfg)
    if not out:     # degenerate spec: the clamped defaults regardless
        out.append(_matmul_config(hw, m, n, k, min(256, m), min(256, n),
                                  min(512, k), dtype_bits))
    return out


def _flash_config(hw: HardwareSpec, b: int, sq: int, skv: int, h: int,
                  kv_heads: int, dh: int, bq: int, bkv: int,
                  dtype_bits: int) -> TileConfig:
    bpe = dtype_bits // 8
    # double-buffered q, k, v and output blocks + fp32 scores and
    # accumulator + the two (bq, 1) stats, padded to 128 lanes.
    vmem = (2 * (2 * bq + 2 * bkv) * dh * bpe
            + bq * bkv * 4 + bq * dh * 4 + 2 * bq * 128 * 4)
    gq, gkv = ceil_div(sq, bq), ceil_div(skv, bkv)
    blocks = b * h * gq * gkv
    cell_flops = 4.0 * bq * bkv * dh
    wave = GridWaveModel(hw, cell_flops).evaluate(blocks)
    # q and the output move once; k/v blocks are re-fetched per q block
    # (the kernel's kv index map changes every innermost step).
    total_bytes = (2 * b * h * sq * dh + 2 * b * h * gq * skv * dh) * bpe
    latency = max(wave.latency_s, total_bytes / hw.hbm_bandwidth)
    tail_free = (sq % bq == 0 and skv % bkv == 0
                 and blocks % hw.cores_per_chip == 0)
    return TileConfig(
        kernel="flash_attention", blocks=(bq, bkv),
        grid=(b * h, gq, gkv), grid_blocks=blocks, waves=wave.waves,
        tail_free=tail_free, latency_s=latency,
        padded_flops=cell_flops * blocks, vmem_bytes=vmem)


def _flash_candidates(hw: HardwareSpec, shape, dtype_bits: int):
    b, sq, skv, h, kv_heads, dh = shape
    out = []
    # The kernel requires divisibility, so only divisor blocks are legal
    # without padding (ops.flash_attention pads otherwise).
    for bq in _divisor_candidates(sq, (16, 32, 64, 128, 256, 512, 1024),
                                  cap=2048):
        for bkv in _divisor_candidates(skv,
                                       (128, 256, 512, 1024), cap=2048):
            cfg = _flash_config(hw, b, sq, skv, h, kv_heads, dh,
                                bq, bkv, dtype_bits)
            if cfg.vmem_bytes <= _vmem_budget(hw):
                out.append(cfg)
    if not out:
        out.append(_flash_config(hw, b, sq, skv, h, kv_heads, dh,
                                 min(512, sq), min(512, skv), dtype_bits))
    return out


def _moe_config(hw: HardwareSpec, e: int, c: int, d: int, f: int,
                bc: int, bf: int, bd: int,
                dtype_bits: int) -> TileConfig:
    bpe = dtype_bits // 8
    vmem = 2 * (bc * bd + bd * bf + bc * bf) * bpe + bc * bf * 4
    gc, gf, gd = ceil_div(c, bc), ceil_div(f, bf), ceil_div(d, bd)
    blocks = e * gc * gf * gd
    cell_flops = 2.0 * bc * bf * bd
    wave = GridWaveModel(hw, cell_flops).evaluate(blocks)
    total_bytes = e * ((gc * bc) * (gd * bd) * gf
                       + (gd * bd) * (gf * bf) * gc
                       + (gc * bc) * (gf * bf)) * bpe
    latency = max(wave.latency_s, total_bytes / hw.hbm_bandwidth)
    tail_free = (c % bc == 0 and f % bf == 0 and d % bd == 0
                 and blocks % hw.cores_per_chip == 0)
    return TileConfig(
        kernel="moe_gmm", blocks=(bc, bf, bd), grid=(e, gc, gf, gd),
        grid_blocks=blocks, waves=wave.waves, tail_free=tail_free,
        latency_s=latency, padded_flops=cell_flops * blocks,
        vmem_bytes=vmem)


def _moe_candidates(hw: HardwareSpec, shape, dtype_bits: int):
    e, c, d, f = shape
    out = []
    for bc in _edge_candidates(c, _M_EDGES):
        for bf in _edge_candidates(f, _LANE_EDGES):
            for bd in _edge_candidates(d, _LANE_EDGES):
                cfg = _moe_config(hw, e, c, d, f, bc, bf, bd, dtype_bits)
                if cfg.vmem_bytes <= _vmem_budget(hw):
                    out.append(cfg)
    if not out:
        out.append(_moe_config(hw, e, c, d, f, min(128, c), min(256, f),
                               min(256, d), dtype_bits))
    return out


_KERNELS = {
    "matmul": _matmul_candidates,
    "flash_attention": _flash_candidates,
    "moe_gmm": _moe_candidates,
}


def _autotune(kernel: str, hw: HardwareSpec, shape: tuple[int, ...],
              dtype_bits: int,
              cache: Optional[ProfileTableCache]) -> TileConfig:
    key = (hardware_fingerprint(hw), kernel, shape, dtype_bits)
    cfg = _MEMO.get(key)
    if cfg is not None:
        return cfg
    if cache is not None:
        blocks = cache.get_tiles(hw, kernel, shape + (dtype_bits,))
        if blocks is not None:
            # Re-score the persisted blocks (cheap) so the returned
            # TileConfig carries fresh grid/latency fields.
            cfg = _score_blocks(kernel, hw, shape, tuple(blocks),
                                dtype_bits)
            _MEMO[key] = cfg
            return cfg
    cfg = _select(_KERNELS[kernel](hw, shape, dtype_bits))
    _MEMO[key] = cfg
    if cache is not None:
        cache.put_tiles(hw, kernel, shape + (dtype_bits,), cfg.blocks)
    return cfg


def _score_blocks(kernel: str, hw: HardwareSpec, shape, blocks,
                  dtype_bits: int) -> TileConfig:
    fn = {"matmul": _matmul_config, "flash_attention": _flash_config,
          "moe_gmm": _moe_config}[kernel]
    cfg = fn(hw, *shape, *blocks, dtype_bits)
    if cfg.vmem_bytes > _vmem_budget(hw):
        # persisted under a larger budget: rebuild fresh
        return _select(_KERNELS[kernel](hw, shape, dtype_bits))
    return cfg


# ---- public entry points ------------------------------------------------

def autotune_matmul(hw: HardwareSpec, m: int, n: int, k: int, *,
                    dtype_bits: int = 16,
                    cache: Optional[ProfileTableCache] = None) -> TileConfig:
    """Tiles for ``matmul_tiled.matmul_pallas`` on an (M, K) @ (K, N)."""
    return _autotune("matmul", hw, (m, n, k), dtype_bits, cache)


def autotune_flash_attention(hw: HardwareSpec, b: int, sq: int, skv: int,
                             h: int, kv_heads: int, dh: int, *,
                             dtype_bits: int = 16,
                             cache: Optional[ProfileTableCache] = None,
                             ) -> TileConfig:
    """(block_q, block_kv) for ``flash_attention_pallas``."""
    return _autotune("flash_attention", hw, (b, sq, skv, h, kv_heads, dh),
                     dtype_bits, cache)


def autotune_moe_gmm(hw: HardwareSpec, e: int, c: int, d: int, f: int, *,
                     dtype_bits: int = 16,
                     cache: Optional[ProfileTableCache] = None) -> TileConfig:
    """(block_c, block_f, block_d) for ``moe_gmm_pallas``."""
    return _autotune("moe_gmm", hw, (e, c, d, f), dtype_bits, cache)
