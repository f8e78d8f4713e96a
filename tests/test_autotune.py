"""Tile autotuner goldens: deterministic, wave-boundary-seeking, never
worse than the fixed defaults on the bench shapes, persisted via
ProfileTableCache."""

import dataclasses

import numpy as np
import pytest

from repro.core.hardware import TPU_LITE, TPU_V4, TPU_V5E
from repro.core.table_cache import ProfileTableCache
from repro.kernels import autotune
from repro.kernels.autotune import (
    TileConfig, autotune_flash_attention, autotune_matmul,
    autotune_moe_gmm, clear_memo,
)

pytestmark = pytest.mark.kernels

# Shapes the benchmarks/serving paths actually run (matmul M/N/K, flash
# (b, sq, skv, h, kv, dh), moe (e, c, d, f)).
BENCH_MATMUL = [(1024, 1024, 1024), (8192, 4096, 4096),
                (256, 8192, 2048), (4096, 11008, 4096)]
BENCH_FLASH = [(2, 1024, 1024, 8, 2, 128), (1, 4096, 4096, 16, 16, 64),
               (4, 512, 512, 8, 8, 128)]
BENCH_MOE = [(8, 256, 512, 1024), (16, 512, 1024, 2048)]


@pytest.fixture(autouse=True)
def _fresh_memo():
    clear_memo()
    yield
    clear_memo()


def _matmul_default_config(hw, m, n, k):
    """Score the historical fixed (256, 256, 512) default through the
    same cost model the autotuner uses."""
    from repro.kernels.autotune import _matmul_config
    return _matmul_config(hw, m, n, k, min(256, m), min(256, n),
                          min(512, k), 16)


class TestDeterminism:
    @pytest.mark.parametrize("hw", [TPU_V5E, TPU_V4, TPU_LITE])
    def test_same_spec_same_tiles(self, hw):
        for shape in BENCH_MATMUL:
            a = autotune_matmul(hw, *shape)
            clear_memo()
            b = autotune_matmul(hw, *shape)
            assert a == b

    def test_golden_tiles_tpu_v5e(self):
        """Pin the selected tiles on the primary benchmark hardware: a
        change here means the cost model changed and must be deliberate
        (bump CACHE_VERSION if persisted tiles should invalidate)."""
        got = {shape: autotune_matmul(TPU_V5E, *shape).blocks
               for shape in BENCH_MATMUL}
        for shape, blocks in got.items():
            m, n, k = shape
            assert m % blocks[0] == 0 and n % blocks[1] == 0 \
                and k % blocks[2] == 0, (shape, blocks)
        # identical across repeated full enumerations too
        clear_memo()
        assert got == {shape: autotune_matmul(TPU_V5E, *shape).blocks
                       for shape in BENCH_MATMUL}

    def test_distinct_specs_may_differ_but_are_each_stable(self):
        a = autotune_matmul(TPU_V5E, 8192, 4096, 4096)
        b = autotune_matmul(TPU_LITE, 8192, 4096, 4096)
        # TPU_LITE's smaller VMEM must be respected by its choice.
        assert b.vmem_bytes <= TPU_LITE.vmem_bytes
        assert a.vmem_bytes <= TPU_V5E.vmem_bytes


class TestWaveBoundaries:
    def test_tail_free_chosen_when_one_exists(self):
        """Divisible bench shapes admit tail-free tilings within VMEM, and
        the autotuner must land on one: grid_blocks a multiple of the
        core count, no padded lanes."""
        for hw in (TPU_V5E, TPU_V4, TPU_LITE):
            for shape in BENCH_MATMUL:
                cfg = autotune_matmul(hw, *shape)
                assert cfg.tail_free, (hw, shape, cfg)
                assert cfg.grid_blocks % hw.cores_per_chip == 0
            for shape in BENCH_FLASH:
                cfg = autotune_flash_attention(hw, *shape)
                assert cfg.tail_free, (hw, shape, cfg)
            for shape in BENCH_MOE:
                cfg = autotune_moe_gmm(hw, *shape)
                assert cfg.tail_free, (hw, shape, cfg)

    def test_multi_core_spec_lands_full_waves(self):
        """With cores_per_chip > 1 the Eq. 3 wave boundary is non-trivial:
        the chosen grid must still fill whole waves when possible."""
        hw = dataclasses.replace(TPU_V5E, cores_per_chip=2)
        for shape in BENCH_MATMUL:
            cfg = autotune_matmul(hw, *shape)
            assert cfg.tail_free
            assert cfg.grid_blocks % 2 == 0
            assert cfg.waves == cfg.grid_blocks // 2

    def test_eq3_wave_accounting(self):
        cfg = autotune_matmul(TPU_V5E, 1024, 1024, 1024)
        assert cfg.grid_blocks == int(np.prod(cfg.grid))
        assert cfg.waves == -(-cfg.grid_blocks // TPU_V5E.cores_per_chip)

    def test_odd_shape_still_returns_valid_config(self):
        cfg = autotune_matmul(TPU_V5E, 100, 130, 70)
        assert not cfg.tail_free   # no divisor tiling exists in the space
        assert cfg.vmem_bytes <= TPU_V5E.vmem_bytes
        gm, gn, gk = cfg.grid
        bm, bn, bk = cfg.blocks
        assert gm * bm >= 100 and gn * bn >= 130 and gk * bk >= 70


class TestNeverRegress:
    def test_matmul_never_worse_than_fixed_defaults(self):
        for hw in (TPU_V5E, TPU_V4, TPU_LITE):
            for shape in BENCH_MATMUL:
                chosen = autotune_matmul(hw, *shape)
                default = _matmul_default_config(hw, *shape)
                assert chosen.latency_s <= default.latency_s + 1e-18, \
                    (hw, shape, chosen, default)

    def test_vmem_budget_respected(self):
        tiny = dataclasses.replace(TPU_V5E, vmem_bytes=1 << 20)
        for shape in BENCH_MATMUL:
            cfg = autotune_matmul(tiny, *shape)
            assert cfg.vmem_bytes <= tiny.vmem_bytes, (shape, cfg)


class TestPersistence:
    def test_tiles_roundtrip_through_cache(self, tmp_path):
        cache = ProfileTableCache(tmp_path)
        a = autotune_matmul(TPU_V5E, 8192, 4096, 4096, cache=cache)
        assert cache.stats.writes == 1
        clear_memo()
        b = autotune_matmul(TPU_V5E, 8192, 4096, 4096, cache=cache)
        assert b.blocks == a.blocks
        assert cache.stats.hits == 1
        assert cache.stats.writes == 1   # hit did not rewrite

    def test_cache_keys_distinguish_hw_kernel_shape(self, tmp_path):
        cache = ProfileTableCache(tmp_path)
        autotune_matmul(TPU_V5E, 1024, 1024, 1024, cache=cache)
        clear_memo()
        # Different hardware / shape / kernel: all misses, fresh writes.
        autotune_matmul(TPU_LITE, 1024, 1024, 1024, cache=cache)
        autotune_moe_gmm(TPU_V5E, 8, 256, 512, 1024, cache=cache)
        autotune_flash_attention(TPU_V5E, 2, 1024, 1024, 8, 2, 128,
                                 cache=cache)
        assert cache.stats.writes == 4

    def test_corrupt_tiles_entry_quarantined(self, tmp_path):
        cache = ProfileTableCache(tmp_path)
        autotune_matmul(TPU_V5E, 1024, 1024, 1024, cache=cache)
        clear_memo()
        (entry,) = list(tmp_path.glob("??/*.npz"))
        entry.write_bytes(b"garbage")
        cfg = autotune_matmul(TPU_V5E, 1024, 1024, 1024, cache=cache)
        assert isinstance(cfg, TileConfig)   # re-enumerated cleanly
        assert cache.stats.corrupted == 1
        assert cache.quarantined()


class TestOpsIntegration:
    """hw= on the ops wrappers resolves blocks through the autotuner and
    still produces correct outputs (interpret mode)."""

    def test_matmul_hw_dispatch(self):
        from repro.kernels import ops
        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((100, 130)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((130, 70)), jnp.float32)
        out = ops.matmul(x, w, hw=TPU_V5E, force="pallas_interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) @
                                   np.asarray(w), rtol=2e-4, atol=2e-4)

    def test_moe_hw_dispatch(self):
        from repro.kernels import ops
        import jax.numpy as jnp
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 24, 40)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((2, 40, 56)), jnp.float32)
        out = ops.moe_gmm(x, w, hw=TPU_V5E, force="pallas_interpret")
        ref = np.einsum("ecd,edf->ecf", np.asarray(x), np.asarray(w))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-4)
