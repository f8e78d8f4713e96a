"""Work counts against hand-computed numbers at qwen1.5-0.5b's shapes, and
the peak table."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from peaks import peaks_for  # noqa: E402
from work import (ModelShape, attn_core_flops, attn_proj_flops,  # noqa: E402
                  chunk_contexts, head_flops, matmul_bytes, mlp_cost,
                  roofline_seconds, step_flops)


@pytest.fixture(scope="module")
def qwen():
    with open(HERE / "configs" / "qwen1.5-0.5b.json") as f:
        return ModelShape.from_conf(json.load(f))


def test_shape_from_config(qwen):
    assert (qwen.d_model, qwen.n_heads, qwen.n_kv_heads, qwen.head_dim,
            qwen.vocab) == (1024, 16, 16, 64, 151936)


def test_mlp_projections(qwen):
    # 16 decode tokens at the sliced width 2688: up, gate, down
    flops, nbytes = mlp_cost(16, 1024, 2688)
    assert flops == 3 * 2 * 16 * 1024 * 2688 == 264241152
    one = 2 * (16 * 1024 + 1024 * 2688 + 16 * 2688)
    assert nbytes == 2 * one + 2 * (16 * 2688 + 2688 * 1024 + 16 * 1024)
    assert nbytes == 3 * 5623808 == 16871424
    assert matmul_bytes(512, 1024, 2688) == 2 * (512 * 1024 + 1024 * 2688
                                                 + 512 * 2688)


def test_attention_at_live_lengths(qwen):
    # 14 of 16 heads kept: q and o at 14 heads, k and v at 14 (MHA)
    assert attn_proj_flops(1, qwen, 14) == 2 * 1024 * 14 * 64 * 4 == 7340032
    # two tokens attending 100 and 2000 keys, not max_len
    assert attn_core_flops([100, 2000], qwen, 14) == 4 * 14 * 64 * 2100
    assert chunk_contexts(512, 3) == [513, 514, 515]


def test_lm_head(qwen):
    assert head_flops(16, qwen) == 2 * 16 * 1024 * 151936 == 4978638848


def test_step_sums_layers(qwen):
    layers = 24
    heads, ffn = [14] * layers, [2688] * layers
    ctx = [10, 20]
    want = head_flops(2, qwen) + layers * (
        attn_proj_flops(2, qwen, 14) + attn_core_flops(ctx, qwen, 14)
        + mlp_cost(2, 1024, 2688)[0])
    assert step_flops(qwen, heads, ffn, ctx, 2) == want


def test_roofline_bound():
    p = peaks_for("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    t, bound = roofline_seconds(197e12, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = roofline_seconds(1.0, 819e9, p)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
