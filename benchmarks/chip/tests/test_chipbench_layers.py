"""A configuration of windowed and expert layers goes through the harness
from its own file: ``testdata/mellum2-l4.json`` holds one period of
Mellum2-12B-A2.5B's layer pattern (three sliding-window layers, then a
global one, every FFN 64 routed experts, 8 a token) at the published
widths, a ``program`` block and a ``rehearsal`` block.  A stub stands in
for the reference module such a model would bring."""

import json
import sys
import types
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import trace_reduce as tr  # noqa: E402
from engine_adapter import Call  # noqa: E402
from peaks import peaks_for  # noqa: E402
from work import (ModelShape, attn_core_flops, attn_proj_flops,  # noqa: E402
                  expert_layer_cost, head_flops, mlp_cost, step_flops)

bench.add_paths()

SEED = 3_000_000_019
KERNEL_OP = 'custom_call_target="tpu_custom_call"'


def _stub(rules=None):
    ref = types.ModuleType("stub_reference")
    ref.accepted = []
    ref.accepts = lambda cfg, conf: ref.accepted.append(cfg.name)
    if rules is not None:
        ref.WEIGHT_RULES = rules
    return ref


@pytest.fixture
def mellum():
    return json.loads((HERE / "testdata" / "mellum2-l4.json").read_text())


# ---------------------------------------------------------------------------
# the program's config, the rehearsal, the weights
# ---------------------------------------------------------------------------
def test_program_config_from_the_file(mellum):
    ref = _stub()
    cfg = bench.program_config(mellum, ref)
    assert ref.accepted == [cfg.name]
    assert cfg.block_pattern == ("local", "local", "local", "attn")
    assert (cfg.window, cfg.moe, cfg.n_experts, cfg.experts_per_token,
            cfg.moe_d_ff) == (1024, True, 64, 8, 896)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.rope_theta,
            cfg.tie_embeddings) == (4, 2304, 32, 4, 128, 98304, 5e5, False)
    # the program's layer kinds are the ones the work counts read
    kinds = {"local": "sliding_attention", "attn": "full_attention"}
    assert [kinds[k] for k in cfg.layer_kinds()] \
        == list(ModelShape.from_conf(mellum).layer_types)


def test_work_counts_see_the_layers_the_program_runs(mellum):
    cfg = bench.program_config(mellum, _stub())
    bench.check_layer_kinds(cfg, ModelShape.from_conf(mellum))
    qwen = json.loads((HERE / "configs" / "qwen1.5-0.5b.json").read_text())
    bench.check_layer_kinds(bench.program_config(qwen),
                            ModelShape.from_conf(qwen))
    # the program's shared expert runs at the dense width, d_ff
    shared = dict(mellum, program=dict(mellum["program"], shared_expert=True),
                  shared_expert_intermediate_size=mellum["intermediate_size"])
    bench.check_layer_kinds(bench.program_config(shared, _stub()),
                            ModelShape.from_conf(shared))
    for program, counted in [({"block_pattern": ["local", "attn"]}, {}),
                             ({"moe": False}, {}),
                             ({}, {"sliding_window": 512}),
                             ({"experts_per_token": 4}, {}),
                             ({"shared_expert": True}, {}),
                             ({}, {"n_shared_experts": 1}),
                             ({"shared_expert": True},
                              {"n_shared_experts": 2})]:
        c = dict(mellum, program=dict(mellum["program"], **program))
        with pytest.raises(ValueError, match="counted|work counts"):
            bench.check_layer_kinds(bench.program_config(c, _stub()),
                                    ModelShape.from_conf(dict(c, **counted)))


def test_dense_reference_refuses_the_file(mellum):
    with pytest.raises(ValueError, match="not the dense decoder the "
                                         "reference computes"):
        bench.program_config(mellum)


def test_unknown_program_field_stops_the_run(mellum):
    with pytest.raises(KeyError, match="n_routers"):
        bench.program_config(dict(mellum, program={"n_routers": 2}), _stub())


def test_rehearsal_block(mellum):
    c = bench.rehearsal_conf(mellum)
    assert (c["num_hidden_layers"], c["hidden_size"], c["num_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["sliding_window"], c["num_key_value_heads"]) \
        == (4, 256, 8, 2, 64, 32, 2)
    assert c["engine"] == dict(mellum["engine"], **bench.REHEARSAL["engine"])
    c2 = bench.rehearsal_conf(dict(mellum, rehearsal={
        "program": {"capacity_factor": 2.0}, "engine": {"slots": 2}}))
    assert c2["program"] == dict(mellum["program"], capacity_factor=2.0)
    assert c2["engine"]["slots"] == 2 and c2["num_experts"] == 64
    cfg = bench.program_config(c, _stub())
    assert (cfg.window, cfg.n_experts, cfg.experts_per_token,
            cfg.moe_d_ff, cfg.n_layers) == (32, 8, 2, 64, 4)


def test_make_weights_fills_the_router(mellum):
    import jax
    import numpy as np
    from repro.models import init_params
    from weights import make_weights
    ref = _stub()
    cfg = bench.program_config(bench.rehearsal_conf(mellum), ref)
    w = make_weights(cfg, SEED, init_params, ref)
    leaves = {jax.tree_util.keystr(p): np.asarray(x)
              for p, x in jax.tree_util.tree_flatten_with_path(w)[0]}
    routers = [v for k, v in leaves.items() if k.endswith("['router']")]
    assert len(routers) == 4                # one per layer of the unit
    for r in routers:
        assert r.shape == (1, 256, 8)       # (units, d_model, experts)
        assert np.std(r) == pytest.approx(256 ** -0.5, rel=0.1)
    assert all(np.isfinite(v).all() for v in leaves.values())


def test_make_weights_fills_a_norm_bias():
    """command-r's LayerNorm has a bias: 0.1 N(0, 1), like the others."""
    import dataclasses
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import init_params
    from weights import make_weights
    cfg = dataclasses.replace(get_config("command-r-plus-104b"), n_layers=2,
                              d_model=64, n_heads=4, n_kv_heads=1,
                              head_dim=16, d_ff=128, vocab_size=256)
    w = make_weights(cfg, SEED, init_params, _stub())
    biases = [np.asarray(x).ravel()
              for p, x in jax.tree_util.tree_flatten_with_path(w)[0]
              if str(p[-1].key) == "bias"]
    assert len(biases) == 2                 # norm1 of the stack, final_norm
    b = np.concatenate(biases)
    assert np.std(b) == pytest.approx(0.1, rel=0.25)
    assert abs(np.mean(b)) < 0.05


def test_weight_rules_of_the_reference_fill_unknown_leaves():
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import init_params
    from weights import make_weights
    cfg = dataclasses.replace(get_config("recurrentgemma-2b"), n_layers=3,
                              d_model=64, n_heads=4, n_kv_heads=1,
                              head_dim=16, d_ff=128, vocab_size=256)
    with pytest.raises(KeyError, match="no weight rule for parameter"):
        make_weights(cfg, SEED, init_params, _stub())
    unknown = ("a_param", "conv_b", "conv_w", "in_gate_b", "in_gate_w",
               "rec_gate_b", "rec_gate_w", "w_out", "w_x")
    rules = {n: (lambda key, shape, v=0.01 * i: jnp.full(shape, v))
             for i, n in enumerate(unknown, 1)}
    w = make_weights(cfg, SEED, init_params, _stub(rules))
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        name = str(path[-1].key)
        leaf = np.asarray(leaf)
        if name in unknown:
            seen.add(name)
            assert (leaf == np.float32(0.01 * (unknown.index(name) + 1))).all()
        else:
            assert np.std(leaf) > 0         # this file's own rules
    assert seen == set(unknown)


# ---------------------------------------------------------------------------
# work by layer kind
# ---------------------------------------------------------------------------
def test_layer_kinds_from_the_file(mellum):
    s = ModelShape.from_conf(mellum)
    assert s.layer_types == ("sliding_attention",) * 3 + ("full_attention",)
    assert s.mlp_layer_types == ("sparse",) * 4
    assert (s.sliding_window, s.n_experts, s.experts_per_token,
            s.expert_ffn, s.shared_ffn) == (1024, 64, 8, 896, 0)
    # leading dense layers, by either key; a shared expert by either key
    c = {k: v for k, v in mellum.items() if k != "mlp_layer_types"}
    for key in ("first_k_dense_replace", "num_dense_layers"):
        s = ModelShape.from_conf(dict(c, **{key: 1}))
        assert s.mlp_layer_types == ("dense",) + ("sparse",) * 3
    assert ModelShape.from_conf(dict(c, n_shared_experts=2)).shared_ffn \
        == 2 * 896
    assert ModelShape.from_conf(
        dict(c, shared_expert_intermediate_size=5632)).shared_ffn == 5632
    with pytest.raises(ValueError, match="no work count"):
        ModelShape.from_conf(dict(mellum, layer_types=["linear_attention"]
                                  * 4))


def test_sliding_layer_attends_its_window(mellum):
    s = ModelShape.from_conf(mellum)
    assert s.keys_attended(0, [10, 1024, 5000]) == [10, 1024, 1024]
    assert s.keys_attended(3, [10, 1024, 5000]) == [10, 1024, 5000]
    # one decode token at 3000 keys: 3 layers read 1024 of them, 1 all
    core = step_flops(s, [32] * 4, [7168] * 4, [3000], 0) \
        - 4 * attn_proj_flops(1, s, 32) - 4 * expert_layer_cost(1, s)[0]
    assert core == 4 * 32 * 128 * (3 * 1024 + 3000)


def test_sparse_layer_is_router_plus_k_experts(mellum):
    s = ModelShape.from_conf(mellum)
    d, e, k, f = 2304, 64, 8, 896
    m = 16
    router = 2 * m * d * e                  # (16, 2304) x (2304, 64)
    experts = 3 * 2 * (m * k) * d * f       # gate, up, down of 128 rows
    flops, nbytes = expert_layer_cost(m, s, experts_hit=40)
    assert flops == router + experts == 1_590_165_504
    assert nbytes == 2 * (m * d + d * e + m * e) \
        + 2 * 3 * (40 * d * f + m * k * (d + f)) == 498_280_448
    # one token hits its k experts, no more
    assert expert_layer_cost(1, s)[1] == pytest.approx(
        expert_layer_cost(1, s, experts_hit=k)[1])
    # the plan's FFN width plays no part in a sparse layer
    want = head_flops(m, s) + 4 * (attn_proj_flops(m, s, 32)) \
        + 3 * attn_core_flops([1024] * m, s, 32) \
        + attn_core_flops([2000] * m, s, 32) + 4 * (router + experts)
    for width in (7168, 512):
        assert step_flops(s, [32] * 4, [width] * 4, [2000] * m, m) == want
    # a shared expert adds a dense FFN at its width
    sh = ModelShape.from_conf(dict(mellum, n_shared_experts=1))
    assert expert_layer_cost(m, sh, 40)[0] == flops + mlp_cost(m, d, f)[0]


def test_matmul_roofline_leaves_sparse_layers_out(mellum):
    peaks = peaks_for("TPU v5 lite")
    red = tr.Reduced(window=(0.0, 10.0), offset=0.0, busy=[(0.0, 1.0)],
                     busy_s=1.0, ops={f"%matmul_tiled.1 = {KERNEL_OP}": 0.5},
                     modules=[], spans=[], n_devices=1)
    calls = [Call("decode", 1.0, 1.1, [100] * 8, 8, (32,) * 4, (7168,) * 4)]
    read = bench.load_reader("matmul_roofline")

    def run(conf):
        return SimpleNamespace(trace=red, calls=calls, clock_to_trace=0.0,
                               model=ModelShape.from_conf(conf), peaks=peaks)
    assert read(run(mellum)) is None        # no layer runs the kernel
    half = dict(mellum, mlp_layer_types=["dense", "sparse"] * 2)
    dense = {k: v for k, v in mellum.items() if k != "mlp_layer_types"
             and k != "num_experts_per_tok"}
    assert ModelShape.from_conf(dense).mlp_layer_types == ()
    # two dense layers of four read half of what four dense layers read
    assert read(run(half)) == pytest.approx(read(run(dense)) / 2, rel=1e-12)
