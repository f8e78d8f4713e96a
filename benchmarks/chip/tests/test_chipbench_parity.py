"""What the dense cells read does not move when the harness learns layer
kinds: the qwen weights bit for bit, and the work counts and the matmul
roofline against the formulas they had before (written out here in full,
not through ``work.py``), on the recorded deepseek calls and on a seeded
set of decode and chunk calls.  And the dense reference still refuses the
models it does not compute."""

import dataclasses
import hashlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import trace_reduce as tr  # noqa: E402
from engine_adapter import Call  # noqa: E402
from peaks import peaks_for  # noqa: E402
from work import ModelShape, chunk_contexts, step_flops  # noqa: E402

bench.add_paths()

SEED = 2_147_483_659
# sha256 over (path, bytes) of every leaf of make_weights(qwen at the
# rehearsal size, SEED) on the CPU, taken before layer kinds existed
QWEN_REHEARSAL_SHA256 = \
    "1154d71825c4dafdeb8dc553cbf76bf7376c7efab220655aa942103816e34263"
KERNEL_OP = 'custom_call_target="tpu_custom_call"'


def _conf(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


# ---------------------------------------------------------------------------
# the formulas as they stood, every layer global attention and a dense FFN
# ---------------------------------------------------------------------------
def _old_mlp(m, d, f, gated):
    mms = [(m, d, f)] + ([(m, d, f)] if gated else []) + [(m, f, d)]
    return mms, sum(2.0 * a * b * c for a, b, c in mms)


def _old_step_flops(s, heads, ffn, contexts, logit_rows):
    m = len(contexts)
    total = 2.0 * logit_rows * s.d_model * s.vocab
    g = s.n_heads // s.n_kv_heads
    for h, f in zip(heads, ffn):
        h = int(h)
        kv = max(h // g, 1)
        total += 2.0 * m * s.d_model * (h + 2 * kv) * s.head_dim \
            + 2.0 * m * h * s.head_dim * s.d_model
        total += 4.0 * h * s.head_dim * float(sum(contexts))
        total += _old_mlp(m, s.d_model, int(f), s.gated)[1]
    return total


def _old_roofline_least(s, calls, peaks, lo, hi, offset):
    least = 0.0
    for c in calls:
        if not lo <= c.start + offset < hi:
            continue
        for f in c.ffn:
            for a, b, n in _old_mlp(len(c.contexts), s.d_model, int(f),
                                    s.gated)[0]:
                tc = 2.0 * a * b * n / peaks["flops_bf16"]
                tm = 2.0 * (a * b + b * n + a * n) / peaks["hbm_bytes_per_s"]
                least += tc if tc >= tm else tm
    return least


def _synthetic_calls(conf, n=40, seed=5):
    """Decode steps of 1-16 slots at live lengths up to max_len, and
    prefill chunks at every offset, at a few plan widths per layer."""
    rng = np.random.default_rng(seed)
    layers = int(conf["num_hidden_layers"])
    nh, dff = int(conf["num_attention_heads"]), int(conf["intermediate_size"])
    max_len, chunk = conf["engine"]["max_len"], conf["engine"]["prefill_chunk"]
    calls = []
    for i in range(n):
        heads = tuple(int(x) for x in rng.integers(nh // 2, nh + 1, layers))
        ffn = tuple(int(x) for x in 128 * rng.integers(dff // 256, dff // 128
                                                       + 1, layers))
        if i % 3:
            ctx = [int(x) for x in rng.integers(1, max_len,
                                                rng.integers(1, 17))]
            calls.append(Call("decode", float(i), i + 0.5, ctx, len(ctx),
                              heads, ffn))
        else:
            off = int(rng.integers(0, max_len // chunk)) * chunk
            ln = int(rng.integers(1, chunk + 1))
            calls.append(Call("chunk", float(i), i + 0.5,
                              chunk_contexts(off, ln), int(i % 2), heads, ffn))
    return calls


def _recorded_calls():
    meta = json.loads((HERE / "testdata" / "deepseek_decode.json").read_text())
    return [Call(**c) for c in meta["calls"]]


CASES = [("qwen1.5-0.5b", "synthetic"), ("deepseek-7b-l3", "synthetic"),
         ("deepseek-7b-l3", "recorded")]


def _calls(name, which):
    return _recorded_calls() if which == "recorded" \
        else _synthetic_calls(_conf(name))


@pytest.mark.parametrize("name,which", CASES)
def test_step_flops_as_before(name, which):
    s = ModelShape.from_conf(_conf(name))
    assert not s.layer_types and not s.mlp_layer_types
    for c in _calls(name, which):
        assert step_flops(s, c.heads, c.ffn, c.contexts, c.logit_rows) \
            == _old_step_flops(s, c.heads, c.ffn, c.contexts, c.logit_rows)


@pytest.mark.parametrize("name,which", CASES)
def test_matmul_roofline_as_before(name, which):
    s = ModelShape.from_conf(_conf(name))
    peaks = peaks_for("TPU v5 lite")
    calls = _calls(name, which)
    red = tr.Reduced(window=(0.0, 1e4), offset=0.0, busy=[(0.0, 1.0)],
                     busy_s=1.0, ops={f"%matmul_tiled.1 = {KERNEL_OP}": 0.75},
                     modules=[], spans=[], n_devices=1)
    run = SimpleNamespace(trace=red, calls=calls, clock_to_trace=0.0,
                          model=s, peaks=peaks)
    least = _old_roofline_least(s, calls, peaks, 0.0, 1e4, 0.0)
    assert least > 0
    assert bench.load_reader("matmul_roofline")(run) == 100.0 * least / 0.75


def test_matmul_roofline_as_before_on_recorded_trace():
    meta = json.loads((HERE / "testdata" / "deepseek_decode.json").read_text())
    red = tr.reduce_file(str(HERE / "testdata" / "deepseek_decode.xplane.pb"),
                         "bench.window", meta["t0"], meta["t1"] - meta["t0"])
    s = ModelShape(**meta["model"])
    calls = _recorded_calls()
    run = SimpleNamespace(trace=red, calls=calls, clock_to_trace=red.offset,
                          model=s, peaks=meta["peaks"])
    least = _old_roofline_least(s, calls, meta["peaks"], *red.window,
                                red.offset)
    dev = tr.kernel_seconds(red, KERNEL_OP)
    assert bench.load_reader("matmul_roofline")(run) == 100.0 * least / dev


def test_qwen_weights_bit_identical():
    import jax
    from references import dense_decoder
    from repro.models import init_params
    from weights import make_weights
    conf = bench.rehearsal_conf(_conf("qwen1.5-0.5b"))
    w = make_weights(bench.program_config(conf), SEED, init_params,
                     dense_decoder)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == QWEN_REHEARSAL_SHA256


def test_rehearsal_without_a_block_as_before():
    conf = _conf("qwen1.5-0.5b")
    old = dict(conf)
    old.update(bench.REHEARSAL["conf"])
    old["engine"] = dict(conf["engine"], **bench.REHEARSAL["engine"])
    assert bench.rehearsal_conf(conf) == old


@pytest.mark.parametrize("change", [
    {"moe": True, "n_experts": 8, "experts_per_token": 2, "moe_d_ff": 64},
    {"block_pattern": ("local", "attn"), "window": 32},
])
def test_dense_reference_refuses(change):
    from references import dense_decoder
    conf = _conf("qwen1.5-0.5b")
    cfg = bench.program_config(conf)
    dense_decoder.accepts(cfg, conf)
    with pytest.raises(ValueError, match="qwen1.5-0.5b: not the dense "
                                         "decoder the reference computes"):
        dense_decoder.accepts(dataclasses.replace(cfg, **change), conf)
    program = {k: list(v) if isinstance(v, tuple) else v
               for k, v in change.items()}
    with pytest.raises(ValueError, match="not the dense decoder"):
        bench.program_config(dict(conf, program=program))
