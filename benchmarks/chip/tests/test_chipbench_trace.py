"""The reduction from trace to metrics: unit cases on hand-made events,
and a trace recorded on a TPU v5e checked in under ``testdata/``."""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402


def test_merge_and_clip():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_self_time_of_nested_ops():
    ev = [("while", 0, 10), ("a", 1, 3), ("b", 4, 9), ("c", 5, 6),
          ("d", 11, 12)]
    assert tr.self_times(ev) == {"while": 3, "a": 2, "b": 4, "c": 1,
                                 "d": 1}


def _reduced():
    return tr.Reduced(
        window=(0.0, 10.0), offset=0.0,
        busy=[(1.0, 2.0), (4.0, 7.0)], busy_s=4.0,
        ops={"%fusion.1 = bf16[8,16]{1,0} fusion(x)": 3.0,
             "%closed_call.2 = bf16[8,16]{1,0} custom-call(a, b), "
             'custom_call_target="tpu_custom_call"': 1.0},
        modules=[("jit_counted(1)", 1.0, 2.0), ("jit_scatter(2)", 4.0, 4.5),
                 ("jit_counted(3)", 4.5, 7.0)],
        spans=[("bench.window", 0.0, 10.0), ("engine.step", 0.5, 8.0),
               ("engine.decode", 0.9, 1.1), ("engine.write_slot", 3.0, 3.9)],
        n_devices=1)


def test_gaps_and_their_spans():
    r = _reduced()
    assert r.gaps() == [(0.0, 1.0), (2.0, 4.0), (7.0, 10.0)]
    assert r.busy_within(1.5, 5.0) == pytest.approx(1.5)
    idle = r.idle_by_span()
    # (0,1): mid 0.5 in engine.step; (2,4): mid 3 in write_slot;
    # (7,10): mid 8.5 in no engine span
    assert idle == {"engine.step": 1.0, "engine.write_slot": 2.0,
                    tr.NO_SPAN: 3.0}
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1 bf16[8,16] fusion", 3.0]
    assert b["device_ops"][1][0].endswith("tpu_custom_call")


def test_calls_pair_with_modules_in_order():
    class Call:
        def __init__(self, kind, start):
            self.kind, self.start = kind, start
    r = _reduced()
    calls = [Call("decode", 0.95), Call("chunk", 4.2)]
    pairs = tr.call_device_times(r, calls, 0.0, {"jit_counted"})
    assert [(c.kind, s) for c, s in pairs] == [("decode", 1.0),
                                               ("chunk", 2.5)]


# ---------------------------------------------------------------------------
# a trace recorded on one TPU v5e: 0.3 s of deepseek-7b-l3 under the
# decode-heavy mix, traced by the harness's own window, with the calls the
# engine adapter recorded (testdata/deepseek_decode.json)
# ---------------------------------------------------------------------------
DATA = HERE / "testdata"


@pytest.fixture(scope="module")
def recorded():
    import json
    meta = json.loads((DATA / "deepseek_decode.json").read_text())
    red = tr.reduce_file(str(DATA / "deepseek_decode.xplane.pb"),
                         "bench.window", meta["t0"], meta["t1"] - meta["t0"])
    return meta, red


def test_recorded_busy_and_idle(recorded):
    meta, red = recorded
    assert red is not None and red.n_devices == 1
    assert 0.0 < red.busy_s <= red.window_s
    assert red.window_s == pytest.approx(meta["t1"] - meta["t0"])
    assert sum(e - s for s, e in red.busy) == pytest.approx(red.busy_s)
    idle = red.idle_by_span()
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    b = red.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10


def test_recorded_calls_pair_with_executables(recorded):
    meta, red = recorded

    class Call:
        def __init__(self, d):
            self.__dict__.update(d)
    calls = [Call(c) for c in meta["calls"]]
    pairs = tr.call_device_times(red, calls, red.offset,
                                 set(meta["modules"]))
    decode = [s for c, s in pairs if c.kind == "decode"]
    assert len(decode) >= 0.8 * sum(c.kind == "decode" for c in calls)
    # the decode executable's executions take the same time, step by step
    assert max(decode) < 1.2 * min(decode)


def test_recorded_matmul_roofline_under_100(recorded):
    meta, red = recorded
    from work import (ModelShape, matmul_bytes, matmul_flops, mlp_matmuls,
                      roofline_seconds)
    m = ModelShape(**meta["model"])
    dev = tr.kernel_seconds(red, 'custom_call_target="tpu_custom_call"')
    calls = [c for c in meta["calls"]
             if red.window[0] <= c["start"] + red.offset < red.window[1]]
    least = sum(roofline_seconds(matmul_flops(*mm), matmul_bytes(*mm),
                                 meta["peaks"])[0]
                for c in calls for f in c["ffn"]
                for mm in mlp_matmuls(len(c["contexts"]), m.d_model, f))
    # the kernel alone reads its weights from VMEM, staged by another op;
    # without that op's time the share would pass 100%
    kernel_only = sum(v for k, v in red.ops.items()
                      if "tpu_custom_call" in k)
    assert dev > kernel_only
    assert 0.0 < least / dev <= 1.0
