"""Idle time cut at the engine's span boundaries (``span_idle``) and the
readers of the engine's spans, on a hand-made ``trace_reduce.Reduced``."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import span_idle  # noqa: E402
import trace_reduce as tr  # noqa: E402

# Two steps.  A holds a chunk and its commit; B considers a boundary.
# Both decode.  The device is busy in three stretches.
STEP_A = [("engine.step", 0.0, 6.0), ("engine.deliver", 0.1, 0.3),
          ("engine.admit", 0.3, 0.5), ("engine.prefill", 0.5, 2.0),
          ("engine.chunk", 0.6, 0.8), ("engine.commit", 1.5, 1.9),
          ("engine.write_slot", 1.6, 1.7), ("engine.inputs", 2.0, 2.4),
          ("engine.decode", 2.4, 2.6), ("engine.sample", 2.6, 2.8),
          ("engine.sync", 2.8, 5.0), ("engine.retire", 5.0, 5.8)]
STEP_B = [("engine.step", 6.0, 10.0), ("engine.deliver", 6.1, 6.2),
          ("engine.boundary", 6.2, 6.6), ("engine.admit", 6.6, 6.7),
          ("engine.inputs", 6.7, 7.0), ("engine.decode", 7.0, 7.2),
          ("engine.sample", 7.2, 7.3), ("engine.sync", 7.3, 9.5),
          ("engine.retire", 9.5, 9.9)]
BUSY = [(0.7, 1.6), (2.5, 4.9), (7.1, 9.4)]

# idle pieces by innermost span, worked out by hand: gaps (0, 0.7),
# (1.6, 2.5), (4.9, 7.1), (9.4, 20)
CUT = {"engine.step": 0.5, "engine.deliver": 0.3, "engine.admit": 0.3,
       "engine.prefill": 0.2, "engine.chunk": 0.1, "engine.commit": 0.2,
       "engine.write_slot": 0.1, "engine.inputs": 0.7,
       "engine.decode": 0.2, "engine.sync": 0.2, "engine.retire": 1.2,
       "engine.boundary": 0.4, tr.NO_SPAN: 10.0}


def reduced(spans):
    return tr.Reduced(window=(0.0, 20.0), offset=0.0, busy=BUSY,
                      busy_s=sum(e - s for s, e in BUSY), ops={},
                      modules=[], spans=[("bench.window", 0.0, 20.0)]
                      + spans, n_devices=1)


def adapter_only(spans):
    """The five spans the benchmark's adapter opens around the engine."""
    keep = ("engine.step", "engine.decode", "engine.chunk",
            "engine.write_slot", "engine.sample")
    return [sp for sp in spans if sp[0] in keep]


def test_gaps_are_cut_at_span_boundaries():
    red = reduced(STEP_A + STEP_B)
    cut = span_idle.idle_by_innermost(red)
    assert cut == pytest.approx(CUT)
    assert sum(cut.values()) == pytest.approx(red.window_s - red.busy_s)
    assert span_idle.steps(red) == 2 and span_idle.program_spans(red)


def test_a_gap_over_two_spans_is_split_between_them():
    # 1 s of idle: 0.8 s in the sync's tail, 0.2 s in the retire loop;
    # the midpoint rule gives all of it to the sync
    spans = [("engine.step", 0.0, 4.0), ("engine.sync", 0.0, 2.8),
             ("engine.retire", 2.8, 4.0)]
    red = tr.Reduced(window=(0.0, 4.0), offset=0.0,
                     busy=[(0.0, 2.0), (3.0, 4.0)], busy_s=3.0, ops={},
                     modules=[], spans=spans, n_devices=1)
    assert span_idle.idle_by_innermost(red) == pytest.approx(
        {"engine.sync": 0.8, "engine.retire": 0.2})
    assert red.idle_by_span() == pytest.approx({"engine.sync": 1.0})


def test_step_contents():
    red = reduced(STEP_A + STEP_B)
    (da, held_a), (db, held_b) = span_idle.step_contents(red)
    assert (da, db) == pytest.approx((6.0, 4.0))
    assert "engine.chunk" in held_a and "engine.chunk" not in held_b
    assert "engine.boundary" in held_b and "engine.decode" in held_b


@pytest.mark.parametrize("name, value", [
    ("idle_sync_ms_per_step", 1e3 * (0.7 + 0.2) / 2),
    ("idle_sched_ms_per_step",
     1e3 * (0.5 + 0.3 + 0.3 + 0.2 + 0.2 + 1.2 + 0.4) / 2),
    ("boundary_ms_per_step", 1e3 * 0.4 / 2),
    ("chunk_stall_ms", 1e3 * (6.0 - 4.0)),
])
def test_readers(name, value):
    read = bench.load_reader(name)
    assert read(SimpleNamespace(trace=reduced(STEP_A + STEP_B))) \
        == pytest.approx(value)
    assert read(SimpleNamespace(trace=None)) is None


def test_sync_and_sched_with_model_calls_make_the_step_idle():
    red = reduced(STEP_A + STEP_B)
    run = SimpleNamespace(trace=red)
    parts = sum(bench.load_reader(n)(run) for n in (
        "idle_sync_ms_per_step", "idle_sched_ms_per_step"))
    calls = sum(CUT[n] for n in span_idle.MODEL_CALLS if n in CUT)
    in_steps = sum(v for n, v in CUT.items() if n != tr.NO_SPAN)
    assert parts + 1e3 * calls / 2 == pytest.approx(1e3 * in_steps / 2)


@pytest.mark.parametrize("name", ["idle_sync_ms_per_step",
                                  "idle_sched_ms_per_step",
                                  "boundary_ms_per_step"])
def test_engine_span_readers_need_the_engines_own_spans(name):
    # a trace of an engine without spans of its own: the adapter's five
    red = reduced(adapter_only(STEP_A + STEP_B))
    assert not span_idle.program_spans(red)
    assert bench.load_reader(name)(SimpleNamespace(trace=red)) is None


def test_chunk_stall_reads_the_adapters_spans():
    red = reduced(adapter_only(STEP_A + STEP_B))
    read = bench.load_reader("chunk_stall_ms")
    assert read(SimpleNamespace(trace=red)) == pytest.approx(2000.0)
    # no step without a chunk: nothing to compare
    assert read(SimpleNamespace(trace=reduced(STEP_A))) is None
