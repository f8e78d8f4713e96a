"""Spans of the continuous engine's step (``serving.spans``).

A recording span factory drives a reduced engine through joins, chunks,
commits and decode steps: the names, their nesting under ``engine.step``,
the request id on the spans of one request, and the extents of the four
spans that wrap one model call each.  With spans off the factory is never
called and the served tokens and model calls are those of a spans-on run.
"""

import contextlib

import numpy as np
import pytest

import jax

from repro.configs import get_config, reduced_config
from repro.models import init_params
from repro.serving import ContinuousServeEngine, Request
from repro.serving import spans as spans_mod

LENS = (5, 13, 27, 3, 21)

# span name -> the engine callable whose every call it wraps, alone
CALL_SPANS = {"engine.decode": "_decode", "engine.chunk": "_chunk",
              "engine.sample": "_sample", "engine.write_slot": "_write_slot"}

STEP_CHILDREN = {"engine.deliver", "engine.boundary", "engine.admit",
                 "engine.prefill", "engine.inputs", "engine.decode",
                 "engine.sample", "engine.sync", "engine.retire"}


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config(get_config("qwen1.5-0.5b"), d_model=128,
                         n_layers=2, d_ff=576)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


class Recorder:
    """A span factory that logs each span's entry and exit, and wraps the
    engine's model calls so that their calls land in the same log."""

    def __init__(self):
        self.log = []
        self.made = 0

    def __call__(self, name, rid=None):
        self.made += 1
        return self._span(name, rid)

    @contextlib.contextmanager
    def _span(self, name, rid):
        self.log.append(("enter", name, rid))
        try:
            yield
        finally:
            self.log.append(("exit", name, rid))

    def tap(self, eng):
        for attr in CALL_SPANS.values():
            fn = getattr(eng, attr)

            def called(*a, _fn=fn, _attr=attr):
                self.log.append(("call", _attr, None))
                out = _fn(*a)
                self.log.append(("return", _attr, None))
                return out
            setattr(eng, attr, called)


def engine(cfg, params, spans, chunk=4):
    return ContinuousServeEngine(params, cfg, max_len=64, batch_slots=2,
                                 prefill_chunk=chunk, step_token_budget=8,
                                 boundary_every=3, spans=spans)


def serve(cfg, eng, *, tap=None):
    if tap is not None:
        tap.tap(eng)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, size=(n,))
                    .astype(np.int32), max_new_tokens=6) for n in LENS]
    results = eng.run(reqs)
    return eng, [np.asarray(r.tokens) for r in results]


def tree(log):
    """(name, rid, parent name, ancestors) of every span, in order."""
    out, stack = [], []
    for kind, name, rid in log:
        if kind == "enter":
            out.append((name, rid, stack[-1] if stack else None,
                        tuple(stack)))
            stack.append(name)
        elif kind == "exit":
            assert stack and stack[-1] == name, (name, stack)
            stack.pop()
    assert not stack
    return out


@pytest.fixture(scope="module")
def chunked(model):
    cfg, params = model
    rec = Recorder()
    eng, toks = serve(cfg, engine(cfg, params, rec), tap=rec)
    return rec, eng, toks


def test_every_span_nests_under_a_step(chunked):
    rec, eng, _ = chunked
    spans = tree(rec.log)
    assert sum(n == "engine.step" for n, *_ in spans) == eng.steps
    for name, _, parent, anc in spans:
        if name == "engine.step":
            assert parent is None
        else:
            assert "engine.step" in anc, name
        if name in STEP_CHILDREN:
            assert parent == "engine.step", (name, parent)
    assert {n for n, *_ in spans} == STEP_CHILDREN | {
        "engine.step", "engine.chunk", "engine.commit",
        "engine.write_slot"}


def test_request_spans_sit_in_prefill_with_their_rid(chunked):
    rec, eng, _ = chunked
    spans = tree(rec.log)
    chunks = [(rid, p) for n, rid, p, _ in spans if n == "engine.chunk"]
    commits = [(rid, p) for n, rid, p, _ in spans if n == "engine.commit"]
    assert len(chunks) == eng.chunk_steps == sum(-(-n // 4) for n in LENS)
    assert sorted(rid for rid, _ in commits) == list(range(len(LENS)))
    assert {p for _, p in chunks + commits} == {"engine.prefill"}
    # each commit writes its slot, inside the commit
    slot_writes = [p for n, _, p, _ in spans if n == "engine.write_slot"]
    assert slot_writes == ["engine.commit"] * len(LENS)


@pytest.mark.parametrize("name", sorted(CALL_SPANS))
def test_model_call_spans_keep_the_call_extent(chunked, name):
    """Each of these spans holds exactly one call of its callable and
    nothing else: its entry and exit are next to the call's start and
    return, whether the span opens at the call site or in the body."""
    rec, _, _ = chunked
    log, attr = rec.log, CALL_SPANS[name]
    enters = [k for k, e in enumerate(log) if e[:2] == ("enter", name)]
    calls = [k for k, e in enumerate(log) if e[:2] == ("call", attr)]
    assert enters and len(enters) == len(calls)
    for k in enters:
        j = next(j for j in range(k, len(log)) if log[j][:2] == ("exit",
                                                                  name))
        inner = log[k + 1:j]
        if inner and inner[0][:2] == ("call", attr):
            assert inner == [("call", attr, None), ("return", attr, None)]
        else:                       # opened in the callable's own body
            assert log[k - 1][:2] == ("call", attr)
            assert log[j + 1][:2] == ("return", attr)
            assert all(e[0] in ("enter", "exit") for e in inner)


def test_whole_prompt_join_spans_sit_in_admit(model):
    cfg, params = model
    rec = Recorder()
    serve(cfg, engine(cfg, params, rec, chunk=None))
    spans = tree(rec.log)
    parents = {(n, p) for n, _, p, _ in spans
               if n in ("engine.prefill", "engine.write_slot")}
    assert parents == {("engine.prefill", "engine.admit"),
                       ("engine.write_slot", "engine.admit")}


def test_spans_off_calls_nothing_and_serves_the_same(model, chunked,
                                                     monkeypatch):
    cfg, params = model
    rec_on, _, toks_on = chunked
    made = []

    class Counting:
        def __init__(self, name, **meta):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    rec = Recorder()
    eng = engine(cfg, params, rec)
    eng.spans = False           # the caller switches the factory off
    calls = Recorder()
    _, toks_off = serve(cfg, eng, tap=calls)
    assert rec.made == 0 and calls.made == 0 and made == []
    for a, b in zip(toks_on, toks_off):
        assert np.array_equal(a, b)
    # the same model calls, in the same order, as the spans-on run
    on = [e for e in rec_on.log if e[0] in ("call", "return")]
    assert calls.log == on
    # on, the engine's spans are profiler annotations, and serve the same
    _, toks_true = serve(cfg, engine(cfg, params, True))
    assert "engine.step" in made and "engine.chunk" in made
    for a, b in zip(toks_on, toks_true):
        assert np.array_equal(a, b)


def test_span_helper():
    assert spans_mod.span(False, "engine.step") is spans_mod.NOOP
    assert spans_mod.span(None, "engine.chunk", 3) is spans_mod.NOOP
    seen = []
    spans_mod.span(lambda n, **m: seen.append((n, m)), "engine.chunk", 3)
    spans_mod.span(lambda n, **m: seen.append((n, m)), "engine.step")
    assert seen == [("engine.chunk", {"rid": 3}), ("engine.step", {})]
