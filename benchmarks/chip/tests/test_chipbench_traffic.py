"""The traffic generator: a seed fixes the schedule, every seed offers the
same sizes and gaps in another order, and no request reaches the engine
before it falls due."""

import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from traffic import (BLOCK, Dispatcher, build_schedule,  # noqa: E402
                     load_mix)

SEED = 3_000_000_019          # past 32 signed bits


def _sizes(s):
    return Counter((r.prompt_len, r.max_new) for r in s.requests)


def test_same_seed_same_schedule():
    mix = load_mix("chat")
    a = build_schedule(mix, SEED, 30.0, 1000, 16)
    b = build_schedule(mix, SEED, 30.0, 1000, 16)
    assert [(r.due, r.prompt_len, r.max_new) for r in a.requests] == \
        [(r.due, r.prompt_len, r.max_new) for r in b.requests]
    assert all((x.tokens == y.tokens).all()
               for x, y in zip(a.requests, b.requests))


def test_seeds_change_order_not_work():
    mix = load_mix("chat")
    a = build_schedule(mix, SEED, 30.0, 1000, 16)
    b = build_schedule(mix, SEED + 1, 30.0, 1000, 16)
    assert _sizes(a) == _sizes(b)
    assert [r.prompt_len for r in a.requests] != \
        [r.prompt_len for r in b.requests]
    for start, part in ((-a.preroll_s, lambda r: r.due < 0),
                        (0.0, lambda r: r.due >= 0)):
        da = sorted(r.due for r in a.requests if part(r))
        db = sorted(r.due for r in b.requests if part(r))
        ga, gb = np.diff([start] + da), np.diff([start] + db)
        assert len(da) == len(db)
        np.testing.assert_allclose(np.sort(ga), np.sort(gb), atol=1e-9)
    in_window = [r for r in a.requests if 0 <= r.due < 30.0]
    assert len(in_window) == round(mix["rate_rps"] * 30.0)
    # each block of arrivals holds the same sizes at the same time
    k = BLOCK
    for i in range(0, len(a.requests) - k + 1, k):
        blk_a, blk_b = a.requests[i:i + k], b.requests[i:i + k]
        assert _sizes(type(a)(a.mode, blk_a, 0, 0)) == \
            _sizes(type(b)(b.mode, blk_b, 0, 0))
        assert max(r.due for r in blk_a) == pytest.approx(
            max(r.due for r in blk_b))


def test_lengths_respect_bounds():
    for name in ("chat", "longprompt", "decode_heavy"):
        mix = load_mix(name)
        s = build_schedule(mix, SEED, 30.0, 1000, 8)
        for r in s.requests:
            assert mix["prompt"]["min"] <= r.prompt_len <= mix["prompt"]["max"]
            assert r.max_new <= mix["output"]["max"]
            assert len(r.tokens) == r.prompt_len
            assert r.tokens.max() < 1000


def test_no_request_before_due():
    s = build_schedule(load_mix("chat"), SEED, 10.0, 1000, 16)
    t0 = 100.0
    d = Dispatcher(s, t0)
    handed = []
    for now in np.arange(t0 - 10.0, t0 + 12.0, 0.01):
        for r in d.due(float(now)):
            assert t0 + r.due <= now
            handed.append((r, now))
    assert len(handed) == len(s.requests)
    # each one handed out within the tick after it fell due
    assert all(now - (t0 + r.due) < 0.0101 for r, now in handed)


def test_closed_loop_seed_draws_prompts_only():
    mix = load_mix("decode_heavy")
    a = build_schedule(mix, SEED, 30.0, 1000, 4)
    b = build_schedule(mix, SEED + 1, 30.0, 1000, 4)
    assert [(r.client, r.seq, r.prompt_len, r.max_new) for r in a.requests] \
        == [(r.client, r.seq, r.prompt_len, r.max_new) for r in b.requests]
    assert any((x.tokens != y.tokens).any()
               for x, y in zip(a.requests, b.requests))


def test_closed_loop_next_request_due_at_finish():
    s = build_schedule(load_mix("decode_heavy"), SEED, 30.0, 1000, 4)
    assert len({r.client for r in s.requests}) == 4
    t0 = 50.0
    d = Dispatcher(s, t0)
    first = d.due(t0 - s.preroll_s)
    assert len(first) == 4 and all(r.seq == 0 for r in first)
    assert d.due(t0 + 1000.0) == []          # nothing more until a finish
    d.finished(first[0], t0 + 3.0)
    assert d.due(t0 + 2.999) == []
    [nxt] = d.due(t0 + 3.0)
    assert nxt.client == first[0].client and nxt.seq == 1
    # the first requests are cut so their finishes spread out
    lens = sorted(r.max_new for r in first)
    assert lens[0] < lens[-1]
