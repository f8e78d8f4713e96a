"""The check has teeth: the whole run at a reduced size on the CPU, with the
timed path broken underneath, comes out not correct; the sound run and the
fp8 control read as they must against the cell's limit.

The run skips only the harness's look for a chip (``rehearse``): the same
traffic generator, engine, window, serve-out and reference as on the chip.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import bench  # noqa: E402
import traffic  # noqa: E402

bench.add_paths()

SEED = 2_147_483_659          # past 31 bits
CELL = "qwen1.5-0.5b.chat"


def _run(fault=None, control=False):
    return bench.run_cell(CELL, SEED, 3.0, False, rehearse=True,
                          fault=fault, control=control, log=lambda *a: None)


@pytest.mark.parametrize("fault", [None, "stale_state", "half_batch",
                                   "altered_token"])
def test_broken_path_is_not_correct(fault, monkeypatch):
    # under the closed-loop mix every slot is busy, so half the batch is
    # half the requests (at a reduced size the chat mix leaves the upper
    # slots mostly empty)
    load = traffic.load_mix
    monkeypatch.setattr(traffic, "load_mix",
                        lambda name: load("decode_heavy"))
    res = _run(fault)
    gap = res["checks"]["logit_gap_max"]
    assert res["info"]["check"]["sampled_tokens"] > 0
    assert res["correct"] is (fault is None), (fault, gap)


def test_control_fails_the_limit():
    res = _run(control=True)
    chk = res["info"]["check"]
    limit = res["checks"]["logit_gap_max"]["limit"]
    assert chk["logit_gap_max"] <= limit < chk["control_gap_max"], chk


def test_stale_state_under_a_donated_decode(monkeypatch):
    """A decode that donates its state (``donate_argnums=(3,)``) deletes
    the state it was given: the stale-state fault still reads not correct,
    and returns no deleted buffer to the engine."""
    import jax
    import engine_adapter
    from repro.models import transformer as tfm
    load = traffic.load_mix
    monkeypatch.setattr(traffic, "load_mix",
                        lambda name: load("decode_heavy"))
    donated = []
    break_path = engine_adapter.EngineAdapter.break_path

    def donating_then_break(self, fault):
        cfg = self.eng.cfg
        step = jax.jit(lambda p, t, pos, st: tfm.decode_step(p, cfg, t, pos,
                                                             st),
                       donate_argnums=(3,))

        def decode(p, t, pos, st):
            out = step(p, t, pos, st)
            donated.append(all(x.is_deleted() for x in jax.tree.leaves(st)))
            return out
        self.eng._decode = decode
        break_path(self, fault)

    monkeypatch.setattr(engine_adapter.EngineAdapter, "break_path",
                        donating_then_break)
    res = _run("stale_state")
    assert donated and all(donated)
    assert res["info"]["check"]["sampled_tokens"] > 0
    assert res["correct"] is False, res["checks"]["logit_gap_max"]
