"""Traffic generator: one general reader of the mix files in ``traffic/``.

A mix file is JSON.  Its keys:

    mode          "open" (independent users on a Poisson schedule) or
                  "closed" (one client per engine slot; each sends its next
                  request the moment its last one finishes)
    rate_rps      open loop: offered requests per second
    preroll_s     seconds of traffic before the window opens (set-up), so
                  the window starts in steady state
    prompt, output
                  length distributions: {"dist": "lognormal", "median": m,
                  "sigma": s, "min": a, "max": b} or {"dist": "uniform",
                  "min": a, "max": b} (bounds inclusive)
    clients_per_slot, requests_per_client
                  closed loop: clients per engine slot, and the length of
                  each client's request list
    sizes_seed    the fixed seed of the size pool

What a run's ``--seed`` changes is the order, never the work.  The pool of
(prompt length, output length) pairs and the set of inter-arrival gaps are
drawn from ``sizes_seed`` alone; the run seed draws the token ids and, in
the open loop, permutes the gaps and the pairs within each block of
``BLOCK`` consecutive arrivals.  So every seed offers the same sizes and
the same arrivals, each block of them at the same time in another order:
the set of shapes the engine meets is the same from run to run, the load
over each few seconds of the window is too, and runs with different seeds
measure the same work.  A closed-loop client keeps its list of sizes; the
seed draws its prompts.

The open-loop schedule is a Poisson process conditioned on its count: the
preroll and the window each hold ``round(rate * length)`` arrivals at
uniform order statistics.  Requests are handed to the engine only when
they fall due (see ``due_requests``); the engine never sees a future
arrival.  Adapted from ``repro.serving.chaos.open_loop_arrivals``, extended
to drawn lengths, a closed-loop mode and due-time submission.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
BLOCK = 4


@dataclasses.dataclass
class Planned:
    """One request of a schedule.  ``due`` is seconds from window start
    (negative in the preroll); closed-loop requests after a client's first
    have ``due=None`` until their predecessor finishes."""

    idx: int
    prompt_len: int
    max_new: int
    due: Optional[float]
    client: int = -1
    seq: int = 0                     # position in the client's list
    tokens: Optional[np.ndarray] = None


@dataclasses.dataclass
class Schedule:
    mode: str
    requests: List[Planned]
    preroll_s: float
    seconds: float

    def prompt_lengths(self) -> List[int]:
        return sorted({r.prompt_len for r in self.requests})


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    if mix.get("mode") not in ("open", "closed"):
        raise ValueError(f"{path}: mode must be 'open' or 'closed'")
    return mix


def _draw_lengths(rng, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, size=n)
    elif spec["dist"] == "lognormal":
        x = np.rint(rng.lognormal(np.log(spec["median"]), spec["sigma"],
                                  size=n))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def _arrivals(rng, rate: float, start: float, length: float) -> np.ndarray:
    """Sorted arrival times of a Poisson process on [start, start+length)
    conditioned on holding round(rate * length) arrivals."""
    n = int(round(rate * length))
    return start + np.sort(rng.uniform(0.0, length, size=n))


def _block_permutation(n: int, rng) -> np.ndarray:
    """A permutation of range(n) that moves items only within consecutive
    blocks of ``BLOCK``."""
    return np.concatenate([b + rng.permutation(min(BLOCK, n - b))
                           for b in range(0, n, BLOCK)]).astype(np.int64) \
        if n else np.zeros(0, np.int64)


def _gaps_permuted(times: np.ndarray, start: float, perm_rng) -> np.ndarray:
    """The same inter-arrival gaps, each block of them in another order:
    the arrival that ends each block, and the last, do not move."""
    gaps = np.diff(np.concatenate([[start], times]))
    return start + np.cumsum(gaps[_block_permutation(len(gaps), perm_rng)])


def build_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                   slots: int, *, scale_len: float = 1.0) -> Schedule:
    """The run's schedule.  ``scale_len`` shrinks every length (a CPU
    rehearsal at a reduced size); the chip runs use 1."""
    sizes = np.random.default_rng(int(mix["sizes_seed"]))
    run = np.random.default_rng(int(seed))
    pre = float(mix.get("preroll_s", 0.0))

    def lens(spec, n):
        x = _draw_lengths(sizes, spec, n)
        if scale_len != 1.0:
            x = np.maximum(np.rint(x * scale_len), 1).astype(np.int64)
        return x

    out: List[Planned] = []
    if mix["mode"] == "open":
        rate = float(mix["rate_rps"])
        parts = [(-pre, pre), (0.0, float(seconds))]
        for start, length in parts:
            if length <= 0:
                continue
            times = _arrivals(sizes, rate, start, length)
            n = len(times)
            p, o = lens(mix["prompt"], n), lens(mix["output"], n)
            times = _gaps_permuted(times, start, run)
            order = _block_permutation(n, run)
            for t, j in zip(times, order):
                out.append(Planned(idx=len(out), prompt_len=int(p[j]),
                                   max_new=int(o[j]), due=float(t)))
    else:
        clients = int(mix["clients_per_slot"]) * int(slots)
        per = int(mix["requests_per_client"])
        n = clients * per
        p, o = lens(mix["prompt"], n), lens(mix["output"], n)
        for c in range(clients):
            for s in range(per):
                j = c * per + s
                new = int(o[j])
                if s == 0:
                    # stagger: client c's first request is cut to
                    # (c + 1) / clients of its length, so the first
                    # finishes spread over the preroll instead of landing
                    # together
                    new = max(1, int(round(new * (c + 1) / clients)))
                out.append(Planned(
                    idx=len(out), prompt_len=int(p[j]), max_new=new,
                    due=-pre if s == 0 else None, client=c, seq=s))
    tok = np.random.default_rng([int(seed), 1])
    for r in out:
        r.tokens = tok.integers(0, vocab, size=r.prompt_len).astype(np.int32)
    return Schedule(mode=mix["mode"], requests=out, preroll_s=pre,
                    seconds=float(seconds))


def warmup_prompts(lengths, vocab: int, seed: int) -> list:
    """One prompt of each of ``lengths`` (the distinct prompt lengths of
    the run's schedule), with token ids from a stream apart from the
    run's: serving them once before the window compiles every shape the
    window will meet."""
    rng = np.random.default_rng([int(seed), 2])
    return [rng.integers(0, vocab, size=int(n)).astype(np.int32)
            for n in lengths]


class Dispatcher:
    """Hands requests to the server when they fall due.  Open loop: by
    schedule.  Closed loop: a client's next request falls due when its
    last one finishes (``finished``)."""

    def __init__(self, schedule: Schedule, t0: float):
        self.t0 = t0
        self.schedule = schedule
        self._ready = sorted((r for r in schedule.requests
                              if r.due is not None),
                             key=lambda r: (r.due, r.idx))
        self._pos = 0
        self._next = {}
        for r in schedule.requests:
            if r.client >= 0 and r.seq > 0:
                self._next[(r.client, r.seq - 1)] = r

    def due(self, now: float) -> List[Planned]:
        """Every request due at host time ``now`` not yet handed out."""
        out = []
        while self._pos < len(self._ready):
            r = self._ready[self._pos]
            if self.t0 + r.due > now:
                break
            out.append(r)
            self._pos += 1
        return out

    def next_due(self) -> Optional[float]:
        if self._pos < len(self._ready):
            return self.t0 + self._ready[self._pos].due
        return None

    def finished(self, r: Planned, t_host: float) -> None:
        """Closed loop: ``r`` finished at ``t_host``; its client's next
        request falls due then."""
        nxt = self._next.get((r.client, r.seq))
        if nxt is None:
            return
        nxt.due = t_host - self.t0
        # keep the ready list ordered: insert after the handed-out prefix
        tail = self._ready[self._pos:]
        tail.append(nxt)
        tail.sort(key=lambda q: (q.due, q.idx))
        self._ready[self._pos:] = tail
