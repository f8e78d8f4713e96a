"""The one place that knows the serving engine's private fields.

``ContinuousServeEngine`` has no public hook for per-token times, seat
times or the active plan's widths.  Everything the benchmark reads or taps
inside it goes through this class, so a change to the engine's internals
needs one edit here and nowhere else.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Call:
    """One model call the engine made: a decode step or a prefill chunk."""

    kind: str                   # "decode" | "chunk"
    start: float                # host clock at dispatch
    end: float
    contexts: list              # keys attended by each real token
    logit_rows: int             # rows whose next token is read
    heads: tuple                # query heads per layer, active plan
    ffn: tuple                  # FFN channels per layer, active plan


@dataclasses.dataclass
class Progress:
    """What the engine holds for one request after a step."""

    tokens: int
    seated_t: Optional[float]   # engine clock when it took a slot
    done: bool                  # terminal (finished, shed or failed)
    ok: bool                    # finished, not shed or failed


class EngineAdapter:
    def __init__(self, engine, clock: Callable[[], float]):
        self.eng = engine
        self.clock = clock
        self.calls: List[Call] = []
        self.waiting_log: List[tuple] = []
        self._annotate = None

    # -- requests ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int,
               arrival_t: float) -> int:
        from repro.serving import Request
        return self.eng.submit(Request(prompt=prompt, max_new_tokens=max_new),
                               arrival_t=arrival_t)

    def outstanding(self) -> bool:
        return self.eng._outstanding()

    def waiting(self) -> int:
        """Requests handed to the engine and not yet seated in a slot."""
        e = self.eng
        return len(e._pending) + len(e._queue) + len(e._retry)

    def progress(self, rids) -> Dict[int, Progress]:
        """Tokens held, seat time and state of each of ``rids``."""
        seated = {tr.rid: tr for tr in self.eng._slots if tr is not None}
        out = {}
        for rid in rids:
            tr = seated.get(rid)
            if tr is not None:
                out[rid] = Progress(len(tr.generated), tr.join_t, False,
                                    False)
                continue
            res = self.eng._results.get(rid)
            if res is not None:
                out[rid] = Progress(len(res.tokens), None, True,
                                    not (res.shed or res.failed))
        return out

    def served_tokens(self, rid: int) -> np.ndarray:
        return np.asarray(self.eng._results[rid].tokens, np.int32)

    # -- the width plan ---------------------------------------------------
    def widths(self) -> tuple:
        """(query heads per layer, FFN channels per layer) of the plan the
        engine serves with now."""
        key = self.eng._key_active
        cfg = self.eng.cfg
        n = len(self.eng._full_heads)
        if key is None or self.eng._masked_active:
            mlp = (cfg.d_ff,) * n
            heads = tuple(int(h) for h in self.eng._heads_active)
            return heads, mlp
        mlp, heads = key
        return tuple(int(h) for h in heads), tuple(int(f) for f in mlp)

    def boundaries(self) -> list:
        return list(self.eng.boundary_log)

    def plan_is_full(self, plans) -> bool:
        sw = self.eng.swapper
        for p in plans:
            mlp, heads = sw.realize_plan(p)
            if (mlp != self.eng.cfg.d_ff).any() \
                    or (heads != self.eng.cfg.n_heads).any():
                return False
        return True

    # -- taps ---------------------------------------------------------------
    def tap(self, spans: bool) -> None:
        """Record every decode and chunk call with its algorithmic work,
        and, with ``spans``, wrap the step and its parts in profiler spans
        so the trace shows what the host did."""
        import jax
        eng = self.eng
        ann = jax.profiler.TraceAnnotation if spans else None
        self._annotate = ann

        def wrap(name, fn, record=None):
            def tapped(*a):
                t0 = self.clock()
                if ann is not None:
                    with ann(name):
                        out = fn(*a)
                else:
                    out = fn(*a)
                if record is not None:
                    record(t0, self.clock(), a)
                return out
            return tapped

        def rec_decode(t0, t1, a):
            heads, ffn = self.widths()
            ctx = [int(eng.pos[i]) + 1 for i, tr in enumerate(eng._slots)
                   if tr is not None and tr.chunk_state is None]
            self.calls.append(Call("decode", t0, t1, ctx, len(ctx), heads,
                                   ffn))

        def rec_chunk(t0, t1, a):
            # runs before the engine advances the request past this chunk
            st = a[3]
            tr = next((tr for tr in eng._slots
                       if tr is not None and tr.chunk_state is st), None)
            if tr is None:
                return
            heads, ffn = self.widths()
            target = len(tr.request.prompt) + len(tr.generated)
            off = int(tr.prefill_done)
            clen = min(eng.prefill_chunk, target - off)
            self.calls.append(Call(
                "chunk", t0, t1, list(range(off + 1, off + clen + 1)),
                1 if off + clen >= target else 0, heads, ffn))

        eng._decode = wrap("engine.decode", eng._decode, rec_decode)
        eng._chunk = wrap("engine.chunk", eng._chunk, rec_chunk)
        if spans:
            eng._write_slot = wrap("engine.write_slot", eng._write_slot)
            eng._sample = wrap("engine.sample", eng._sample)

    def step(self) -> bool:
        if self._annotate is not None:
            with self._annotate("engine.step"):
                return self.eng.step()
        return self.eng.step()

    # -- faults, for the benchmark's own tests --------------------------------
    def break_path(self, fault: str) -> None:
        """Break the timed path underneath the benchmark, as a test of its
        correctness check: ``stale_state`` (the decode step returns the
        cache it was given: a copy taken before the call, which outlives
        a decode that donates its state), ``half_batch`` (the upper half
        of the slots gets the lower half's logits), ``altered_token``
        (each sampled token is changed where it is produced)."""
        import jax
        import jax.numpy as jnp
        eng = self.eng
        if fault == "stale_state":
            dec = eng._decode

            def stale(p, t, pos, st):
                before = jax.tree.map(jnp.copy, st)
                logits, _ = dec(p, t, pos, st)
                return logits, before
            eng._decode = stale
        elif fault == "half_batch":
            dec = eng._decode

            def half(p, t, pos, st):
                logits, st2 = dec(p, t, pos, st)
                h = (logits.shape[0] + 1) // 2
                return jnp.concatenate(
                    [logits[:h], logits[:logits.shape[0] - h]]), st2
            eng._decode = half
        elif fault == "altered_token":
            sample = eng._sample
            v = eng.cfg.vocab_size

            def altered(logits, active):
                return (sample(logits, active) + 1) % v
            eng._sample = altered
        else:
            raise ValueError(f"unknown fault {fault!r}")
