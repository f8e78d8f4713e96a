"""Host spans of the serving engine, on the profiler's clock.

``span(on, name)`` is what the engine opens around each phase of a step.
``on`` is the engine's ``spans`` attribute:

* false (the default): one shared no-op context manager comes back; no
  clock is read, no string is built and JAX is not called, so an engine
  that is not being traced pays one function call per phase;
* true: ``jax.profiler.TraceAnnotation(name)``.  While a profiler trace is
  recording, the span lands in the host plane of the same ``.xplane.pb``
  as the device's operations, on the clock the profiler aligns with the
  device, so each idle gap on the device can be put down to the phase the
  host was in;
* any other callable: a factory called as ``on(name)`` or, for a span of
  one request, ``on(name, rid=rid)``, returning a context manager (tests
  record with it).

A span of one request (``engine.chunk``, ``engine.commit``) carries the
request's id as annotation metadata; the span's name stays as given.
"""

from __future__ import annotations

import contextlib
from typing import Optional

NOOP = contextlib.nullcontext()


def annotation(name: str, **meta):
    """A profiler span: ``jax.profiler.TraceAnnotation``."""
    import jax
    return jax.profiler.TraceAnnotation(name, **meta)


def span(on, name: str, rid: Optional[int] = None):
    """The context manager for one span (see the module docstring)."""
    if not on:
        return NOOP
    factory = annotation if on is True else on
    return factory(name) if rid is None else factory(name, rid=rid)
