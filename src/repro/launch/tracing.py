"""Host spans and a compile counter, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: it records
only while a profiler runs (costing under a microsecond otherwise), into
the same trace as the device's planes, so each idle gap of the device can
be put down to the span the host was in.  Arguments land as the event's
stats; a value holding a comma is cut there by the profiler's encoding, so
lists go space-separated (``ids``).

``CompileCounter`` counts XLA backend compiles and their seconds while it
is open: a program taken from the in-memory or the persistent compile cache
is not a compile and does not count.
"""
from __future__ import annotations

import collections

import jax

# the duration event ``jax._src.dispatch`` records around each backend
# compile
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, **args):
    return jax.profiler.TraceAnnotation(name, **args)


def ids(xs) -> str:
    """A span argument naming several integers (request ids)."""
    return " ".join(str(int(x)) for x in xs)


class CompileCounter:
    """``with CompileCounter() as cc: ...`` counts the backend compiles
    inside the block: ``cc.n``, ``cc.seconds`` and ``cc.programs`` (compiles
    by the jitted function's name)."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.programs: collections.Counter = collections.Counter()

    def _listen(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1
            self.seconds += duration
            self.programs[kw.get("fun_name", "?")] += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)
