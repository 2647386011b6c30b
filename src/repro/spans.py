"""Program spans: named host intervals on the device trace's clock.

``with span("repro/solve") as s: ...`` opens a `jax.profiler.TraceAnnotation`
on the calling thread, so that a profiler session (``jax.profiler.trace``)
records the interval on its host plane, on the same clock as the device's
ops, and measures the interval's host seconds, readable as ``s.seconds``
once the block has exited.  Keyword arguments become the event's stats
(``span("repro/fit", fit=7)``).  A span's parent is the span that encloses
it on the same thread; nothing else links them.

The span neither blocks nor syncs: a span whose body dispatches device work
without waiting for it bounds the dispatch, not the work.  Spans are kept by
the profiler session, which writes them out when it stops; with no session
running a span costs one annotation object and two clock reads.  Under a JAX
trace (inside ``jax.jit``, ``shard_map``, ``vmap``, ...) the annotation is
left out, since it would mark the time of tracing, not of running.
"""

from __future__ import annotations

import time

import jax


class span:
    """Context manager: one named span; ``.seconds`` after exit."""

    __slots__ = ("name", "stats", "seconds", "_ann", "_t0")

    def __init__(self, name: str, **stats):
        self.name = name
        self.stats = stats
        self.seconds: float | None = None
        self._ann = None

    def __enter__(self) -> "span":
        if jax.core.trace_ctx.is_top_level():
            self._ann = jax.profiler.TraceAnnotation(self.name, **self.stats)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
