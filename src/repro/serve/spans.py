"""Host spans of the annealing service: named, timed stretches of host work.

``with service.spans("stack"):`` does two things, always:

* it opens ``jax.profiler.TraceAnnotation("repro.stack")`` — a no-op unless a
  profiler is running, in which case the span lands in the trace beside the
  device ops, on the same clock.  Keyword metadata (``spans("solve",
  solve=3)``) rides as event stats; the event name stays the bare
  ``repro.<name>``;
* it adds the span's duration in ns to ``stats["span_ns.<name>"]`` and 1 to
  ``stats["span_n.<name>"]`` of the service's one ``stats`` counter, which the
  streaming service shares.

A span costs a few µs; spans open per request, per group and per chunk,
never per lane or per trial.  The names and how they nest are listed in
``LEAVES`` and ``PARENTS``: leaves never overlap one another within one path
(one-shot or stream), so their sums add up.  ``compile`` is neither: it
opens inside whichever leaf makes a program's first call (``init``,
``chunk.launch``, ``quantum.seat`` or ``quantum.launch``).
"""
from __future__ import annotations

import threading
import time
from typing import Iterable

from jax.profiler import TraceAnnotation

PREFIX = "repro."

# One-shot path (AnnealService.solve).
LEAVES = ("normalize", "admit", "autotune", "weight_bits", "program", "stack",
          "init", "chunk.launch", "chunk.sync", "chunk.book", "chunk.snap",
          "finalize", "decode")
# Streaming path (StreamingAnnealService.pump): one scheduling quantum.
QUANTUM = ("quantum.launch", "quantum.sync", "quantum.retire", "quantum.seat")
PARENTS = ("solve", "group", "chunk", "quantum")


class Spans:
    """Span factory bound to one ``stats`` counter.

    Concurrent ``solve()`` calls and the streaming scheduler thread close
    spans at once, so the counter updates are made under a lock.
    """

    __slots__ = ("stats", "lock")

    def __init__(self, stats):
        self.stats = stats
        self.lock = threading.Lock()

    def __call__(self, name: str, **meta) -> "_Span":
        return _Span(self, name, meta)

    def ms_per(self, names: Iterable[str], count: int) -> dict:
        """Host ms of each span in ``names`` per ``count`` (0.0 when none)."""
        return {n: (self.stats["span_ns." + n] / 1e6 / count if count else 0.0)
                for n in names}


class _Span:
    __slots__ = ("_owner", "_name", "_ann", "_t0")

    def __init__(self, owner: Spans, name: str, meta: dict):
        self._owner = owner
        self._name = name
        self._ann = TraceAnnotation(PREFIX + name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        owner = self._owner
        with owner.lock:
            owner.stats["span_ns." + self._name] += dt
            owner.stats["span_n." + self._name] += 1
        return False
