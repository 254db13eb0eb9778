"""Host-side spans (`repro.telemetry.timing`).

One span API for every layer.  ``timed(name, **meta)`` does two things for
the same interval:

* it appends ``{"name", "ms", **meta}`` to a small in-memory buffer.
  ``repro.sweep.cache`` records program build / first-call (compile)
  times, ``repro.sweep.runners.run_bucketed`` per-bucket dispatch times,
  the sharded runners per-mesh dispatch times, and ``repro.api.run`` its
  phases (resolve, tau-bar, dispatch, record).  ``api.run`` drains the
  buffer and folds the events into the run's ``RunRecord`` (see
  ``.ledger``), which is how compile-ms vs warm-ms gets attributed
  without touching any jitted code;
* it opens ``jax.profiler.TraceAnnotation("repro." + name, **meta)``, so
  the span lands in a profiler trace on the device trace's clock, with
  ``meta`` as the event's stats and the event name left bare.

``span(name, **meta)`` only annotates: for loops that nothing drains,
such as the trainer's.  ``step_span(name, step, **meta)`` is the
``StepTraceAnnotation`` form, for one step of a training loop.  Outside a
profiler trace an annotation costs about a microsecond and records
nothing.  ``numbered_run()`` numbers ``api.run`` calls; ``run_number()``
is the number of the one in progress (0 outside any), so spans of inner
layers can carry it.

This module must stay a leaf (no repro imports) so every layer can use it
without cycles.  All spans are opened on the host, never inside jitted
code.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["record_timing", "drain_timings", "timed", "span", "step_span",
           "numbered_run", "run_number"]

_LOCK = threading.Lock()
_EVENTS: List[Dict[str, Any]] = []

# names api.run treats as compile-side when splitting elapsed time into
# compile-ms vs warm-ms (program construction + the first dispatch of a
# freshly built executable, where XLA compiles synchronously on CPU)
COMPILE_EVENT_NAMES = ("program_build", "program_first_call")

# every span of the program appears in a profiler trace under this prefix
SPAN_PREFIX = "repro."

_RUN_COUNT = itertools.count(1)
_RUN = contextvars.ContextVar("repro_run", default=0)


def record_timing(name: str, ms: float, **meta: Any) -> None:
    """Append one timing event: ``{"name", "ms", **meta}``."""
    ev = {"name": str(name), "ms": float(ms)}
    for k, v in meta.items():
        ev[k] = v
    with _LOCK:
        _EVENTS.append(ev)


def drain_timings() -> List[Dict[str, Any]]:
    """Return all buffered events and clear the buffer."""
    with _LOCK:
        out, _EVENTS[:] = list(_EVENTS), []
    return out


def span(name: str, **meta: Any) -> TraceAnnotation:
    """``with span("name", key=...):`` -- a profiler annotation
    ``repro.<name>`` with ``meta`` as its stats; nothing is buffered."""
    return TraceAnnotation(SPAN_PREFIX + name, **meta)


def step_span(name: str, step: int, **meta: Any) -> StepTraceAnnotation:
    """``with step_span("name", k, key=...):`` -- step ``k`` of a loop, as
    the profiler's step marker ``repro.<name>`` with ``step_num=k``."""
    return StepTraceAnnotation(SPAN_PREFIX + name, step_num=int(step), **meta)


class timed:
    """``with timed("name", key=...):`` context recording wall-clock ms
    into the buffer and annotating the profiler trace as ``repro.<name>``."""

    def __init__(self, name: str, **meta: Any):
        self.name, self.meta = name, meta

    def __enter__(self):
        self._span = span(self.name, **self.meta)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        record_timing(self.name, (time.perf_counter() - self._t0) * 1e3,
                      **self.meta)
        self._span.__exit__(*exc)
        return False


@contextlib.contextmanager
def numbered_run() -> Iterator[int]:
    """Number one more ``api.run`` call of this process (1, 2, ...) and
    make it ``run_number()`` for the duration of the block."""
    n = next(_RUN_COUNT)
    token = _RUN.set(n)
    try:
        yield n
    finally:
        _RUN.reset(token)


def run_number() -> int:
    """The number of the ``api.run`` call in progress; 0 outside one."""
    return _RUN.get()
