"""Spans and counters of the FL round, on the profiler's clock.

The program's only tracing layer. It writes nothing of its own and has no
setting: spans and counts exist while ``jax.profiler`` records (between
``start_trace`` and ``stop_trace``) and cost one check otherwise.

* ``span(name, **args)``: a host span on the profiler's host plane, on the
  same clock as the device planes. ``args`` (``round``, the span's shape)
  show in a trace viewer.
* ``sync(site, **args)``: the span ``fl.sync.<site>`` around a blocking
  device-to-host read.
* ``count(name, n)``: while the profiler records, adds ``n`` to the
  session tally (``session()``). The tally restarts at the first count of
  a new profiler session, so after ``stop_trace`` it holds exactly what
  the traced rounds counted.

The tally is per process because the profiler is: a session records every
thread of the process, and a reader of the trace runs in the same process
after ``stop_trace``.
"""
from __future__ import annotations

import contextlib
import threading
from collections import Counter

from jax.profiler import TraceAnnotation

_NULL = contextlib.nullcontext()
_session: Counter = Counter()
_live = False   # the last count was made while the profiler recorded
_lock = threading.Lock()   # sweeps run engines on threads


def span(name: str, **args):
    """A context manager: the profiler span ``name`` while the profiler
    records, else one shared null context."""
    if not TraceAnnotation.is_enabled():
        return _NULL
    return TraceAnnotation(name, **args)


def sync(site: str, **args):
    """The span ``fl.sync.<site>``, for a blocking device-to-host read."""
    return span(f"fl.sync.{site}", **args)


def count(name: str, n: int = 1) -> None:
    global _live
    if not TraceAnnotation.is_enabled():
        _live = False
        return
    with _lock:
        if not _live:
            _session.clear()
            _live = True
        _session[name] += n


def session() -> dict[str, int]:
    """What was counted in the newest profiler session."""
    with _lock:
        return dict(_session)
