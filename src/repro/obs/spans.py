"""Wall-clock program spans on the profiler's own clock (DESIGN.md §13).

The rest of ``obs/`` observes on *virtual* time, for the operators of a
fleet.  These spans answer a different question — where the host's wall
time goes — and exist only while ``jax.profiler`` is tracing::

    with span("engine.assimilate"):
        ...

    @spanned("backend.submit")
    def submit(self, ...): ...

    if enabled():                      # a per-message hot path
        with span("intake.transport"):
            ...

Names are ``<layer>.<op>``.  With the profiler off, ``span`` returns one
shared no-op and ``spanned`` calls straight through; the cost is one
``TraceMe.is_enabled()`` check (``enabled``), and the hottest call sites
branch on ``enabled()`` themselves to pay nothing more.  With it on, a span has two
sinks:

* a ``TraceMe`` of its name, so the span lands on the host thread's line
  of the same ``.xplane.pb`` as the device operations, on the same clock;
* in-memory totals per name, read by ``totals()`` and cleared by
  ``reset()``: ``count``; ``total_ns``, the time the enclosed code ran;
  ``self_ns``, that less the whole cost of the program spans nested in
  it (kept on a stack per thread); and ``overhead_ns``, the span's own
  bookkeeping (the ``TraceMe``, the clock reads, the totals).  A span's
  bookkeeping is thus in no span's self time: the self times of one
  layer are that layer's work, and what tracing costs is
  ``overhead_ns``, apart.  What the clock cannot see — the call into the
  span and the return from it, under a microsecond — stays in the
  enclosing span's self time.

The profiler being on is the only switch: no flag, no exporter.  A
process that starts the profiler once, after its warm-up, and stops it at
the end of its measured window (as ``bench/harness.py`` does) therefore
holds totals for exactly that window; a process that traces several
windows calls ``reset()`` before each.  Spans only read: they never touch
the state they enclose.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Dict

from jax.profiler import TraceAnnotation

enabled = TraceAnnotation.is_enabled
_now = time.perf_counter_ns
_lock = threading.Lock()
_local = threading.local()
# name -> [count, total_ns, self_ns, overhead_ns]
_totals: Dict[str, list] = {}


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _Noop()


class _Span:
    __slots__ = ("name", "trace_me", "t0", "t1", "child_ns")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        t0 = _now()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self.t0 = t0
        self.child_ns = 0
        self.trace_me = TraceAnnotation(self.name)
        self.trace_me.__enter__()
        self.t1 = _now()
        return self

    def __exit__(self, exc_type, exc, tb):
        t2 = _now()
        self.trace_me.__exit__(exc_type, exc, tb)
        stack = _local.stack
        stack.pop()
        dur = t2 - self.t1
        with _lock:
            t = _totals.get(self.name)
            if t is None:
                t = _totals[self.name] = [0, 0, 0, 0]
            t[0] += 1
            t[1] += dur
            t[2] += dur - self.child_ns
            cost = _now() - self.t0
            t[3] += cost - dur
        if stack:
            stack[-1].child_ns += cost
        return False


def span(name: str):
    """A context manager timing ``name`` while the profiler traces; the
    shared no-op otherwise."""
    if not enabled():
        return _NOOP
    return _Span(name)


def spanned(name: str):
    """Decorator form of ``span``: the call is one ``name`` span."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if not enabled():
                return fn(*args, **kwargs)
            with _Span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def totals() -> Dict[str, Dict[str, int]]:
    """``{name: {"count", "total_ns", "self_ns", "overhead_ns"}}``, a
    copy."""
    with _lock:
        return {k: {"count": c, "total_ns": t, "self_ns": s,
                    "overhead_ns": o}
                for k, (c, t, s, o) in _totals.items()}


def reset() -> None:
    """Forget every total (open spans still close normally)."""
    with _lock:
        _totals.clear()
