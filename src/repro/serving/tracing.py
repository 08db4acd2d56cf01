"""Spans of the serving stack: what the host did, when, and for which request.

``with span(name, rec, **ids):`` does two things around its block:

- it enters ``jax.profiler.TraceAnnotation(name, **ids)``, so while a
  profiler trace is on the span lands on the trace's host lines, on the
  device trace's clock;
- on leaving, it appends ``Span(name, t0, t1, parent, ids, seq)`` to the
  recorder ``rec``, a bounded ring on ``time.perf_counter()`` (the clock of
  ``Request.t_submit``). ``parent`` is the ``seq`` of the span that was open
  when this one began (-1 for none), so spans nest across modules; ``ids``
  carry the request's ``uid`` where there is one.

Nothing turns the recording off: with the profiler off a span costs about a
microsecond, and an engine tick records a few tens. One ``SpanRecorder``
belongs to each engine (``EngineStats.spans``); ``Request.trace`` names the
recorder a request's spans go to.

Two process-wide hooks write into every live recorder: ``host.gc`` spans,
one per garbage collection with its ``generation``, from ``gc.callbacks``;
and ``jax.compile`` spans, one per backend compile, ending when JAX reports
its duration, whose parent is the span that was open when the compile ran.
"""
from __future__ import annotations

import contextvars
import gc
import itertools
import math
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, NamedTuple, Optional

import jax

RING_SPANS = 65536
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    t0: float           # time.perf_counter() at entry
    t1: float           # ... and at exit
    parent: int         # seq of the span open at entry, -1 for none
    ids: dict           # e.g. {"uid": 3}
    seq: int            # process-wide, in order of entry


_seq = itertools.count()
_current = contextvars.ContextVar("repro_open_span", default=-1)
_live: "weakref.WeakSet[SpanRecorder]" = weakref.WeakSet()
_hooks_lock = threading.Lock()
_hooked = False


class SpanRecorder:
    """A bounded ring of closed spans, with running totals per name.

    The ring keeps the newest ``maxlen`` spans; each span pushed out counts
    in ``dropped``, and ``dropped_t1`` is the latest end among them, so a
    reader can tell whether a time range is whole (``intact_since``).
    ``totals`` (seconds) and ``counts`` cover every span ever recorded."""

    def __init__(self, maxlen: int = RING_SPANS):
        self.ring: deque = deque(maxlen=maxlen)
        self.dropped = 0
        self.dropped_t1 = -math.inf
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        # re-entrant: a collection can start inside add() on this thread
        self._lock = threading.RLock()
        _install_hooks()
        _live.add(self)

    def add(self, sp: Span):
        with self._lock:
            if len(self.ring) == self.ring.maxlen:
                self.dropped += 1
                self.dropped_t1 = max(self.dropped_t1, self.ring[0].t1)
            self.ring.append(sp)
            self.totals[sp.name] = self.totals.get(sp.name, 0.0) \
                + (sp.t1 - sp.t0)
            self.counts[sp.name] = self.counts.get(sp.name, 0) + 1

    def spans(self, t0: float = -math.inf, t1: float = math.inf,
              name: Optional[str] = None) -> List[Span]:
        """Spans in the ring that lie wholly inside [t0, t1], optionally of
        one name, in order of closing."""
        with self._lock:
            ring = list(self.ring)
        return [s for s in ring if s.t0 >= t0 and s.t1 <= t1
                and (name is None or s.name == name)]

    def intact_since(self, t: float) -> bool:
        """No span that ended at or after ``t`` was dropped."""
        return self.dropped_t1 < t


class span:
    """``with span(name, rec, **ids):`` — a profiler annotation and, on
    exit, a ``Span`` in ``rec``. ``rec`` may be set on the handle inside
    the block (``as sp: ... sp.rec = ...``) when the recorder is only known
    there, as when the front end routes a request."""
    __slots__ = ("name", "rec", "ids", "_ann", "_t0", "_parent", "_seq",
                 "_token")

    def __init__(self, name: str, rec: Optional[SpanRecorder] = None,
                 **ids):
        self.name, self.rec, self.ids = name, rec, ids

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self._ann.__enter__()
        self._seq = next(_seq)
        self._parent = _current.get()
        self._token = _current.set(self._seq)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        _current.reset(self._token)
        self._ann.__exit__(*exc)
        if self.rec is not None:
            self.rec.add(Span(self.name, self._t0, t1, self._parent,
                              self.ids, self._seq))
        return False


# -- process-wide hooks -------------------------------------------------------

_gc_open: list = []


def _on_gc(phase: str, info: dict):
    if phase == "start":
        gen = info.get("generation", -1)
        ann = jax.profiler.TraceAnnotation("host.gc", generation=gen)
        ann.__enter__()
        _gc_open[:] = [ann, time.perf_counter(), _current.get(), next(_seq),
                       gen]
        return
    if not _gc_open:
        return
    ann, t0, parent, seq, gen = _gc_open
    del _gc_open[:]
    t1 = time.perf_counter()
    ann.__exit__(None, None, None)
    _to_all(Span("host.gc", t0, t1, parent, {"generation": gen}, seq))


def _on_duration(event: str, duration: float, **kw):
    if event != BACKEND_COMPILE:
        return
    t1 = time.perf_counter()
    _to_all(Span("jax.compile", t1 - duration, t1, _current.get(),
                 {"fun": kw.get("fun_name", "")}, next(_seq)))


def _to_all(sp: Span):
    for rec in list(_live):
        rec.add(sp)


def _install_hooks():
    global _hooked
    with _hooks_lock:
        if _hooked:
            return
        _hooked = True
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
