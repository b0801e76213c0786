"""Tracing: spans on the profiler's clock, plus trace contexts.

reference: the `tracing` spans on loro's hot paths + dev-utils
(crates/dev-utils/src/lib.rs:9-31 writes ./log/trace-*.json for
chrome://tracing when DEBUG is set).

ONE switch, no environment variable: a ``span(name)`` records when, and
only when, a profiler session is recording
(``jax.profiler.TraceAnnotation.is_enabled()``) or after an explicit
``enable()``.  Off is the default and costs one flag read per span site.

A recording span writes two records (docs/OBSERVABILITY.md "Spans"):

- under a profiler session it enters a ``jax.profiler.TraceAnnotation``
  of the same name, so it lands in the ``.xplane.pb`` beside ``XLA Ops``
  / ``XLA Modules`` on the trace's own time base — the record an
  operator opens in xprof / Perfetto;
- the in-process record: ``name, span_id, parent_id`` (the enclosing
  span of the same thread), ``trace_id`` (the thread's ambient
  ``current()``), the real thread id, ``start_ns`` / ``end_ns``
  (``perf_counter_ns``), ``cpu_ns`` (``thread_time_ns`` over the span,
  taken on a thread's root spans only) and the site's small args.  It is a bounded ring (``RING_SPANS``)
  appended without a lock; what falls off the ring's end is counted in
  ``trace.spans_dropped_total``.  A new recording session starts a new
  record: ``events()`` returns the newest session's spans only.
  ``dump()`` writes it as chrome://tracing JSON.

The collector's pauses: while a record is kept, a ``gc.callbacks`` hook
keeps each collection in a bounded list of its OWN beside the spans
(``pauses()``; ``dump()`` writes them as ``gc.pause`` instants).  A pause
lands inside whatever span is open and would read as that stage's time;
it never enters ``events()``, whose readers count spans.

Span observers (obs bridge): loro_tpu.obs.enable_span_metrics()
registers a callback that receives every span's (name, duration_s) so
ONE instrumentation point feeds both the trace and the metrics
histograms.  ``instant()`` events fire observers too (duration 0.0), so
the bridge sees point events as well as spans.

The observer list is COPY-ON-WRITE: ``span`` iterates an immutable
tuple snapshot while add/remove rebuild it under the module lock, so a
concurrent (un)register can never skip or double-fire an observer
mid-iteration (the ISSUE 14 race: list.append/remove raced the
unsynchronized iteration in span()).

Trace contexts (docs/OBSERVABILITY.md "Request tracing"): a trace id is
a process-unique opaque string minted at a request entry point
(``new_trace_id()``) and carried end-to-end — push tickets, pipeline
rounds, WAL round stamps, follower applies.  ``set_current()`` /
``current()`` keep a per-thread ambient id so deep layers (the WAL
append inside a pipelined commit) can stamp the request that caused
them without threading an argument through every signature; every span
records the ambient id of its thread.
"""
from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

# four windows of the widest writer: a 30 s b4_import.packed64 window
# writes about 26,500 spans (PERF.md PR 25)
RING_SPANS = 1 << 17

_enabled = False  # enable() / disable(): the explicit half of the switch
_live = False  # what the switch read last: a rise starts a new record
_record: deque = deque(maxlen=RING_SPANS)
_annotation = None  # jax.profiler.TraceAnnotation, once this process has jax
_lock = threading.Lock()
# COW snapshot: readers iterate whatever tuple they loaded; writers
# replace the whole tuple under _lock (never mutate in place)
_span_observers: Tuple[Callable[[str, float], None], ...] = ()
_span_ids = itertools.count(1)
RING_PAUSES = 1 << 12  # collections kept a session: the newest 4,096
_pauses: deque = deque(maxlen=RING_PAUSES)
_gc_t0 = 0  # start of the collection under way, 0 where none is recorded
_gc_ns = 0  # pause time recorded so far, by the hook alone
_gc_counted = 0  # how much of it trace.gc_pause_ns_total has been given
# .trace: ambient trace id; .span: open span id; .leaf: under a leaf span
_ambient = threading.local()


def _profiling() -> bool:
    """A profiler session is recording.  jax is looked up, never
    imported: a process that has not loaded it has no session."""
    global _annotation
    if _annotation is None:
        mod = sys.modules.get("jax.profiler")
        if mod is None:
            return False
        _annotation = mod.TraceAnnotation
    return _annotation.is_enabled()


def _recording() -> bool:
    """THE switch, read once per span site."""
    on = _enabled or _profiling()
    if on != _live:
        _roll(on)
    return on


def _roll(on: bool) -> None:
    global _live, _record, _pauses
    with _lock:
        if on and not _live:
            _record = deque(maxlen=RING_SPANS)
            _pauses = deque(maxlen=RING_PAUSES)
        _live = on


def enable() -> None:
    """Record spans from now on, in a new record, with or without a
    profiler session."""
    global _enabled
    _enabled = True
    _recording()


def disable() -> None:
    global _enabled
    _enabled = False
    _recording()


def is_enabled() -> bool:
    return _recording()


def add_span_observer(fn: Callable[[str, float], None]) -> None:
    """Register a (name, duration_seconds) callback fired at every span
    exit and instant event, independent of trace collection (the obs
    bridge).  Copy-on-write under the module lock: a span iterating the
    old snapshot is unaffected."""
    global _span_observers
    with _lock:
        if fn not in _span_observers:
            _span_observers = _span_observers + (fn,)


def remove_span_observer(fn: Callable[[str, float], None]) -> None:
    global _span_observers
    with _lock:
        if fn in _span_observers:
            _span_observers = tuple(f for f in _span_observers if f is not fn)


# -- trace contexts ----------------------------------------------------
# process-unique request ids: pid + monotonic counter (deterministic,
# no wall clock / randomness — chaos replays stay byte-stable where it
# matters and the id still tells you which process minted it)
_trace_counter = itertools.count(1)


def new_trace_id(prefix: str = "t") -> str:
    """Mint a process-unique trace id (cheap: one counter bump)."""
    return f"{prefix}{os.getpid():x}-{next(_trace_counter):x}"


def set_current(trace_id: Optional[str]) -> None:
    """Install the ambient trace id for this thread (None clears it).
    Deep layers read it via ``current()`` to stamp work they perform on
    behalf of a request (e.g. the WAL append inside a commit)."""
    _ambient.trace = trace_id


def current() -> Optional[str]:
    """The ambient trace id of this thread, or None."""
    return getattr(_ambient, "trace", None)


@contextmanager
def ambient(trace_id: Optional[str]):
    """Scope an ambient trace id (restores the previous one)."""
    prev = current()
    set_current(trace_id)
    try:
        yield
    finally:
        set_current(prev)


# -- spans -------------------------------------------------------------
def _append(rec: tuple) -> None:
    ring = _record
    if len(ring) == ring.maxlen:  # the ring's oldest span falls off
        from ..obs import metrics as obs

        obs.counter("trace.spans_dropped_total").inc()
    ring.append(rec)
    if _gc_ns != _gc_counted:
        _count_pauses()


class span:
    """``with span(name, **small_int_args):`` — a trace span; one flag
    read when tracing is off and no observer is registered.  A span that
    stands for a request names it (``trace_id=``): while it records, that
    id is the thread's ambient one, so its children carry it too.  A
    ``leaf=True`` span is one of many that run side by side under another
    thread's wait: what its thread opens under it records nothing, so a
    stage's spans sum to the waiter's time and not to the threads'."""

    __slots__ = ("name", "args", "_trace", "_leaf", "_prev", "_obs", "_id",
                 "_parent", "_ann", "_t0", "_cpu0")

    def __init__(self, name: str, trace_id: Optional[str] = None,
                 leaf: bool = False, **args):
        self.name = name
        self.args = args
        self._trace = trace_id
        self._leaf = leaf

    def __enter__(self) -> "span":
        self._obs = _span_observers  # COW snapshot: stable for this span
        if not _recording() or getattr(_ambient, "leaf", False):
            self._id = 0
            if self._obs:
                self._t0 = time.perf_counter_ns()
            return self
        self._id = next(_span_ids)
        self._parent = getattr(_ambient, "span", 0)
        _ambient.span = self._id
        if self._leaf:
            _ambient.leaf = True
        if self._trace is not None:
            self._prev = current()
            _ambient.trace = self._trace
        self._ann = None
        if _profiling():  # not after a bare enable(): no session to write to
            self._ann = _annotation(self.name, **self.args)
            self._ann.__enter__()
        # the thread clock is a system call (6.6 us on the chip's host, where
        # reading it on every span cost the packed stream 2.7 %, PERF.md
        # PR 25): a thread's root spans answer "was this thread on a CPU"
        self._cpu0 = None if self._parent else time.thread_time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if not self._id and not self._obs:
            return False
        end = time.perf_counter_ns()
        if self._id:
            cpu = None if self._cpu0 is None else time.thread_time_ns() - self._cpu0
            if self._ann is not None:
                self._ann.__exit__(*exc)
            _ambient.span = self._parent
            if self._leaf:
                _ambient.leaf = False
            _append((self.name, self._id, self._parent, current(),
                     threading.get_ident(), self._t0, end, cpu, self.args))
            if self._trace is not None:
                _ambient.trace = self._prev
        for fn in self._obs:
            fn(self.name, (end - self._t0) * 1e-9)
        return False


def instant(name: str, **args) -> None:
    obs = _span_observers
    if _recording() and not getattr(_ambient, "leaf", False):
        now = time.perf_counter_ns()
        _append((name, next(_span_ids), getattr(_ambient, "span", 0), current(),
                 threading.get_ident(), now, now, None, args))
    # point events reach the obs bridge too (duration 0.0): counters of
    # named occurrences, not timings
    for fn in obs:
        fn(name, 0.0)


_FIELDS = ("name", "span_id", "parent_id", "trace_id", "tid", "start_ns",
           "end_ns", "cpu_ns", "args")


def events() -> List[Dict[str, Any]]:
    """The newest session's spans, one dict of ``_FIELDS`` each, in the
    order they ended.  ``parent_id`` 0 = a root of its thread; only a
    root has ``cpu_ns`` (None on child spans and on an ``instant()``, which
    also has ``end_ns == start_ns``)."""
    _recording()  # a session that ended since the last span site is over
    return [dict(zip(_FIELDS, rec)) for rec in list(_record)]


def clear() -> None:
    global _record, _pauses
    with _lock:
        _record = deque(maxlen=RING_SPANS)
        _pauses = deque(maxlen=RING_PAUSES)


# -- the collector's pauses ----------------------------------------------
def _on_gc(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` hook: one flag read while no record is kept
    (``_live``: what THE switch read last).  It takes no lock and asks the
    registry for nothing — a collection can start while its thread holds
    one of their locks — so the counter is fed by the next span's end or
    the next ``pauses()`` (``_count_pauses``).  Collections do not nest."""
    global _gc_t0, _gc_ns
    if phase == "start":
        if _live:
            _gc_t0 = time.perf_counter_ns()
    elif _gc_t0:
        end = time.perf_counter_ns()
        _pauses.append((info["generation"], info["collected"],
                        threading.get_ident(), _gc_t0, end))
        _gc_ns += end - _gc_t0
        _gc_t0 = 0


gc.callbacks.append(_on_gc)


def _count_pauses() -> None:
    global _gc_counted
    with _lock:
        total = _gc_ns
        ns, _gc_counted = total - _gc_counted, total
    if ns:
        from ..obs import metrics as obs

        obs.counter("trace.gc_pause_ns_total").inc(ns)


_PAUSE_FIELDS = ("gen", "collected", "tid", "start_ns", "end_ns")


def pauses() -> List[Dict[str, Any]]:
    """The newest session's collections, one dict of ``_PAUSE_FIELDS``
    each, oldest first (the last ``RING_PAUSES`` of them): the generation
    collected, the objects it freed, the thread it ran on and when
    (``perf_counter_ns``, the spans' clock)."""
    _recording()
    _count_pauses()
    return [dict(zip(_PAUSE_FIELDS, p)) for p in list(_pauses)]


def _safe(v):
    if isinstance(v, (int, float, str, bool)) or v is None:
        return v
    return str(v)


# dump() collision guard: two dumps in the same wall-second used to
# overwrite each other (the ISSUE 14 satellite) — the default filename
# now carries pid + a monotonic per-process counter
_dump_counter = itertools.count(1)


def dump(path: Optional[str] = None) -> str:
    """Write the record as chrome://tracing JSON; returns the path.  The
    default path is collision-free across processes and across
    same-second dumps (timestamp + pid + per-process counter)."""
    if path is None:
        os.makedirs("log", exist_ok=True)
        path = os.path.join(
            "log",
            f"trace-{int(time.time())}-{os.getpid()}-{next(_dump_counter)}.json",  # tpulint: disable=LT-TIME(artifact filename stamp; wall time is the point)
        )
    pid = os.getpid()
    out = []
    for e in events():
        ev = {
            "name": e["name"],
            "ph": "X",
            "ts": e["start_ns"] / 1e3,
            "dur": (e["end_ns"] - e["start_ns"]) / 1e3,
            "pid": pid,
            "tid": e["tid"],
            "args": {"span": e["span_id"], "parent": e["parent_id"],
                     "trace": e["trace_id"],
                     **{k: _safe(v) for k, v in e["args"].items()}},
        }
        if e["end_ns"] == e["start_ns"]:  # an instant()
            ev.update(ph="i", s="t")
            del ev["dur"]
        if e["cpu_ns"] is not None:
            ev["args"]["cpu_us"] = e["cpu_ns"] / 1e3
        out.append(ev)
    for p in pauses():  # an instant each, on the thread that collected
        out.append({"name": "gc.pause", "ph": "i", "s": "t",
                    "ts": p["start_ns"] / 1e3, "pid": pid, "tid": p["tid"],
                    "args": {"gen": p["gen"], "collected": p["collected"],
                             "ns": p["end_ns"] - p["start_ns"]}})
    with open(path, "w") as f:
        json.dump({"traceEvents": out}, f)
    return path
