"""Spans and counters of the served path, in one bounded in-memory log.

The wall-clock engine, the admission plane and the hook client write
finished spans here; the benchmark's per-layer readers and an operator
read them back, also after the serving system has stopped. The log is
process-wide and always on: a span is one plain tuple of floats, ints
and strings (the collector stops tracking such tuples after its first
pass over them), appended without a lock, and the oldest spans give
way once ``CAPACITY`` is passed. ``dropped()`` counts what gave way and
``complete_since(t)`` says whether any span that started at or after
``t`` did.

Every span starts with its kind. The spans of one request join on
``(instance, seq)``; each layout is a ``NamedTuple`` below, which
``read`` returns:

- ``segment``: one per dispatched segment, written by the engine's device
  thread when its turn ends. Stamps, all ``time.perf_counter`` seconds:
  ``submit`` (the client's submit), ``launch`` (placed on the device
  queue), ``start`` (dequeued), ``dispatched`` (the jitted call returned,
  before the wait; ``models.segmentation`` stamps it), ``end`` (the wait
  returned), ``booked`` (the scheduler's completion bookkeeping done and
  the engine lock released) and ``released`` (the completion callback
  returned). The phases dispatch, sync, book and callback add up to
  ``released - start``. ``ahead`` is the number of queued
  equal-priority requests, launched earlier, that the device queue took
  this segment ahead of (0 where it took the queue's head).
- ``host_work``: the segment's host post-processing (the head's sampling),
  run by the client inside the completion callback or its own loop.
- ``gap``: a predicted idle gap the scheduler opened after a holder
  kernel: ``predicted`` SG, ``opened``, ``closed`` (the holder's next
  submit, or the task's end), the ``fills`` launched into it and their
  ``overshoot`` past its close. ``seq`` is the segment whose submit
  closed it, or -1.
- ``admission``: one per ticket of the admission plane: ``arrival``,
  ``popped`` (its group left the queue), ``invoked`` (the engine task was
  launched; ``instance`` names it), ``resolved``; None where a ticket
  never reached a stamp. A ticket precedes placement: its ``seq`` and
  ``device`` are -1.
- ``gc``: one per garbage collection, with its ``generation`` and the
  objects ``collected``.

While a ``jax.profiler`` session records, each span also appears in the
trace as a host event named ``fikit/<kind>`` (a segment's phases as
``fikit/segment/<phase>``), and each ``fikit/segment`` event carries its
``perf_counter`` stamps as metadata: the offset between this log and the
profiler's clock. With no session no annotation object is built.
"""
from __future__ import annotations

import gc
import sys
import threading
import time
from collections import deque
from typing import List, NamedTuple, Optional

#: spans the log holds; a 51-s window of the benchmark's cell writes
#: about 41k
CAPACITY = 1 << 18

SEGMENT, HOST_WORK, GAP, ADMISSION, GC = (
    "segment", "host_work", "gap", "admission", "gc")


class Segment(NamedTuple):
    kind: str
    instance: int
    seq: int
    service: str
    priority: int
    device: int
    filler: bool
    submit: float
    launch: float
    start: float
    dispatched: float
    end: float
    booked: float
    released: float
    ahead: int


class HostWork(NamedTuple):
    kind: str
    instance: int
    seq: int
    service: str
    priority: int
    device: int
    start: float
    end: float


class Gap(NamedTuple):
    kind: str
    instance: int
    seq: int
    service: str
    priority: int
    device: int
    predicted: float
    opened: float
    closed: float
    fills: int
    overshoot: float
    closed_by: str


class Admission(NamedTuple):
    kind: str
    instance: int
    seq: int
    service: str
    priority: int
    device: int
    arrival: float
    popped: Optional[float]
    invoked: Optional[float]
    resolved: float
    outcome: str


class Collection(NamedTuple):
    kind: str
    start: float
    end: float
    generation: int
    collected: int


LAYOUTS = {SEGMENT: Segment, HOST_WORK: HostWork, GAP: Gap,
           ADMISSION: Admission, GC: Collection}
#: index of each layout's first stamp, which ``read`` filters on
_FIRST = {SEGMENT: Segment._fields.index("submit"),
          HOST_WORK: HostWork._fields.index("start"),
          GAP: Gap._fields.index("opened"),
          ADMISSION: Admission._fields.index("arrival"),
          GC: Collection._fields.index("start")}

_log: deque = deque()
# guards eviction only: an append is one atomic deque operation; the lock
# is re-entrant because the collector's callback may run in any thread
_lock = threading.RLock()
_dropped = 0
_dropped_first = float("-inf")     # latest first stamp that gave way
#: counters: collector pauses and their seconds, since the process began
counters = {"gc_pauses": 0, "gc_pause_s": 0.0}


def record(span: tuple) -> None:
    """Append one finished span (a plain tuple, kind first)."""
    _log.append(span)
    if len(_log) > CAPACITY:
        _evict()


def _evict() -> None:
    global _dropped, _dropped_first
    with _lock:
        while len(_log) > CAPACITY:
            old = _log.popleft()
            _dropped += 1
            _dropped_first = max(_dropped_first, old[_FIRST[old[0]]])


def read(kind: Optional[str] = None, since: float = float("-inf"),
         until: float = float("inf")) -> List[tuple]:
    """The spans of ``kind`` (all kinds if None) whose first stamp lies in
    ``[since, until]``, oldest written first, as their ``NamedTuple``."""
    rows = _log.copy()                 # one atomic copy
    return [LAYOUTS[r[0]]._make(r) for r in rows
            if (kind is None or r[0] == kind)
            and since <= r[_FIRST[r[0]]] <= until]


def dropped() -> int:
    """Spans that gave way to newer ones since the process began."""
    return _dropped


def complete_since(t: float) -> bool:
    """True when no span whose first stamp is at or after ``t`` gave way."""
    return _dropped_first < t


def clear() -> None:
    """Empty the log and zero the counters (tests)."""
    global _dropped, _dropped_first
    with _lock:
        _log.clear()
        _dropped, _dropped_first = 0, float("-inf")
        counters.update(gc_pauses=0, gc_pause_s=0.0)


# --------------------------------------------------------------- profiler
_annotation_cls = None


def annotation(name: str):
    """A started profiler annotation ``name`` when a ``jax.profiler``
    session records, else None (and nothing is built). A session needs
    ``jax.profiler``, so a process that never imported it records none."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        mod = sys.modules.get("jax.profiler")
        if mod is None:
            return None
        cls = _annotation_cls = mod.TraceAnnotation
    if not cls.is_enabled():
        return None
    tm = cls(name)
    tm.__enter__()
    return tm


def close(tm, **stamps) -> None:
    """End an annotation from ``annotation`` (None is a no-op), with
    ``stamps`` as its metadata."""
    if tm is not None:
        if stamps:
            tm.set_metadata(**stamps)
        tm.__exit__(None, None, None)


# ------------------------------------------------------- segment dispatch
_local = threading.local()


def begin_wait():
    """Mark, on this thread, that a segment's jitted call has returned and
    its wait begins; returns the wait's annotation (see ``annotation``)."""
    _local.dispatched = time.perf_counter()
    return annotation("fikit/segment/sync")


def dispatched_since(t0: float, t1: float) -> float:
    """This thread's dispatch stamp if a segment run between ``t0`` and
    ``t1`` made it; else ``t1`` (a payload that does not wait through
    ``models.segmentation`` counts as dispatch throughout)."""
    d = getattr(_local, "dispatched", None)
    return d if d is not None and t0 <= d <= t1 else t1


# -------------------------------------------------------------- host work
def run_host_work(fn, state, req, device: int):
    """``fn(state)``, recorded as the host-work span of segment ``req``."""
    tm = annotation("fikit/host_work")
    t0 = time.perf_counter()
    try:
        return fn(state)
    finally:
        t1 = time.perf_counter()
        close(tm)
        record((HOST_WORK, req.task_instance, req.seq_index,
                req.task_key.process, req.priority, device, t0, t1))


# -------------------------------------------------------------------- gaps
class GapLog:
    """Records the gaps one device's scheduling policy opens: the policy
    calls ``open``, ``fill``, ``close`` and ``fill_done`` where it acts.
    A gap's span is written once it has closed and its fills have run.
    Fillers run in launch order on the device's serial queue, so each
    ``fill_done`` belongs to the oldest running fill."""

    def __init__(self, device: int):
        self.device = device
        self._task = None            # the open gap's holder task, or None
        self._predicted = self._opened = 0.0
        self._fills = 0
        self._tm = None
        self._filled = None          # the open gap's fill record, if any
        self._running = deque()      # per running fill: its gap's record

    def open(self, task, predicted: float) -> None:
        if self._task is not None:
            self.close(-1, "reopen")
        self._task, self._predicted = task, predicted
        self._opened = time.perf_counter()
        self._fills = 0
        self._tm = annotation("fikit/gap")

    def fill(self) -> None:
        self._fills += 1
        if self._filled is None:
            # [span without its overshoot and closer, running, overshoot]
            self._filled = [None, 0, 0.0]
        self._filled[1] += 1
        self._running.append(self._filled)

    def close(self, seq: int, by: str) -> None:
        task = self._task
        if task is None:
            return
        closed = time.perf_counter()
        if self._tm is not None:
            close(self._tm, predicted=self._predicted, opened=self._opened,
                  closed=closed, fills=self._fills)
            self._tm = None
        self._task = None
        filled = self._filled
        if filled is None:
            record((GAP, task.instance, seq, task.key.process,
                    task.priority, self.device, self._predicted,
                    self._opened, closed, 0, 0.0, by))
            return
        self._filled = None
        filled[0] = (GAP, task.instance, seq, task.key.process,
                     task.priority, self.device, self._predicted,
                     self._opened, closed, self._fills, by)
        self._write_if_done(filled)

    def fill_done(self) -> None:
        if not self._running:
            return
        filled = self._running.popleft()
        filled[1] -= 1
        if filled[0] is not None:      # the gap has closed: time past it
            filled[2] += max(0.0, time.perf_counter() - filled[0][8])
            self._write_if_done(filled)

    @staticmethod
    def _write_if_done(filled) -> None:
        head, running, overshoot = filled
        if running == 0:
            record(head[:-1] + (overshoot, head[-1]))


# --------------------------------------------------------------- collector
_gc_start = threading.local()


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_start.t = time.perf_counter()
        _gc_start.tm = annotation("fikit/gc")
        return
    t0 = getattr(_gc_start, "t", None)
    if t0 is None:
        return
    t1 = time.perf_counter()
    _gc_start.t = None
    close(getattr(_gc_start, "tm", None), generation=info["generation"],
          collected=info["collected"])
    _gc_start.tm = None
    with _lock:
        counters["gc_pauses"] += 1
        counters["gc_pause_s"] += t1 - t0
    record((GC, t0, t1, info["generation"], info["collected"]))


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
