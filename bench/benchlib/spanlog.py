"""The program's span log (``repro.core.spans``) as the per-layer readers
see it: one run's window, with its requests chosen as
``RunView.hi_segments`` chooses them.

``window(run)`` is None where the program has no span log, or where the
log dropped spans inside the window; readers then return None.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

try:
    from repro.core import spans
except ImportError:          # a program without the span log
    spans = None


class Window:
    """The spans of ``[run.t0, run.t_end]``, all times host seconds.

    ``hi`` is the window's highest priority level (the lowest number);
    ``requests`` maps each request of that level whose first segment was
    submitted inside the window to its segment spans, in order, including
    those that ran after the window closed."""

    def __init__(self, run):
        t0, t1 = run.t0, run.t_end
        self.seconds = run.seconds
        self.all_segments = spans.read(spans.SEGMENT)
        self.segments = [s for s in self.all_segments if t0 <= s.start <= t1]
        self.host_work = {(h.instance, h.seq): h
                          for h in spans.read(spans.HOST_WORK, since=t0)}
        self.gaps = spans.read(spans.GAP, t0, t1)
        self.admissions = spans.read(spans.ADMISSION, t0, t1)
        self.collections = spans.read(spans.GC, t0, t1)
        inside = [s for s in self.all_segments if t0 <= s.submit <= t1]
        self.hi = min((s.priority for s in inside), default=None)
        by_inst = defaultdict(list)
        for s in self.all_segments:
            if s.priority == self.hi and s.submit >= t0:
                by_inst[s.instance].append(s)
        self.requests = {i: sorted(v, key=lambda s: s.seq)
                         for i, v in by_inst.items()
                         if min(s.submit for s in v) <= t1}

    def turns(self, device: int):
        """(starts, segments) of ``device``'s turns, in start order; a turn
        is a segment's hold on its device thread, ``start`` to
        ``released``."""
        got = getattr(self, "_turns", None)
        if got is None:
            got = self._turns = {}
            for s in sorted(self.all_segments, key=lambda s: s.start):
                got.setdefault(s.device, ([], []))
                got[s.device][0].append(s.start)
                got[s.device][1].append(s)
        return got.get(device, ([], []))

    def waits_behind(self, seg):
        """Device-thread seconds that ``seg`` spent, between its launch and
        its start, on the turns of other hi requests and of lower levels."""
        starts, turns = self.turns(seg.device)
        behind_hi = behind_lo = 0.0
        j = max(0, bisect.bisect_left(starts, seg.launch) - 1)
        while j < len(turns) and turns[j].start < seg.start:
            x = turns[j]
            j += 1
            if x.instance == seg.instance:
                continue
            held = min(x.released, seg.start) - max(x.start, seg.launch)
            if held <= 0:
                continue
            if x.priority == self.hi:
                behind_hi += held
            else:
                behind_lo += held
        return behind_hi, behind_lo


def window(run):
    if spans is None or not spans.complete_since(run.t0):
        return None
    return Window(run)


def mean_ms(values):
    values = list(values)
    return 1e3 * sum(values) / len(values) if values else None
