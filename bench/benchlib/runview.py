"""What a per-layer metric reader is given: one run's records, plain.

Readers (``bench/metrics/<name>.py``) take a ``RunView`` and return a
number, or None where the run holds nothing for them to read.
"""
from __future__ import annotations

from benchlib import flops


class RunView:
    def __init__(self, cell, win: dict, peak: dict, trace=None):
        self.seconds = win["seconds"]
        self.t0 = win["t0"]
        self.t_end = win["t0"] + win["seconds"]
        #: completed high-priority requests of the window: latency_s,
        #: ticket_latency_s (admission plane, submit to resolve), jct_s
        #: (engine task begin to end), done_s (head result on the host)
        self.hi = [r for r in win["hi"] if r["ok"]]
        #: per high-priority request of the window, its segments in order
        #: as (seq_index, submit, start, end, is_hi), host clock seconds
        self.hi_segments = win["segments"]
        self.fills = win["fills"]
        self.lo_done = len(win["lo_done"])
        self.hi_done_in_window = sum(r["done_s"] <= self.t_end
                                     for r in self.hi)
        self.peak = peak
        #: ``benchlib.trace.reduce`` of the traced sub-window, or None
        self.trace = trace
        self._roles = {n: (r.family, r.m, r.batch, r.seq)
                       for n, r in cell.roles.items()}

    def request_flops(self, role: str) -> int:
        return flops.request_flops(*self._roles[role])

    def least_time(self, role: str) -> dict:
        """Per layer program of ``role``, by its program name: the
        roofline time of one call, and the bound ('compute' or 'memory')
        that sets it."""
        family, m, b, s = self._roles[role]
        return {p: flops.least_time(f, n, self.peak)
                for p, (_calls, f, n) in
                family.layer_programs(m, b, s).items()}

    def span(self, label: str):
        """Calls and device seconds of a traced span label, or None."""
        if self.trace is None:
            return None
        s = self.trace["spans"].get(label)
        if not s or not s["calls"] or s["device_s"] <= 0:
            return None
        return s
