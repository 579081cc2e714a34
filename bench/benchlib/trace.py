"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three things, all in nanoseconds on the trace's one clock: the traced
window (the harness's ``bench/window`` span), the device's operations (the
``XLA Ops`` line of the first TPU plane) and the harness's host spans
(``<role>/<program>`` around each segment, ``<role>/head.host`` around the
head's host sampling). ``reduce`` turns that into busy time, device time
per span label, and the breakdown; it is checked on a small recorded
trace in ``bench/tests``.

A device operation belongs to the span that contains its midpoint. Each
segment waits for its result before its span closes, so a segment's
operations fall inside its own span, once the device's clock is aligned
with the host's: on a v5e the device plane runs about a millisecond off
the host plane, so ``align`` finds the shift that puts the most device
time inside the host spans, and ``reduce`` applies it.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

import numpy as np

WINDOW_SPAN = "bench/window"
SPAN_PREFIXES = ("hi/", "lo/", "bench/")


def op_name(hlo: str) -> str:
    """An XLA op's event name cut to its instruction and result type,
    e.g. '%fusion.32 = bf16[4,512,2048]'."""
    return hlo.split("{", 1)[0].split(" fusion(", 1)[0].strip()


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, spans = [], []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    if device_planes:
        first = min(device_planes, key=lambda p: p.name)
        for line in first.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    ops.append([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)])
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w0, wd = win[0][1], win[0][2]
    return {"window": [w0, w0 + wd],
            "ops": sorted(ops, key=lambda o: o[1]),
            "spans": sorted((s for s in spans if s[0] != WINDOW_SPAN),
                            key=lambda s: s[1])}


def _inside_ns(ops, starts, ends, shift):
    """Device nanoseconds that lie wholly inside a host span once the
    device's timestamps are moved by ``shift``."""
    s = ops[:, 0] + shift
    e = s + ops[:, 1]
    i = np.searchsorted(starts, s, side="right") - 1
    ok = (i >= 0) & (e <= ends[np.maximum(i, 0)])
    return int(ops[ok, 1].sum())


def align(ex: dict, reach_ns: int = 5_000_000) -> int:
    """The shift (ns) to add to device timestamps that puts the most
    device time inside the host spans: a coarse search over +-reach_ns,
    then a fine one; the middle of the best shifts."""
    spans = sorted((s[1], s[1] + s[2]) for s in ex["spans"])
    if not spans or not ex["ops"]:
        return 0
    starts = np.array([a for a, _ in spans], np.int64)
    ends = np.array([b for _, b in spans], np.int64)
    ops = np.array([[o[1], o[2]] for o in ex["ops"]], np.int64)
    best = 0
    for step, reach in ((20_000, reach_ns), (1_000, 40_000)):
        shifts = np.arange(best - reach, best + reach + 1, step)
        got = np.array([_inside_ns(ops, starts, ends, d) for d in shifts])
        top = shifts[got >= got.max()]
        best = int(top[len(top) // 2])
    return best


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _SpanIndex:
    """Host spans sorted by start, for 'which span holds time t'."""

    def __init__(self, spans):
        self.spans = spans
        self.starts = [s[1] for s in spans]

    def at(self, t):
        """The span (name, start, duration) that holds time t, or None."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            span = self.spans[i]
            if span[1] <= t <= span[1] + span[2]:
                return span
            if t - span[1] > 10 ** 9:      # no span lasts a second
                break
            i -= 1
        return None

    def before(self, t):
        i = bisect.bisect_right(self.starts, t) - 1
        return self.spans[i][0] if i >= 0 else "start"

    def after(self, t):
        i = bisect.bisect_left(self.starts, t)
        return self.spans[i][0] if i < len(self.spans) else "end"


def reduce(ex: dict, top: int = 10) -> dict:
    """Busy and window seconds, device seconds and call counts per span
    label (spans wholly inside the window), the breakdown, and the shift
    applied to the device's clock."""
    w0, w1 = ex["window"]
    shift = align(ex)
    ops = [[n, s + shift, d] for n, s, d in ex["ops"]]
    ops = [o for o in ops if o[1] < w1 and o[1] + o[2] > w0]
    clipped = [[max(o[1], w0), min(o[1] + o[2], w1)] for o in ops]
    busy = _union(clipped)
    busy_ns = sum(e - s for s, e in busy)
    index = _SpanIndex(ex["spans"])

    def whole(span):
        return span[1] >= w0 and span[1] + span[2] <= w1

    # device time per call: the spans wholly inside the window, and the
    # operations that lie in them
    per_span = defaultdict(lambda: {"calls": 0, "device_s": 0.0})
    for span in ex["spans"]:
        if whole(span):
            per_span[span[0]]["calls"] += 1
    by_op = defaultdict(float)
    for (name, s, d), (cs, ce) in zip(ops, clipped):
        span = index.at(s + d // 2)
        if span is not None and whole(span):
            per_span[span[0]]["device_s"] += d * 1e-9
        by_op[f"{span[0] if span else 'no span'}:{name}"] += (ce - cs) * 1e-9

    gaps = defaultdict(float)
    edges = [[w0, w0]] + busy + [[w1, w1]]
    for (_, prev_end), (nxt_start, _) in zip(edges, edges[1:]):
        if nxt_start > prev_end:
            mid = (prev_end + nxt_start) // 2
            span = index.at(mid)
            label = (span[0] if span else
                     f"{index.before(mid)} > {index.after(mid)}")
            gaps[label] += (nxt_start - prev_end) * 1e-9

    def top_n(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": busy_ns * 1e-9,
            "device_shift_s": shift * 1e-9,
            "spans": dict(per_span),
            "device_ops": top_n(by_op),
            "idle_gaps": top_n(gaps)}
