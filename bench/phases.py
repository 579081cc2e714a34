"""Where a traced run's device idle time goes, by the program's phases.

    python3 bench/phases.py --workload <name> --seed <n> --seconds <s> \
        [--out <file.json>]

Runs the cell once as ``bench/run.py --trace 1`` does, printing the same
lines and result, and keeps what that run reduces away: the trace's
device operations and the program's ``fikit/segment`` events. The span
log of ``repro.core.spans`` is then put on the trace's clock by the
``perf_counter`` stamps each event carries. Printed last, as one JSON
object (and written to ``--out``):

- ``offset_us``: each segment event's profiler start less its
  ``perf_counter`` start. The profiler reads its clock after the log's
  stamp, so an event reads the offset plus a delay (the thread may lose
  the interpreter lock in between). Of the lowest reading in each of ten
  stretches of the window, the middle one is taken as the offset and
  their spread is the ``drift`` (one clock if small); ``delay`` is the
  median reading above the offset;
- ``idle_s``: the traced window's device idle seconds by what the device
  thread was doing: ``dispatch`` (start to the jitted call's return),
  ``sync`` (waiting on the result), ``book`` (the engine's completion
  bookkeeping), ``host_work``, ``identify_submit`` (the rest of the
  completion callback: identification and the next submit), ``dequeue``
  (between two turns with work queued), ``empty_queue`` (nothing queued)
  and ``no_span`` (outside every turn);
- ``device_wait``: for the window's hi requests, the mean summed
  ``start - submit`` of their segments beside its parts: ``launch -
  submit``, the rest of the request's own previous turn, the wait behind
  other hi requests' turns and behind lower levels', and the rest;
- ``phases_add_up``: whether every segment's phases sum to its turn;
- ``collector``: the collector's pauses begun in the window (count,
  seconds, the longest and its generation), beside the feeder's latest
  wake-up;
- ``gaps``: the gaps opened in the window, how each closed, their
  predicted and actual lengths (median and largest) and how many were
  filled, beside the median run (``end - start``) of a lower-level
  segment, which a fill would have to fit.

Run on the chip; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402  (sets up the import paths)
from benchlib import cell as cells  # noqa: E402
from benchlib import spanlog  # noqa: E402
from benchlib import trace as traces  # noqa: E402

PHASES = ("dispatch", "sync", "book", "host_work", "identify_submit",
          "dequeue", "empty_queue", "no_span")


def segment_events(trace_dir: str) -> list:
    """(perf_counter start, profiler start ns) of each ``fikit/segment``
    event in the trace, in time order."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "fikit/segment":
                    stats = dict(e.stats)
                    out.append((float(stats["start"]), int(e.start_ns)))
    return sorted(out)


def clock_offset(events, stretches: int = 10) -> dict:
    """Profiler clock less ``perf_counter``, in seconds, from the events'
    lower envelope (see ``offset_us`` above)."""
    readings = [ns * 1e-9 - start for start, ns in events]
    k = max(1, len(readings) // stretches)
    lows = [min(readings[i:i + k]) for i in range(0, len(readings), k)]
    low = sorted(lows)[len(lows) // 2]
    return {"offset": low, "drift": max(lows) - min(lows),
            "delay": sorted(r - low for r in readings)[len(readings) // 2],
            "events": len(readings)}


def thread_timeline(w, device: int):
    """The device thread's phases as sorted (start, end, phase), host
    seconds."""
    segs = sorted((s for s in w.all_segments if s.device == device),
                  key=lambda s: s.start)
    out = []
    for s, nxt in zip(segs, segs[1:] + [None]):
        out += [(s.start, s.dispatched, "dispatch"),
                (s.dispatched, s.end, "sync"), (s.end, s.booked, "book")]
        hw = w.host_work.get((s.instance, s.seq))
        if hw is not None and s.booked <= hw.start <= hw.end <= s.released:
            out += [(s.booked, hw.start, "identify_submit"),
                    (hw.start, hw.end, "host_work"),
                    (hw.end, s.released, "identify_submit")]
        else:
            out.append((s.booked, s.released, "identify_submit"))
        if nxt is not None:
            out.append((s.released, nxt.start, "dequeue"
                        if nxt.launch <= s.released else "empty_queue"))
    return [iv for iv in out if iv[1] > iv[0]]


def idle_by_phase(idle, timeline) -> dict:
    """Seconds of each idle interval under each phase of the timeline."""
    got = defaultdict(float)
    starts = [iv[0] for iv in timeline]
    for a, b in idle:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(timeline) and timeline[i][0] < b:
            s, e, phase = timeline[i]
            i += 1
            d = min(b, e) - max(a, s)
            if d > 0:
                got[phase] += d
                covered += d
        got["no_span"] += (b - a) - covered
    return {p: got.get(p, 0.0) for p in PHASES}


def device_wait(w) -> dict:
    parts = defaultdict(float)
    for segs in w.requests.values():
        prev = None
        for s in segs:
            parts["device_wait"] += s.start - s.submit
            parts["launch_minus_submit"] += s.launch - s.submit
            own = 0.0
            if prev is not None:
                own = max(0.0, min(prev.released, s.start)
                          - max(prev.start, s.launch))
            hi, lo = w.waits_behind(s)
            parts["own_previous_turn"] += own
            parts["behind_hi"] += hi
            parts["behind_lo"] += lo
            parts["rest"] += (s.start - s.launch) - own - hi - lo
            prev = s
    n = max(1, len(w.requests))
    out = {k: 1e3 * v / n for k, v in parts.items()}
    named = sum(out[k] for k in ("launch_minus_submit", "own_previous_turn",
                                 "behind_hi", "behind_lo"))
    out["named_over_device_wait"] = (named / out["device_wait"]
                                     if out.get("device_wait") else None)
    out["requests"] = len(w.requests)
    return out


def analyse(win, ex, events) -> dict:
    class View:          # what spanlog reads of a run
        t0 = win["t0"]
        t_end = win["t0"] + win["seconds"]
        seconds = win["seconds"]
    w = spanlog.window(View)
    if w is None:
        raise RuntimeError("the span log is missing or dropped spans")
    clock = clock_offset(events)
    off = clock["offset"]
    w0, w1 = (t * 1e-9 - off for t in ex["window"])      # host seconds
    shift = traces.align(ex)
    ops = [((s + shift) * 1e-9 - off, (s + shift + d) * 1e-9 - off)
           for _, s, d in ex["ops"]]
    busy = traces._union([[max(a, w0), min(b, w1)] for a, b in ops
                          if a < w1 and b > w0])
    idle, edge = [], w0
    for a, b in busy:
        if a > edge:
            idle.append((edge, a))
        edge = max(edge, b)
    if edge < w1:
        idle.append((edge, w1))
    split = idle_by_phase(idle, thread_timeline(w, 0))
    idle_total = sum(b - a for a, b in idle)
    return {
        "offset_us": {"drift": 1e6 * clock["drift"],
                      "delay": 1e6 * clock["delay"],
                      "events": clock["events"]},
        "window_s": w1 - w0,
        "idle_total_s": idle_total,
        "idle_s": split,
        "idle_covered_share": (1.0 - split["no_span"] / idle_total
                               if idle_total else None),
        "device_wait": device_wait(w),
        "phases_add_up": all(
            abs((s.dispatched - s.start) + (s.end - s.dispatched)
                + (s.booked - s.end) + (s.released - s.booked)
                - (s.released - s.start)) < 1e-9
            for s in w.all_segments),
        "collector": collector(w, win),
        "gaps": gaps(w),
    }


def gaps(w) -> dict:
    def ms(values):
        values = sorted(values)
        return ([1e3 * values[len(values) // 2], 1e3 * values[-1]]
                if values else None)
    closed_by = defaultdict(int)
    for g in w.gaps:
        closed_by[g.closed_by] += 1
    return {"opened": len(w.gaps), "filled": sum(g.fills > 0 for g in w.gaps),
            "closed_by": dict(closed_by),
            "predicted_ms": ms(g.predicted for g in w.gaps),
            "actual_ms": ms(g.closed - g.opened for g in w.gaps
                            if g.closed_by == "submit"),
            "lower_segment_ms": ms(s.end - s.start for s in w.segments
                                   if s.priority != w.hi)}


def collector(w, win) -> dict:
    longest = max(w.collections, key=lambda c: c.end - c.start,
                  default=None)
    return {"pauses": len(w.collections),
            "pause_s": sum(c.end - c.start for c in w.collections),
            "longest_s": longest.end - longest.start if longest else 0.0,
            "longest_generation": longest.generation if longest else None,
            "feeder_lag_max_s": win["feeder_lag_max_s"]}


def main(argv=None, root: Path = run.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    kept = {}
    extract, window = traces.extract, cells.Cell.window

    def keep_extract(trace_dir):
        kept["events"] = segment_events(trace_dir)
        kept["ex"] = extract(trace_dir)
        return kept["ex"]

    def keep_window(self, *a, **kw):
        kept["win"] = window(self, *a, **kw)
        return kept["win"]

    traces.extract, cells.Cell.window = keep_extract, keep_window
    try:
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"],
                      root=root)
    finally:
        traces.extract, cells.Cell.window = extract, window
    if rc != 0:
        return rc
    got = analyse(kept["win"], kept["ex"], kept["events"])
    got.update(workload=args.workload, seed=args.seed)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(got, indent=1))
    print(json.dumps(got))
    return 0


if __name__ == "__main__":
    sys.exit(main())
