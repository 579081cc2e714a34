"""What the span log costs a served segment with no profiler session.

    PYTHONPATH=src python -m benchmarks.bench_spans

Times every call a served request of the benchmark's hi service (38
segments: embed, 36 layers, head) makes into ``repro.core.spans``: per
segment the engine's stamps, annotations and record, the dispatch stamp
in ``models.segmentation._sync`` and a gap's open and close; per request
the head's host-work wrapper. The payload, the wait and the host work
themselves are empty. Prints microseconds per segment (median of
rounds), the same for the bare ``ExecRecord`` the engine built before the
log existed, and their difference. Not part of ``benchmarks.run``.
"""
from __future__ import annotations

import statistics
import time

import jax  # noqa: F401  (a served process has jax.profiler loaded)

from repro.core import spans
from repro.core.executor import ExecRecord
from repro.core.kernel_id import KernelID
from repro.core.policy import ActiveTask
from repro.core.task import KernelRequest, TaskKey

N = 500                 # requests per round
SEGMENTS = 38
ROUNDS = 7


def _segment(req, task, gaps, t_launch, host_work):
    """The span-log calls of one served segment, in the engine's order."""
    pc = time.perf_counter
    t0 = pc()
    tm = spans.annotation("fikit/segment")
    wait = spans.begin_wait()                     # models.segmentation._sync
    if wait is not None:
        spans.close(wait)
    t1 = pc()
    rec = ExecRecord(req, t0, t1, False, 0, t_launch,
                     spans.dispatched_since(t0, t1))
    book = tm and spans.annotation("fikit/segment/book")
    gaps.open(task, 1e-3)                         # kernel_end opens a gap
    rec.booked = pc()
    if book is not None:
        spans.close(book)
    call = tm and spans.annotation("fikit/segment/callback")
    if host_work:
        spans.run_host_work(_identity, None, req, 0)
    gaps.close(req.seq_index + 1, "submit")       # the next submit
    if call is not None:
        spans.close(call)
    rec.released = pc()
    spans.record(rec.span())
    spans.close(tm)


def _request(req, task, gaps, t_launch):
    for i in range(SEGMENTS):
        _segment(req, task, gaps, t_launch, i == SEGMENTS - 1)


def _bare_request(req, t_launch):
    pc = time.perf_counter
    for _ in range(SEGMENTS):
        t0 = pc()
        t1 = pc()
        ExecRecord(req, t0, t1, False, 0)


def _identity(x):
    return x


def _per_segment_us(fn, *args) -> float:
    rounds = []
    for _ in range(ROUNDS):
        t = time.perf_counter()
        for _ in range(N):
            fn(*args)
        rounds.append((time.perf_counter() - t) / (N * SEGMENTS) * 1e6)
    return statistics.median(rounds)


def main() -> dict:
    key = TaskKey("svc", (1, 512))
    req = KernelRequest(task_key=key, kernel_id=KernelID("svc/layer"),
                        priority=0, task_instance=1, seq_index=3,
                        submit_time=time.perf_counter())
    task = ActiveTask(1, key, 0, 0.0)
    gaps = spans.GapLog(0)
    out = {"span_log_us_per_segment": _per_segment_us(
               _request, req, task, gaps, time.perf_counter()),
           "bare_record_us_per_segment": _per_segment_us(
               _bare_request, req, time.perf_counter())}
    out["added_us_per_segment"] = (out["span_log_us_per_segment"]
                                   - out["bare_record_us_per_segment"])
    spans.clear()
    for k, v in out.items():
        print(f"{k}: {v:.3f}")
    return out


if __name__ == "__main__":
    main()
