"""Wall-clock engine + hook-client integration tests (real threads, tiny
sleep-based kernels so tests are fast and robust)."""
import threading
import time

import pytest

from repro.core.client import HookClient, Segment
from repro.core.executor import WallClockEngine
from repro.core.profiler import ProfiledData, Profiler
from repro.core.scheduler import Mode
from repro.core.task import TaskKey

pytestmark = pytest.mark.fast


def sleep_segments(name, n, dur, host_gap=0.0):
    def fn(state):
        time.sleep(dur)
        return state
    hw = (lambda s: (time.sleep(host_gap), s)[1]) if host_gap else None
    return [Segment(f"{name}{i}", fn, host_work=hw) for i in range(n)]


def test_engine_runs_and_records():
    key = TaskKey("svc")
    with WallClockEngine(Mode.SHARING) as eng:
        cl = HookClient(eng, key, 0, sleep_segments("s", 4, 0.002))
        _, jct = cl.run("state")
    recs = eng.records()
    assert len(recs) == 4
    assert jct >= 0.008
    # serial device: no overlapping intervals
    recs = sorted(recs, key=lambda r: r.start)
    for a, b in zip(recs, recs[1:]):
        assert b.start >= a.end - 1e-9


def test_measurement_produces_profile():
    key = TaskKey("svc")
    prof = Profiler(key)
    with WallClockEngine(Mode.EXCLUSIVE) as eng:
        cl = HookClient(eng, key, 0,
                        sleep_segments("m", 3, 0.004, host_gap=0.003))
        for _ in range(3):
            cl.measure_run("state", prof)
    stats = prof.statistics()
    assert stats.runs == 3
    assert len(stats.SK) == 3
    for v in stats.SK.values():
        assert 0.003 < v < 0.02          # ~4ms measured
    for v in stats.SG.values():
        assert v > 0.002                 # host gap visible as device idle


def test_exclusive_serializes_tasks():
    key_a, key_b = TaskKey("a"), TaskKey("b")
    order = []

    def seg(name):
        def fn(state):
            order.append(name)
            time.sleep(0.003)
            return state
        return [Segment(name + str(i), fn) for i in range(3)]

    with WallClockEngine(Mode.EXCLUSIVE) as eng:
        ca = HookClient(eng, key_a, 0, seg("a"))
        cb = HookClient(eng, key_b, 0, seg("b"))
        ta = threading.Thread(target=lambda: ca.run("x"))
        tb = threading.Thread(target=lambda: cb.run("x"))
        ta.start()
        time.sleep(0.005)
        tb.start()
        ta.join(); tb.join()
    # no interleaving: all of one task before the other
    joined = "".join(order)
    assert joined in ("aaabbb", "bbbaaa")


def test_fikit_mode_prioritizes_and_fills():
    key_hi, key_lo = TaskKey("hi"), TaskKey("lo")
    segs_hi = sleep_segments("hi", 5, 0.002, host_gap=0.006)
    segs_lo = sleep_segments("lo", 8, 0.002)

    # profile both
    pd = ProfiledData()
    for key, segs in ((key_hi, segs_hi), (key_lo, segs_lo)):
        prof = Profiler(key)
        with WallClockEngine(Mode.EXCLUSIVE) as eng:
            cl = HookClient(eng, key, 0, segs)
            for _ in range(3):
                cl.measure_run("x", prof)
        pd.load(prof.statistics())

    with WallClockEngine(Mode.FIKIT, pd) as eng:
        hi = HookClient(eng, key_hi, 0, segs_hi)
        lo = HookClient(eng, key_lo, 5, segs_lo)
        res = {}
        tl = threading.Thread(
            target=lambda: res.setdefault("lo", lo.run("x")[1]))
        th = threading.Thread(
            target=lambda: res.setdefault("hi", hi.run("x")[1]))
        tl.start()
        time.sleep(0.004)
        th.start()
        th.join(); tl.join()
        fills = eng.fill_count
    solo_hi = 5 * 0.002 + 4 * 0.006
    # high-priority stays near its solo JCT (some fills may overshoot)
    assert res["hi"] < solo_hi * 2.2
    assert fills > 0                     # low kernels ran inside hi's gaps
    assert res["lo"] > 0


def test_multi_device_threads_spread_and_steal():
    """devices=2: two real device threads. A pinned discipline co-locates
    hi+lo on device 0 (lo parks behind the hi holder) and sends tiny to
    device 1; when tiny retires, device 1 goes idle and must steal the
    fully-parked lo — across threads, with stream order preserved."""
    from repro.core.kernel_id import KernelID
    from repro.core.task import KernelRequest

    def pin(layer, instance, key, priority, arrival):
        return 1 if key.process == "tiny" else 0

    def sleeper(dur):
        def call():
            time.sleep(dur)
        return call

    def reqs_for(key, prio, inst, n, dur):
        return [KernelRequest(task_key=key, kernel_id=KernelID(f"{key.process}/k"),
                              priority=prio, task_instance=inst, seq_index=i,
                              payload=sleeper(dur)) for i in range(n)]

    key_hi, key_lo, key_tiny = TaskKey("hi"), TaskKey("lo"), TaskKey("tiny")
    with WallClockEngine(Mode.FIKIT, devices=2, discipline=pin) as eng:
        # tiny FIRST: it must occupy device 1, otherwise lo's first parked
        # submit already finds device 1 idle and steals immediately
        eng.task_begin(3, key_tiny, 9)
        tiny_futs = [eng.submit(r)
                     for r in reqs_for(key_tiny, 9, 3, 1, 0.02)]
        eng.task_begin(1, key_hi, 0)
        hi_futs = [eng.submit(r) for r in reqs_for(key_hi, 0, 1, 4, 0.02)]
        eng.task_begin(2, key_lo, 5)         # parks behind the hi holder
        lo_futs = [eng.submit(r) for r in reqs_for(key_lo, 5, 2, 2, 0.003)]
        assert eng.steal_count == 0          # both devices busy: no steal
        for f in tiny_futs:
            f.result(timeout=5)
        eng.task_end(3)                      # device 1 idle -> steal lo
        assert eng.steal_count == 1          # synchronous under the lock
        for f in lo_futs:                    # stolen work actually runs
            f.result(timeout=5)
        eng.task_end(2)
        for f in hi_futs:
            f.result(timeout=5)
        eng.task_end(1)
        recs = eng.records()
    by_task = {}
    for r in recs:
        by_task.setdefault(r.req.task_instance, []).append(r)
    # lo migrated: both kernels ran on device 1, in seq order
    assert [r.device for r in by_task[2]] == [1, 1]
    lo_sorted = sorted(by_task[2], key=lambda r: r.start)
    assert [r.req.seq_index for r in lo_sorted] == [0, 1]
    # hi stayed on device 0 and was never blocked behind lo
    assert all(r.device == 0 for r in by_task[1])
    # per-device serial execution
    for d in (0, 1):
        spans = sorted((r.start, r.end) for r in recs if r.device == d)
        for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
            assert s1 >= e0 - 1e-9


# ------------------------------------------------- equal-priority order
def _equal_priority_tasks(mode, names=("a", "b"), n=4, **engine_kw):
    """Tasks ``names``, all priority 0, run through ``run_async`` and begun
    in that order. The first task's first segment holds the device until
    every later task's first segment is queued behind it. Returns the
    order segments ran in, as ``<task><seq>``, and the engine's records."""
    order = []
    started, go = threading.Event(), threading.Event()

    def segments(name):
        def seg(i):
            def fn(state):
                order.append(f"{name}{i}")
                if name == names[0] and i == 0:
                    started.set()
                    go.wait(5)
                time.sleep(0.001)
                return state
            return fn
        return [Segment(f"{name}{i}", seg(i)) for i in range(n)]

    errors, all_done = {}, threading.Event()

    def on_done(name):
        def done(result, jct, err):
            errors[name] = err
            if len(errors) == len(names):
                all_done.set()
        return done

    with WallClockEngine(mode, **engine_kw) as eng:
        clients = [HookClient(eng, TaskKey(nm), 0, segments(nm))
                   for nm in names]
        clients[0].run_async("x", on_done(names[0]))
        assert started.wait(5)
        for nm, cl in zip(names[1:], clients[1:]):
            cl.run_async("x", on_done(nm))
        go.set()
        assert all_done.wait(5)
    assert errors == {nm: None for nm in names}
    return order, eng.records()


def _runs_of(names, n):
    return [f"{nm}{i}" for nm in names for i in range(n)]


def _turns_of(names, n):
    return [f"{nm}{i}" for i in range(n) for nm in names]


@pytest.mark.parametrize("mode", [Mode.FIKIT, Mode.PREEMPT])
def test_equal_priority_tasks_run_in_task_begin_order(mode):
    """Once both are in flight, every remaining segment of the earlier
    task runs before the later task's queued segment."""
    order, _ = _equal_priority_tasks(mode)
    assert order == _runs_of("ab", 4)


def test_three_equal_priority_tasks_run_earliest_begun_first():
    order, _ = _equal_priority_tasks(Mode.FIKIT, names="abc", n=3)
    assert order == _runs_of("abc", 3)


@pytest.mark.parametrize("mode,discipline", [
    (Mode.SHARING, "fifo"), (Mode.FIKIT, "edf"), (Mode.FIKIT, "sjf")],
    ids=["sharing", "edf", "sjf"])
def test_equal_priority_tasks_keep_launch_order(mode, discipline):
    """SHARING, and ``edf``/``sjf`` levels, serve the device queue in
    launch order: two tasks in flight take one segment each in turn."""
    order, _ = _equal_priority_tasks(mode, queue_discipline=discipline)
    assert order == _turns_of("ab", 4)


def test_lower_priority_request_queued_first_keeps_its_place():
    """A queued lo request is not passed by a hi request launched after
    it: the device queue reorders only among equal priorities."""
    from repro.core.kernel_id import KernelID
    from repro.core.task import KernelRequest

    order = []
    started, go = threading.Event(), threading.Event()

    def payload(name, gate=False):
        def call():
            order.append(name)
            if gate:
                started.set()
                go.wait(5)
        return call

    def req(key, prio, inst, seq, gate=False):
        return KernelRequest(task_key=key,
                             kernel_id=KernelID(f"{key.process}/k"),
                             priority=prio, task_instance=inst,
                             seq_index=seq,
                             payload=payload(f"{key.process}{seq}", gate))

    key_lo, key_hi = TaskKey("lo"), TaskKey("hi")
    with WallClockEngine(Mode.FIKIT) as eng:
        eng.task_begin(1, key_lo, 5)
        futs = [eng.submit(req(key_lo, 5, 1, 0, gate=True)),
                eng.submit(req(key_lo, 5, 1, 1))]
        assert started.wait(5)
        eng.task_begin(2, key_hi, 0)
        futs.append(eng.submit(req(key_hi, 0, 2, 0)))
        go.set()
        for f in futs:
            f.result(timeout=5)
        eng.task_end(1)
        eng.task_end(2)
        recs = eng.records()
    assert order == ["lo0", "lo1", "hi0"]
    assert all(r.ahead == 0 for r in recs)


@pytest.mark.parametrize("mode", [Mode.FIKIT, Mode.SHARING])
def test_ahead_counts_the_requests_each_segment_was_taken_over(mode):
    """In FIKIT mode each of a's segments after the first is taken ahead of
    b's queued first segment (1), and b then runs alone (0). SHARING
    never reorders. The span log carries the same field."""
    from repro.core import spans

    spans.clear()
    _, recs = _equal_priority_tasks(mode)
    got = {(r.req.task_key.process, r.req.seq_index): r.ahead for r in recs}
    if mode is Mode.FIKIT:
        want = {("a", i): int(i > 0) for i in range(4)}
        want.update({("b", i): 0 for i in range(4)})
    else:
        want = {k: 0 for k in got}
    assert got == want
    logged = {(s.service, s.seq): s.ahead
              for s in spans.read(spans.SEGMENT)}
    assert logged == want
    spans.clear()
