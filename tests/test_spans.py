"""The span log of the served path (``repro.core.spans``): segment stamps
and phases, host work, gaps and their fills, admission tickets, the
collector, the log's bound, the profiler form of each span, and what the
serving engine keeps (a bounded decision trace, records without
payloads)."""
import functools
import gc
import glob
import os
import re
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import pytest

from repro.config import get_config
from repro.core import spans
from repro.core.client import HookClient, Segment
from repro.core.executor import WallClockEngine
from repro.core.policy import DEFAULT_RING, RingTrace
from repro.core.profiler import ProfiledData, Profiler
from repro.core.scheduler import Mode
from repro.core.task import TaskKey
from repro.models import segmentation
from repro.serving import QoSClass, ServingSystem
from repro.serving.engine import InferenceService

STAMPS = ("submit", "launch", "start", "dispatched", "end", "booked",
          "released")


def sleep_segments(name, n, dur, host_gap=0.0):
    def fn(state):
        time.sleep(dur)
        return state
    hw = (lambda s: (time.sleep(host_gap), s)[1]) if host_gap else None
    return [Segment(f"{name}{i}", fn, host_work=hw) for i in range(n)]


def profile(key, segs, runs=3):
    prof = Profiler(key)
    with WallClockEngine(Mode.EXCLUSIVE) as eng:
        cl = HookClient(eng, key, 0, segs)
        for _ in range(runs):
            cl.measure_run("x", prof)
    return prof.statistics()


@pytest.fixture(scope="module")
def filled():
    """A FIKIT engine in which lo kernels fill hi's host gaps."""
    key_hi, key_lo = TaskKey("span-hi"), TaskKey("span-lo")
    segs_hi = sleep_segments("hi", 5, 0.002, host_gap=0.006)
    segs_lo = sleep_segments("lo", 8, 0.002)
    pd = ProfiledData()
    for key, segs in ((key_hi, segs_hi), (key_lo, segs_lo)):
        pd.load(profile(key, segs))
    t0 = time.perf_counter()
    with WallClockEngine(Mode.FIKIT, pd) as eng:
        hi = HookClient(eng, key_hi, 0, segs_hi)
        lo = HookClient(eng, key_lo, 5, segs_lo)
        tl = threading.Thread(target=lo.run, args=("x",))
        th = threading.Thread(target=hi.run, args=("x",))
        tl.start()
        time.sleep(0.004)
        th.start()
        th.join()
        tl.join()
    return {"engine": eng, "spans": spans.read(since=t0)}


@pytest.fixture(scope="module")
def served():
    """Two 2-layer jitted services behind the admission plane."""
    hi = InferenceService(get_config("qwen3-4b").reduced(), priority=0,
                          batch=1, seq=16)
    lo = InferenceService(get_config("stablelm-1.6b").reduced(),
                          priority=5, batch=2, seq=16)
    classes = (QoSClass("gold", priority=0), QoSClass("bronze", priority=5))
    with ServingSystem(Mode.FIKIT, measure_runs=2,
                       admission={"classes": classes,
                                  "max_inflight": 8}) as system:
        system.onboard(hi)
        system.onboard(lo)
        t0 = time.perf_counter()
        tickets = ([system.submit_async(lo, "bronze") for _ in range(3)]
                   + [system.submit_async(hi, "gold") for _ in range(3)])
        for t in tickets:
            assert t.result(60) == "completed"
        engine = system.engine
    # read after stop(): the log outlives the serving system
    return {"engine": engine, "tickets": tickets,
            "spans": spans.read(since=t0)}


def of(kind, got):
    return [s for s in got if s.kind == kind]


# ---------------------------------------------------------------- segments
@pytest.mark.parametrize("case", ["sleep", "jitted"])
def test_segment_stamps_are_monotone_and_phases_add_up(case, filled,
                                                       served):
    got = (filled if case == "sleep" else served)["spans"]
    segs = of(spans.SEGMENT, got)
    assert segs
    for s in segs:
        stamps = [getattr(s, k) for k in STAMPS]
        assert stamps == sorted(stamps), s
        phases = ((s.dispatched - s.start) + (s.end - s.dispatched)
                  + (s.booked - s.end) + (s.released - s.booked))
        assert phases == pytest.approx(s.released - s.start, abs=1e-9)
    if case == "jitted":
        # the wait is stamped apart from the dispatch in every segment
        assert all(s.dispatched < s.end for s in segs)
        assert {s.service for s in segs} == {"qwen3-4b-reduced",
                                             "stablelm-1.6b-reduced"}


def test_segment_spans_match_the_engine_records(served):
    segs = {(s.instance, s.seq): s for s in of(spans.SEGMENT,
                                               served["spans"])}
    recs = [r for r in served["engine"].records()
            if (r.req.task_instance, r.req.seq_index) in segs]
    assert len(recs) == len(segs)
    for r in recs:
        s = segs[r.req.task_instance, r.req.seq_index]
        assert (s.submit, s.start, s.end, s.released) == (
            r.req.submit_time, r.start, r.end, r.released)
        assert s.priority == r.req.priority and s.device == r.device


@pytest.mark.parametrize("case", ["sync", "async"])
def test_host_work_spans_join_their_segment(case, filled, served):
    got = (filled if case == "sync" else served)["spans"]
    segs = {(s.instance, s.seq): s for s in of(spans.SEGMENT, got)}
    works = of(spans.HOST_WORK, got)
    assert works
    for h in works:
        seg = segs[h.instance, h.seq]
        assert (h.service, h.priority, h.device) == (
            seg.service, seg.priority, seg.device)
        assert seg.end <= h.start <= h.end
        if case == "async":
            # the completion callback runs it on the device thread
            assert seg.booked <= h.start and h.end <= seg.released


# -------------------------------------------------------------------- gaps
def test_gap_span_records_the_fills_launched_into_it(filled):
    gaps = of(spans.GAP, filled["spans"])
    # lo holds the device until hi begins, so both open gaps
    assert {g.service for g in gaps} <= {"span-hi", "span-lo"}
    assert any(g.service == "span-hi" and g.fills for g in gaps)
    assert sum(g.fills for g in gaps) == filled["engine"].fill_count > 0
    for g in gaps:
        assert g.opened <= g.closed and g.predicted > 0
        assert g.overshoot >= 0.0
        assert g.closed_by in ("submit", "end", "reopen", "pause")
        if g.closed_by == "submit":
            assert g.seq > 0
    segs = of(spans.SEGMENT, filled["spans"])
    fills = [s for s in segs if s.filler]
    assert len(fills) == filled["engine"].fill_count
    # every fill was launched while some gap stood open
    for f in fills:
        assert any(g.opened <= f.launch <= g.closed for g in gaps)


# --------------------------------------------------------------- admission
def test_admission_span_names_the_instance_it_launched(served):
    got = served["spans"]
    adm = of(spans.ADMISSION, got)
    assert len(adm) == len(served["tickets"])
    by_inst = {}
    for s in of(spans.SEGMENT, got):
        by_inst.setdefault(s.instance, set()).add((s.service, s.priority))
    for a in adm:
        assert a.outcome == "completed"
        assert a.arrival <= a.popped <= a.invoked <= a.resolved
        assert by_inst[a.instance] == {(a.service, a.priority)}
    assert {a.priority for a in adm} == {0, 5}


# --------------------------------------------------------------- collector
def test_collector_spans_and_counters():
    t0 = time.perf_counter()
    before = dict(spans.counters)
    gc.collect()
    got = spans.read(spans.GC, since=t0)
    assert any(c.generation == 2 for c in got)
    assert all(c.start <= c.end and c.collected >= 0 for c in got)
    assert spans.counters["gc_pauses"] >= before["gc_pauses"] + 1
    assert spans.counters["gc_pause_s"] > before["gc_pause_s"]


# --------------------------------------------------------------------- log
def test_log_is_bounded_counts_drops_and_says_where(monkeypatch):
    spans.clear()
    monkeypatch.setattr(spans, "CAPACITY", 8)
    try:
        for i in range(20):
            spans.record((spans.GC, float(i), float(i) + 0.5, 0, 0))
        got = spans.read(spans.GC)
        assert [c.start for c in got] == [float(i) for i in range(12, 20)]
        assert spans.dropped() == 12
        assert not spans.complete_since(11.0)     # span 11 gave way
        assert spans.complete_since(11.5)
    finally:
        spans.clear()


def test_log_is_readable_after_stop():
    key = TaskKey("span-stop")
    t0 = time.perf_counter()
    with WallClockEngine(Mode.FIKIT) as eng:
        HookClient(eng, key, 0, sleep_segments("s", 3, 0.001)).run("x")
    assert eng._stopped
    segs = [s for s in spans.read(spans.SEGMENT, since=t0)
            if s.service == "span-stop"]
    assert [s.seq for s in segs] == [0, 1, 2]


# ---------------------------------------------------------------- profiler
def _traced_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, dict(e.stats)) for plane in pd.planes
            for line in plane.lines for e in line.events
            if e.name.startswith("fikit/")]


def _jitted_segments(n):
    prog = jax.jit(lambda x: x + 1.0)
    fn = lambda s: segmentation._sync(prog(s))  # noqa: E731
    return [Segment(f"j{i}", fn, host_work=(lambda s: s) if i == n - 1
                    else None) for i in range(n)]


def test_profiler_session_gets_fikit_events_with_perf_counter_stamps(
        tmp_path):
    key = TaskKey("span-prof")
    segs = _jitted_segments(3)
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with WallClockEngine(Mode.FIKIT) as eng:
            HookClient(eng, key, 0, segs).run(jnp.zeros(4))
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    events = _traced_events(str(tmp_path))
    names = {n for n, _, _ in events}
    assert {"fikit/segment", "fikit/segment/sync", "fikit/segment/book",
            "fikit/host_work", "fikit/gc"} <= names
    assert not any(n.startswith(("hi/", "lo/", "bench/")) for n in names)
    logged = {s.start: s for s in spans.read(spans.SEGMENT, since=t0)
              if s.service == "span-prof"}
    traced = [(ns, st) for n, ns, st in events if n == "fikit/segment"]
    assert len(traced) == len(logged) == 3
    offsets = []
    for ns, st in traced:
        s = logged[st["start"]]
        assert {k: st[k] for k in STAMPS} == {k: getattr(s, k)
                                              for k in STAMPS}
        offsets.append(ns * 1e-9 - s.start)
    # one clock: the offset from perf_counter to the profiler holds
    assert max(offsets) - min(offsets) < 1e-3


def test_gap_and_admission_events_in_a_profiled_serving_system(tmp_path):
    key_hi = TaskKey("span-prof-gap")
    segs = sleep_segments("g", 4, 0.002, host_gap=0.004)
    pd = ProfiledData()
    pd.load(profile(key_hi, segs, runs=2))

    class Svc:
        def __init__(self):
            self.key, self.priority = key_hi, 0
            self.svc = type("S", (), {"segments": segs,
                                      "make_input": lambda self: "x"})()

        def client(self, engine, identify=True):
            return HookClient(engine, self.key, 0, segs, identify=False)

    system = ServingSystem(Mode.FIKIT, admission={
        "classes": (QoSClass("gold", priority=0),)})
    system.profiles = pd
    jax.profiler.start_trace(str(tmp_path))
    try:
        with system:
            assert system.submit_async(Svc(), "gold").result(10) == \
                "completed"
    finally:
        jax.profiler.stop_trace()
    events = _traced_events(str(tmp_path))
    gaps = [st for n, _, st in events if n == "fikit/gap"]
    adm = [st for n, _, st in events if n == "fikit/admission"]
    assert gaps and all({"predicted", "opened", "closed", "fills"}
                        <= set(st) for st in gaps)
    assert len(adm) == 1 and adm[0]["instance"] > 0


def test_no_annotation_is_built_without_a_session(monkeypatch):
    built = []

    class Never:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, name):
            built.append(name)

    monkeypatch.setattr(spans, "_annotation_cls", Never)
    key = TaskKey("span-quiet")
    with WallClockEngine(Mode.FIKIT) as eng:
        HookClient(eng, key, 0, _jitted_segments(3)).run(jnp.zeros(4))
    gc.collect()
    assert built == []


# ------------------------------------------------- what the engine keeps
def test_serving_engine_keeps_a_bounded_decision_trace():
    with ServingSystem(Mode.FIKIT) as system:
        for policy in system.engine.placement.policies:
            assert isinstance(policy.trace, RingTrace)
            assert policy.trace.maxlen == DEFAULT_RING


class Box:
    """A segment input the test can hold a weak reference to."""


@pytest.mark.parametrize("path", ["run", "run_async"])
def test_a_segment_input_dies_after_its_run(path):
    seg = Segment("w", lambda state: Box())
    box = Box()
    ref = weakref.ref(box)
    with WallClockEngine(Mode.FIKIT) as eng:
        cl = HookClient(eng, TaskKey("span-weak"), 0, [seg, seg])
        if path == "run":
            out, _ = cl.run(box)
        else:
            done = threading.Event()
            cl.run_async(box, lambda result, jct, err: done.set())
            assert done.wait(10)
        del box
        assert ref() is None
        assert all(r.req.payload is None for r in eng.records())


# ------------------------------------------------------ program names
def test_each_service_names_its_programs_and_op_names_stay():
    from repro.models import api
    svcs = {}
    for arch in ("qwen3-4b", "stablelm-1.6b"):
        cfg = get_config(arch).reduced()
        params = api.build_params(cfg, jax.random.key(0))
        svcs[arch] = (segmentation.SegmentedService(cfg, params, 1, 16),
                      params)
    names = {arch: {k: p.__name__ for k, p in svc.programs.items()}
             for arch, (svc, _) in svcs.items()}
    assert names["qwen3-4b"] == {"embed": "qwen3-4b-reduced.embed",
                                 "layer": "qwen3-4b-reduced.layer",
                                 "head": "qwen3-4b-reduced.head"}
    assert set(names["qwen3-4b"].values()).isdisjoint(
        names["stablelm-1.6b"].values())

    svc, params = svcs["qwen3-4b"]
    prog = svc.programs["layer"]
    args = (params["layers"], jnp.asarray(0, jnp.int32),
            svc.programs["embed"](params, svc.make_input()))
    plain = jax.jit(functools.partial(prog.__wrapped__))
    renamed_text = prog.lower(*args).compile().as_text()
    plain_text = plain.lower(*args).compile().as_text()
    assert "jit_qwen3-4b-reduced.layer" in renamed_text
    assert "jit_qwen3-4b-reduced.layer" not in plain_text

    def op_names(text):
        # the instructions as bench/benchlib/trace.py's op_name cuts them
        return [line.split("{", 1)[0].split(" fusion(", 1)[0].strip()
                for line in text.splitlines()
                if re.match(r"\s+(ROOT )?%", line)]
    assert op_names(renamed_text) == op_names(plain_text)
