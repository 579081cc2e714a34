"""One benchmark cell: a co-located pair served through the FIKIT path.

``Cell.setup`` draws both models' weights from the seed, builds each
service over the program's ``SegmentedService``, warms every program the
window runs (the head's host sampling and the input path included),
starts a ``ServingSystem`` with the admission plane in FIKIT mode and
onboards both services (the paper's measurement phase).

``Cell.window`` drives ``ServingSystem.submit_async`` for both services
for a fixed number of seconds: the high-priority stream open loop on the
traffic file's schedule, the low-priority stream as a closed-loop
backlog. It returns plain records of what happened; ``Cell.close`` then
stops the system and drops every reference to the program's state, and
``check`` compares the served tokens with the reference.

Three things the program does not give, the harness supplies from here:

- a request's own input: each service's ``make_input`` is replaced by a
  feed that hands out the next request's seeded tokens;
- a request's output: the head's host work is wrapped so its sampled
  tokens, and the moment they reach the host, are recorded;
- a bounded memory: the engine keeps every request's payload, and each
  payload holds the segment's input. Segment inputs therefore travel in
  a one-item ``Carry`` list that the segment empties when it runs, so the
  kept payloads hold nothing on the device.

What depends on the program family of a role's ``arch``, the harness
takes from that family's module, ``benchlib/families/<family>.py``,
found by name when the role is built (a family with no module is refused
there): the weights' leaves and the program's parameter tree, and the
reference's forward pass. The rest is shared by every family: drawing
the weights from the seed (``weights``), the prompts (``traffic``), the
timed window, and the gap a served token is judged by
(``reference.token_gaps``).

In a traced run (``annotate``), each segment runs inside a
``jax.profiler.TraceAnnotation`` named ``<role>/<program>`` so the trace
can tell the two services apart; untraced runs carry no annotation.
"""
from __future__ import annotations

import gc
import threading
import time

import jax
import numpy as np

from benchlib import families, traffic
from benchlib.reference import token_gaps
from benchlib.weights import make_weights, seed32

GRACE_S = 60.0
TRACE_S = 3.0


class Carry(list):
    """A segment's input or output as a one-item list, tagged with the
    request it belongs to. The program's kernel identification reads the
    arrays inside a list, so identification is unchanged."""

    __slots__ = ("rid",)

    def __init__(self, item, rid):
        super().__init__((item,))
        self.rid = rid


class Feed:
    """Hands out the seeded prompt of each next request of one role."""

    def __init__(self, seed, role, vocab, batch, seq):
        self.seed, self.role = seed, role
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self.phase, self.n = 0, 0
        self._lock = threading.Lock()

    def start_phase(self, phase: int) -> None:
        with self._lock:
            self.phase, self.n = phase, 0

    def make_input(self, key=None):
        with self._lock:
            rid = (self.phase, self.n)
            self.n += 1
        toks = traffic.tokens(self.seed, self.role, rid[0], rid[1],
                              self.vocab, self.batch, self.seq)
        return Carry(jax.device_put(toks), rid)


class Role:
    """One served model of the pair, as the configuration file states."""

    def __init__(self, name: str, spec: dict, cls: dict, seed: int,
                 annotate: bool = False):
        from repro.config import get_config
        self.name = name
        self.m = dict(spec["model"])
        self.batch, self.seq = int(spec["batch"]), int(spec["seq"])
        self.cfg = get_config(spec["arch"]).replace(**self.m)
        #: the ``benchlib.families`` module of the program's family
        self.family = families.find(self.cfg.family)
        self.annotate = annotate
        self.qos = cls["name"]
        self.cls = cls
        self.feed = Feed(seed, name, self.m["vocab_size"], self.batch,
                         self.seq)
        self.results = {}          # rid -> (host time, sampled tokens)
        self.service = None

    def build(self, seed: int) -> None:
        from repro.core.task import TaskKey
        from repro.models.segmentation import SegmentedService
        from repro.serving.engine import InferenceService

        w = jax.block_until_ready(make_weights(self.family, self.m, seed))
        svc = SegmentedService(self.cfg,
                               self.family.program_tree(self.m, w),
                               self.batch, self.seq)
        del w
        svc.make_input = self.feed.make_input
        for seg in svc.segments:
            self._wrap(seg)
        service = InferenceService.__new__(InferenceService)
        service.cfg, service.priority = self.cfg, int(self.cls["priority"])
        service.key = TaskKey(self.cfg.name, (self.batch, self.seq))
        service.svc, service.profiled = svc, False
        self.service = service

    def _wrap(self, seg) -> None:
        label = f"{self.name}/{seg.name.rsplit('/', 1)[-1]}"
        fn, host_work = seg.fn, seg.host_work
        if self.annotate:
            fn = _spanned(fn, label)

        def run(carry):
            return Carry(fn(carry.pop()), carry.rid)
        seg.fn = run
        if host_work is None:
            return
        if self.annotate:
            host_work = _spanned(host_work, label + ".host")
        results = self.results

        def work(carry):
            rid = carry.rid
            toks = host_work(carry.pop())
            results[rid] = (time.perf_counter(), np.asarray(toks))
            return toks
        seg.host_work = work

    def warm(self) -> None:
        """Run one request through every segment and its host work."""
        state = self.feed.make_input()
        for seg in self.service.svc.segments:
            state = seg.fn(state)
            if seg.host_work is not None:
                state = seg.host_work(state)


class CompileCounter:
    """Counts executables compiled or loaded from the persistent cache
    (``n``), and those of them the cache did not hold (``misses``)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.n = self.misses = 0

        def on_duration(name, secs, **kw):
            if name == self.EVENT:
                self.n += 1

        def on_event(name, **kw):
            if name == self.MISS:
                self.misses += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Cell:
    def __init__(self, config: dict, traffic_spec: dict, seed: int,
                 annotate: bool = False):
        self.config, self.seed = config, seed
        self.traffic = traffic.check_traffic(traffic_spec)
        serving = config["serving"]
        self.roles = {r: Role(r, config["roles"][r], serving["classes"][r],
                              seed, annotate) for r in traffic.ROLES}
        self.system = None
        self.compiles = CompileCounter()
        self.solo_jct_s = {}
        self.hbm_after_setup = {}
        self.setup_programs = (0, 0)

    # ----------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.core.scheduler import Mode
        from repro.serving import QoSClass, ServingSystem

        for role in self.roles.values():
            role.build(self.seed)
            role.warm()
        serving = self.config["serving"]
        classes = tuple(QoSClass(c["name"], priority=int(c["priority"]),
                                 queue_limit=int(c["queue_limit"]),
                                 deadline=None,
                                 max_batch=int(c["max_batch"]))
                        for c in serving["classes"].values())
        self.system = ServingSystem(
            Mode(serving["mode"]), measure_runs=int(serving["measure_runs"]),
            admission={"classes": classes,
                       "max_inflight": int(serving["max_inflight"])}).start()
        for role in self.roles.values():
            self.solo_jct_s[role.name] = self.system.onboard(role.service)
        for role in self.roles.values():       # the admission path, once
            self.system.submit_async(role.service, role.qos).result(GRACE_S)
        self.hbm_after_setup = device_memory()
        self.setup_programs = (self.compiles.n, self.compiles.misses)
        # what set-up made lives to the end of the run: leave it out of
        # the collector's full passes, as a long-running server does
        gc.collect()
        gc.freeze()

    # ----------------------------------------------------------- window
    def window(self, seconds: float, phase: int = 1, rate=None,
               trace_dir=None) -> dict:
        hi, lo = self.roles["hi"], self.roles["lo"]
        for role in (hi, lo):
            role.feed.start_phase(phase)
        rate = float(self.traffic["hi"]["rate_per_s"] if rate is None
                     else rate)
        arrivals = traffic.hi_arrivals(rate, seconds)
        backlog = int(self.traffic["lo"]["backlog"])
        system, engine = self.system, self.system.engine
        stop = threading.Event()
        lo_tickets = []

        def lo_client():
            while not stop.is_set():
                t = system.submit_async(lo.service, lo.qos)
                lo_tickets.append(t)
                t.result(GRACE_S)

        traced = {}
        threads = [threading.Thread(target=lo_client, name=f"lo-client-{i}")
                   for i in range(backlog)]
        compiles0 = self.compiles.n
        fills0 = engine.fill_count
        n_rec0 = len(engine.records())
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        if trace_dir is not None:
            tracer = threading.Thread(
                target=_trace, args=(t0 + 0.3 * seconds,
                                     min(TRACE_S, 0.5 * seconds),
                                     trace_dir, traced), name="tracer")
            tracer.start()
            threads.append(tracer)
        hi_sent, lag_max = [], 0.0
        for a in arrivals:
            due = t0 + a
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            else:
                lag_max = max(lag_max, now - due)
            hi_sent.append((due, system.submit_async(hi.service, hi.qos)))
        t_end = t0 + seconds
        now = time.perf_counter()
        if t_end > now:
            time.sleep(t_end - now)
        fills = engine.fill_count - fills0
        compiles = self.compiles.n - compiles0
        stop.set()
        give_up = time.perf_counter() + GRACE_S
        for _, t in hi_sent:
            t.result(max(0.0, give_up - time.perf_counter()))
        for th in threads:
            th.join(max(0.0, give_up - time.perf_counter()))
        if any(th.is_alive() for th in threads):
            raise RuntimeError("a client or the tracer did not finish "
                               f"within {GRACE_S} s of the window's close")

        # plain records only: nothing below refers to the program's state
        accepted = [(due, t) for due, t in hi_sent if t.outcome != "rejected"]
        hi_req = []
        for k, (due, t) in enumerate(accepted):
            res = hi.results.get((phase, k))
            ok = t.outcome == "completed" and res is not None
            hi_req.append({
                "rid": (phase, k), "ok": ok,
                "latency_s": (res[0] - due) if ok else None,
                "done_s": res[0] if ok else None,
                "ticket_latency_s": t.latency, "jct_s": t.jct})
        n_hi_fail = (len(hi_sent) - len(accepted)
                     + sum(not r["ok"] for r in hi_req))
        lo_done = sorted(rid for rid, (t_host, _) in lo.results.items()
                         if rid[0] == phase and t_host <= t_end)
        n_lo_fail = sum(t.outcome != "completed" for t in lo_tickets)
        recs = engine.records()[n_rec0:]
        by_inst = {}
        for r in recs:
            by_inst.setdefault(r.req.task_instance, []).append(
                (r.req.seq_index, r.req.submit_time, r.start, r.end,
                 r.req.task_key == hi.service.key))
        return {
            "seconds": seconds, "rate_per_s": rate, "t0": t0,
            "offered": {"hi": len(hi_sent), "lo": len(lo_tickets)},
            "failed": {"hi": n_hi_fail, "lo": n_lo_fail},
            "hi": hi_req,
            "lo_done": lo_done,
            "segments": [sorted(v) for v in by_inst.values()
                         if v[0][4] and min(x[1] for x in v) >= t0],
            "fills": fills, "compiles": compiles,
            "feeder_lag_max_s": lag_max,
            "refused": {"hi": len(hi_sent) - len(accepted),
                        "lo": sum(t.outcome == "rejected"
                                  for t in lo_tickets)},
            "trace_dir": traced.get("dir"),
        }

    # ----------------------------------------------------------- close
    def close(self) -> None:
        """Stop the system and drop the program's state from the device."""
        if self.system is not None:
            self.system.stop()
        self.system = None
        for role in self.roles.values():
            role.service = None
        # set-up's objects were frozen out of the collector's passes, and
        # the serving system's reference cycles hold the weights: thaw
        # them, or nothing here is freed
        gc.unfreeze()
        gc.collect()

    # ----------------------------------------------------------- check
    def check(self, win: dict, limits: dict, precision: str = "float32"):
        """The widest gap, per role, between the reference's best logit
        and the logit of the token the program served, over a sample of
        the window's completed requests drawn from the seed."""
        rng = np.random.default_rng([seed32(self.seed), 99])
        done = {"hi": sorted(r["rid"] for r in win["hi"] if r["ok"]),
                "lo": win["lo_done"]}
        want = self.config["check"]["requests"]
        out = {}
        for name, role in self.roles.items():
            rids = done[name]
            pick = [rids[i] for i in sorted(rng.choice(
                len(rids), size=min(len(rids), int(want[name])),
                replace=False))] if rids else []
            w = make_weights(role.family, role.m, self.seed)
            widest = None
            for rid in pick:
                toks = traffic.tokens(self.seed, name, rid[0], rid[1],
                                      role.m["vocab_size"], role.batch,
                                      role.seq)
                ref = role.family.served_logits(role.m, w, toks)
                if precision == "float32":
                    served = role.results[rid][1]
                else:
                    served = role.family.served_logits(
                        role.m, w, toks, precision).argmax(-1)
                g = float(token_gaps(ref, served).max())
                g = g if np.isfinite(g) else float("inf")
                widest = g if widest is None else max(widest, g)
            del w
            if widest is not None and not np.isfinite(widest):
                widest = None
            out[f"gap.{name}"] = {"value": widest,
                                  "limit": limits[f"gap.{name}"],
                                  "requests": len(pick)}
        return out


def _spanned(fn, label: str):
    """``fn`` run inside a profiler span named ``label``."""
    def spanned(x):
        with jax.profiler.TraceAnnotation(label):
            return fn(x)
    return spanned


def checks_pass(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def _trace(start_at, length, trace_dir, traced) -> None:
    now = time.perf_counter()
    if start_at > now:
        time.sleep(start_at - now)
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            time.sleep(length)
    finally:
        jax.profiler.stop_trace()
    traced["dir"] = trace_dir


def device_memory() -> dict:
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}
