"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``. The cell's
configuration, traffic mix and per-layer metric readers are found by the
names in ``BENCHMARK.json`` (``bench/benchlib/spec.py``). The run sets up
(weights from the seed, warm-up, onboarding), serves both services for
``--seconds`` through the FIKIT admission path, then frees the program's
state and compares what it served with the plain reference.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` they are its per-layer metrics, and a profiler trace of
a few seconds inside the window gives the device's busy time and the
breakdown. The last line of standard output is one JSON object; the last
lines of standard error are each compared number beside its limit. A run
on anything but a TPU, or on fewer chips than the cell asks for, exits
with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import spec as specs  # noqa: E402


class NoChip(RuntimeError):
    pass


def require_accelerator(chips: int):
    """The chips this cell runs on; raises ``NoChip`` off a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def load_peak(root: Path, device_kind: str) -> dict:
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in peaks:
        raise NoChip(f"no peaks for device kind {device_kind!r} in "
                     f"bench/peaks.json")
    return peaks[device_kind]


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, for every program however quick its compile, and with no
    size limit: an evicted program would compile again in a later run."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(root / "bench" / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def end_to_end(win: dict, cell, setup_s: float) -> dict:
    lat = [r["latency_s"] for r in win["hi"] if r["ok"]]
    lo = cell.roles["lo"]
    lo_tokens = len(win["lo_done"]) * lo.batch * lo.seq
    out = {"setup_s": (setup_s, "s"),
           "lo_tokens_per_s": (lo_tokens / win["seconds"], "tokens/s")}
    if lat:
        out["hi_latency_p95_ms"] = (1e3 * float(np.percentile(lat, 95)),
                                    "ms")
        out["hi_latency_p50_ms"] = (1e3 * float(np.percentile(lat, 50)),
                                    "ms")
    return out


def summary_lines(cell, win: dict, mem: dict, setup_s: float) -> list:
    lat = sorted(r["latency_s"] for r in win["hi"] if r["ok"])
    gib = 2 ** 30
    lines = [
        f"setup_s {setup_s:.3f}  hbm after set-up in use "
        f"{cell.hbm_after_setup.get('bytes_in_use', 0) / gib:.3f} GiB, "
        f"peak {cell.hbm_after_setup.get('peak_bytes_in_use', 0) / gib:.3f}"
        f" GiB; after the window peak "
        f"{mem.get('peak_bytes_in_use', 0) / gib:.3f} GiB",
        "solo jct ms (onboarding): " + "  ".join(
            f"{r}: " + ",".join(f"{1e3 * j:.2f}" for j in js)
            for r, js in cell.solo_jct_s.items()),
        f"set-up: {cell.setup_programs[0]} programs, "
        f"{cell.setup_programs[1]} of them compiled (not in the cache)",
        f"hi rate {win['rate_per_s']} /s over {win['seconds']} s; "
        f"feeder lag max {1e3 * win['feeder_lag_max_s']:.3f} ms; "
        f"compiles in window {win['compiles']}",
    ]
    for r in ("hi", "lo"):
        done = (len(lat) if r == "hi" else len(win["lo_done"]))
        lines.append(f"{r}: offered {win['offered'][r]}  completed "
                     f"{done}{' in window' if r == 'lo' else ''}  refused "
                     f"{win['refused'][r]}  failed {win['failed'][r]}")
    if lat:
        lines.append(f"hi latency ms: n {len(lat)} min {1e3 * lat[0]:.3f} "
                     f"p50 {1e3 * float(np.median(lat)):.3f} max "
                     f"{1e3 * lat[-1]:.3f}; fills {win['fills']}")
    return lines


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = specs.load_benchmark(root)
    entry, config, traffic_spec = specs.find_cell(root, bench,
                                                  args.workload)
    try:
        devices = require_accelerator(int(entry["chips"]))
        peak = load_peak(root, devices[0].device_kind)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    use_compile_cache(root)

    from benchlib import trace as traces
    from benchlib.cell import Cell, checks_pass, device_memory
    from benchlib.runview import RunView

    cell = Cell(config, traffic_spec, args.seed,
                annotate=bool(args.trace))
    cell.setup()
    setup_s = time.perf_counter() - T_START
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        win = cell.window(args.seconds,
                          trace_dir=tdir if args.trace else None)
        mem = device_memory()
        reduced = None
        if args.trace:
            if win["trace_dir"] is None:
                raise RuntimeError("the traced sub-window did not run")
            reduced = traces.reduce(traces.extract(win["trace_dir"]))
    for line in summary_lines(cell, win, mem, setup_s):
        print(line)
    view = RunView(cell, win, peak, reduced)
    cell.close()
    checks = cell.check(win, config["check"]["limits"])
    correct = checks_pass(checks) and bool(win["hi"]) and bool(
        win["lo_done"])

    metrics = {}
    if args.trace:
        for m in specs.metrics_for(bench, "per_layer", args.workload):
            v = specs.reader(root, m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for role in ("hi", "lo"):
            for program, (least, bound) in view.least_time(role).items():
                print(f"{role}/{program} roofline: least {1e3 * least:.4f} "
                      f"ms per call, {bound}-bound")
    else:
        e2e = end_to_end(win, cell, setup_s)
        for m in specs.metrics_for(bench, "end_to_end", args.workload):
            if m["name"] in e2e:
                v, unit = e2e[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": unit}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem.get("peak_bytes_in_use", 0)}
    result = {"correct": correct,
              "attempted": win["offered"]["hi"] + win["offered"]["lo"],
              "failed": win["failed"]["hi"] + win["failed"]["lo"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    sys.stdout.flush()
    for k, c in checks.items():
        print(f"check {k}: {c['value']} limit {c['limit']} "
              f"({c['requests']} requests)", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
