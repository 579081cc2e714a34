"""Find a cell's knee: the highest high-priority rate it keeps up with.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 2 4 6 8 ...

One process sets the cell up once, then serves one window per rate, the
low-priority backlog running as in the cell, and prints a table: offered
and completed high-priority requests, how many were still unanswered when
the window closed, the latency median and 95th percentile, and low-
priority tokens per second. The knee is the highest rate at which the
unanswered count stays near zero, that is, completions keep up with
arrivals; the cell's traffic file then takes about four fifths of it.
A window that a host stall spoils reads as not keeping up, so the knee is
the highest rate that keeps up, not the first that fails; windows of
30 s or more keep one stall from deciding it. Run on the chip; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

import run  # noqa: E402  (sets up the import paths)
from benchlib import spec as specs  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    bench = specs.load_benchmark(run.ROOT)
    entry, config, traffic_spec = specs.find_cell(run.ROOT, bench,
                                                  args.workload)
    try:
        run.require_accelerator(int(entry["chips"]))
    except run.NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 2
    run.use_compile_cache(run.ROOT)
    from benchlib.cell import Cell

    cell = Cell(config, traffic_spec, args.seed)
    cell.setup()
    lo = cell.roles["lo"]
    print("rate_per_s offered done_in_window unanswered_at_close "
          "p50_ms p95_ms first_third_p50_ms last_third_p50_ms "
          "lo_tokens_per_s fills compiles")
    knee = None
    for phase, rate in enumerate(sorted(args.rates), start=1):
        win = cell.window(args.seconds, phase=phase, rate=rate)
        t_end = win["t0"] + win["seconds"]
        ok = [r for r in win["hi"] if r["ok"]]
        in_win = sum(r["done_s"] <= t_end for r in ok)
        lat = [r["latency_s"] for r in ok]
        p50, p95 = (1e3 * np.percentile(lat, [50, 95]) if lat
                    else (float("nan"),) * 2)
        thirds = [[r["latency_s"] for r in ok
                   if k / 3 <= (r["done_s"] - r["latency_s"] - win["t0"])
                   / args.seconds < (k + 1) / 3] for k in (0, 2)]
        first, last = (1e3 * float(np.median(t)) if t else float("nan")
                       for t in thirds)
        lo_tps = len(win["lo_done"]) * lo.batch * lo.seq / win["seconds"]
        print(f"{rate:g} {win['offered']['hi']} {in_win} "
              f"{win['offered']['hi'] - in_win} {p50:.3f} {p95:.3f} "
              f"{first:.3f} {last:.3f} {lo_tps:.1f} {win['fills']} "
              f"{win['compiles']}", flush=True)
        if len(ok) == len(win["hi"]) > 0 and last <= 1.5 * first:
            knee = rate
    cell.close()
    print(f"knee {knee} /s" if knee is not None else "knee below every rate")
    return 0


if __name__ == "__main__":
    sys.exit(main())
