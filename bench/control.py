"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> \
        --seeds <n> <n> ... [--control-seeds <n> ...]

For each seed, in one process: set the cell up, serve one short window at
the cell's own load, free the program's state, and read the widest gap
between the reference's best logit and the logit of each served token
(``gap.hi``, ``gap.lo``) over the same sample of requests a run compares.
For each control seed it also reads the control: the reference computed
in float8 (e4m3, one scale per tensor) put in the program's place, the
gap read for the token the control puts first. The lower reading of a
limit is the largest program reading; the upper, the smallest control
reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402  (sets up the import paths)
from benchlib import spec as specs  # noqa: E402

NO_LIMIT = {"gap.hi": float("inf"), "gap.lo": float("inf")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)

    bench = specs.load_benchmark(run.ROOT)
    entry, config, traffic_spec = specs.find_cell(run.ROOT, bench,
                                                  args.workload)
    try:
        run.require_accelerator(int(entry["chips"]))
    except run.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    run.use_compile_cache(run.ROOT)
    from benchlib.cell import Cell

    rows = []
    for seed in args.seeds:
        cell = Cell(config, traffic_spec, seed)
        cell.setup()
        win = cell.window(args.seconds)
        cell.close()
        row = {"seed": seed, "program": {
            k: c["value"] for k, c in cell.check(win, NO_LIMIT).items()}}
        if seed in args.control_seeds:
            row["control"] = {k: c["value"] for k, c in
                              cell.check(win, NO_LIMIT, "fp8").items()}
        rows.append(row)
        print(json.dumps(row), flush=True)
    for side in ("program", "control"):
        for k in NO_LIMIT:
            vals = [r[side][k] for r in rows if side in r]
            if vals:
                pick = max if side == "program" else min
                print(f"{side} {k}: {'largest' if pick is max else 'smallest'}"
                      f" {pick(v if v is not None else float('inf') for v in vals)}"
                      f" over {len(vals)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
