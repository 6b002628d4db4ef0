#!/usr/bin/env python3
"""Readings that set a cell's fixed numbers, in one process on the chip.

    python bench/calibrate.py control --workload <cell> --seeds 1,2,3 \
        --seconds 12
    python bench/calibrate.py sweep --workload <cell> --seed 7 \
        --rates 0.4,0.6,0.8 --seconds 30

``control`` runs the cell once per seed, as ``bench/run.py`` does but
with a short window, and reads two numbers on the same sampled prompts
and served tokens: the program's widest logit gap (the lower reading of
the ``logit_gap`` limit) and the control's, where the reference computed
in float8 e4m3 takes the program's place and its top token at each
position is read against the float32 reference (the upper reading).

``sweep`` runs an open-loop cell at each offered rate (sessions or
requests per second) and prints the tails and the backlog at the end of
each window, to find the highest rate the server sustains.

Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def control(cell, seeds, seconds, run_cell) -> None:
    from bench.harness import check

    def after(weights, sample, ref, _run):
        top = check.control_tokens(weights, sample, ref)
        return {"control_gap": check.widest_gap(weights, sample, ref,
                                                probe=top)}

    for seed in seeds:
        r = run_cell(cell, seed, seconds, False, after=after)
        gc.collect()
        _emit({"mode": "control", "workload": cell.name, "seed": seed,
               "program_gap": r["checks"]["logit_gap"]["value"],
               "control_gap": r["calibration"]["control_gap"],
               "tokens": r["checks"]["tokens_compared"]["value"],
               "metrics": r["metrics"]})


def sweep(cell, seed, rates, seconds, run_cell) -> None:
    for rate in rates:
        cell.server = dict(cell.server, rate_per_s=rate)
        r = run_cell(cell, seed, seconds, False, after=_backlog)
        gc.collect()
        _emit({"mode": "sweep", "workload": cell.name, "seed": seed,
               "rate_per_s": rate, "attempted": r["attempted"],
               "metrics": r["metrics"], **r["calibration"]})


def _backlog(_weights, _sample, _ref, run) -> dict:
    """What a sweep reads beside the tails: requests due in the window
    and how many of them had not finished when it closed."""
    from bench.harness import readers
    due = run.due
    return {"due": len(due),
            "unfinished": sum(1 for t in due if not (
                t.finished and t.times[-1] <= run.t_end)),
            "client_lag_p99_ms": readers.client_lag_ms(run, 99),
            "busy_share": run.busy_s() / run.seconds}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("control", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import spec
    from bench.run import run_cell
    cell = spec.load_cell(args.workload)
    if args.mode == "control":
        control(cell, [int(s) for s in args.seeds.split(",")], args.seconds,
                run_cell)
    else:
        sweep(cell, args.seed, [float(r) for r in args.rates.split(",")],
              args.seconds, run_cell)
    return 0


if __name__ == "__main__":
    sys.exit(main())
