#!/usr/bin/env python3
"""Readings for a cell's limits and rate, many runs in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5
        [--control | --fault <name>] [--trace]

Runs the cell once per seed, each run as ``bench/run.py`` would make it,
and prints one JSON line per run: the seed, the checks and the metrics. ``--control`` puts the plain
reference one precision step down in the program's place: the readings
that a limit must refuse. ``--fault`` plants one of ``bench/faults.py``'s
faults under the timed path instead. The benchmark's own runs do neither.
Set-up after the first run is shorter than a run of its own would be,
since the process keeps its compiled programs, so ``setup_s`` here means
nothing.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="")
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args(argv)
    run._paths()
    run.enable_cache()
    watch = run.CompileWatch()
    plant = contextlib.nullcontext()
    if args.fault:
        import jax

        from bench import faults

        jax.clear_caches()
        _, _, traffic = run.cell_parts(args.workload)
        plant = faults.FAULTS[traffic["generator"]][args.fault]()
    with plant:
        for seed in (int(s) for s in args.seeds.split(",")):
            try:
                r = run.run_cell(args.workload, seed, args.seconds,
                                 args.trace, control=args.control,
                                 watch=watch)
            except run.Refused as e:
                print(f"calibrate: {e}", file=sys.stderr)
                return 2
            print(json.dumps({"seed": seed, "control": args.control,
                              "fault": args.fault, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
