"""Benchmark harness — one module per paper table/figure.

``python -m benchmarks.run [--quick] [--only name] [--json out.json]``

CSV rows: name,us_per_call,derived. Mapping to the paper:
  sweeps          — Fig. 3/4 + Table I (vary N / l / k; naive vs work-matrix)
  precision       — §V-B FP16 runtimes + the deferred quality question
  chunking        — §IV-B-3 memory-budgeted evaluation
  greedy_modes    — beyond-paper optimizer-aware greedy + engine modes
  optimizers      — §IV-A optimizer evaluation-count profile + engine plans
  streaming       — sieve family: per-element host loop vs device block offer
  functions       — zoo objectives through the shared engine at n ∈ {4k, 32k}
  contracts       — compiled-contract audit metrics (traced signatures,
                    collective census, donated bytes) per audited entry point

``--json`` additionally writes the rows as a machine-readable artifact
(``{module: [{name, us_per_call, derived, backend, peak_device_bytes,
function}, ...]}``) so CI can accumulate a perf trajectory across PRs;
``backend`` records the evaluation backend each entry scored through ("jnp"
unless the module tagged the row "pallas"/"pallas_interpret"), so
BENCH_*.json trajectories can attribute speedups to the kernel wiring;
``peak_device_bytes`` the device-0 allocator *process-lifetime* high-water
mark (None on backends without stats; a cross-PR trend line for the whole
module run, not a per-row measurement); and ``function`` the submodular
objective the row scored ("exemplar" unless the module tagged it), so the
function-zoo rows chart per-objective slopes. The sharded plans' O(n/p)
per-device memory claim is certified by the analytic
``*_bytes_per_device`` columns those rows carry in ``derived``. ``--only``
takes a comma-separated module list. A module that raises (or emits no
rows) is recorded under ``_errors`` in the JSON artifact and the run
exits non-zero — an errored benchmark must fail CI, not flatline the
trajectory.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import traceback

from repro.core.compile_cache import enable_compile_cache

MODULES = ["sweeps", "precision", "chunking", "greedy_modes",
           "optimizers", "streaming", "functions", "serving", "contracts"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of modules")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to PATH as JSON (CI artifact)")
    args = ap.parse_args()
    enable_compile_cache()
    mods = args.only.split(",") if args.only else MODULES
    print("name,us_per_call,derived,backend,peak_device_bytes,function,"
          "n_batch")
    collected: dict[str, list[dict]] = {}
    errors: dict[str, str] = {}
    for m in mods:
        try:
            mod = importlib.import_module(f"benchmarks.{m}")
            rows = mod.run(quick=args.quick)
            if not rows:
                raise RuntimeError(f"module {m!r} emitted no rows")
        except Exception:
            # a failing module must fail the job — a silently-empty
            # BENCH_pr.json would read as a flat perf trajectory
            errors[m] = traceback.format_exc()
            collected[m] = []
            continue
        collected[m] = [
            {"name": row[0], "us_per_call": row[1], "derived": row[2],
             # 4th column = the evaluation backend the entry scored
             # through; 5th = device-0 peak allocator bytes (None on
             # backends without memory stats); 6th = the submodular
             # objective the row scored (the function-zoo axis); 7th =
             # requests per dispatch (the serving-throughput axis)
             "backend": row[3] if len(row) > 3 else "jnp",
             "peak_device_bytes": row[4] if len(row) > 4 else None,
             "function": row[5] if len(row) > 5 else "exemplar",
             "n_batch": row[6] if len(row) > 6 else 1}
            for row in (rows or [])
        ]
    if args.json:
        payload: dict = dict(collected)
        if errors:
            payload["_errors"] = errors
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {args.json}")
    if errors:
        for m, tb in errors.items():
            print(f"# benchmark module {m!r} FAILED:\n{tb}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
