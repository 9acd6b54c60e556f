"""Benchmark runner — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME[,NAME]]
                                            [--out reports/bench]

Prints one table per benchmark, validates the paper's claims, writes JSON
reports, and exits non-zero if any claim fails.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time

from benchmarks.common import FULL, QUICK, Result, render_table, save_result

BENCHES = [
    "bench_motivational",  # Table 3 / Fig 2
    "bench_fetchers",      # Fig 5
    "bench_batch_pool",    # Fig 6
    "bench_to_device",     # Fig 7
    "bench_lazy_init",     # Fig 8
    "bench_cache",         # Fig 9
    "bench_heatmap",       # Figs 10/11
    "bench_autotune",      # Figs 10/11, online (closed-loop knob control)
    "bench_pipeline",      # beyond paper: staged streaming pipeline (stages)
    "bench_procpool",      # A.4 closed: process CPU stage escapes the GIL
    "bench_multihost",     # beyond paper: multi-host coordination (coord)
    "bench_sharded",       # beyond paper: device-sharded batch delivery
    "bench_shm",           # beyond paper: zero-copy shm transport + ingest
    "bench_columnar",      # beyond paper: columnar projection + pushdown
    "bench_serve",         # beyond paper: online-serving read path
    "bench_elastic",       # beyond paper: elastic fleet + append-log journal
    "bench_dataset_pool",  # Fig 12
    "bench_e2e",           # Figs 13/14/15
    "bench_shards",        # A.5
    "bench_gil",           # A.4
    "bench_fade",          # A.6
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="full-scale statistics")
    ap.add_argument("--only", default="", help="comma-separated bench names")
    ap.add_argument("--out", default="reports/bench")
    args = ap.parse_args()
    scale = FULL if args.full else QUICK

    selected = BENCHES
    if args.only:
        want = {w if w.startswith("bench_") else f"bench_{w}"
                for w in args.only.split(",")}
        unknown = want - set(BENCHES)
        if unknown:
            # a typo'd/renamed bench must not silently pass CI (0/0 claims)
            print(f"error: unknown benchmark(s) {sorted(unknown)}; "
                  f"known: {BENCHES}", file=sys.stderr)
            return 2
        selected = [b for b in BENCHES if b in want]

    failures = 0
    all_claims = []
    for mod_name in selected:
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        print(f"\n=== {mod.NAME}  [{mod.PAPER_REF}]  (scale={scale.name}) ===",
              flush=True)
        t0 = time.monotonic()
        result: Result = mod.run(scale)
        result.wall_s = round(time.monotonic() - t0, 1)
        print(render_table(result.rows))
        if result.notes:
            print(f"note: {result.notes}")
        for claim, ok in result.claims:
            mark = "PASS" if ok else "FAIL"
            print(f"  [{mark}] {claim}")
            all_claims.append((mod.NAME, claim, ok))
            failures += not ok
        print(f"  ({result.wall_s}s)")
        save_result(result, args.out)

    print(f"\n=== claim summary: {sum(ok for _, _, ok in all_claims)}/"
          f"{len(all_claims)} passed ===")
    for name, claim, ok in all_claims:
        if not ok:
            print(f"  FAIL {name}: {claim}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
