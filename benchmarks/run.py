"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows plus PASS/FAIL validation of
the paper's claims.  ``--quick`` shrinks row counts (used by CI/tests).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig2,table4]
"""

from __future__ import annotations

import argparse
import json
import os
import time


def dist_smoke() -> None:
    """Tiny multi-process serve-plane check for CI: spawns a real worker
    fleet, gates only on the noise-immune claims (bit-identity with the
    in-process engine, compressed-shipped < 0.2 of dense) — the
    throughput race gates in the full bench where the trend machinery
    can absorb runner noise."""
    from repro.data.tables import make_census_like

    from . import bench_fig6

    # 24k rows -> 3k-row segments: big enough that the 24-byte wire
    # header stops dominating the per-segment compressed payload
    rows = bench_fig6.run_distributed(make_census_like(24_000), queries=8,
                                      hosts=(2,))
    failed = False
    for r in rows:
        if r["hosts"] < 2:
            continue
        ok = r["agrees_with_local"] and r["compressed_to_dense"] < 0.2
        failed |= not ok
        print(f"dist-smoke hosts={r['hosts']}: "
              f"bit-identical={r['agrees_with_local']} "
              f"compressed/dense={r['compressed_to_dense']:.3f} "
              f"speedup={r['speedup_vs_one']:.2f}x "
              f"({r['cpus']:.0f} cpus): {'PASS' if ok else 'FAIL'}")
    raise SystemExit(1 if failed else 0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="results/benchmarks.json")
    ap.add_argument("--dist-smoke", action="store_true",
                    help="run only the multi-process serve-plane smoke "
                         "(bit-identity + wire-compression gates) and exit")
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.dist_smoke:
        dist_smoke()

    from . import (bench_fig2, bench_fig3, bench_fig4, bench_fig6,
                   bench_moe_dispatch, bench_scaling, bench_table3,
                   bench_table4, bench_workload)

    suites = {
        "fig2_dirty_probability": bench_fig2,
        "fig3_column_gain": bench_fig3,
        "fig4_column_orderings": bench_fig4,
        "table3_percolumn_sort": bench_table3,
        "table4_index_sizes": bench_table4,
        "fig6_query_cost": bench_fig6,
        "scaling_prefix_growth": bench_scaling,
        "moe_dispatch_bitmaps": bench_moe_dispatch,
        "workload_replay": bench_workload,
    }
    if args.only:
        keys = [k for k in suites if any(s in k for s in args.only.split(","))]
        suites = {k: suites[k] for k in keys}

    all_results = {}
    all_checks = []
    print("name,us_per_call,derived")
    for name, mod in suites.items():
        t0 = time.perf_counter()
        rows = mod.run(quick=args.quick)
        dt = (time.perf_counter() - t0) * 1e6
        checks = mod.validate(rows)
        all_results[name] = {"rows": rows, "checks": checks}
        all_checks.extend(checks)
        derived = f"{len(rows)}rows/{sum('PASS' in c for c in checks)}pass"
        print(f"{name},{dt:.0f},{derived}")
        for c in checks:
            print(f"#   {c}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(all_results, f, indent=1, default=str)
    n_fail = sum("FAIL" in c for c in all_checks)
    print(f"# total: {len(all_checks)} checks, {n_fail} failures")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
