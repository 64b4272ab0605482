"""Run one cell of the benchmark once, on the chips of this machine.

  python3 bench/run.py --workload tpch_lineitem.adhoc_rows --seed 7 \
      --seconds 50 --trace 0

Reads ``BENCHMARK.json`` at the root of the checkout, builds the cell's
deployment from ``--seed``, warms up every program the window uses,
measures a closed loop of requests for ``--seconds`` seconds and checks
every answer against the dense reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's first
requests), ``device``, ``breakdown`` when traced, and ``checks`` last:
each number compared beside its limit, which also end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  JAX's persistent compilation cache lives
at ``<checkout>/.jax_cache``, so only a cell's first run in a checkout
compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    # the program takes its cache directory from this variable; a fixed
    # path inside the checkout, so that a later run of the cell hits
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program is worth keeping: the group programs are what set-up
    # compiles, and the small ones would otherwise compile in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: the two cells' programs take some 200 MB on a v5e, and
    # an evicted program compiles again inside set-up
    jax.config.update("jax_compilation_cache_max_size", -1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    try:
        cell = harness.cell(spec, args.workload)
        print(f"bench: {args.workload} seed {args.seed}, compile cache "
              f"{cache_dir}", file=sys.stderr, flush=True)
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               log=lambda s: print(s, file=sys.stderr,
                                                   flush=True))
    except harness.Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 3
    harness.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
