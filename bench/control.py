"""The control of the check that decides ``correct``: the plain reference
put in the program's place with one guarantee of the configuration broken,
tombstones ignored, judged by the harness's own comparison.  It has to come
out not correct.

  python3 bench/control.py --workload tpch_lineitem.adhoc_rows \
      --seeds 1 2 3 --requests 120

For each seed it builds the cell's table at full size, answers the first
``--requests`` requests of the cell's order with deleted rows left in, and
prints the compared numbers beside their limits.  It runs on the host only
and is not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_checks(c: dict, seed: int, requests: int) -> dict:
    """The control's compared numbers for one seed."""
    import numpy as np

    from bench import harness, mix, reference, tables

    config, traffic = c["config"], c["traffic"]
    columns = tables.make_table(config, seed)
    names = [col["name"] for col in config["columns"]]
    live = reference.live_mask(harness.positions(config["delete"], names),
                               columns)
    ignored = np.ones_like(live)
    pool = mix.make_pool(c["pool"], config)
    order = mix.request_order(traffic, c["pool"])
    kind = traffic["answer"]
    memo = {}
    answers = []
    for _ in range(requests):
        i = next(order)
        if i not in memo:
            memo[i] = reference.answer(pool[i], columns, ignored, kind)
        answers.append((i, memo[i]))
    return harness.judge(kind, answers, pool, columns, live)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        c = harness.cell(json.load(fh), args.workload)
    failed_all = True
    for seed in args.seeds:
        checks = control_checks(c, seed, args.requests)
        bad = {k: v for k, v in checks.items() if v > harness.LIMITS[k]}
        failed_all &= bool(bad)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": not bad, "checks": checks,
                          "limits": harness.LIMITS}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
