"""Roofline report: three terms per (arch x shape) from the dry-run artifacts.

  PYTHONPATH=src python -m benchmarks.roofline --dryrun results/dryrun \
      --out results/roofline.md

Reads the per-cell JSON written by repro.launch.dryrun (memory analysis,
HLO collective bytes) and combines it with the analytic FLOP/byte models
(benchmarks/analytic.py) — see EXPERIMENTS.md §Roofline for why analytic
FLOPs are authoritative (XLA cost analysis counts scan bodies once).
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.shapes import SHAPES, cell_config
from repro.models import transformer

from . import analytic

SDS = jax.ShapeDtypeStruct

# ---------------------------------------------------------------------------
# Query-plane memory-bandwidth bound (the bench_fig6 fusion scenario and
# benchmarks/trend.py's wall-clock-vs-roofline column)
# ---------------------------------------------------------------------------

_MEASURED_BW = None

#: Published HBM bandwidth (bytes/s) per TPU ``device_kind``.  Source:
#: Google Cloud TPU documentation, system architecture pages ("TPU v5e":
#: 819 GB/s HBM2 per chip).
HBM_BW_BY_KIND = {
    "TPU v5 lite": analytic.HBM_BW,
}


def stream_bandwidth() -> float:
    """Achievable streaming memory bandwidth (bytes/s) on the machine the
    benchmarks actually run on: on a TPU, the published HBM rate of its
    ``device_kind`` (:data:`HBM_BW_BY_KIND`; a kind missing from the
    table raises rather than borrowing another chip's rate); otherwise
    measured once by streaming large uint32 arrays through a bitwise op —
    the same instruction mix the word-space kernels execute, so the bound
    is what THIS machine could do with zero non-memory overhead.
    Memoized; the probe costs ~100 ms."""
    global _MEASURED_BW
    if jax.default_backend() == "tpu":
        kind = jax.devices()[0].device_kind
        if kind not in HBM_BW_BY_KIND:
            raise ValueError(
                f"no published HBM bandwidth for TPU device_kind {kind!r}; "
                f"known: {sorted(HBM_BW_BY_KIND)}")
        return HBM_BW_BY_KIND[kind]
    if _MEASURED_BW is None:
        import time

        a = np.arange(8 * 2**20, dtype=np.uint32)   # 32 MiB each side
        b = a[::-1].copy()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            c = np.bitwise_and(a, b)
            best = min(best, time.perf_counter() - t0)
        _MEASURED_BW = (a.nbytes + b.nbytes + c.nbytes) / best
    return _MEASURED_BW


def query_bound_us(leaf_words: float, result_words: float = 0.0,
                   bw: float | None = None) -> float:
    """Memory-bandwidth lower bound (us) for evaluating one fused plan
    over decompressed word planes: every leaf plane word is read once
    (``leaf_words`` = m * W for an m-leaf plan) and the result plus its
    EWAH classification written once (``2 * result_words``) — no
    execution strategy beats moving those bytes.  The fusion acceptance
    gate compares the megakernel's warm wall-clock against this."""
    if bw is None:
        bw = stream_bandwidth()
    return 4.0 * (leaf_words + 2.0 * result_words) / bw * 1e6


def param_count(cfg) -> int:
    shapes = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg), SDS((2,), "uint32"))
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(shapes)))


def cell_report(rec: dict) -> dict | None:
    if rec["status"] != "ok":
        return None
    cfg = cell_config(get_config(rec["arch"]), SHAPES[rec["shape"]])
    shape = SHAPES[rec["shape"]]
    n = param_count(cfg)
    n_act = analytic.active_params(cfg, n)
    kind = rec["kind"]
    chips = rec["n_devices"]
    terms = analytic.roofline_terms(
        cfg, shape.global_batch, shape.seq_len, kind, n,
        rec["collectives"]["bytes"], n_chips=chips,
        remat_policy=rec.get("remat_policy", "dots"),
        microbatches=rec.get("microbatches", 1))
    dominant = max(terms, key=terms.get)
    mf = analytic.model_flops(cfg, shape.global_batch, shape.seq_len, kind,
                              n, n_act)
    sf = analytic.step_flops(cfg, shape.global_batch, shape.seq_len, kind,
                             rec.get("remat_policy", "dots"))
    bound_s = max(terms.values())
    mfu_bound = mf / (chips * analytic.PEAK_FLOPS) / bound_s if bound_s else 0
    return {
        **{k: rec[k] for k in ("arch", "shape", "kind", "mesh")},
        "params": n, "active_params": n_act,
        "terms": terms, "dominant": dominant.replace("_s", ""),
        "model_flops": mf, "step_flops": sf,
        "useful_ratio": mf / sf if sf else 0.0,
        "hlo_flops_raw": rec["cost"].get("flops"),
        "roofline_fraction": mfu_bound,
        "temp_bytes_per_dev": rec["memory"]["temp_bytes"],
        "coll_bytes": rec["collectives"]["total_bytes"],
    }


def fmt_row(r):
    t = r["terms"]
    return (f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{t['compute_s']*1e3:.2f} | {t['memory_s']*1e3:.2f} | "
            f"{t['collective_s']*1e3:.2f} | **{r['dominant']}** | "
            f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.2%} | "
            f"{r['temp_bytes_per_dev']/2**30:.1f} |")


HEADER = (
    "| arch | shape | mesh | compute (ms) | memory (ms) | collective (ms) "
    "| bottleneck | useful flops ratio | roofline frac | temp GiB/dev |\n"
    "|---|---|---|---|---|---|---|---|---|---|")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="results/dryrun")
    ap.add_argument("--out", default="results/roofline.md")
    ap.add_argument("--json-out", default="results/roofline.json")
    args = ap.parse_args()

    with open(os.path.join(args.dryrun, "summary.json")) as f:
        records = json.load(f)
    reports = []
    skipped = []
    for rec in records:
        if rec["status"] == "skipped":
            skipped.append(rec)
            continue
        r = cell_report(rec)
        if r:
            reports.append(r)

    lines = ["# Roofline (single-pod 16x16 = 256 chips unless noted)", "",
             HEADER]
    for r in sorted(reports, key=lambda r: (r["mesh"], r["arch"], r["shape"])):
        if r["mesh"] == "16x16":
            lines.append(fmt_row(r))
    lines += ["", "## Multi-pod (2x16x16 = 512 chips)", "", HEADER]
    for r in sorted(reports, key=lambda r: (r["arch"], r["shape"])):
        if r["mesh"] != "16x16":
            lines.append(fmt_row(r))
    lines += ["", "## Skipped cells", ""]
    for s in skipped:
        lines.append(f"- {s['mesh']} {s['arch']} {s['shape']}: {s['reason']}")
    out = "\n".join(lines)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        f.write(out + "\n")
    with open(args.json_out, "w") as f:
        json.dump(reports, f, indent=1)
    print(out)


if __name__ == "__main__":
    main()
