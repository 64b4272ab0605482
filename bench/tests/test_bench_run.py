"""A whole run at a tiny size on the CPU (the harness's look for a chip
skipped), its result line, the faults it has to catch, and the command's
refusals."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.tests.cells import CELLS, METRICS, SPEC, cell_of
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def tiny_cell(name):
    c = cell_of(name, 2, 4096, tail=1000)
    # a pool entry whose answers hold tombstoned rows is among them
    preds = c["pool"]["predicates"]
    c["pool"] = dict(c["pool"], predicates=[dict(e, repeat=1) for e in
                                            preds[:1] + preds[-1:] + preds[6:7]])
    return c


def run(name, seconds=1.0):
    return harness.run_cell(tiny_cell(name), 2**31 + 11, seconds, False,
                            require_tpu=False, log=lambda s: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_has_the_contract_keys(name):
    out = run(name)
    assert list(out) == KEYS  # no breakdown untraced; checks come last
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert sorted(out["metrics"]) == sorted(
        m["name"] for m in SPEC["end_to_end"]
        if name in m.get("workloads", [name]))
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(v == {"value": 0, "limit": 0}
               for v in out["checks"].values())


def _drop_last(fn):
    def inner(self, *a, **k):
        return [(rows[:-1] if len(rows) else np.array([7]), s)
                for rows, s in fn(self, *a, **k)]
    return inner


def _half_batch(fn):
    from repro.core import ewah
    from repro.core.ewah_stream import EwahStream

    def inner(self, plans):
        out = fn(self, plans)
        keep = len(out) // 2
        return out[:keep] + [
            EwahStream(ewah.compress(np.zeros(p.n_words, np.uint32)),
                       p.n_rows, 0) for p in plans[keep:]]
    return inner


FAULTS = {
    "answer_altered": ("repro.core.segment.SegmentedIndex", "query_many",
                       _drop_last),
    "count_altered": ("repro.core.segment.SegmentedIndex", "count",
                      lambda fn: lambda self, *a, **k: fn(self, *a, **k) + 1),
    "half_batch_left_out": ("repro.core.query.JaxBackend",
                            "execute_compressed_many", _half_batch),
    "tombstones_ignored": ("repro.core.segment", "with_live_mask",
                           lambda fn: lambda plan, live: plan),
}
# where each fault is produced on the cell's timed path: row ids come from
# query_many, counts from count; every cell plans, masks and runs the batch
CASES = [(cell, fault) for cell in CELLS for fault in sorted(FAULTS)
         if fault != ("count_altered" if cell_of(cell)["traffic"]["answer"]
                      == "rows" else "answer_altered")]


@pytest.mark.parametrize("name,fault", CASES)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    import importlib

    where, attr, breaker = FAULTS[fault]
    mod, _, cls = where.rpartition(".")
    try:
        obj = getattr(importlib.import_module(mod), cls)
    except (AttributeError, ValueError):
        obj = importlib.import_module(where)
    monkeypatch.setattr(obj, attr, breaker(getattr(obj, attr)))
    out = run(name, seconds=0.5)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "tpch_lineitem.adhoc_rows", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_without_a_tpu():
    p = _command(harness.ROOT)
    assert p.returncode != 0
    assert "refused" in p.stderr
    assert not p.stdout.strip()


def test_command_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_cells_mixes_and_metrics_are_found_by_name(tmp_path):
    """A new configuration, traffic mix, pool and per-layer metric are new
    files and BENCHMARK.json entries only."""
    for kind, name, body in [
            ("configs", "toy", {"rows": 64, "columns": [
                {"name": "a", "card": 3, "dist": "uniform"}]}),
            ("traffic", "mix1", {"pool": "p1", "order": "interleave",
                                 "answer": "count", "result_cache": "clear"}),
            ("pools", "p1", {"seed": 1, "predicates": [
                {"shape": ["eq", "a"], "repeat": 2}]})]:
        (tmp_path / kind).mkdir()
        (tmp_path / kind / f"{name}.json").write_text(json.dumps(body))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "twice.py").write_text(
        "def read(run):\n    return 2 * run['requests']\n")
    spec = {"workloads": [{"name": "toy.mix1", "config": "toy",
                           "traffic": "mix1", "chips": 1}],
            "end_to_end": [{"name": "queries_per_s", "unit": "queries/s"}],
            "per_layer": [{"name": "twice", "unit": "x",
                           "workloads": ["toy.mix1"]},
                          {"name": "elsewhere", "unit": "x",
                           "workloads": ["other.cell"]}]}
    c = harness.cell(spec, "toy.mix1", base=str(tmp_path))
    assert c["config"]["rows"] == 64 and c["pool"]["seed"] == 1
    assert [m["name"] for m in c["per_layer"]] == ["twice"]
    assert harness.metric_reader("twice", str(tmp_path))({"requests": 4}) == 8


def test_each_per_layer_metric_has_a_reader():
    assert {m["name"] for m in SPEC["per_layer"]} <= set(METRICS)


@pytest.mark.parametrize("name", METRICS)
def test_reader_returns_nothing_where_nothing_was_read(name):
    read = harness.metric_reader(name)
    run = {"requests": 0, "window_s": 1.0, "merges": 0, "compiles": 0,
           "index_words": 0, "rows": 0, "trace": None,
           "device_kind": "TPU v5 lite"}
    assert read(run) in (None, 0)


def test_traced_run_traces_the_first_requests(monkeypatch, tmp_path):
    """The traced window holds the window's first ``TRACE_REQUESTS``
    requests, and the rest of the window runs untraced.  The CPU has no
    device plane, so the reduction is handed the host spans alone."""
    from bench import devtrace

    seen = {}

    def host_only(events):
        seen["events"] = events
        return None

    monkeypatch.setattr(devtrace, "reduce", host_only)
    c = tiny_cell("tpch_lineitem.adhoc_rows")
    monkeypatch.setattr(harness, "TRACE_REQUESTS", 5)
    out = harness.run_cell(c, 2**31 + 5, 3.0, True, require_tpu=False,
                           trace_dir=str(tmp_path), log=lambda s: None)
    assert out["correct"] is True
    assert out["attempted"] > 5  # the window went on past the trace
    spans = [e for e in seen["events"] if e[0] == devtrace.HOST_PLANE]
    win = [e for e in spans if e[2] == devtrace.WINDOW_SPAN]
    assert len(win) == 1
    w0, w1 = win[0][3], win[0][3] + win[0][4]
    inside = [e for e in spans if e[2] == devtrace.REQUEST_SPAN
              and w0 <= e[3] and e[3] + e[4] <= w1]
    assert len(inside) == 5
