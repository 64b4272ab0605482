"""The per-layer metrics read from the program's own request records
(``bench/records.py``): a traced run of each cell at a tiny size on the
CPU reads all of them, and the shipped bytes match the plans.

``BENCHMARK.json`` does not list these readers yet (see
``bench/records.py``), so each test adds them to its cell itself."""

import pytest

from bench import devtrace, harness
from bench.tests.cells import CELLS
from bench.tests.test_bench_run import tiny_cell

RECORD_METRICS = {"plan_ms_per_query": "ms", "stage_ms_per_query": "ms",
                  "device_wait_ms_per_query": "ms",
                  "unpack_ms_per_query": "ms",
                  "shipped_mb_per_query": "MB/query", "leaf_fill_pct": "%"}


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_the_programs_request_records(name, monkeypatch,
                                                       tmp_path):
    from repro.core import trace
    from repro.core.query import JaxBackend

    # the CPU has no device plane to reduce
    monkeypatch.setattr(devtrace, "reduce", lambda events: None)
    monkeypatch.setattr(harness, "TRACE_REQUESTS", 2)
    shipped = []  # bytes of each backend call, from its plans' groups
    group = JaxBackend._group

    def counting_group(self, plans, idxs=None):
        groups = group(self, plans, idxs)
        shipped.append(sum(4 * len(ix) * len(plans[ix[0]].streams) * (cap + 1)
                           for (_, cap, _), ix in groups.items()))
        return groups

    monkeypatch.setattr(JaxBackend, "_group", counting_group)
    c = tiny_cell(name)
    c["per_layer"] = c["per_layer"] + [{"name": k, "unit": u} for k, u in
                                       RECORD_METRICS.items()]
    out = harness.run_cell(c, 2**31 + 23, 3.0, True,
                           require_tpu=False, trace_dir=str(tmp_path),
                           log=lambda s: None)
    assert out["correct"] is True and out["failed"] == 0
    n = out["attempted"] - harness.TRACE_REQUESTS
    assert n > 0
    got = {k: out["metrics"][k]["value"] for k in RECORD_METRICS}
    assert all(v is not None and v >= 0 for v in got.values())
    # 4 bytes a word: B * m * C padded words and B * m lengths per group
    assert got["shipped_mb_per_query"] == pytest.approx(
        sum(shipped[-n:]) / n / 1e6)
    assert 0 < got["leaf_fill_pct"] <= 100
    records = trace.recent(n)
    for rec in records:
        root, start, end, parent = rec.spans[0]
        assert root == "query" and parent is None
        for span, s, e, _ in rec.spans[1:]:
            assert span.startswith("query.") and start <= s <= e <= end
    assert got["device_wait_ms_per_query"] == pytest.approx(
        sum(r.self_ns_of({"query.device_wait"}) for r in records) * 1e-6 / n)


def test_records_give_nothing_without_the_programs_recorder(monkeypatch):
    """The parent of the change that added the recorder has no
    ``repro.core.trace``: each reader then gives nothing."""
    import sys

    import repro.core

    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    run = {"requests": harness.TRACE_REQUESTS + 5}
    for name in RECORD_METRICS:
        assert harness.metric_reader(name)(run) is None
