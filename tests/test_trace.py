"""The query path's in-memory span and counter recorder
(``repro.core.trace``): nesting and self time, request ids, joining,
threads, dropped failures, the ring's bound, and a JAX-free numpy path."""

import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.core import Eq, IndexSpec, IndexWriter, Range, trace


def _index(rows=600, seal=128):
    rng = np.random.default_rng(3)
    w = IndexWriter(IndexSpec(), seal_rows=seal)
    w.append([rng.integers(0, 5, rows), rng.integers(0, 40, rows)])
    w.close()
    return w.index


def _new_records(before):
    return [r for r in trace.recent() if r.id > before]


def _last_id():
    # records enter the ring as they complete, not in id order
    return max((r.id for r in trace.recent()), default=0)


def test_spans_nest_and_self_times_sum_to_the_request():
    before = _last_id()
    with trace.request("unit"):
        with trace.span("a"):
            with trace.span("a.inner"):
                pass
            with trace.span("a.inner"):
                trace.add("n", 2)
        with trace.span("b"):
            trace.add("n", 3)
    (rec,) = _new_records(before)
    assert rec.kind == "unit"
    assert [(name, parent) for name, _, _, parent in rec.spans] == [
        ("query", None), ("a", 0), ("a.inner", 1), ("a.inner", 1), ("b", 0)]
    for name, start, end, parent in rec.spans[1:]:
        _, p0, p1, _ = rec.spans[parent]
        assert p0 <= start <= end <= p1
    own = rec.self_ns()
    assert all(t >= 0 for t in own)
    assert sum(own) == rec.duration_ns
    assert rec.self_ns_of({"a.inner"}) == sum(
        e - s for n, s, e, _ in rec.spans if n == "a.inner")
    assert rec.counters == {"n": 5}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_query_path_spans_lie_inside_their_request(backend):
    index = _index()
    before = _last_id()
    rows, _ = index.query_many([Range(1, 3, 20)], backend=backend)[0]
    (rec,) = _new_records(before)
    assert rec.kind == "query_many" and len(rows)
    names = [n for n, *_ in rec.spans]
    assert names[0] == "query"
    for want in ("query.plan", "query.backend", "query.concat",
                 "query.to_rows", "query.map_ids", "query.sort"):
        assert want in names
    if backend == "jax":
        assert {"query.lookup", "query.pad", "query.dispatch",
                "query.device_wait", "query.readback",
                "query.results"} <= set(names)
        assert rec.counters["padded_words"] >= rec.counters["leaf_words"] > 0
        assert rec.counters["shipped_bytes"] > 4 * rec.counters["leaf_words"]
    _, q0, q1, _ = rec.spans[0]
    assert all(q0 <= s <= e <= q1 for _, s, e, _ in rec.spans)
    assert sum(rec.self_ns()) == rec.duration_ns


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_nested_public_calls_join_one_request(backend):
    index = _index()
    before = _last_id()
    n = index.count(Eq(0, 2), backend=backend)
    index.execute_compressed_many([Eq(0, 1), Eq(1, 7)], backend=backend)
    recs = _new_records(before)
    assert [r.kind for r in recs] == ["count", "execute_compressed_many"]
    assert len({r.id for r in recs}) == 2
    assert n == len(index.query(Eq(0, 2))[0])
    count_rec = recs[0]
    names = [s[0] for s in count_rec.spans]
    assert names.count("query") == 1 and names[-1] == "query.count"


def test_threads_keep_separate_records():
    barrier = threading.Barrier(2, timeout=10)
    seen = {}

    def work(tag):
        with trace.request(tag):
            barrier.wait()  # both requests are open at once
            with trace.span(f"work.{tag}"):
                trace.add(tag, 1)
                barrier.wait()
        seen[tag] = True

    before = _last_id()
    threads = [threading.Thread(target=work, args=(t,)) for t in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert seen == {"x": True, "y": True}
    recs = {r.kind: r for r in _new_records(before)}
    assert sorted(recs) == ["x", "y"] and recs["x"].id != recs["y"].id
    for tag, rec in recs.items():
        assert [s[0] for s in rec.spans] == ["query", f"work.{tag}"]
        assert rec.counters == {tag: 1}


def test_a_raising_request_is_not_recorded_and_the_ring_is_bounded(
        monkeypatch):
    monkeypatch.setattr(trace, "_RING", trace._Ring(4))
    with pytest.raises(KeyError):
        with trace.request("fails"):
            with trace.span("x"):
                raise KeyError("boom")
    assert trace.recent() == []
    for i in range(10):
        with trace.request(f"r{i}"):
            pass
    kept = trace.recent()
    assert [r.kind for r in kept] == ["r6", "r7", "r8", "r9"]
    assert [r.kind for r in trace.recent(2)] == ["r8", "r9"]
    assert trace.recent(0) == []
    assert [r.kind for r in trace.recent(100)] == ["r6", "r7", "r8", "r9"]
    kept[0].spans.clear()  # copies: the ring's records are untouched
    assert trace.recent()[0].spans


def test_span_outside_a_request_times_itself_and_records_nothing():
    before = _last_id()
    with trace.span("alone") as s:
        trace.add("ignored", 1)
    assert s.seconds >= 0 and s.end_ns >= s.start_ns
    assert _new_records(before) == []


def test_numpy_backend_path_imports_no_jax():
    code = textwrap.dedent("""
        import sys
        import numpy as np
        from repro.core import Eq, IndexSpec, IndexWriter, trace
        w = IndexWriter(IndexSpec(), seal_rows=64)
        w.append([np.arange(300) % 3, np.arange(300) % 7])
        w.close()
        w.index.query_many([Eq(0, 1)])
        w.index.count(Eq(1, 2))
        assert len(trace.recent()) == 2, trace.recent()
        print(sorted(m for m in sys.modules if m.split(".")[0] == "jax"))
    """)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env = dict(os.environ, PYTHONPATH=src)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "[]"


def test_serve_phase_profile_records_through_trace_spans(capsys):
    from repro.launch.serve import PhaseProfile

    prof = PhaseProfile()
    before = _last_id()
    with trace.request("serve"):
        with prof.span("pack"):
            pass
        with prof.span("pack"):
            pass
    with prof.span("prefill"):  # outside a request: timed, not recorded
        pass
    (rec,) = _new_records(before)
    assert [s[0] for s in rec.spans] == ["query", "serve.pack", "serve.pack"]
    pack = sum(e - s for n, s, e, _ in rec.spans if n == "serve.pack")
    assert prof.acc["pack"] == pytest.approx(pack * 1e-9)
    assert set(prof.acc) == {"pack", "prefill"}
    prof.report()
    out = capsys.readouterr().out.splitlines()
    assert out[0] == "# top serving phases (wall-clock)"
    assert sorted(line.split()[0] for line in out[1:]) == ["pack", "prefill"]
