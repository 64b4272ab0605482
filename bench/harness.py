"""One run of one benchmark cell: set-up, a measured window of requests,
the check against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in ``BENCHMARK.json``:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` (which
names a pool in ``bench/pools/<pool>.json``) and
``bench/metrics/<metric>.py``, a module with ``read(run) -> float | None``.
Adding a cell, a mix or a metric adds files; it edits none.

A request is one predicate through the cell's entry point, a closed loop
with one client: ``SegmentedIndex.query_many([p], backend="jax")`` for
row ids, or ``SegmentedIndex.count(p, backend="jax")``.  A request ends
when its answer is a host array or a Python int.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import ExitStack, contextmanager

import numpy as np

from . import devtrace, mix, reference, tables

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: Requests at the start of the window that the traced run traces.  Each
#: request runs some 470,000 device ops (the decode loop's iterations), and
#: the profiler dropped its buffers after about 6.3 million on a v5e.
TRACE_REQUESTS = 8
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}


class Refused(RuntimeError):
    """The run cannot be measured here (no chip, or too few chips)."""


def load(kind: str, name: str, base: str = HERE) -> dict:
    with open(os.path.join(base, kind, f"{name}.json")) as fh:
        return json.load(fh)


def cell(spec: dict, workload: str, base: str = HERE) -> dict:
    """The cell ``workload`` of the benchmark ``spec`` with its files."""
    found = [w for w in spec["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(f"no workload {workload!r}; known: "
                       f"{[w['name'] for w in spec['workloads']]}")
    w = found[0]
    traffic = load("traffic", w["traffic"], base)
    return {
        "workload": w,
        "config": load("configs", w["config"], base),
        "traffic": traffic,
        "pool": load("pools", traffic["pool"], base),
        "end_to_end": [m for m in spec["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in spec["per_layer"]
                      if workload in m.get("workloads", [workload])],
        "base": base,
    }


def metric_reader(name: str, base: str = HERE):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(base, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def positions(pred, names):
    """A predicate written with column names -> with column positions."""
    op = pred[0]
    if op in ("not", "and", "or"):
        return [op] + [positions(p, names) for p in pred[1:]]
    return [op, names.index(pred[1])] + list(pred[2:])


def to_program(pred):
    """The program's predicate object for a benchmark predicate."""
    from repro.core import And, Eq, In, Not, Or, Range

    op = pred[0]
    if op == "eq":
        return Eq(pred[1], pred[2])
    if op == "in":
        return In(pred[1], pred[2])
    if op == "range":
        return Range(pred[1], pred[2], pred[3])
    if op == "not":
        return Not(to_program(pred[1]))
    if op == "and":
        return And(*[to_program(p) for p in pred[1:]])
    if op == "or":
        return Or(*[to_program(p) for p in pred[1:]])
    raise ValueError(f"not a predicate: {pred!r}")


@contextmanager
def spans():
    """Host spans around the calls into each layer, for the traced run:
    planning, the backend call (digests, padding, transfer, the device,
    stream read-back), padding, stream-to-rows unpacking, the id mapping
    and the stream concatenation."""
    import jax

    from repro.core import segment
    from repro.core.ewah_stream import EwahStream
    from repro.core.query import JaxBackend

    def wrap(fn, name):
        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return inner

    targets = [(segment, "compile_plan", "plan", False),
               (segment, "concat_streams", "concat", False),
               (JaxBackend, "execute_compressed_many", "backend_call", False),
               (JaxBackend, "_pad_group", "pad", True),
               (EwahStream, "to_rows", "to_rows", False),
               (segment.Segment, "original_rows", "map_ids", False)]
    saved = []
    try:
        for obj, attr, name, static in targets:
            raw = obj.__dict__[attr]
            saved.append((obj, attr, raw))
            fn = raw.__func__ if static else raw
            new = wrap(fn, name)
            setattr(obj, attr, staticmethod(new) if static else new)
        yield [name for *_, name, _ in targets]
    finally:
        for obj, attr, raw in reversed(saved):
            setattr(obj, attr, raw)


class CompileCounter:
    """XLA compilations (or persistent-cache loads) seen while ``armed``,
    and the persistent cache's hits and misses over the whole run."""

    def __init__(self):
        self.armed = False
        self.window = 0
        self.cache = {"hits": 0, "misses": 0}

    def on_duration(self, event, duration, **kw):
        if event == COMPILE_EVENT and self.armed:
            self.window += 1

    def on_event(self, event, **kw):
        if event in CACHE_EVENTS:
            self.cache[CACHE_EVENTS[event]] += 1

    @contextmanager
    def listening(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self.on_duration)
        mon.register_event_listener(self.on_event)
        try:
            yield self
        finally:
            mon.unregister_event_duration_listener(self.on_duration)
            mon.unregister_event_listener(self.on_event)


def build(config: dict, seed: int):
    """The configuration's table from ``seed``, ingested through
    ``IndexWriter`` one sealed segment of ``rows_per_segment`` at a time,
    the rest sealed as the last segment when the writer closes, with the
    delete applied.  Returns ``(columns, writer, deleted, timings)``."""
    from repro.core import IndexSpec, IndexWriter

    names = [c["name"] for c in config["columns"]]
    t0 = time.perf_counter()
    columns = tables.make_table(config, seed)
    t1 = time.perf_counter()
    per = config["rows_per_segment"]
    writer = IndexWriter(IndexSpec(**config["index"]), seal_rows=per)
    for lo in range(0, config["rows"], per):
        writer.append([c[lo:lo + per] for c in columns])
    writer.close()
    t2 = time.perf_counter()
    deleted = writer.delete(to_program(positions(config["delete"], names)))
    t3 = time.perf_counter()
    return columns, writer, deleted, {"generate_s": t1 - t0,
                                      "build_s": t2 - t1,
                                      "delete_s": t3 - t2}


def judge(kind: str, answers, pool, columns, live) -> dict:
    """Every answer of the window against the reference: the number of
    wrong answers and of rows (or counted rows) they got wrong."""
    want = {}
    wrong = wrong_rows = 0
    for i, got in answers:
        if i not in want:
            want[i] = reference.answer(pool[i], columns, live, kind)
        if kind == "rows":
            ok = (isinstance(got, np.ndarray) and got.shape == want[i].shape
                  and np.array_equal(got, want[i]))
            if not ok:
                wrong += 1
                wrong_rows += len(np.setxor1d(np.asarray(got, np.int64),
                                              want[i]))
        elif got != want[i]:
            wrong += 1
            wrong_rows += abs(int(got) - want[i])
    return {"wrong_answers": wrong, "wrong_rows": wrong_rows}


LIMITS = {"wrong_answers": 0, "wrong_rows": 0, "failed_requests": 0}


def run_cell(c: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, require_tpu: bool = True,
             trace_dir: str | None = None, log=print) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    from repro.core.query import get_backend, workload_snapshot

    t_start = time.perf_counter() if t_start is None else t_start
    w, config, traffic = c["workload"], c["config"], c["traffic"]
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise Refused(f"no TPU: JAX found {dev.platform!r} devices")
    if len(devices) < w["chips"]:
        raise Refused(f"the cell asks for {w['chips']} chips, JAX found "
                      f"{len(devices)}")
    counter = CompileCounter()
    with ExitStack() as stack:
        stack.enter_context(counter.listening())
        be = get_backend("jax")
        if require_tpu and be.interpret:
            raise Refused("the jax backend resolved interpret=True")
        columns, writer, deleted, timings = build(config, seed)
        index = writer.index
        pool = mix.make_pool(c["pool"], config)
        preds = [to_program(p) for p in pool]
        kind = traffic["answer"]
        if traffic["result_cache"] != "clear":
            raise ValueError(f"unknown result-cache policy "
                             f"{traffic['result_cache']!r}")
        if kind == "rows":
            def entry(p):
                return index.query_many([p], backend="jax")[0][0]
        else:
            def entry(p):
                return index.count(p, backend="jax")
        t0 = time.perf_counter()
        for p in preds:  # compiles, or loads, every program of the window
            be.result_cache.clear()
            entry(p)
        be.result_cache.clear()
        timings["warmup_s"] = time.perf_counter() - t0
        paths0 = be.group_paths()
        merges0 = sum(v["merges"] for v in workload_snapshot().values())
        setup_s = time.perf_counter() - t_start

        order = mix.request_order(traffic, c["pool"])
        latencies, answers = [], []
        failed = 0
        tdir = window_span = None
        names = []
        if trace:
            # the spans wrap the window's calls only: the Pallas kernels'
            # serialized code carries the call stack of their lowering, and
            # with it the persistent cache's keys, so warm-up lowers every
            # program as an untraced run does
            names = stack.enter_context(spans())
            # the trace covers the window's first TRACE_REQUESTS requests;
            # the rest of the window runs untraced
            tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            devtrace.start(tdir)
            window_span = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
            window_span.__enter__()
        counter.armed = True
        w0 = time.perf_counter()
        deadline = w0 + seconds
        while time.perf_counter() < deadline:
            if (window_span is not None
                    and len(answers) + failed >= TRACE_REQUESTS):
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                window_span = None
            i = next(order)
            with jax.profiler.TraceAnnotation("clear_cache"):
                be.result_cache.clear()
            try:
                with jax.profiler.TraceAnnotation(devtrace.REQUEST_SPAN):
                    ts = time.perf_counter()
                    got = entry(preds[i])
                    latencies.append(time.perf_counter() - ts)
            except Exception as e:  # a failed request is counted, not fatal
                failed += 1
                log(f"request {i} failed: {e!r}")
                continue
            answers.append((i, got))
        window_s = time.perf_counter() - w0
        if window_span is not None:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        counter.armed = False
        paths1 = be.group_paths()
        merges = sum(v["merges"] for v in workload_snapshot().values()) \
            - merges0
        stats = dev.memory_stats() or {}
        memory_peak = stats.get("peak_bytes_in_use")
        index_words = index.size_words()
        n_rows = index.n_sealed_rows
        summary = None
        if tdir is not None:
            try:
                r0 = time.perf_counter()
                events = devtrace.read_xplane(tdir, names)
                summary = devtrace.reduce(events)
                log(f"trace: {len(events)} events read and reduced in "
                    f"{time.perf_counter() - r0:.3f} s")
                if summary is not None:
                    log(f"trace: {summary['requests']} requests in "
                        f"{summary['window_s']:.3f} s, buffers dropped: "
                        f"{summary['dropped']}")
                del events
            finally:
                if trace_dir is None:
                    shutil.rmtree(tdir, ignore_errors=True)
        del writer, index

    log(f"setup: {json.dumps(timings)}, setup_s {setup_s:.3f}; persistent "
        f"compile cache hits {counter.cache['hits']} misses "
        f"{counter.cache['misses']} (whole run)")
    window_paths = {k: paths1[k] - paths0[k] for k in paths1}
    log(f"plan groups in the window: {json.dumps(window_paths)}; all run: "
        f"{json.dumps(paths1)}")
    log(f"window: {len(latencies)} requests in {window_s:.3f} s, {failed} "
        f"failed, {counter.window} compiles, {deleted} rows tombstoned")

    # the reference runs on the host once the window has closed and the
    # device memory peak has been read
    names_ = [col["name"] for col in config["columns"]]
    live = reference.live_mask(positions(config["delete"], names_), columns)
    checks = judge(kind, answers, pool, columns, live)
    checks["failed_requests"] = failed
    gone = int(np.count_nonzero(~live))
    if int(deleted) != gone:  # the delete itself is an answer to check
        checks["wrong_rows"] += abs(int(deleted) - gone)
        checks["wrong_answers"] += 1
    correct = all(checks[k] <= LIMITS[k] for k in checks) and bool(answers)

    run = {"requests": len(latencies), "window_s": window_s,
           "latencies_s": latencies, "setup_s": setup_s, "merges": merges,
           "compiles": counter.window, "index_words": index_words,
           "rows": n_rows, "trace": summary, "device_kind": dev.device_kind,
           "group_paths": window_paths}
    metrics = {}
    wanted = c["per_layer"] if trace else c["end_to_end"]
    for m in wanted:
        if trace:
            value = metric_reader(m["name"], c["base"])(run)
        else:
            value = END_TO_END[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": w["chips"], "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(latencies) + failed,
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                     for k, v in checks.items()}
    return out


def _p90_ms(run):
    return float(np.percentile(run["latencies_s"], 90)) * 1e3 \
        if run["latencies_s"] else None


#: End-to-end metrics, taken by the harness on the host's clock.
END_TO_END = {
    "setup_s": lambda run: run["setup_s"],
    "queries_per_s": lambda run: run["requests"] / run["window_s"],
    "query_p90_ms": _p90_ms,
}


def print_checks(checks: dict, stream=sys.stderr) -> None:
    """Each compared number beside its limit, as the last lines of
    standard error."""
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=stream)
