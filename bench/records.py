"""The program's own request records (``repro.core.trace``) over the
window's untraced requests, for the per-layer metrics that read them.

The traced run profiles the window's first ``harness.TRACE_REQUESTS``
requests; the rest of the window runs with the profiler off, and those
are the newest ``run["requests"] - TRACE_REQUESTS`` records in the
program's ring once the window has closed.  A program without the
recorder, or a window too short to hold such requests, gives nothing.

``BENCHMARK.json`` does not list these readers yet: the harness stops
the profiler inside the window, and on a v5e that takes longer than the
window, so a traced window holds one unprofiled request, always the
same filter.  They are listed once the profiler runs outside the window.
"""

from bench import harness


def window(run):
    """The untraced window requests' records, oldest first, or None."""
    n = run["requests"] - harness.TRACE_REQUESTS
    if n <= 0:
        return None
    try:
        from repro.core import trace
    except ImportError:  # a program that records no spans
        return None
    records = trace.recent(n)
    return records if len(records) == n else None


def ms_per_query(run, names):
    """Self time of the spans named in ``names``, milliseconds per
    request."""
    records = window(run)
    if records is None:
        return None
    return sum(r.self_ns_of(names) for r in records) * 1e-6 / len(records)


def counter_sum(records, name):
    return sum(r.counters.get(name, 0) for r in records)
