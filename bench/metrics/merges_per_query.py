"""Planner: binary stream merges that ``compile_plan`` put into the
window's plans (``repro.core.query.workload_snapshot``), per request."""


def read(run):
    if not run["requests"]:
        return None
    return run["merges"] / run["requests"]
