"""Every cell the benchmark lists, and cut-down copies of them for tests
on the CPU."""

import json
import os

from bench import harness

SPEC = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(harness.HERE,
                                                         "configs")))
METRICS = sorted(f[:-3] for f in os.listdir(os.path.join(harness.HERE,
                                                         "metrics"))
                 if f.endswith(".py"))


def cell_of(name, segments=None, per=None, tail=0):
    """The cell ``name`` (``<config>.<traffic>``), optionally cut to
    ``segments`` segments of ``per`` rows and a last one of ``tail``."""
    config, traffic = name.split(".")
    spec = dict(SPEC, workloads=[{"name": name, "config": config,
                                  "traffic": traffic, "chips": 1}])
    c = harness.cell(spec, name)
    if segments:
        c["config"] = dict(c["config"], rows=segments * per + tail,
                           rows_per_segment=per)
    return c
