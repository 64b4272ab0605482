"""Table generators of the benchmark's deployments, copied from the
repository's data module so that the yardstick cannot move with it.

A configuration file lists its columns, each with a cardinality and a
distribution; value ids are dense and 0-based.  The one distribution is
``uniform``, as DBGEN draws TPC-H's columns.  The same seed gives the same
table.
"""

from __future__ import annotations

import numpy as np


def make_table(config: dict, seed: int) -> list:
    """The configuration's table from ``seed``: one int64 array per column,
    ``config["rows"]`` rows each."""
    rng = np.random.default_rng(seed)
    n = config["rows"]
    cols = []
    for col in config["columns"]:
        if col["dist"] != "uniform":
            raise ValueError(f"column {col['name']!r}: unknown distribution "
                             f"{col['dist']!r}")
        cols.append(rng.integers(0, col["card"], size=n).astype(np.int64))
    return cols
