"""The one traffic generator: it reads a traffic mix and its predicate pool
(data files under ``bench/traffic/`` and ``bench/pools/``) and makes the
requests of a run.

A pool lists predicate *shapes* over a configuration's columns, by name::

    ["eq", col]  /  ["eq", col, v]       a value drawn from the domain / v
    ["in", col, k]  /  ["in", col, [v, ...]]   k distinct drawn values / these
    ["range", col, width]                 width adjacent values, drawn
    ["not", s], ["and", s, ...], ["or", s, ...]

Each entry is ``{"shape": s, "repeat": n}``; its constants are drawn from
the pool's own ``seed``, never from the run's, so every run of a pool asks
the same predicates and compiles the same programs.

``"order": "interleave"`` cycles the pool in one fixed order that spreads
each entry's repeats evenly over the cycle.  The order is the same for
every run's seed, which changes only the table: a closed loop's window
ends inside a cycle, and when each seed shuffled the cycles, which
filters filled that last part moved the rate by some 7% from seed to
seed on a v5e.
"""

from __future__ import annotations

import numpy as np


def draw(shape, names, cards, rng):
    """One concrete predicate (column positions, constants) of ``shape``."""
    op = shape[0]
    if op in ("not", "and", "or"):
        return [op] + [draw(s, names, cards, rng) for s in shape[1:]]
    col = names.index(shape[1])
    card = cards[col]
    if op == "eq":
        if len(shape) > 2:
            return ["eq", col, int(shape[2])]
        return ["eq", col, int(rng.integers(0, card))]
    if op == "in":
        if isinstance(shape[2], list):
            return ["in", col, sorted(int(v) for v in shape[2])]
        vals = rng.choice(card, size=int(shape[2]), replace=False)
        return ["in", col, sorted(int(v) for v in vals)]
    if op == "range":
        width = int(shape[2])
        if not 0 < width <= card:
            raise ValueError(f"range width {width} does not fit {card} "
                             f"values of column {shape[1]!r}")
        lo = int(rng.integers(0, card - width + 1))
        return ["range", col, lo, lo + width - 1]
    raise ValueError(f"unknown predicate shape {shape!r}")


def make_pool(pool: dict, config: dict) -> list:
    """The pool's predicates, in pool order, against ``config``'s columns."""
    names = [c["name"] for c in config["columns"]]
    cards = [c["card"] for c in config["columns"]]
    rng = np.random.default_rng(pool["seed"])
    out = []
    for entry in pool["predicates"]:
        for _ in range(entry.get("repeat", 1)):
            out.append(draw(entry["shape"], names, cards, rng))
    return out


def request_order(traffic: dict, pool: dict):
    """Endless indices into ``make_pool(pool, ...)`` in the order the run
    sends them: the r-th of an entry's n repeats at ``(r + 0.5) / n`` of
    the cycle, ties in pool order."""
    if traffic["order"] != "interleave":
        raise ValueError(f"unknown request order {traffic['order']!r}")
    keys, i = [], 0
    for e, entry in enumerate(pool["predicates"]):
        n = entry.get("repeat", 1)
        for r in range(n):
            keys.append(((r + 0.5) / n, e, i))
            i += 1
    cycle = [i for *_, i in sorted(keys)]
    while True:
        yield from cycle
