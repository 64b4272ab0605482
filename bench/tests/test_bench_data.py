"""Generators, pools and request orders are fixed by their seeds."""

import numpy as np
import pytest

from bench import harness, mix, tables
from bench.tests.cells import CELLS, CONFIGS, SPEC, cell_of


def small(config, segments=2, per=2048, tail=0):
    return dict(config, rows=segments * per + tail, rows_per_segment=per)


@pytest.mark.parametrize("name", CONFIGS)
def test_tables_are_fixed_by_seed(name):
    config = small(harness.load("configs", name))
    a = tables.make_table(config, 2**33 + 1)
    b = tables.make_table(config, 2**33 + 1)
    c = tables.make_table(config, 2**33 + 2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    for col, card in zip(a, [k["card"] for k in config["columns"]]):
        assert len(col) == config["rows"]
        assert col.min() >= 0 and col.max() < card


@pytest.mark.parametrize("name", CONFIGS)
def test_config_files_state_their_deployment(name):
    config = harness.load("configs", name)
    for entry in [c for c in SPEC["configs"] if c["name"] == name]:
        assert entry["file"] == f"bench/configs/{name}.json"
        assert sorted(entry["reduced"]) == sorted(config["reduced"])
        assert len(entry["source"]) <= 200
    assert config["assumed"] and config["guarantees"]
    assert config["rows_per_segment"] % 32 == 0  # sealed segments word-aligned
    assert len(config["source"]) <= 200


def test_lineitem_is_the_papers_dbgen_table():
    config = harness.load("configs", "tpch_lineitem")
    assert config["rows"] == 13_977_980 and not config["reduced"]
    assert [c["card"] for c in config["columns"]] == [7, 11, 2526, 400_000]


@pytest.mark.parametrize("cell", CELLS)
def test_pool_does_not_depend_on_run_seed(cell):
    c = cell_of(cell)
    pool = mix.make_pool(c["pool"], c["config"])
    assert pool == mix.make_pool(c["pool"], c["config"])
    assert len(pool) == sum(e.get("repeat", 1)
                            for e in c["pool"]["predicates"])
    cards = [k["card"] for k in c["config"]["columns"]]
    for p in pool:  # every constant lies in its column's domain
        stack = [p]
        while stack:
            q = stack.pop()
            if q[0] in ("not", "and", "or"):
                stack.extend(q[1:])
            else:
                vals = q[2] if q[0] == "in" else q[2:]
                assert all(0 <= v < cards[q[1]] for v in np.ravel(vals))


@pytest.mark.parametrize("cell", CELLS)
def test_request_order_is_fixed_by_seed(cell):
    """Every seed sends the same requests in the same order: the pool's
    cycle, again and again."""
    c = cell_of(cell)
    n = len(mix.make_pool(c["pool"], c["config"]))
    it = mix.request_order(c["traffic"], c["pool"])
    first = [next(it) for _ in range(n)]
    assert sorted(first) == list(range(n))
    assert [next(it) for _ in range(2 * n)] == 2 * first


def test_shuffle_cycles_every_entry_once_per_cycle():
    """Each entry's repeats are spread over the cycle, not sent in a row."""
    pool = {"predicates": [{"shape": ["eq", "a"], "repeat": 4},
                           {"shape": ["eq", "b"]},
                           {"shape": ["eq", "c"], "repeat": 2}]}
    it = mix.request_order({"order": "interleave"}, pool)
    cycle = [next(it) for _ in range(7)]
    # pool indices: a 0-3, b 4, c 5-6
    assert cycle == [0, 5, 1, 4, 2, 6, 3]
    assert [next(it) for _ in range(7)] == cycle


def test_unknown_order_is_refused():
    with pytest.raises(ValueError):
        next(mix.request_order({"order": "zipf"}, {"predicates": []}))


def test_tpch_pool_plan_roots_do_not_depend_on_data_seed():
    """A new seed changes the data but not the programs: every pool
    predicate compiles to the same plan roots on both seeds, one for the
    full segments and one for the last, and each runs as one fused
    megakernel, never per stage."""
    from repro.core.query import JaxBackend, compile_plan, with_live_mask

    c = cell_of("tpch_lineitem.adhoc_rows")
    config = small(c["config"], segments=2, per=32768, tail=10_000)
    pool = mix.make_pool(c["pool"], config)
    roots = []
    for seed in (5, 2**31 + 7):
        _, writer, _, _ = harness.build(config, seed)
        segs = writer.index.segments
        assert len(segs) == 3
        roots.append([
            [with_live_mask(compile_plan(s.index, harness.to_program(p)),
                            s.live_stream()).root for s in segs]
            for p in pool])
    assert all(r[0] == r[1] for r in roots[0])
    assert roots[0] == roots[1]
    be = JaxBackend(interpret=True)
    assert all(be._fused_tape(root) is not None
               for r in roots[0] for root in r)
