"""The dense reference agrees with the program's numpy backend, and the
check that decides ``correct`` sees what it must."""

import numpy as np
import pytest

from bench import control, harness, mix, reference
from bench.tests.cells import CELLS, cell_of


def small_cell(name, segments=2, per=2048):
    return cell_of(name, segments, per)


@pytest.mark.parametrize("tail", [0, 1000])
def test_reference_agrees_with_numpy_backend(tail):
    c = cell_of("tpch_lineitem.adhoc_rows", 2, 2048, tail)
    config = c["config"]
    columns, writer, deleted, _ = harness.build(config, 2**32 + 17)
    assert len(writer.index.segments) == 2 + bool(tail)
    names = [k["name"] for k in config["columns"]]
    live = reference.live_mask(harness.positions(config["delete"], names),
                               columns)
    assert deleted == int((~live).sum()) > 0
    pool = mix.make_pool(c["pool"], config)
    got = writer.index.query_many([harness.to_program(p) for p in pool],
                                  backend="numpy")
    for p, (rows, _) in zip(pool, got):
        assert np.array_equal(rows, reference.answer(p, columns, live, "rows"))
        assert (writer.index.count(harness.to_program(p), backend="numpy")
                == reference.answer(p, columns, live, "count"))


def test_reference_evaluates_each_operator():
    cols = [np.array([0, 1, 2, 3, 4]), np.array([4, 3, 2, 1, 0])]
    m = reference.dense_mask
    assert m(["eq", 0, 2], cols).tolist() == [0, 0, 1, 0, 0]
    assert m(["in", 1, [0, 4]], cols).tolist() == [1, 0, 0, 0, 1]
    assert m(["range", 0, 1, 3], cols).tolist() == [0, 1, 1, 1, 0]
    assert m(["not", ["eq", 0, 2]], cols).tolist() == [1, 1, 0, 1, 1]
    assert m(["and", ["range", 0, 1, 3], ["eq", 1, 2]], cols).tolist() == \
        [0, 0, 1, 0, 0]
    assert m(["or", ["eq", 0, 0], ["eq", 1, 0]], cols).tolist() == \
        [1, 0, 0, 0, 1]
    live = np.array([True, True, False, True, True])
    assert reference.answer(["range", 0, 1, 3], cols, live,
                            "rows").tolist() == [1, 3]
    assert reference.answer(["range", 0, 1, 3], cols, live, "count") == 2


def test_judge_counts_wrong_answers_and_rows():
    cols = [np.arange(10)]
    live = np.ones(10, bool)
    pool = [["range", 0, 2, 5]]
    right = np.arange(2, 6)
    assert harness.judge("rows", [(0, right)], pool, cols, live) == \
        {"wrong_answers": 0, "wrong_rows": 0}
    assert harness.judge("rows", [(0, right[:-1]), (0, right)], pool, cols,
                         live) == {"wrong_answers": 1, "wrong_rows": 1}
    assert harness.judge("count", [(0, 5)], pool, cols, live) == \
        {"wrong_answers": 1, "wrong_rows": 1}


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_check(name):
    """Tombstones ignored: the reference in the program's place comes out
    not correct, by the harness's own comparison and limits."""
    checks = control.control_checks(small_cell(name, per=4096), 2**31 + 3,
                                    200)
    assert any(v > harness.LIMITS[k] for k, v in checks.items())
