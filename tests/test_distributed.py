"""Distributed-layer tests on a small fake-device mesh.

Runs in a subprocess with XLA_FLAGS host-device-count (so the main pytest
process keeps 1 device for everything else).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist.sharding import (batch_shardings, cache_shardings,
                                 opt_shardings, param_shardings,
                                 zero_pad_for)
from repro.launch.mesh import make_debug_mesh
from repro.models import transformer
from repro.models.common import ShardingCtx
from repro.optim import OptConfig, init_opt_state
from repro.train import train_step
from functools import partial

results = {}
mesh = make_debug_mesh(4, 2)
cfg = get_config("tinyllama-1.1b").smoke()

with ShardingCtx(mesh):
    p_sh = param_shardings(mesh, cfg)
    o_sh = opt_shardings(mesh, cfg)
    zp = zero_pad_for(mesh)
    params = jax.jit(lambda k: transformer.init_params(k, cfg),
                     out_shardings=p_sh)(jax.random.PRNGKey(0))
    opt = jax.jit(partial(init_opt_state, zero_pad=zp),
                  out_shardings=o_sh)(params)
    # param sharding places ff dim on model axis
    wg = params["layers"]["ffn"]["w_gate"]
    results["ffn_sharded"] = "model" in str(wg.sharding.spec)
    # ZeRO: moments pick up the data axis somewhere
    mm = opt["m"]["layers"]["ffn"]["w_gate"]
    results["zero1"] = "data" in str(mm.sharding.spec)
    # flat ZeRO-1: EVERY moment leaf is 1-D, padded to the data-axis
    # size, and actually sharded over "data" — dimension divisibility
    # no longer decides which leaves shard
    results["zero1_pad"] = zp
    m_leaves = jax.tree.leaves(opt["m"])
    results["zero1_all_flat"] = all(
        l.ndim == 1 and l.shape[0] % zp == 0 for l in m_leaves)
    results["zero1_all_sharded"] = all(
        "data" in str(l.sharding.spec) for l in m_leaves)

    b_sh = batch_shardings(mesh, cfg, "train")
    rng = np.random.default_rng(0)
    batch = {
        "inputs": jax.device_put(
            rng.integers(0, cfg.vocab_size, (8, 32)), b_sh["inputs"]),
        "labels": jax.device_put(
            rng.integers(0, cfg.vocab_size, (8, 32)), b_sh["labels"]),
    }
    opt_cfg = OptConfig(total_steps=10, warmup_steps=1)
    step = jax.jit(partial(train_step, cfg=cfg, opt_cfg=opt_cfg,
                           microbatches=2, grad_shardings=o_sh["m"]),
                   in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    p2, o2, m = step(params, opt, batch)
    results["loss_finite"] = bool(np.isfinite(float(m["loss"])))
    results["sharded_loss"] = float(m["loss"])

# single-device reference: same math without mesh
cfg1 = cfg
params1 = transformer.init_params(jax.random.PRNGKey(0), cfg1)
opt1 = init_opt_state(params1)
batch1 = {k: np.asarray(v) for k, v in batch.items()}
p1, o1, m1 = jax.jit(partial(train_step, cfg=cfg1, opt_cfg=opt_cfg,
                             microbatches=2))(params1, opt1, batch1)
results["ref_loss"] = float(m1["loss"])
print("RESULTS:" + json.dumps(results))
"""


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULTS:")][0]
    return json.loads(line[len("RESULTS:"):])


def test_param_tp_sharding(dist_results):
    assert dist_results["ffn_sharded"]


def test_zero1_moment_sharding(dist_results):
    assert dist_results["zero1"]


def test_zero1_flat_shards_every_leaf(dist_results):
    """Regression (ROADMAP): flat ZeRO-1 — moments store 1-D, padded to
    the data-axis size, and every leaf shards over "data", including
    leaves whose dims the old placement could not divide."""
    assert dist_results["zero1_pad"] == 4
    assert dist_results["zero1_all_flat"]
    assert dist_results["zero1_all_sharded"]


def test_zero1_flat_apply_updates_matches_param_shaped():
    """The flat+padded moment storage computes bit-for-bit the same update
    as param-shaped moments (padding lanes stay exactly zero), including
    leaves whose sizes do not divide the pad multiple."""
    import jax
    import jax.numpy as jnp

    from repro.optim import OptConfig, apply_updates, init_opt_state

    r = np.random.default_rng(0)
    # 15, 7, 1: none divisible by 4 — the shapes the old placement skipped
    params = {"a": jnp.asarray(r.normal(size=(5, 3)), jnp.float32),
              "b": jnp.asarray(r.normal(size=(7,)), jnp.float32),
              "c": jnp.asarray(r.normal(size=(1,)), jnp.float32),
              "d": jnp.asarray(r.normal(size=(4, 2)), jnp.float32)}
    grads = jax.tree.map(
        lambda p: jnp.asarray(r.normal(size=p.shape), jnp.float32), params)
    cfg = OptConfig(total_steps=10, warmup_steps=1)

    s_ref = init_opt_state(params)
    s_flat = init_opt_state(params, zero_pad=4)
    assert all(l.ndim == 1 and l.shape[0] % 4 == 0
               for l in jax.tree.leaves(s_flat["m"]))

    for _ in range(3):  # a few steps so moments are non-trivial
        p_ref, s_ref, _ = apply_updates(cfg, params, grads, s_ref)
        p_flat, s_flat, _ = apply_updates(cfg, params, grads, s_flat)
        jax.tree.map(np.testing.assert_array_equal, p_ref, p_flat)
    # moments agree after unflattening, and the padding stays zero
    for key in ("m", "v"):
        for name, ref_leaf in s_ref[key].items():
            flat_leaf = s_flat[key][name]
            np.testing.assert_array_equal(
                np.asarray(flat_leaf)[: ref_leaf.size].reshape(ref_leaf.shape),
                np.asarray(ref_leaf))
            np.testing.assert_array_equal(
                np.asarray(flat_leaf)[ref_leaf.size:], 0.0)


def test_sharded_step_runs(dist_results):
    assert dist_results["loss_finite"]


def test_sharded_matches_single_device(dist_results):
    """Distribution must not change the math (same seed, same loss)."""
    np.testing.assert_allclose(
        dist_results["sharded_loss"], dist_results["ref_loss"],
        rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Query fan-out over row-range index shards (repro.dist.query_fanout) —
# in-process, no mesh needed.
# ---------------------------------------------------------------------------


def _fanout_fixture(n=4017, seed=11, k=2):
    from repro.core import BitmapIndex, IndexSpec
    from repro.dist.query_fanout import ShardedIndex

    r = np.random.default_rng(seed)
    cols = [r.integers(0, c, size=n) for c in (6, 11, 29)]
    spec = IndexSpec(k=k, row_order="grayfreq")
    return cols, BitmapIndex.build(cols, spec), \
        ShardedIndex.build(cols, spec, n_shards=4)


def test_shard_ranges_word_aligned():
    from repro.dist.query_fanout import shard_ranges

    for n, s in [(1000, 4), (31, 4), (64, 2), (65, 4), (100_000, 7), (32, 1)]:
        ranges = shard_ranges(n, s)
        assert ranges[0][0] == 0 and ranges[-1][1] == n
        assert all(start % 32 == 0 for start, _ in ranges)
        assert all(b == c for (_, b), (c, _) in zip(ranges, ranges[1:]))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_fanout_4_shards_matches_single(backend):
    """Fan-out over a 4-shard split returns identical row ids to
    single-shard execution, for every predicate shape."""
    from repro.core import And, Eq, In, Not, Or, Range

    cols, single, sharded = _fanout_fixture()
    assert sharded.n_shards == 4
    preds = [
        Eq(0, 3), In(1, [1, 5, 9]), Range(2, 4, 25), Range(2, 2, 27),
        Not(Eq(0, 0)),
        And(In(0, [0, 1, 2]), Range(1, 0, 6), Not(Eq(2, 5))),
        Or(And(Eq(0, 1), Eq(1, 1)), Not(In(2, [0, 1, 2]))),
    ]
    for pred in preds:
        rows_single, _ = single.query(pred, backend=backend)
        expect = np.sort(single.row_perm[rows_single])
        got, scanned = sharded.query(pred, backend=backend)
        np.testing.assert_array_equal(got, expect)
        assert scanned >= 0


def test_fanout_ships_compressed_and_coalesces():
    """Shards ship EWAH streams; the merge is concatenation with clean-run
    coalescing, so the merged stream counts exactly the matched rows and
    is no longer than the sum of its parts."""
    from repro.core import Eq, Not

    cols, single, sharded = _fanout_fixture()
    for pred in (Eq(0, 3), Not(Eq(1, 2))):
        results, merged = sharded.execute_compressed(pred)
        assert len(results) == 4
        assert merged.n_rows == len(cols[0])
        rows_single, _ = single.query(pred)
        assert merged.count() == len(rows_single)
        assert len(merged) <= sum(len(r) for r in results)
        # per-shard word alignment: every shard but the last covers a
        # multiple of 32 rows
        assert all(sh.n_rows % 32 == 0 for sh in sharded.shards[:-1])
    # shards are Segments sealed WITHOUT the raw-column row store (they
    # are never compacted; keeping the arrays would double memory)
    assert all(sh.columns is None for sh in sharded.shards)


def test_fanout_shard_local_value_domains():
    """A value only some shards ever saw still resolves globally (missing
    shards compile it to a constant-empty plan)."""
    from repro.core import Eq
    from repro.core.strategies import IndexSpec
    from repro.dist.query_fanout import ShardedIndex

    col = np.zeros(256, dtype=np.int64)
    col[200:210] = 7                    # value 7 exists only in shard 4
    sharded = ShardedIndex.build([col], IndexSpec(k=1, row_order="unsorted",
                                                  column_order="given"),
                                 n_shards=4)
    rows, _ = sharded.query(Eq(0, 7))
    np.testing.assert_array_equal(rows, np.arange(200, 210))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_fanout_query_many_batches_across_predicates(backend):
    """query_many sends all predicates' per-shard plans to the backend in
    one call and matches per-predicate query() results."""
    from repro.core import Eq, In

    cols, single, sharded = _fanout_fixture()
    preds = [Eq(0, v) for v in range(4)] + [In(1, [1, 5])]
    batched = sharded.query_many(preds, backend=backend)
    for pred, (rows, scanned) in zip(preds, batched):
        one_rows, one_scanned = sharded.query(pred, backend=backend)
        np.testing.assert_array_equal(rows, one_rows)
        rows_single, _ = single.query(pred, backend=backend)
        np.testing.assert_array_equal(
            rows, np.sort(single.row_perm[rows_single]))


def test_metadata_index_query_fanout():
    """MetadataIndex(query_fanout=N) routes queries through the sharded
    path (original-row-space ids) and guards the single-index accessor."""
    from repro.core import In
    from repro.data.metadata_index import MetadataIndex

    r = np.random.default_rng(5)
    meta = {c: r.integers(0, k, size=500) for c, k in
            zip(MetadataIndex.COLS, (4, 8, 16, 6))}
    plain = MetadataIndex(k=1)
    plain.add_batch(meta)
    fanned = MetadataIndex(k=1, query_fanout=4)
    fanned.add_batch(meta)

    # both modes answer in original ingest row space
    rows_plain, _ = plain.query(where={"domain": 3, "quality_bin": 8})
    expect = np.flatnonzero((meta["domain"] == 3) & (meta["quality_bin"] == 8))
    np.testing.assert_array_equal(rows_plain, expect)
    rows_fan, _ = fanned.query(where={"domain": 3, "quality_bin": 8})
    np.testing.assert_array_equal(rows_fan, expect)
    rows_pred, _ = fanned.query_pred(In("domain", [1, 3]), backend="jax")
    np.testing.assert_array_equal(
        rows_pred, np.flatnonzero(np.isin(meta["domain"], [1, 3])))
    assert fanned.sharded.n_shards == 4
    assert fanned.size_words() > 0
    with pytest.raises(ValueError, match="sharded"):
        fanned.index  # would silently build a second, inconsistent surface


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_fanout_deletes_and_ttl(backend):
    """ShardedIndex.delete tombstones across the fan-out — each shard ORs
    its share into its compressed tombstone bitmap and later queries AND
    the live mask in — and expiry deadlines fold lazily on the build
    clock.  Answers track a dense oracle throughout."""
    from repro.core import Eq, In, Range, evaluate_mask
    from repro.core.strategies import IndexSpec
    from repro.dist.query_fanout import ShardedIndex

    r = np.random.default_rng(9)
    cols = [r.integers(0, 6, size=500), r.integers(0, 11, size=500)]
    fake = [1000.0]
    expiry = np.full(500, np.inf)
    expiry[100:200] = 1050.0
    sharded = ShardedIndex.build(
        cols, IndexSpec(k=1, row_order="unsorted", column_order="given"),
        n_shards=4, expiry=expiry, clock=lambda: fake[0])
    alive = np.ones(500, dtype=bool)
    assert sharded.delete(row_ids=np.arange(40, 80)) == 40
    alive[40:80] = False
    kill = Eq(0, 2)
    expect = int((evaluate_mask(kill, cols) & alive).sum())
    assert sharded.delete(kill, backend=backend) == expect
    alive &= ~evaluate_mask(kill, cols)
    preds = [Eq(0, 3), In(1, [1, 5, 9]), Range(1, 2, 8)]
    for p in preds:
        rows, _ = sharded.query(p, backend=backend)
        np.testing.assert_array_equal(
            rows, np.flatnonzero(evaluate_mask(p, cols) & alive))
    fake[0] = 1100.0                             # cross the TTL deadline
    alive[100:200] = False
    for p in preds:
        rows, _ = sharded.query(p, backend=backend)
        np.testing.assert_array_equal(
            rows, np.flatnonzero(evaluate_mask(p, cols) & alive))


def test_metadata_index_fanout_lsm_matches_single():
    """MetadataIndex deletes / TTLs / compaction answer identically through
    the fan-out and the single segmented path (the fan-out view rebuilds
    over the surviving ingest ids, so ids stay stable across purges)."""
    from repro.data.metadata_index import MetadataIndex

    r = np.random.default_rng(11)

    def batch(n):
        return {c: r.integers(0, k, size=n) for c, k in
                zip(MetadataIndex.COLS, (4, 8, 16, 6))}

    fake = [1000.0]
    fan = MetadataIndex(query_fanout=3)
    fan.writer.clock = lambda: fake[0]
    single = MetadataIndex()
    single.writer.clock = lambda: fake[0]
    batches = [batch(100) for _ in range(3)]
    for i, b in enumerate(batches):
        ttl = 50.0 if i == 1 else None
        fan.add_batch(b, ttl=ttl)
        single.add_batch(b, ttl=ttl)
    assert fan.delete(where={"domain": 2}) == \
        single.delete(where={"domain": 2})
    fan.delete(row_ids=np.arange(10, 40))
    single.delete(row_ids=np.arange(10, 40))
    queries = [{"source": 1}, {"quality_bin": 5, "source": 2}]
    for q in queries:
        a, _ = fan.query(q)
        b, _ = single.query(q)
        np.testing.assert_array_equal(a, b)
    _ = fan.sharded                              # build pre-expiry
    fake[0] = 1100.0                             # batch 1 TTLs out lazily
    for q in queries:
        a, _ = fan.query(q)
        b, _ = single.query(q)
        np.testing.assert_array_equal(a, b)
        assert not ((a >= 100) & (a < 200)).any()
    single.compact(span=(0, len(single.writer.segments)))  # physical purge
    fan._sharded = None                          # rebuild over survivors
    for backend in ("numpy", "jax"):
        for q in queries:
            a, _ = fan.query(q, backend=backend)
            b, _ = single.query(q, backend=backend)
            np.testing.assert_array_equal(a, b)
