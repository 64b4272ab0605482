"""Compile the index path's Pallas kernels and device programs for a TPU v5e
that is described, not attached, at the widths the chip runs them.

Nothing runs: each test lowers with ``interpret=False`` and asserts that
the compiled program holds the Mosaic kernel (``tpu_custom_call``).  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the worker given this file loads
the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import And, BitmapIndex, Eq, IndexSpec, Not, Range
from repro.core.containers import CHUNK_WORDS
from repro.core.ewah import MAX_DIRTY
from repro.core.query import JaxBackend, compile_plan, lower_plan
from repro.kernels import ops

SEGMENTS = 8          # plans per group: one per sealed segment
N_WORDS = MAX_DIRTY   # the widest segment whose results re-encode on device


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read
    # back without the chip, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", [3, 250])
def test_plan_fuse_compiles(one_chip, m):
    """The megakernel over m leaf planes of a whole 8-segment group."""
    tape, _ = lower_plan(("or", tuple(("leaf", i) for i in range(m))))
    hlo = _compile(lambda x: ops.plan_fuse(x, tape, interpret=False),
                   _spec(one_chip, (m, SEGMENTS * N_WORDS), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_slice_fold_compiles(one_chip):
    """An 18-plane bit-sliced comparison (a 239,667-value column)."""
    fops = ("and", "or", "xor") * 5 + ("and", "or")
    hlo = _compile(lambda x: ops.slice_fold(x, fops, interpret=False),
                   _spec(one_chip, (18, SEGMENTS * N_WORDS), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_recompress_batch_compiles(one_chip):
    hlo = _compile(
        lambda w: ops.recompress_batch(w, N_WORDS + 1, interpret=False),
        _spec(one_chip, (SEGMENTS, N_WORDS), jnp.uint32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("op", ["and", "andnot"])
def test_container_pairs_compiles(one_chip, op):
    hlo = _compile(lambda a, b: ops.container_pairs(a, b, op, interpret=False),
                   _spec(one_chip, (64, CHUNK_WORDS), jnp.uint32),
                   _spec(one_chip, (64, CHUNK_WORDS), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_container_gallop_compiles(one_chip):
    hlo = _compile(lambda p, w: ops.container_gallop(p, w, interpret=False),
                   _spec(one_chip, (64, 4096), jnp.int32),
                   _spec(one_chip, (64, CHUNK_WORDS), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_bitpack_compiles(one_chip):
    hlo = _compile(lambda b: ops.bitpack(b, interpret=False),
                   _spec(one_chip, (65536, 256), jnp.int8))
    assert "tpu_custom_call" in hlo


def test_moe_route_bitmap_compiles(one_chip):
    """Dispatch words for 64 experts, top-8, over 8192 tokens."""
    hlo = _compile(lambda e: ops.moe_route_bitmap(e, 64, interpret=False),
                   _spec(one_chip, (8192, 8), jnp.int32))
    assert "tpu_custom_call" in hlo


def _group_program_hlo(one_chip, fuse, seed):
    """HLO text of the jax backend's group program for a two-column filter,
    compiled over SEGMENTS segments of N_WORDS words."""
    rng = np.random.default_rng(seed)
    cols = [rng.integers(0, c, size=4096) for c in (7, 2526)]
    idx = BitmapIndex.build(cols, IndexSpec(encoding="auto"))
    plan = compile_plan(idx, And(Range(1, 100, 1400), Not(Eq(0, 3))))
    be = JaxBackend(interpret=False, fuse=fuse)
    assert (be._fused_tape(plan.root) is not None) == fuse
    m, cap = len(plan.streams), N_WORDS + 1
    fn = be._compiled(plan.root, cap, N_WORDS, compressed=True)
    return fn.lower(_spec(one_chip, (SEGMENTS, m, cap), jnp.uint32),
                    _spec(one_chip, (SEGMENTS, m), jnp.int32)).compile().as_text()


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_stage"])
def test_jax_backend_group_program_compiles(one_chip, fuse):
    """The whole group program a query runs on the device: decode every
    leaf stream, evaluate the plan (one megakernel, or wordops_fold and
    slice_fold per stage), re-encode the results in the graph."""
    assert "tpu_custom_call" in _group_program_hlo(one_chip, fuse, seed=0)


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_stage"])
def test_group_program_ops_carry_their_stage_scope(one_chip, fuse):
    """Every stage of the group program names its ops: ``decode``,
    ``evaluate`` and ``recompress`` sit in the ops' ``op_name`` paths,
    which the profiler's device ops carry, and the fused kernel keeps its
    instruction name ``plan_fuse.<n>``."""
    hlo = _group_program_hlo(one_chip, fuse, seed=1)
    for scope in ("decode", "evaluate", "recompress"):
        assert re.search(rf'op_name="[^"]*/{scope}/', hlo), scope
    kernel = re.search(r'%(plan_fuse(\.\d+)?) = [^\n]*op_name="([^"]*)"', hlo)
    if fuse:
        assert kernel and "/evaluate/" in kernel.group(3)
    else:
        assert kernel is None


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_stage"])
def test_group_program_decode_has_no_scatter(one_chip, fuse):
    """The decode places stream words with static shifts and selects: no
    op of the compiled group program under ``/decode/`` is a scatter
    (recompression keeps its own)."""
    hlo = _group_program_hlo(one_chip, fuse, seed=2)
    # a scatter, fused or not, keeps the jax op it came from at the end of
    # its op_name path (".../decode/jit(decompress)/scatter-add")
    names = re.findall(r'op_name="([^"]*)"', hlo)

    def scatters(stage):
        return [n for n in names if f"/{stage}/" in n
                and n.rsplit("/", 1)[-1].startswith("scatter")]

    assert scatters("recompress"), "the check sees no scatter at all"
    assert not scatters("decode")


def test_stream_bandwidth_is_keyed_by_device_kind(monkeypatch):
    """A TPU's peak comes from the table of its device kind; a kind the
    table lacks raises instead of borrowing another chip's rate."""
    from benchmarks import analytic, roofline

    class Device:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Device()])
    assert roofline.stream_bandwidth() == analytic.HBM_BW
    Device.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        roofline.stream_bandwidth()
