"""chip_smoke.py off the chip: its checks pass at a tiny size on the CPU
(Pallas interpreter), catch a wrong answer, and the script itself refuses
to run without a TPU or outside a checkout."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.core import evaluate_mask
from repro.core.segment import SegmentedIndex
from repro.data.tables import make_dbgen_like

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    # imported by name: its reference workers unpickle functions from it
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import chip_smoke

    return chip_smoke


def test_dense_mask_agrees_with_evaluate_mask(smoke):
    cols = make_dbgen_like(4096, seed=5)
    cards = [int(c.max()) + 1 for c in cols]
    for pred in smoke.predicates(cards, np.random.default_rng(5)):
        np.testing.assert_array_equal(smoke.dense_mask(pred, cols),
                                      evaluate_mask(pred, cols))


def test_index_phase_passes_at_a_tiny_size(smoke):
    out = smoke.index_phase(0, segments=2, seal_rows=32 * 64,
                            log=lambda *_: None)
    assert out["predicates"] == 32 and out["deleted"] > 0
    assert out["paths"]["fused"] > 0
    assert out["paths"]["host_reencode"] == 0


def test_index_phase_catches_a_wrong_answer(smoke, monkeypatch):
    real = SegmentedIndex.query_many

    def drop_last_row(self, preds, backend="numpy", **kw):
        out = real(self, preds, backend=backend, **kw)
        if backend == "jax":
            out[-1] = (out[-1][0][:-1], out[-1][1])
        return out

    monkeypatch.setattr(SegmentedIndex, "query_many", drop_last_row)
    with pytest.raises(smoke.SmokeFailure, match="predicate 31"):
        smoke.index_phase(1, segments=1, seal_rows=32 * 64,
                          log=lambda *_: None)


def test_serve_phase_at_smoke_width(smoke, monkeypatch):
    import repro.launch.serve as serve

    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    argv = [a if a != "--no-smoke" else "--smoke" for a in smoke.SERVE_ARGV]
    out = smoke.serve_phase(argv, log=lambda *_: None)
    assert out["answered"] == out["requests"] == 16


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_refuses_without_a_chip(tmp_path, where):
    script = SCRIPT
    if where == "alone":
        script = shutil.copy(SCRIPT, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "FAILED" in r.stderr


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """The checkout's fixed ``.jax_cache`` unless JAX_COMPILATION_CACHE_DIR
    names one, in which case nothing is set in code."""
    import jax

    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv(ENV_VAR, raising=False)
            assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        else:
            monkeypatch.setenv(ENV_VAR, str(tmp_path / env_dir))
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
