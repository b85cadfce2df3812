"""Rehearsal of ``chip_smoke.py`` on the CPU.

The phase functions run here at R-MAT scale 8, g=1, with the Pallas
kernels in interpret mode (chosen by this test; the script itself always
asks for ``impl="pallas"``), and the script run without a TPU must fail
without printing a result.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph(smoke):
    return smoke.graph_phase(8, 16, 0, g=1, block_size=32)


def test_graph_phase_matches_scipy(graph):
    a_h, csr, _ = graph
    assert a_h.g == 1 and csr.shape == (256, 256)
    dense = a_h.densify()
    assert set(map(float, dense.ravel().tolist())) <= {0.0, 1.0}
    assert (dense == csr.toarray()).all()


def test_spmm_phase_interpret_within_bound(smoke, graph):
    a_h, csr, _ = graph
    r = smoke.spmm_phase(a_h, csr, seed=0, impl="interpret")
    assert r["algorithm"] == "ring_c" and r["impl"] == "interpret"
    assert r["max_err_over_bound"] <= 1.0
    assert len(r["steady_s"]) == smoke.STEADY_CALLS
    assert r["out_devices"] == [0]


def test_spgemm_phase_interpret_exact(smoke, graph):
    a_h, csr, _ = graph
    r = smoke.spgemm_phase(a_h, csr, impl="interpret")
    assert r["max_abs_err"] == 0.0
    assert r["out_blocks"] == (csr @ csr).tobsr(blocksize=(32, 32)).nnz \
        // (32 * 32)
    assert r["pairs"] > 0


def test_spmm_tolerance_is_bf16_pass_bound(smoke, graph):
    """B rounded or truncated to bf16, against the float64 product, stays
    inside the stated bound, and the bound is not looser than 10x."""
    import ml_dtypes
    import numpy as np
    _, csr, _ = graph
    b = np.random.default_rng(1).standard_normal((256, 8))
    want = csr.astype(np.float64) @ b
    bound = smoke.spmm_tolerance(csr, b)
    rounded = b.astype(ml_dtypes.bfloat16).astype(np.float64)
    truncated = (b.astype(np.float32).view(np.uint32)
                 & 0xFFFF0000).view(np.float32).astype(np.float64)
    for b_bf16 in (rounded, truncated):
        err = np.abs(csr.astype(np.float64) @ b_bf16 - want)
        assert (err <= bound).all()
        assert (err / np.maximum(bound, 1e-300)).max() > 0.1


def test_script_without_tpu_fails_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
