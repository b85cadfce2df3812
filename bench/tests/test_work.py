"""Work counts from the CSR alone, checked against scipy and dense numpy
on tiny graphs, and the table of peaks."""
import numpy as np
import pytest
import scipy.sparse as sps

from harness import roofline, spec


def _graph(seed: int, n: int = 64, density: float = 0.08):
    rng = np.random.default_rng(seed)
    dense = (rng.random((n, n)) < density) * rng.random((n, n))
    return sps.csr_matrix(dense), dense


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_spgemm_work_matches_dense_count(seed):
    kind = spec.load_module(f"{spec.BENCH_DIR}/kinds/spgemm.py")
    csr, dense = _graph(seed)
    nz = (dense != 0).astype(np.int64)
    mads = int((nz @ nz).sum())                 # one per matching pair
    nnz_c = int(np.count_nonzero(nz @ nz))
    w = kind.work(csr, {})
    assert w["flops"] == 2 * mads
    assert w["bytes"] == 8 * (csr.nnz + nnz_c)
    assert nnz_c == (csr @ csr).nnz


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks_for("TPU v99 imaginary")


def test_known_device_kind_and_least_time():
    peaks = spec.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"]["bf16"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    work = {"flops": 197e12, "bytes": 819e9 * 3, "flops_peak": "bf16"}
    assert roofline.least_time_s(work, peaks, 1) == pytest.approx(3.0)
    assert roofline.least_time_s(work, peaks, 4) == pytest.approx(0.75)
