"""The spmm kind (``bench/kinds/spmm.py``): its reference and control, the
comparison that decides ``correct``, its work count, and the 2x2 cell run
on four virtual CPU devices.

Runs on the CPU at a small size (R-MAT scale 9 or 10, 32x32 blocks), with
the kernels' plain ``jnp`` implementation in place of the Pallas ones.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, spec
from helpers import run_small, small_cell

SPMM = "spmm-w512-rmat-s16-2x2"
TESTS = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _fresh_plans():
    from repro.core import api

    api.clear_plan_cache()
    yield
    api.clear_plan_cache()


def _one_chip(scale=9):
    """The cell on a 1x1 grid: the kind's path on the one CPU device."""
    cell = small_cell(SPMM, scale=scale)
    return dataclasses.replace(cell, chips=1, config=dict(cell.config, g=1))


def _inputs(cell, seed):
    csr = cell.generator.weighted_csr(cell.config, seed)
    return csr, check.to_host(cell.kind.inputs(csr, cell.traffic, seed))


def test_inputs_are_float32_normals_apart_from_the_weights():
    cell = small_cell(SPMM)
    csr = cell.generator.weighted_csr(cell.config, 5)
    b = cell.kind.inputs(csr, cell.traffic, 5)["b"]
    assert b.shape == (csr.shape[1], 512) and b.dtype == np.float32
    assert abs(b.mean()) < 0.01 and abs(b.std() - 1) < 0.01
    again = cell.kind.inputs(csr, cell.traffic, 5)["b"]
    np.testing.assert_array_equal(b, again)
    other = cell.kind.inputs(csr, cell.traffic, 2**31 + 5)["b"]
    assert not np.array_equal(b, other)


@pytest.mark.parametrize("seed", [11, 2**31 + 11])
def test_reference_and_high_control_agree_yet_the_control_fails(seed):
    """The control is the reference at three bfloat16 passes: it agrees
    with the float64 reference to some 2**-16 of ``|A| @ |B|``, and fails
    the limit by more than three times."""
    cell = small_cell(SPMM, scale=10)
    kind = cell.kind
    csr, inputs = _inputs(cell, seed)
    ref = kind.reference(csr, inputs)
    np.testing.assert_allclose(ref["c"], csr.toarray() @ inputs["b"],
                               rtol=1e-12, atol=1e-12)
    readings = kind.compare(kind.control(csr, inputs), ref)
    assert readings["max_err_ratio"] < 2.0 ** -14
    correct, checks = check.verdict(readings, cell.limits)
    assert not correct, checks
    assert readings["max_err_ratio"] > 3 * cell.limits["max_err_ratio"][
        "limit"]


def test_exact_output_reads_zero_and_one_entry_off_fails():
    """One entry off by 1e-3 of its ``|A| @ |B|`` reads 1e-3."""
    cell = small_cell(SPMM)
    kind = cell.kind
    csr, inputs = _inputs(cell, 3)
    ref = kind.reference(csr, inputs)
    got = ref["c"].copy()
    assert kind.compare(got, ref) == {"max_err_ratio": 0.0}
    r, c = np.unravel_index(np.argmax(ref["absprod"]), got.shape)
    got[r, c] += 1e-3 * ref["absprod"][r, c]
    readings = kind.compare(got, ref)
    assert readings["max_err_ratio"] == pytest.approx(1e-3, rel=1e-9)
    assert not check.verdict(readings, cell.limits)[0]


def test_a_nonzero_where_a_has_no_row_fails():
    cell = small_cell(SPMM)
    kind = cell.kind
    csr, inputs = _inputs(cell, 4)
    ref = kind.reference(csr, inputs)
    empty = np.flatnonzero(np.diff(csr.indptr) == 0)
    assert empty.size
    got = ref["c"].copy()
    got[empty[0], 0] = 1e-30
    assert not check.verdict(kind.compare(got, ref), cell.limits)[0]


def test_sound_run_is_correct():
    cell = _one_chip()
    run, correct, checks = run_small(cell, seed=2**31 + 5)
    assert correct, checks
    assert run.n_products >= 1 and set(checks) == set(cell.limits)


def _round_bf16(x):
    """``x`` rounded to the nearest bfloat16 (ties to even) on its bits."""
    from jax import lax

    bits = lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                    jnp.float32)


def _one_pass(fn):
    """Both operands rounded to bfloat16 before the products: what the
    kernel's one bfloat16 MXU pass does at the default precision."""
    def broken(blocks, rows, cols, dense, **kw):
        return fn(_round_bf16(blocks), rows, cols, _round_bf16(dense), **kw)
    return broken


def _zero(fn):
    def broken(*args, **kw):
        return jnp.zeros_like(fn(*args, **kw))
    return broken


def _half(fn):
    """Half the stored A blocks left out, the rest counted twice."""
    def broken(blocks, *args, **kw):
        keep = (jnp.arange(blocks.shape[0]) % 2 == 0).astype(blocks.dtype)
        return 2 * fn(blocks * keep[:, None, None], *args, **kw)
    return broken


FAULTS = {"one_pass": _one_pass, "unchanged": _zero, "half": _half}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails(fault, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "bsr_spmm_raw", FAULTS[fault](ops.bsr_spmm_raw))
    _, correct, checks = run_small(_one_chip(), seed=99)
    assert not correct, checks


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_spmm_work_matches_a_hand_count(seed):
    import scipy.sparse as sps

    kind = spec.load_module(f"{spec.BENCH_DIR}/kinds/spmm.py")
    rng = np.random.default_rng(seed)
    dense = (rng.random((48, 64)) < 0.1) * rng.random((48, 64))
    csr = sps.csr_matrix(dense)
    w = kind.work(csr, {"width": 7})
    mads = sum(1 for v in dense.ravel() if v) * 7     # one per nonzero, column
    assert w["flops"] == 2 * mads
    assert w["bytes"] == 8 * csr.nnz + 4 * 7 * (64 + 48)
    assert w["flops_peak"] == "bf16"


_CELL_2X2 = r"""
import json, sys
sys.path[:0] = sys.argv[1:]
from repro.runtime.platform import set_host_device_count
set_host_device_count(4)
import numpy as np
from harness import program
from helpers import run_small, small_cell

cell = small_cell("spmm-w512-rmat-s16-2x2")
run, correct, checks = run_small(cell, seed=2**31 + 21)
csr = cell.generator.weighted_csr(cell.config, 1)
c = program.counters()
print(json.dumps({
    "correct": correct, "checks": checks, "n": csr.shape[0],
    "blocks": [[int(r), int(k)] for r, k in zip(*csr.nonzero())],
    "gauges": {k: program.gauge(c, k) for k in (
        "plan.spmm_block_steps", "plan.spmm_real_blocks", "plan.wire_bytes")},
    "metrics": {n: cell.readers[n].read(run) for n in (
        "spmm_block_util", "wire_gb", "spmm_kernel_ms", "collective_ms",
        "tiling_s", "plan_s", "lower_compile_s", "kernel_ms",
        "device_idle_pct")}}))
"""


def test_the_2x2_cell_on_four_virtual_devices():
    """The whole cell on a 2x2 grid: correct, and its gauges equal hand
    counts of the graph's blocks (untraced: no trace readings)."""
    from repro.core.grid import bucket_capacity

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CELL_2X2, spec.BENCH_DIR,
         os.path.join(spec.ROOT, "src"), TESTS],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    bs, g, n = 32, 2, out["n"]
    tile_b = n // g // bs                      # block rows of a tile
    blocks = {(r // bs, k // bs) for r, k in out["blocks"]}
    counts = np.zeros((g, g), int)
    for br, bc in blocks:
        counts[br // tile_b, bc // tile_b] += 1
    store = bucket_capacity(counts.max()) + tile_b
    steps = g * store                          # one 256-wide panel a call
    assert out["gauges"]["plan.spmm_block_steps"] == steps
    assert out["gauges"]["plan.spmm_real_blocks"] == counts.sum(axis=1).max()
    a_tile = store * (bs * bs * 4 + 8)
    b_tile = n // g * 256 * 4
    # one shift of A's and of B's tile on a ring of two
    assert out["gauges"]["plan.wire_bytes"] == (g - 1) * (a_tile + b_tile)
    m = out["metrics"]
    assert m["spmm_block_util"] == pytest.approx(
        100.0 * counts.sum(axis=1).max() / steps, rel=1e-12)
    assert m["wire_gb"] == pytest.approx((g - 1) * (a_tile + b_tile) / 1e9)
    assert m["spmm_kernel_ms"] is None and m["collective_ms"] is None
    assert m["kernel_ms"] is None and m["device_idle_pct"] is None
    assert all(m[n] > 0 for n in ("tiling_s", "plan_s", "lower_compile_s"))
