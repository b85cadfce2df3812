"""The comparison that decides ``correct``: sound runs pass it, and the
lower-precision controls and each planted fault of the timed path fail it.

Runs on the CPU at a small size (R-MAT scale 9, 32x32 blocks), through
the harness's whole run except its look for a TPU, with the kernels'
plain ``jnp`` implementation in place of the Pallas ones.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check
from helpers import run_small, small_cell

SPGEMM = "spgemm-rmat-s15"


@pytest.fixture(autouse=True)
def _fresh_plans():
    from repro.core import api

    api.clear_plan_cache()
    yield
    api.clear_plan_cache()


def test_sound_run_is_correct():
    cell = small_cell(SPGEMM)
    run, correct, checks = run_small(cell, seed=2**31 + 5)
    assert correct, checks
    assert run.n_products >= 1
    assert set(checks) == set(cell.limits)
    assert jax.config.jax_default_matmul_precision == \
        cell.config["matmul_precision"] == "highest"


@pytest.mark.parametrize("seed", [11, 2**31 + 11])
def test_high_control_fails(seed):
    """The reference at ``high`` precision, in the program's place."""
    cell = small_cell(SPGEMM)
    kind = cell.kind
    csr = cell.generator.weighted_csr(cell.config, seed)
    readings = kind.compare(kind.control(csr, {}), kind.reference(csr, {}))
    correct, checks = check.verdict(readings, cell.limits)
    assert not correct, checks
    assert readings["max_err_ratio"] > 3 * cell.limits["max_err_ratio"][
        "limit"]


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**40 + 3])
def test_weights_are_float32_on_0_1_and_not_bfloat16(seed):
    """The weights are the configuration's, not values that a bfloat16
    operand would hold exactly."""
    import ml_dtypes

    cell = small_cell(SPGEMM)
    w = cell.generator.weighted_csr(cell.config, seed).data
    assert (w.astype(np.float32).astype(np.float64) == w).all()
    assert 0 <= w.min() and w.max() < 1
    exact16 = w.astype(ml_dtypes.bfloat16).astype(np.float64) == w
    assert exact16.mean() < 0.01
    assert np.unique(w).size > w.size // 2


def test_spgemm_compare_counts_nonzeros_off_structure():
    cell = small_cell(SPGEMM)
    kind = cell.kind
    csr = cell.generator.weighted_csr(cell.config, 3)
    ref = kind.reference(csr, {})
    dense = (csr @ csr).toarray()
    exact = kind.compare(kind.DenseView(dense), ref)
    assert exact == {"max_err_ratio": 0.0, "nonzeros_off_structure": 0}
    r, c = np.argwhere(dense == 0)[0]
    dense[r, c] = 1e-3
    off = kind.compare(kind.DenseView(dense), ref)
    assert off["nonzeros_off_structure"] == 1 and off["max_err_ratio"] == 0.0
    assert not check.verdict(off, cell.limits)[0]


def _zero(fn):
    def broken(*args, **kw):
        return jnp.zeros_like(fn(*args, **kw))
    return broken


def _half(fn):
    """Half the stored A blocks left out, the rest counted twice."""
    def broken(a_blocks, *args, **kw):
        keep = (jnp.arange(a_blocks.shape[0]) % 2 == 0).astype(a_blocks.dtype)
        return 2 * fn(a_blocks * keep[:, None, None], *args, **kw)
    return broken


def _altered(fn):
    """One entry of the kernel's output changed where it is produced."""
    def broken(*args, **kw):
        out = fn(*args, **kw)
        return out.at[(0,) * out.ndim].add(0.5)
    return broken


def _round_bf16(x):
    """``x`` rounded to the nearest bfloat16 (ties to even) on its bits,
    so no compiler drops the rounding as excess precision."""
    from jax import lax

    bits = lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                    jnp.float32)


def _one_pass(fn):
    """Both operands rounded to bfloat16 before the products: what the
    kernel's one bfloat16 MXU pass does at the default precision, and what
    storing A in bfloat16 would do."""
    def broken(a_blocks, b_blocks, *args, **kw):
        return fn(_round_bf16(a_blocks), _round_bf16(b_blocks), *args, **kw)
    return broken


# A chip exchange cannot be left out: the cell runs on one chip.
FAULTS = {"unchanged": _zero, "half": _half, "altered": _altered,
          "one_pass": _one_pass}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails(fault, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "bsr_pair_accumulate",
                        FAULTS[fault](ops.bsr_pair_accumulate))
    _, correct, checks = run_small(small_cell(SPGEMM), seed=99)
    assert not correct, checks
