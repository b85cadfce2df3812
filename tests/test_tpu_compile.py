"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode never checks TPU tiling or SMEM limits; the TPU compiler
(installed here, no chip needed) does.  Each test compiles one kernel at
the shapes ``chip_smoke.py`` runs (R-MAT scale 15, 128x128 blocks) for a
v5e chip and checks that the Pallas kernel reached the compiled program.
The topology is described inside a fixture, so only the worker that runs
this file loads the TPU compiler.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import bsr_spmm, ops

BS = 128
N = 1 << 15                 # R-MAT scale 15
NBR = N // BS
STORED = 38_500             # 38,244 stored blocks + one coverage block per row


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # noqa: BLE001 (no TPU compiler here)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        compilation_cache.reset_cache()
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_bsr_spmm_compiles_at_scale15_block128(one_chip):
    """SpMM over one g=1 tile of the scale-15 graph, 512 columns wide:
    two stored-block lists of 38,500 entries fit the SMEM budget."""
    assert STORED <= bsr_spmm.list_chunk(2)
    text = jax.jit(
        lambda blocks, rows, cols, dense: bsr_spmm.bsr_spmm_pallas(
            blocks, rows, cols, dense, n_block_rows=NBR, block_n=256),
    ).lower(_sds((STORED, BS, BS), jnp.float32, one_chip),
            _sds((STORED,), jnp.int32, one_chip),
            _sds((STORED,), jnp.int32, one_chip),
            _sds((N, 512), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


def test_chunked_pair_accumulate_compiles_past_smem(one_chip):
    """Sparse-output SpGEMM pair lists of more than 300k pairs (over
    1 MiB of int32 lists, which one kernel call's SMEM would refuse)
    compile as chunked kernel calls into one output buffer."""
    chunk = bsr_spmm.list_chunk(3)
    pairs = 8 * chunk
    assert pairs > 300_000 and 3 * 4 * pairs > 1 << 20
    n_slots = 4096
    lowered = jax.jit(
        lambda a, b, pa, pb, ps: ops.bsr_pair_accumulate(
            a, b, pa, pb, ps, n_slots=n_slots, impl="pallas"),
    ).lower(_sds((STORED, BS, BS), jnp.float32, one_chip),
            _sds((STORED, BS, BS), jnp.float32, one_chip),
            *(_sds((pairs,), jnp.int32, one_chip) for _ in range(3)))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # one aliased output buffer: the result is n_slots blocks, not more
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert out_bytes == n_slots * BS * BS * np.dtype(np.float32).itemsize
