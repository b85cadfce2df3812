"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode never checks TPU tiling or SMEM limits; the TPU compiler
(installed here, no chip needed) does.  Each test compiles one kernel at
the shapes ``chip_smoke.py`` runs (R-MAT scale 15, 128x128 blocks) for a
v5e chip and checks that the Pallas kernel reached the compiled program;
one compiles the 2x2 SpMM cell's whole product for the described 2x2.
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
def topo():
    """A described v5e:2x2, with the persistent compilation cache off (a
    compile for a described chip cannot be read back)."""
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
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        compilation_cache.reset_cache()
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(topo.devices[0])


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


def _lower_kernel(kernel, one_chip):
    """A small call of each kernel; the chunked ones get lists of three
    chunks, so a first call and a loop of calls."""
    bs, stored, n_slots = 128, 600, 512
    blocks = _sds((stored, bs, bs), jnp.float32, one_chip)
    if kernel == "bsr_pair_accumulate":
        pairs = 3 * bsr_spmm.list_chunk(3)
        return jax.jit(
            lambda a, b, pa, pb, ps: bsr_spmm.bsr_pair_accumulate_pallas(
                a, b, pa, pb, ps, n_slots=n_slots),
        ).lower(blocks, blocks,
                *(_sds((pairs,), jnp.int32, one_chip) for _ in range(3)))
    if kernel == "bsr_spmm":
        steps = 3 * bsr_spmm.list_chunk(2)
        return jax.jit(
            lambda a, rows, cols, dense: bsr_spmm.bsr_spmm_pallas(
                a, rows, cols, dense, n_block_rows=n_slots, block_n=256,
                chunked=True),
        ).lower(_sds((steps, bs, bs), jnp.float32, one_chip),
                *(_sds((steps,), jnp.int32, one_chip) for _ in range(2)),
                _sds((stored * bs, 256), jnp.float32, one_chip))
    return jax.jit(
        lambda a, b, pa, pb, pr, pc: bsr_spmm.bsr_pair_matmul_pallas(
            a, b, pa, pb, pr, pc, n_block_rows=8, n_block_cols=8),
    ).lower(blocks, blocks,
            *(_sds((4096,), jnp.int32, one_chip) for _ in range(4)))


@pytest.mark.parametrize("kernel,calls", [("bsr_pair_accumulate", 2),
                                          ("bsr_spmm", 2),
                                          ("bsr_pair_matmul", 1)])
def test_kernel_calls_carry_their_stable_name(one_chip, kernel, calls):
    """Every Pallas call of a kernel, the chunk loop's included, compiles to
    an instruction named ``<kernel>.<n>``: the name a profiler trace shows
    for it (the benchmark's kernel-time readers select ops by it)."""
    import re

    text = _lower_kernel(kernel, one_chip).compile().as_text()
    names = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", text, re.M)
    assert len(names) == calls, names
    assert all(re.fullmatch(rf"{kernel}\.\d+", n) for n in names), names


def _pallas_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


def test_pair_accumulate_compiles_with_the_group_it_reports(one_chip):
    """At 128x128 float32 blocks the pair-accumulate kernel takes
    ``pair_group`` list entries per grid step: each chunk's call has
    ``cdiv(chunk, G)`` grid steps and buffers of two halves of G blocks,
    and it compiles at that size for a v5e chip."""
    chunk = bsr_spmm.list_chunk(3)
    group = bsr_spmm.pair_group(BS, jnp.float32)
    assert group == 32
    pairs, n_slots = 3 * chunk, 4096

    def run(a, b, pa, pb, ps):
        return bsr_spmm.bsr_pair_accumulate_pallas(a, b, pa, pb, ps,
                                                   n_slots=n_slots)

    args = (_sds((STORED, BS, BS), jnp.float32, one_chip),
            _sds((STORED, BS, BS), jnp.float32, one_chip),
            *(_sds((pairs,), jnp.int32, one_chip) for _ in range(3)))
    calls = list(_pallas_calls(jax.make_jaxpr(run)(*args).jaxpr))
    assert calls
    for eqn in calls:
        assert eqn.params["grid_mapping"].grid == (-(-chunk // group),)
        halves = [v.aval.shape for v in eqn.params["jaxpr"].invars
                  if v.aval.shape == (2, group, BS, BS)]
        assert len(halves) == 3         # A blocks, B blocks, running sums
    assert bsr_spmm.pair_grid_steps(pairs, BS, jnp.float32) == \
        3 * -(-chunk // group)
    assert "tpu_custom_call" in jax.jit(run).lower(*args).compile().as_text()


def test_entry_ring_sends_what_its_gauge_counts(topo):
    """The 2x2 SpMM cell's product (scale-16 R-MAT, 39,740 stored slots a
    tile, B 512 wide) with ``ring_c``'s A stream shipped as entries, at the
    capacity of its fullest tile (287,175 nonzeros), compiled for a
    described v5e 2x2: its collective-permutes send ``plan.wire_bytes``,
    under a fiftieth of what the block stream sends; the received tile is
    rebuilt by one XLA scatter in the block stream's receive buffer, and
    the temp grows by B's received tile, which leaves VMEM."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import obs
    from repro.core import api, entries, schedules
    from repro.launch.hlo_analysis import analyze_hlo

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("row", "col"))
    n, w, cap = 1 << 16, 512, 39_484
    store = cap + n // 2 // BS
    a_key = ("bsr", (n, n), (2, 2), BS, cap, "float32")
    b_key = ("dense", (n, w), 2, "float32")
    geom = schedules._Geom(g=2, tm=n // 2, tn=w // 2, a_nbr=n // 2 // BS,
                     b_nbr=0, b_nbc=0, impl="pallas", axr="row", axc="col",
                     out_dtype=jnp.float32, overlap=True)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    ecap = entries.entry_capacity(287_175)
    assert entries.entries_win(ecap, store, BS, 4)
    sent, temp = {}, {}
    for wire, entry_cap in (("padded", 0), ("entries", ecap)):
        plan = api.MatmulPlan(api.REGISTRY.get("ring_c"), geom, mesh, a_key,
                              b_key, wire=wire, entry_cap=entry_cap)
        a = {"blocks": sds((2, 2, store, BS, BS), jnp.float32,
                           P("row", "col", None, None, None)),
             "rows": sds((2, 2, store), jnp.int32, P("row", "col", None)),
             "cols": sds((2, 2, store), jnp.int32, P("row", "col", None))}
        if entry_cap:
            a["vals"] = sds((2, 2, entry_cap), jnp.float32,
                            P("row", "col", None))
            a["idx"] = sds((2, 2, entry_cap), jnp.int32,
                           P("row", "col", None))
        b = {"dense": sds((n, w), jnp.float32, P("row", "col"))}
        compiled = plan._exec.lower(a, b).compile()
        text = compiled.as_text()
        temp[wire] = compiled.memory_analysis().temp_size_in_bytes
        sent[wire] = analyze_hlo(text).collective_bytes["collective-permute"]
        api._set_dense_output_gauges(plan, None, None)
        gauge = obs.registry().snapshot()["plan.wire_bytes"][
            f"algorithm=ring_c,wire={wire}"]
        assert gauge == sent[wire], wire
    assert sent["padded"] == 2_638_272_992
    assert sent["entries"] == 36_225_848 < sent["padded"] / 50
    # the decoded tile takes the received blocks' place; the scatter moves
    # B's received tile (32 MiB) from VMEM into HBM temp, and no more
    b_tile = n // 2 * (w // 2) * 4
    assert temp["entries"] - temp["padded"] < b_tile + (1 << 20)


@pytest.mark.parametrize("cap", [294_187, 3_700_000])
def test_entry_encoder_work_space_stays_small(one_chip, cap):
    """The encoder of one tile of the 2x2 cell's shape (39,740 stored
    128 x 128 float32 slots), compiled for a v5e, at the cell's entry
    capacity and at one near the fullest tile the rule still ships as
    entries: it places its entries a chunk at a time, so its temp stays
    a few chunks' worth (under 128 MiB) beside a 2.6 GB tile, however many
    entries there are."""
    from repro.core import entries

    slots = 39_740
    assert entries.entries_win(cap, slots, BS, 4)
    blocks = _sds((1, 1, slots, BS, BS), jnp.float32, one_chip)
    compiled = entries._encode.lower(blocks, None, cap).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20
