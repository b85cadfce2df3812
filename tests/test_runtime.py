"""Runtime-layer unit tests: fault tolerance, elastic sizing, injection.

Covers the pieces the elastic replanning controller is assembled from,
without touching jax or a device mesh (those paths run in
``test_distributed.py`` via the ``elastic`` selftest):

* StragglerDetector warmup gating and variance poisoning (an outlier must
  not inflate the EWMA variance it was detected against);
* choose_mesh_shape divisor/shaving boundaries and the prefer_model path;
* choose_grid_shape square-fit boundaries;
* RestartableLoop consecutive-vs-lifetime restart accounting, the
  recover-raises path, and bounded-retry overflow;
* PreemptionSignal handler chaining, uninstall restore, and the context
  manager;
* determinism of the seeded fault injectors (StragglerInjector,
  TransientFailure, DeviceLoss);
* where the persistent compilation cache goes (``platform``).
"""
import signal

import numpy as np
import pytest

from repro.runtime import (DeviceLoss, PreemptionSignal, RestartableLoop,
                           StragglerDetector, StragglerInjector,
                           TransientFailure, choose_grid_shape,
                           choose_mesh_shape)


# ---------------------------------------------------------------------------
# StragglerDetector
# ---------------------------------------------------------------------------
def test_straggler_detector_warmup_suppresses_flags():
    """A spike inside the warmup window is never flagged, even when it
    would clear the z-score threshold with room to spare."""
    det = StragglerDetector(alpha=0.5, threshold=2.0, warmup=5)
    for step in range(4):
        det.observe(step, 1.0)
    assert det.observe(4, 100.0) is False     # count == warmup: still warm
    assert det.events == []


def test_straggler_detector_flags_after_warmup():
    det = StragglerDetector(alpha=0.1, threshold=4.0, warmup=5)
    rng = np.random.default_rng(0)
    for step in range(20):
        det.observe(step, 1.0 + 0.01 * rng.standard_normal())
    assert det.observe(20, 8.0) is True
    assert det.events[-1]["step"] == 20


def test_straggler_detector_outlier_does_not_poison_variance():
    """Flagged steps must not feed the EWMA stats: after one huge outlier,
    an equally huge follow-up step is still flagged (if the outlier had
    inflated the variance, the second spike would pass as normal)."""
    det = StragglerDetector(alpha=0.1, threshold=4.0, warmup=5)
    rng = np.random.default_rng(1)
    for step in range(20):
        det.observe(step, 1.0 + 0.01 * rng.standard_normal())
    mean_before, var_before = det.mean, det.var
    assert det.observe(20, 50.0) is True
    assert det.mean == mean_before and det.var == var_before
    assert det.observe(21, 50.0) is True      # still an outlier
    assert len(det.events) == 2


# ---------------------------------------------------------------------------
# choose_mesh_shape / choose_grid_shape
# ---------------------------------------------------------------------------
def test_choose_mesh_shape_divisor_boundary():
    # model axis must divide 64 heads; largest divisor <= max_model wins
    assert choose_mesh_shape(256, model_divisors=(64,), max_model=16) \
        == (16, 16)
    # max_model caps the model axis even when a larger divisor exists
    assert choose_mesh_shape(256, model_divisors=(64,), max_model=8) \
        == (32, 8)


def test_choose_mesh_shape_shaves_failed_nodes():
    # 250 chips cap the model axis at 2 (250 = 2*5^3); shaving to 248
    # unlocks model-8, which the scan over the shave range must find
    assert choose_mesh_shape(250, model_divisors=(8,), max_model=8) \
        == (31, 8)
    # 8 chips needing model-7: only 7 of them are usable
    assert choose_mesh_shape(8, model_divisors=(7,), max_model=7) == (1, 7)
    # shaving is bounded at 87.5% utilization, never below
    data, model = choose_mesh_shape(250, model_divisors=(64,), max_model=16)
    assert data * model >= int(250 * 0.875)
    with pytest.raises(ValueError, match="no usable mesh"):
        choose_mesh_shape(8, max_model=0)


def test_choose_mesh_shape_prefer_model():
    # without preference the largest valid model axis wins ...
    assert choose_mesh_shape(64, model_divisors=(16,), max_model=16) \
        == (4, 16)
    # ... prefer_model overrides when it's a valid candidate
    assert choose_mesh_shape(64, model_divisors=(16,), max_model=16,
                             prefer_model=4) == (16, 4)
    # an invalid preference (doesn't divide the heads) is ignored
    assert choose_mesh_shape(64, model_divisors=(16,), max_model=16,
                             prefer_model=3) == (4, 16)


def test_choose_grid_shape_boundaries():
    assert choose_grid_shape(1) == 1
    assert choose_grid_shape(3) == 1          # 2x2 doesn't fit on 3
    assert choose_grid_shape(4) == 2
    assert choose_grid_shape(8) == 2
    assert choose_grid_shape(9) == 3
    assert choose_grid_shape(10 ** 6) == 1000  # exact square, no fp slip
    # survivor-id collections count, ids themselves don't matter
    assert choose_grid_shape((0, 3, 4, 5)) == 2
    assert choose_grid_shape(range(9), max_g=2) == 2
    with pytest.raises(ValueError, match="at least one"):
        choose_grid_shape(0)


# ---------------------------------------------------------------------------
# RestartableLoop
# ---------------------------------------------------------------------------
def test_restartable_loop_consecutive_vs_total_restarts():
    """Failures separated by progress never accumulate: a loop with
    max_restarts=1 survives three separate single failures, and the
    lifetime count still reports all of them."""
    failed = set()

    def body(step):
        if step in (1, 3, 5) and step not in failed:
            failed.add(step)
            raise RuntimeError(f"fault at {step}")

    loop = RestartableLoop(7, recover=lambda: max(failed), max_restarts=1)
    assert loop.run(body) == 7
    assert loop.restarts == 0                 # reset by progress
    assert loop.total_restarts == 3


def test_restartable_loop_bounded_consecutive_failures():
    loop = RestartableLoop(5, recover=lambda: 0, max_restarts=2)
    with pytest.raises(RuntimeError, match="always fails"):
        loop.run(lambda step: (_ for _ in ()).throw(
            RuntimeError("always fails")))
    assert loop.restarts == 3                 # the raising failure
    assert loop.total_restarts == 3


def test_restartable_loop_recover_raises_propagates():
    """A broken recovery path (e.g. corrupt checkpoint) surfaces its own
    exception instead of being swallowed by the retry loop."""
    def body(step):
        if step == 2:
            raise RuntimeError("node failure")

    def recover():
        raise OSError("checkpoint unreadable")

    loop = RestartableLoop(4, recover, max_restarts=3)
    with pytest.raises(OSError, match="checkpoint unreadable"):
        loop.run(body)
    assert loop.total_restarts == 1


# ---------------------------------------------------------------------------
# PreemptionSignal
# ---------------------------------------------------------------------------
def test_preemption_signal_chains_and_restores():
    seen = {"outer": 0}

    def outer_handler(signum, frame):
        seen["outer"] += 1

    orig = signal.signal(signal.SIGTERM, outer_handler)
    try:
        with PreemptionSignal() as ps:
            assert not ps.requested
            signal.raise_signal(signal.SIGTERM)
            assert ps.requested
            assert seen["outer"] == 1         # chained, not clobbered
        # context exit restored the outer handler
        assert signal.getsignal(signal.SIGTERM) is outer_handler
        signal.raise_signal(signal.SIGTERM)
        assert seen["outer"] == 2
    finally:
        signal.signal(signal.SIGTERM, orig)


def test_preemption_signal_uninstall_idempotent():
    orig = signal.getsignal(signal.SIGTERM)
    ps = PreemptionSignal(install=False)
    assert ps.install() is True
    assert ps.install() is True               # second install is a no-op
    ps.uninstall()
    ps.uninstall()                            # and so is double-uninstall
    assert signal.getsignal(signal.SIGTERM) is orig


# ---------------------------------------------------------------------------
# fault injectors: seeded determinism
# ---------------------------------------------------------------------------
def test_straggler_injector_deterministic_and_scoped():
    inj = StragglerInjector(device=2, factor=8.0, seed=7, jitter=0.5,
                            start_step=3)
    assert inj.step_time(5, 0, 1.0) == 1.0    # healthy device untouched
    assert inj.step_time(2, 2, 1.0) == 1.0    # before start_step
    t = inj.step_time(5, 2, 1.0)
    assert 8.0 <= t <= 12.0                   # factor x (1 + jitter*u)
    inj2 = StragglerInjector(device=2, factor=8.0, seed=7, jitter=0.5,
                             start_step=3)
    assert inj2.step_time(5, 2, 1.0) == t     # seeded replay
    with pytest.raises(ValueError, match="factor"):
        StragglerInjector(device=0, factor=0.5)


def test_transient_failure_fails_listed_calls_only():
    fail = TransientFailure(fail_on=(2, 4), message="boom")
    wrapped = fail(lambda x: x + 1)
    assert wrapped(1) == 2
    with pytest.raises(RuntimeError, match="boom .call 2."):
        wrapped(1)
    assert wrapped(1) == 2
    with pytest.raises(RuntimeError, match="call 4"):
        wrapped(1)
    assert wrapped(1) == 2
    assert (fail.calls, fail.failures) == (5, 2)


def test_device_loss_seeded_and_partitioned():
    loss = DeviceLoss(9, 5, seed=0)
    again = DeviceLoss(9, 5, seed=0)
    assert loss.lost() == again.lost()
    assert len(loss.survivors()) == 4
    assert sorted(loss.lost() + loss.survivors()) == list(range(9))
    assert DeviceLoss(9, 5, seed=1).lost() != loss.lost() or True
    with pytest.raises(ValueError, match="n_lost"):
        DeviceLoss(4, 4)


# ---------------------------------------------------------------------------
# Persistent compilation cache placement
# ---------------------------------------------------------------------------
def test_compile_cache_honours_env_else_fixed_repo_path(monkeypatch,
                                                        tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing and
    returns that directory; without it the cache goes to one fixed
    in-repo path, the same on every call."""
    import os

    import jax

    from repro.runtime import platform

    old = jax.config.jax_compilation_cache_dir
    try:
        env_dir = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        jax.config.update("jax_compilation_cache_dir", None)
        assert platform.enable_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir is None
        assert not os.path.exists(env_dir)

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = platform.enable_compile_cache()
        second = platform.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == second == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
