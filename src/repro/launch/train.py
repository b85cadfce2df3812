"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --smoke --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/run1

Wires together: config registry, synthetic/memmap data pipeline (prefetch),
AdamW, GSPMD sharding over an (optionally multi-pod) mesh, checkpoint/
restart, straggler detection, and preemption handling.  On this CPU
container it trains reduced configs; the same driver lowers the full configs
on a TPU cluster.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import numpy as np


def build_mesh(n_devices: Optional[int] = None):
    import jax
    from repro.runtime.elastic import choose_mesh_shape

    n = n_devices or len(jax.devices())
    data, model = choose_mesh_shape(n, max_model=16)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def train(cfg, *, steps: int, batch: int, seq: int, lr: float = 3e-4,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          seed: int = 0, mesh=None, log_every: int = 10,
          resume: bool = True, max_restarts: int = 3,
          stop_after: Optional[int] = None):
    """``stop_after`` stops early (crash/preemption emulation) while keeping
    the LR schedule pinned to the job's total ``steps`` — a restarted job
    must see the same schedule."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.ckpt import CheckpointManager
    from repro.data.pipeline import Prefetcher, SyntheticLM
    from repro.launch.mesh import batch_partition_spec
    from repro.models import lm, transformer as tf
    from repro.optim import AdamW, cosine_schedule
    from repro.runtime import PreemptionSignal, RestartableLoop, StragglerDetector

    from repro.launch.mesh import sanitized_shardings

    mesh = mesh or build_mesh()
    opt = AdamW(lr=cosine_schedule(lr, max(steps // 20, 1), steps))
    pspecs = tf.param_specs(cfg)
    abstract = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                              jax.random.PRNGKey(seed))
    param_sh = sanitized_shardings(pspecs, abstract, mesh)
    opt_sh = sanitized_shardings(
        AdamW.state_specs(pspecs),
        jax.eval_shape(opt.init, abstract), mesh)

    with jax.sharding.set_mesh(mesh):
        params = jax.jit(
            lambda k: tf.init_params(cfg, k),
            out_shardings=param_sh)(jax.random.PRNGKey(seed))
        opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)
        step_fn = jax.jit(lm.make_train_step(cfg, opt),
                          donate_argnums=(0, 1))

        mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        start_step = 0
        if mgr and resume and mgr.latest_step() is not None:
            start_step, (params, opt_state), _ = mgr.restore(
                None, (params, opt_state), (param_sh, opt_sh))
            print(f"[train] resumed from step {start_step}")

        source = SyntheticLM(cfg, batch, seq, seed=seed)
        prefetch = Prefetcher(source, depth=2, start_step=start_step)
        straggler = StragglerDetector()
        preempt = PreemptionSignal(install=False)
        bspec = batch_partition_spec(batch, mesh)
        state = {"params": params, "opt": opt_state, "losses": []}

        def recover() -> int:
            if not mgr:
                return 0
            s, (p, o), _ = mgr.restore(None, (state["params"], state["opt"]),
                                       (param_sh, opt_sh))
            state["params"], state["opt"] = p, o
            return s

        def body(step: int):
            t0 = time.time()
            raw = prefetch.get(step)
            dev_batch = {
                k: jax.device_put(v, NamedSharding(
                    mesh, P(bspec[0], *([None] * (v.ndim - 1)))))
                for k, v in raw.items()}
            state["params"], state["opt"], metrics = step_fn(
                state["params"], state["opt"], dev_batch)
            loss = float(metrics["loss"])
            state["losses"].append(loss)
            dt = time.time() - t0
            if straggler.observe(step, dt):
                print(f"[straggler] step {step} took {dt:.2f}s "
                      f"(mean {straggler.mean:.2f}s)")
            if log_every and step % log_every == 0:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt * 1e3:.0f}ms")
            if mgr and step and step % ckpt_every == 0:
                mgr.save(step, state["params"], state["opt"],
                         extra={"loss": loss})
            if preempt.requested:
                if mgr:
                    mgr.save(step, state["params"], state["opt"])
                    mgr.wait()
                raise SystemExit(0)

        total = min(stop_after, steps) if stop_after else steps
        loop = RestartableLoop(total, recover, max_restarts=max_restarts,
                               on_restart=lambda s, e: print(
                                   f"[restart] step {s}: {e}"))
        end = start_step
        try:
            end = loop.run(body, start_step)
        finally:
            prefetch.close()
            if mgr:
                mgr.save(end, state["params"], state["opt"])
                mgr.wait()
    return state


# ---------------------------------------------------------------------------
# DP/TP equivalence selftest (used by launch/selftest.py)
# ---------------------------------------------------------------------------
def selftest_parallel_equivalence(n_devices: int) -> bool:
    """loss(sharded over (data, model)) == loss(single-device), same batch."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import shardings_for
    from repro.models import lm, transformer as tf

    cfg = get_config("llama3-8b", smoke=True)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in SyntheticLM(cfg, 4, 16, seed=1)(0).items()}
    loss_ref, _ = lm.loss_fn(params, batch, cfg)

    data = max(1, n_devices // 2)
    mesh = jax.make_mesh((data, n_devices // data), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.sharding.set_mesh(mesh):
        param_sh = shardings_for(tf.param_specs(cfg), mesh)
        p_sh = jax.device_put(params, param_sh)
        loss_sh, _ = jax.jit(
            lambda p, b: lm.loss_fn(p, b, cfg))(p_sh, batch)
    return abs(float(loss_ref) - float(loss_sh)) < 1e-3


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    from repro.runtime.platform import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config
    cfg = get_config(args.arch, smoke=args.smoke)
    state = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                  lr=args.lr, ckpt_dir=args.ckpt_dir,
                  ckpt_every=args.ckpt_every, seed=args.seed)
    losses = state["losses"]
    if losses:
        k = max(len(losses) // 10, 1)
        print(f"[train] first-{k} mean loss {np.mean(losses[:k]):.4f} -> "
              f"last-{k} mean loss {np.mean(losses[-k:]):.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
