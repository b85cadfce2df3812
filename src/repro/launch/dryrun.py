import os

from repro.runtime.platform import set_host_device_count

# Must run before the first jax backend init (jax locks the device count
# then) — runtime.platform is the repo's single XLA_FLAGS write site.
# REPRO_DRYRUN_DEVICES overrides the full-pod fake count for quick local
# runs.
set_host_device_count(int(os.environ.get("REPRO_DRYRUN_DEVICES", 512)))

"""Multi-pod dry-run driver.

For every (architecture x input-shape x mesh) cell this lowers + compiles
the real step function against ShapeDtypeStruct inputs (no allocation),
prints ``memory_analysis()`` / ``cost_analysis()``, and extracts the
roofline terms (compute / memory / collective) from the optimized HLO via
``launch/hlo_analysis.py``.  Results are cached as JSON per cell so the
sweep is restartable.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import sys
import time
import traceback
from typing import Dict, Optional

__all__ = ["run_cell", "input_specs", "main"]

# TPU v5e constants (per harness): bf16 peak, HBM bw, ICI per-link bw.
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def _lazy_imports():
    import jax  # noqa
    global jax, jnp, NamedSharding, P, get_config, SHAPES, cell_supported
    global tf, lm, AdamW, mesh_mod, hlo_analysis, make_batch_specs
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config, SHAPES, cell_supported
    from repro.models import transformer as tf
    from repro.models import lm
    from repro.optim import AdamW
    from repro.launch import mesh as mesh_mod
    from repro.launch import hlo_analysis
    from repro.data.pipeline import make_batch_specs


def input_specs(cfg, shape, mesh) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.launch.mesh import batch_partition_spec, shardings_for
    from repro.data.pipeline import make_batch_specs
    from jax.sharding import PartitionSpec as P

    batch, seq = shape.batch, shape.seq
    bspec = batch_partition_spec(batch, mesh)

    def sds(shp, dt, spec):
        return jax.ShapeDtypeStruct(
            shp, dt, sharding=NamedSharding(mesh, spec))

    if shape.kind == "train":
        raw = make_batch_specs(cfg, batch, seq)
        out = {}
        for k, (shp, dt) in raw.items():
            spec = P(bspec[0], *([None] * (len(shp) - 1)))
            out[k] = sds(shp, jnp.dtype(dt), spec)
        return out
    if shape.kind == "prefill":
        # a prompt of exactly `seq` tokens (no label shift!) — a +1 here once
        # made every chunked kernel degenerate to per-token scans (§Perf)
        if cfg.frontend == "audio":
            return {"frames": sds((batch, seq, cfg.frontend_dim),
                                  jnp.float32, P(bspec[0], None, None))}
        out = {}
        text = seq - (cfg.num_patches if cfg.frontend == "vlm" else 0)
        out["tokens"] = sds((batch, text), jnp.int32, P(bspec[0], None))
        if cfg.frontend == "vlm":
            out["patches"] = sds(
                (batch, cfg.num_patches, cfg.frontend_dim), jnp.float32,
                P(bspec[0], None, None))
        return out
    # decode: one token step with a cache of length shape.seq
    tok = sds((batch, 1), jnp.int32, P(bspec[0], None))
    return {"tokens": tok}


def _abstract_params(cfg):
    import jax
    from repro.models import transformer as tf
    return jax.eval_shape(lambda k: tf.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def _with_shardings(abstract_tree, spec_tree, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import sanitize_spec

    def attach(s, x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, sanitize_spec(s, x.shape, mesh)))

    return jax.tree.map(attach, spec_tree, abstract_tree,
                        is_leaf=lambda s: isinstance(s, P))


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_path: Optional[str] = None, verbose: bool = True) -> Dict:
    """Lower+compile one (arch, shape, mesh) cell; return the record dict."""
    _lazy_imports()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if cfg.moe is not None:
        import dataclasses as _dc
        batch_shards = 32 if mesh_kind == "multi" else 16
        cfg = _dc.replace(
            cfg, moe_dispatch_groups=batch_shards,
            moe_impl=os.environ.get("REPRO_MOE_IMPL", cfg.moe_impl))
    ok, reason = cell_supported(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "time": time.time()}
    if not ok:
        rec.update(status="skipped", reason=reason)
        _dump(rec, out_path, verbose)
        return rec

    debug_mesh = os.environ.get("REPRO_DRYRUN_MESH")
    if debug_mesh:  # e.g. "4,4" or "2,4,4" — local debugging only
        import jax as _jax
        shape_ = tuple(int(x) for x in debug_mesh.split(","))
        axes_ = ("pod", "data", "model")[-len(shape_):]
        mesh = _jax.make_mesh(
            shape_, axes_,
            axis_types=(_jax.sharding.AxisType.Auto,) * len(axes_))
    else:
        mesh = mesh_mod.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    pspecs = tf.param_specs(cfg)
    params_sds = _with_shardings(_abstract_params(cfg), pspecs, mesh)
    param_sh = jax.tree.map(lambda x: x.sharding, params_sds,
                            is_leaf=lambda x: isinstance(
                                x, jax.ShapeDtypeStruct))

    t0 = time.time()
    try:
        with jax.sharding.set_mesh(mesh):
            if shape.kind == "train":
                opt = AdamW(lr=1e-4)
                opt_specs = AdamW.state_specs(pspecs)
                opt_sds = _with_shardings(
                    jax.eval_shape(opt.init, params_sds), opt_specs, mesh)
                opt_sh = jax.tree.map(lambda x: x.sharding, opt_sds,
                                      is_leaf=lambda x: isinstance(
                                          x, jax.ShapeDtypeStruct))
                batch_sds = input_specs(cfg, shape, mesh)
                step = lm.make_train_step(cfg, opt)
                metr_sh = {k: NamedSharding(mesh, P()) for k in
                           ("loss", "aux", "dropped", "grad_norm")}
                jitted = jax.jit(
                    step,
                    in_shardings=(param_sh, opt_sh,
                                  jax.tree.map(lambda x: x.sharding,
                                               batch_sds)),
                    out_shardings=(param_sh, opt_sh, metr_sh),
                    donate_argnums=(0, 1))
                lowered = jitted.lower(params_sds, opt_sds, batch_sds)
            elif shape.kind == "prefill":
                batch_sds = input_specs(cfg, shape, mesh)

                if cfg.is_encoder:
                    # encoders have no decode cache: "prefill" = one forward
                    def prefill_fn(params, batch):
                        logits, _, _ = tf.forward(params, batch, cfg)
                        return logits
                else:
                    def prefill_fn(params, batch):
                        return lm.prefill(params, batch, cfg,
                                          max_len=shape.seq)

                jitted = jax.jit(
                    prefill_fn,
                    in_shardings=(param_sh,
                                  jax.tree.map(lambda x: x.sharding,
                                               batch_sds)))
                lowered = jitted.lower(params_sds, batch_sds)
            else:  # decode
                batch_sds = input_specs(cfg, shape, mesh)
                cache_specs_tree = tf.cache_specs(cfg)
                cache_abs = jax.eval_shape(
                    lambda: tf.init_cache(cfg, shape.batch, shape.seq))
                cache_sds = _with_shardings(cache_abs, cache_specs_tree, mesh)
                cache_sh = jax.tree.map(lambda x: x.sharding, cache_sds,
                                        is_leaf=lambda x: isinstance(
                                            x, jax.ShapeDtypeStruct))
                pos_sds = jax.ShapeDtypeStruct(
                    (), jnp.int32, sharding=NamedSharding(mesh, P()))
                step = lm.make_decode_step(cfg)
                jitted = jax.jit(
                    step,
                    in_shardings=(param_sh,
                                  batch_sds["tokens"].sharding,
                                  cache_sh, NamedSharding(mesh, P())),
                    donate_argnums=(2,))
                lowered = jitted.lower(params_sds, batch_sds["tokens"],
                                       cache_sds, pos_sds)

            compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        _dump(rec, out_path, verbose)
        return rec
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    mem_rec = {}
    if mem is not None:
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
            mem_rec[field] = getattr(mem, field, None)
    cost_rec = {}
    if cost:
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in cost:
                cost_rec[k] = cost[k]

    hlo = None
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    if out_path:  # keep the optimized HLO for offline re-analysis
        import gzip
        with gzip.open(out_path.replace(".json", "") + ".hlo.gz", "wt") as f:
            f.write(hlo)
    stats = hlo_analysis.analyze_hlo(hlo)

    # ----- roofline terms (per-chip, seconds) -------------------------------
    # HLO stats are whole-program; per-chip = /n_chips for SPMD-partitioned
    # modules (the compiled module is already per-device after GSPMD).
    compute_s = stats.dot_flops / PEAK_FLOPS
    memory_s = stats.hbm_bytes_fused / HBM_BW
    collective_s = stats.total_collective_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)

    model_flops = _model_flops(cfg, shape)
    rec.update(
        status="ok",
        n_chips=n_chips,
        compile_seconds=round(t_compile, 1),
        memory_analysis=mem_rec,
        cost_analysis=cost_rec,
        hlo_stats={
            "dot_flops": stats.dot_flops,
            "hbm_bytes": stats.hbm_bytes,
            "hbm_bytes_fused": stats.hbm_bytes_fused,
            "collective_bytes": stats.collective_bytes,
            "collective_count": stats.collective_count,
        },
        roofline={**terms, "bottleneck": bottleneck,
                  "model_flops": model_flops,
                  "useful_flops_ratio": (
                      model_flops / (stats.dot_flops * n_chips)
                      if stats.dot_flops else None)},
    )
    _dump(rec, out_path, verbose)
    return rec


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode D=batch x 1."""
    n = cfg.active_param_count()
    n_emb = cfg.vocab_size * cfg.d_model
    n_body = max(n - n_emb * (1 if cfg.tie_embeddings else 2), 1)
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * n_body * tokens
    if shape.kind == "prefill":
        return 2.0 * n_body * shape.batch * shape.seq
    return 2.0 * n_body * shape.batch  # one token per sequence


def _dump(rec: Dict, out_path: Optional[str], verbose: bool):
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2, default=float)
    if verbose:
        status = rec.get("status")
        if status == "ok":
            r = rec["roofline"]
            print(f"[ok] {rec['arch']:24s} {rec['shape']:12s} "
                  f"{rec['mesh']:6s} compile={rec['compile_seconds']}s "
                  f"compute={r['compute_s']:.3e}s mem={r['memory_s']:.3e}s "
                  f"coll={r['collective_s']:.3e}s -> {r['bottleneck']}")
            if rec.get("memory_analysis"):
                print(f"     memory_analysis: {rec['memory_analysis']}")
            if rec.get("cost_analysis"):
                print(f"     cost_analysis: {rec['cost_analysis']}")
        elif status == "skipped":
            print(f"[skip] {rec['arch']:24s} {rec['shape']:12s} "
                  f"{rec['mesh']:6s} -- {rec['reason']}")
        else:
            print(f"[ERR] {rec['arch']:24s} {rec['shape']:12s} "
                  f"{rec['mesh']:6s} -- {rec.get('error')}")
            if rec.get("traceback"):
                print(rec["traceback"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", default="single",
                   choices=["single", "multi", "both"])
    p.add_argument("--all", action="store_true",
                   help="sweep every supported (arch x shape) cell")
    p.add_argument("--out-dir", default="results/dryrun")
    p.add_argument("--force", action="store_true",
                   help="recompute cells with existing JSON")
    args = p.parse_args(argv)
    _lazy_imports()
    from repro.configs import list_archs

    os.makedirs(args.out_dir, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        archs = list_archs()
        shapes = list(SHAPES)
    else:
        if not args.arch or not args.shape:
            p.error("--arch and --shape required unless --all")
        archs, shapes = [args.arch], [args.shape]

    failures = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                out = os.path.join(
                    args.out_dir,
                    f"{arch}__{shape_name}__{mesh_kind}.json")
                if not args.force and os.path.exists(out):
                    with open(out) as f:
                        rec = json.load(f)
                    if rec.get("status") in ("ok", "skipped"):
                        print(f"[cached] {arch} {shape_name} {mesh_kind} "
                              f"({rec['status']})")
                        continue
                rec = run_cell(arch, shape_name, mesh_kind, out)
                if rec.get("status") == "error":
                    failures += 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
