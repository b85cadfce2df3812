#!/usr/bin/env python
"""Fit Machine.net_bw / hop_latency from measured benchmark trajectories.

``benchmarks/run.py --json`` records predicted-vs-measured per-multiply
times for every algorithm (g=1 micro-bench + the 4x4 R-MAT balance and
padded-vs-packed wire experiments) in ``BENCH_kernels.json``; packed-wire
records fit against the *packed* byte terms — the bytes those plans
actually ship.  The auto-scheduler's alpha-beta
model (``api._predicted_time``) is linear in the two network unknowns:

    t_comm = total_bytes / (net_bw * duplex) + n_msgs * hop_latency

so, after subtracting the roofline compute term, a least-squares fit over
the records recovers ``1/net_bw`` and ``hop_latency`` — the ROADMAP's
"auto-scheduling calibration": fit the machine the fleet actually is,
instead of trusting nominal v5e constants.

The overlap A/B section (``overlap_rmat_4x4``) additionally calibrates
``Machine.overlap_eff`` — the fraction of compute time the double-buffered
bodies actually hide communication under.  Each schedule's record pairs
the split-step (on) and bulk (off) per-multiply times with the host
roofline's compute floor, so the realized hiding is
``clip((t_off - t_on) / t_comp, 0, 1)`` per schedule and the fitted value
is the median over the non-wire-amortized schedules.  The calibrated
preset carries it into ``auto_select``'s exposed-comm term
(``max(0, t_comm - overlap_eff * t_comp)``).

Usage:
    python tools/fit_machine.py [BENCH_kernels.json]
    python tools/fit_machine.py --write MACHINE_calibrated.json

``--write`` saves the calibrated preset via ``roofline.save_machine``;
load it with ``roofline.load_machine(path)`` and pass it to
``plan_matmul(machine=...)`` / ``auto_select(machine=...)``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _comm_row(cm: Dict[str, float], alg) -> Tuple[float, float]:
    """Design-matrix row (effective bytes, message count) for one record."""
    n_msgs = alg.msgs_per_step if alg.msgs_per_step is not None \
        else len(alg.wire)
    msgs = n_msgs * (1.0 if alg.wire_amortized else cm["steps"])
    return cm["total_net_bytes"] / alg.duplex, msgs


def fit(records: List[Dict], base) -> Tuple[object, Dict]:
    """Least-squares fit of (net_bw, hop_latency) from benchmark records.

    Each record: ``{"cm": cost-model dict, "alg": Algorithm,
    "measured": seconds}``.  BSP schedules pay compute + comm, so their
    comm time is ``measured - t_comp`` exactly; RDMA rings pay
    max(comp, comm), so they inform the fit only when comm-dominated —
    rows whose residual target comes out non-positive are dropped.
    """
    from repro.core import roofline as _roofline

    rows, targets, used = [], [], []
    for rec in records:
        cm, alg = rec["cm"], rec["alg"]
        t_comp = cm["total_flops"] / _roofline.local_peak(
            cm["ai_local"], base)
        if alg.style == "bsp":
            y = rec["measured"] - t_comp
        else:
            # rings pay max(comp, comm): the measured time equals comm only
            # when comm dominates.  A compute-bound ring record would be
            # attributed entirely to the network and wreck the fit, so keep
            # rings only when measured clearly exceeds the compute floor.
            if rec["measured"] <= 2.0 * t_comp:
                continue
            y = rec["measured"]
        if y <= 0:
            continue
        rows.append(_comm_row(cm, alg))
        targets.append(y)
        used.append(rec)
    if len(rows) < 2:
        raise ValueError(
            f"need >= 2 usable records to fit 2 parameters, got {len(rows)}")
    a = np.asarray(rows, dtype=np.float64)
    y = np.asarray(targets, dtype=np.float64)
    # normalize columns so bytes (~1e6) and msgs (~1e1) are comparable
    scale = a.max(axis=0)
    scale[scale == 0] = 1.0
    x, *_ = np.linalg.lstsq(a / scale, y, rcond=None)
    x = x / scale
    inv_bw = max(float(x[0]), 1e-18)     # clip to physical (positive) values
    alpha = max(float(x[1]), 0.0)
    fitted = dataclasses.replace(base, name=base.name + "-fit",
                                 net_bw=1.0 / inv_bw, hop_latency=alpha)
    resid = a @ np.array([inv_bw, alpha]) - y
    diag = {
        "n_records": len(records),
        "n_used": len(rows),
        "rms_residual_s": float(np.sqrt((resid ** 2).mean())),
        "net_bw": fitted.net_bw,
        "hop_latency": fitted.hop_latency,
    }
    return fitted, diag


def _g1_records(payload: Dict) -> List[Dict]:
    """Rebuild the kernels_bench g=1 geometry; attach measured timings."""
    import jax.numpy as jnp

    from repro.core import api
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import random_sparse

    section = payload.get("kernels", {}).get("algorithms_g1", {})
    algos = section.get("algorithms", {})
    if not algos:
        return []
    m = 128 if payload.get("smoke") else 512     # kernels_bench geometry
    a_d = random_sparse(m, m, 0.08, seed=5)
    b = np.zeros((m, 64), dtype=np.float32)
    a_h = DistBSR.from_dense(a_d, g=1, block_size=32)  # default (bucketed)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    geom = api._geometry(a_h, b_h, impl=None, axis_row="row",
                         axis_col="col")
    out = []
    for name, metrics in algos.items():
        if name not in api.REGISTRY:
            continue
        alg = api.REGISTRY.get(name)
        if alg.cost_fn is not None:
            # structure-dependent cost (steal3d) can't be reconstructed
            # from the recorded geometry alone; those records are skipped
            continue
        cm = api._cost_model(alg, geom, a_h.abstract_key(),
                             b_h.abstract_key())
        out.append({"cm": cm, "alg": alg, "source": f"g1/{name}",
                    "measured": metrics["per_multiply_s"],
                    "predicted": metrics.get("predicted_s_v5e")})
    return out


def _balance_records(payload: Dict) -> List[Dict]:
    """Reconstruct the 4x4 balance-bench cost models from recorded meta
    (capacity, block size, scale) — no R-MAT rebuild needed."""
    import jax.numpy as jnp

    from repro.core import api, schedules

    section = payload.get("balance_rmat_4x4", {})
    if "balance" not in section:
        return []
    g = section["g"]
    n = 1 << section["rmat_scale"]
    bs = section["block_size"]
    n_cols = section["n_cols"]
    out = []
    for mode, entry in section["balance"].items():
        cap = entry["capacity"]
        a_key = ("bsr", (n, n), (g, g), bs, cap, "float32")
        b_key = ("dense", (n, n_cols), g, "float32")
        geom = schedules._Geom(g=g, tm=n // g, tn=n_cols // g,
                         a_nbr=(n // g) // bs, b_nbr=0, b_nbc=0, impl=None,
                         axr="row", axc="col", out_dtype=jnp.float32)
        for name, metrics in entry["algorithms"].items():
            if name not in api.REGISTRY or "per_multiply_s" not in metrics:
                continue
            alg = api.REGISTRY.get(name)
            if alg.cost_fn is not None:
                continue                 # see _g1_records

            cm = api._cost_model(alg, geom, a_key, b_key)
            out.append({"cm": cm, "alg": alg,
                        "source": f"balance/{mode}/{name}",
                        "measured": metrics["per_multiply_s"],
                        "predicted": metrics.get("predicted_s_v5e")})
    return out


def _wire_records(payload: Dict) -> List[Dict]:
    """Reconstruct the 4x4 wire-bench cost models from recorded meta.

    Padded records use the stored-stride byte terms; packed records use
    the *packed* terms (``wire_caps`` — blocks-only at the recorded wire
    capacity), so the fit sees the bytes each plan actually ships.
    """
    import jax.numpy as jnp

    from repro.core import api, schedules

    section = payload.get("wire_rmat_4x4", {})
    algos = section.get("algorithms")
    if not algos:
        return []
    g = section["g"]
    n = 1 << section["rmat_scale"]
    bs = section["block_size"]
    n_cols = section["n_cols"]
    cap = section["a_capacity"]
    wc = section["a_wire_capacity"]
    a_key = ("bsr", (n, n), (g, g), bs, cap, "float32")
    b_key = ("dense", (n, n_cols), g, "float32")
    geom = schedules._Geom(g=g, tm=n // g, tn=n_cols // g,
                     a_nbr=(n // g) // bs, b_nbr=0, b_nbc=0, impl=None,
                     axr="row", axc="col", out_dtype=jnp.float32)
    out = []
    for name, metrics in algos.items():
        if name not in api.REGISTRY:
            continue
        alg = api.REGISTRY.get(name)
        if alg.cost_fn is not None:
            continue                     # see _g1_records (steal3d)
        for wire, caps in (("padded", None), ("packed", {"a": wc})):
            measured = metrics.get(f"per_multiply_s_{wire}")
            if measured is None:
                continue
            cm = api._cost_model(alg, geom, a_key, b_key, wire_caps=caps)
            out.append({"cm": cm, "alg": alg,
                        "source": f"wire/{wire}/{name}",
                        "measured": measured,
                        "predicted": metrics.get(
                            f"predicted_s_v5e_{wire}")})
    return out


def collect_records(payload: Dict) -> List[Dict]:
    return _g1_records(payload) + _balance_records(payload) \
        + _wire_records(payload)


def _records_from_drift(raw: List[Dict]) -> List[Dict]:
    """Convert obs drift records ({"algorithm", "cm", "measured_s"}) to
    fit records.  Drift records carry the executed plan's cost-model dict
    verbatim, so no geometry reconstruction is needed; records for
    unregistered algorithms or with structure-dependent cost functions
    (steal3d — see _g1_records) are skipped."""
    from repro.core import api

    out = []
    for rec in raw:
        name = rec.get("algorithm")
        cm = rec.get("cm")
        if cm is None or name not in api.REGISTRY:
            continue
        alg = api.REGISTRY.get(name)
        if alg.cost_fn is not None:
            continue
        out.append({"cm": cm, "alg": alg,
                    "source": f"drift/{name}/{rec.get('wire', '?')}",
                    "measured": rec["measured_s"],
                    "predicted": rec.get("predicted_s")})
    return out


def fit_from_registry(base=None) -> Tuple[object, Dict]:
    """Re-fit (net_bw, hop_latency) from the live obs drift series.

    The observed-step-time loop: any process that executed plans under
    ``obs.enable(drift=True)`` (spans alone record no drift) has
    per-multiply measurements (with their cost-model dicts) sitting in
    ``obs.drift_records()`` — this fits a Machine from them directly, no
    bench JSON round-trip.  Raises ValueError with
    fewer than two usable records, like :func:`fit`.
    """
    from repro import obs
    from repro.core import roofline

    base = base or roofline.TPU_V5E
    return fit(_records_from_drift(obs.drift_records()), base)


def fit_overlap_eff(payload: Dict) -> Tuple[Optional[float], Dict]:
    """Fit ``Machine.overlap_eff`` from the overlap A/B section.

    ``overlap_rmat_4x4`` records min-of-repeats per-multiply times with
    the double-buffered (on) and bulk (off) bodies plus the harness
    roofline's compute floor ``t_comp``; the hiding a schedule realized
    is ``clip((t_off - t_on) / t_comp, 0, 1)``.  Wire-amortized
    schedules are skipped (their bodies have no overlap variant), as are
    segment-split ones (steal3d: its A/B delta measures the opt-in
    second dispatch, not scan-step hiding).
    Returns ``(median_eff | None, diagnostics)``.
    """
    from repro.core import api

    algos = payload.get("overlap_rmat_4x4", {}).get("algorithms", {})
    effs: Dict[str, float] = {}
    for name, rec in algos.items():
        if name not in api.REGISTRY:
            continue
        alg = api.REGISTRY.get(name)
        if alg.wire_amortized or alg.static_planner is not None:
            continue
        t_on = rec.get("per_multiply_s_on")
        t_off = rec.get("per_multiply_s_off")
        t_comp = rec.get("t_comp_host_s")
        if not t_comp or t_on is None or t_off is None:
            continue
        effs[name] = min(max((t_off - t_on) / t_comp, 0.0), 1.0)
    if not effs:
        return None, {"overlap_records": 0}
    eff = float(np.median(list(effs.values())))
    return eff, {"overlap_records": len(effs), "overlap_eff": eff,
                 "overlap_eff_per_alg": effs}


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("bench_json", nargs="?",
                   default=os.path.join(REPO_ROOT, "BENCH_kernels.json"))
    p.add_argument("--machine", default="tpu-v5e",
                   choices=["tpu-v5e", "summit-v100", "dgx2-v100"],
                   help="base preset supplying compute-side constants")
    p.add_argument("--write", nargs="?", const="MACHINE_calibrated.json",
                   default=None, metavar="PATH",
                   help="save the calibrated preset as JSON")
    p.add_argument("--drift", default=None, metavar="PATH",
                   help="fit from an obs.export_drift JSON (live-registry "
                        "records) instead of bench sections")
    args = p.parse_args(argv)

    from repro.core import roofline
    base = {"tpu-v5e": roofline.TPU_V5E, "summit-v100": roofline.SUMMIT_V100,
            "dgx2-v100": roofline.DGX2_V100}[args.machine]
    if args.drift:
        with open(args.drift) as f:
            records = _records_from_drift(json.load(f).get("records", []))
        payload = {}
        source = args.drift
    else:
        with open(args.bench_json) as f:
            payload = json.load(f)
        records = collect_records(payload)
        source = args.bench_json
    if not records:
        print(f"no predicted-vs-measured records in {source}")
        return 1
    fitted, diag = fit(records, base)
    eff, ov_diag = fit_overlap_eff(payload)
    if eff is not None:
        fitted = dataclasses.replace(fitted, overlap_eff=eff)
    diag.update(ov_diag)
    print(f"fit over {diag['n_used']}/{diag['n_records']} records "
          f"(rms residual {diag['rms_residual_s']:.2e} s):")
    print(f"  net_bw      {base.net_bw:.3e} -> {fitted.net_bw:.3e} B/s")
    print(f"  hop_latency {base.hop_latency:.3e} -> "
          f"{fitted.hop_latency:.3e} s")
    if eff is not None:
        print(f"  overlap_eff {base.overlap_eff:.3f} -> "
              f"{fitted.overlap_eff:.3f} "
              f"(median over {ov_diag['overlap_records']} schedules)")
    from repro.core.api import _predicted_time
    for rec in records:
        t_fit = _predicted_time(rec["cm"], rec["alg"], fitted)
        print(f"  {rec['source']:28s} measured {rec['measured']:.3e}  "
              f"fit {t_fit:.3e}")
    if args.write:
        path = args.write if os.path.isabs(args.write) \
            else os.path.join(REPO_ROOT, args.write)
        roofline.save_machine(fitted, path)
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
