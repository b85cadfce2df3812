"""Token-choice Mixture-of-Experts — where the paper's SpMM engine lives in
the LM stack.

Token-choice routing *is* sparse x dense matmul: the dispatch operator D is
a {0,1}-sparse (tokens x expert-slots) matrix and dispatch/combine are
``D @ X`` / ``(D * probs)^T @ Y`` — the paper's SpMM with a stationary-A
(expert-stationary) distribution: expert weights stay put on their shard of
the "model" axis while activation tiles move.

Dispatch implementation: capacity-padded batched scatter/gather; under
GSPMD with experts sharded over "model" and per-group (per-device) capacity
this lowers to the classic all-to-all pattern.  The §Perf study
(EXPERIMENTS.md, olmoe iterations 1-4) documents how the program structure
(vmapped batched scatter) is what lets the partitioner prove shard
alignment and avoid a whole-buffer all-reduce.

``cfg.moe_impl='ring'`` selects :func:`ring_moe_forward` — the paper's
stationary-A ring of ``core/spmm.py`` applied on the expert axis: tokens
ride ``ppermute`` hops instead of one all-to-all.  Measured on
olmoe train_4k it cuts the collective roofline term 3.6x (6.47->1.81 s)
at the cost of the memory term (16 rounds of local dispatch) — the same
async-vs-collective trade the paper studies; see EXPERIMENTS.md §Perf.

The LPT capacity logic in ``core/schedule.py`` motivates the default
capacity factor; dropped-token stats are returned for monitoring.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import BATCH_AXES, MODEL_AXIS, constrain, dense_init
from .config import ModelConfig

__all__ = ["init_moe", "moe_specs", "moe_forward", "route_tokens",
           "route_meta", "expert_ffn", "router_aux", "selftest_distributed"]


def init_moe(cfg: ModelConfig, key) -> Dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    keys = jax.random.split(key, 4)
    p = {
        "router": dense_init(keys[0], (d, e)),
        "w_gate": dense_init(keys[1], (e, d, f), in_axis=1),
        "w_up": dense_init(keys[2], (e, d, f), in_axis=1),
        "w_down": dense_init(keys[3], (e, f, d), in_axis=1),
    }
    return p


def moe_specs(cfg: ModelConfig) -> Dict:
    return {
        "router": P(None, None),
        "w_gate": P(MODEL_AXIS, "data", None),
        "w_up": P(MODEL_AXIS, "data", None),
        "w_down": P(MODEL_AXIS, None, "data"),
    }


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * n_tokens * m.top_k / m.n_experts)
    return max(c, m.top_k)


def route_meta(n_tokens: int, cfg: ModelConfig) -> Tuple[int, int, int]:
    """Static routing geometry ``(cap, G, ng)`` as plain python ints.

    A pure function of the (padded) token count and config.  Host-side
    dispatch planning (the serving engine's sparse operator builder) and
    the traced router share this one implementation, so the ints never
    enter a jit trace and both paths agree on capacity by construction.
    """
    m = cfg.moe
    G = max(1, cfg.moe_dispatch_groups)
    while n_tokens % G:
        G //= 2
    ng = n_tokens // G
    cap = max(_capacity(n_tokens, cfg) // G, m.top_k)
    return cap, G, ng


def route_tokens(router, xf, cfg: ModelConfig) -> Dict:
    """Shared router math: softmax -> top-k -> per-group capacity slots.

    ``xf``: [n, d] flat tokens.  Returns a dict of routing tensors; both
    :func:`moe_forward` and the serving engine's plan-based dispatch
    (``repro.serving``) call this, so the two paths route identically and
    the SpMM formulation can be checked token-for-token against the dense
    scatter/gather reference.
    """
    m = cfg.moe
    n = xf.shape[0]
    e, k = m.n_experts, m.top_k
    cap, G, ng = route_meta(n, cfg)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                    # [n, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # per-group capacity assignment (slot = rank within group+expert)
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)        # [n, k, e]
    flat = onehot.reshape(G, ng * k, e)
    ranks = (jnp.cumsum(flat, axis=1) - flat)                 # excl, per group
    slot = jnp.einsum("gne,gne->gn", ranks, flat).reshape(n, k)
    keep = slot < cap
    return {"logits": logits, "probs": probs, "top_p": top_p,
            "top_e": top_e, "slot": slot, "keep": keep, "onehot": onehot,
            "cap": cap, "G": G, "ng": ng,
            "dropped": 1.0 - keep.mean()}


def expert_ffn(p: Dict, xe, cfg: ModelConfig):
    """Expert MLPs on dispatched slots.  xe: [..., e, cap, d] -> same."""
    act = jax.nn.silu if cfg.mlp_kind != "geglu" else (
        lambda v: jax.nn.gelu(v, approximate=True))
    g = jnp.einsum("...ecd,edf->...ecf", xe, p["w_gate"].astype(xe.dtype))
    u = jnp.einsum("...ecd,edf->...ecf", xe, p["w_up"].astype(xe.dtype))
    return jnp.einsum("...ecf,efd->...ecd", act(g) * u,
                      p["w_down"].astype(xe.dtype))


def router_aux(route: Dict, cfg: ModelConfig) -> Dict:
    """Switch-style aux losses + drop stats from :func:`route_tokens`."""
    m = cfg.moe
    me = route["probs"].mean(0)                               # [e]
    ce = route["onehot"].astype(jnp.float32).sum(1).mean(0)   # fraction routed
    return {
        "moe_aux": m.aux_loss * m.n_experts * jnp.sum(me * ce),
        "moe_z": m.router_z_loss * jnp.mean(
            jnp.square(jax.nn.logsumexp(route["logits"], axis=-1))),
        "moe_dropped": route["dropped"],
    }


def moe_forward(p: Dict, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """x: [B, T, d] -> (y, aux) with load-balance/z losses in aux.

    Dispatch uses *per-group capacity*: tokens are split into G groups
    (G = number of batch shards at scale; 1 on a single device), each group
    ranks its own tokens and scatters into its own capacity slice.  The
    scatter then never crosses batch shards, so under GSPMD the dispatch
    lowers to an all-to-all over the expert axis instead of an all-reduce of
    the whole buffer (§Perf olmoe iterations 1-2: collective 138s -> ~0.3s).
    This is also the production-realistic semantics (per-device capacity).
    """
    m = cfg.moe
    b, t, d = x.shape
    n = b * t
    e, k = m.n_experts, m.top_k
    xf = x.reshape(n, d)

    r = route_tokens(p["router"], xf, cfg)
    top_p, top_e = r["top_p"], r["top_e"]
    slot, keep, cap, G, ng = r["slot"], r["keep"], r["cap"], r["G"], r["ng"]

    # --- dispatch: batched (per-group) scatter — the sparse D applied -------
    idx_e = jnp.where(keep, top_e, e).reshape(G, ng * k)
    idx_c = jnp.where(keep, slot, 0).reshape(G, ng * k)
    x_rep = jnp.repeat(xf[:, None, :], k, axis=1).reshape(G, ng * k, d)
    cap_axes = BATCH_AXES if cfg.moe_shard_capacity else None
    x_rep = constrain(x_rep, cap_axes, None, None)

    def _scatter_one(xg, ie, ic):
        return jnp.zeros((e + 1, cap, d), x.dtype).at[ie, ic].add(xg)

    buf = jax.vmap(_scatter_one)(x_rep, idx_e, idx_c)   # [G, e+1, cap, d]
    xe = buf[:, :e]                                     # [G, e, cap, d]
    xe = constrain(xe, cap_axes, MODEL_AXIS, None, None)

    # --- expert FFN (stationary-A: weights never move) ----------------------
    ye = expert_ffn(p, xe, cfg)
    ye = constrain(ye, cap_axes, MODEL_AXIS, None, None)

    # --- combine: (D * probs)^T @ Y — batched gather ------------------------
    ye_pad = jnp.concatenate(
        [ye, jnp.zeros((G, 1, cap, d), ye.dtype)], axis=1)

    def _gather_one(yg, ie, ic):
        return yg[ie, ic]                               # [ng*k, d]

    gathered = jax.vmap(_gather_one)(ye_pad, idx_e, idx_c)
    gathered = constrain(gathered, cap_axes, None, None)
    w = jnp.where(keep, top_p, 0.0).astype(x.dtype)
    y = jnp.einsum("nkd,nk->nd", gathered.reshape(n, k, d), w).reshape(b, t, d)
    y = constrain(y, BATCH_AXES, None, None)

    # --- aux losses (Switch-style) ------------------------------------------
    return y, router_aux(r, cfg)


# ---------------------------------------------------------------------------
# Distributed equivalence check (called from launch/selftest.py)
# ---------------------------------------------------------------------------
def selftest_distributed(n_devices: int) -> bool:
    """EP-sharded MoE == single-device MoE, on a host-device mesh."""
    import numpy as np
    from jax.sharding import NamedSharding

    from .config import MoEConfig

    cfg = ModelConfig(
        name="moe-selftest", family="moe", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=1, d_ff=32, vocab_size=64, compute_dtype="float32",
        moe=MoEConfig(n_experts=n_devices * 2, top_k=2, d_ff_expert=32,
                      capacity_factor=8.0))
    key = jax.random.PRNGKey(0)
    p = init_moe(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))

    y_ref, _ = moe_forward(p, x, cfg)

    mesh = jax.make_mesh((1, n_devices), ("data", MODEL_AXIS),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    specs = moe_specs(cfg)
    # EP-only for the test: experts over the model axis, rest replicated
    specs = {k: P(MODEL_AXIS, None, None) if k != "router" else P(None, None)
             for k in specs}
    with jax.sharding.set_mesh(mesh):
        p_sh = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                for k, v in p.items()}
        x_sh = jax.device_put(x, NamedSharding(mesh, P(None, None, None)))
        y_ep, _ = jax.jit(lambda pp, xx: moe_forward(pp, xx, cfg))(p_sh, x_sh)
    err = float(np.max(np.abs(np.asarray(y_ref) - np.asarray(y_ep))))
    return err < 1e-4


# ---------------------------------------------------------------------------
# Ring dispatch — the paper's stationary-A ring applied to the expert axis
# ---------------------------------------------------------------------------
def ring_moe_forward(p: Dict, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """MoE with the paper's RDMA stationary-A schedule (``moe_impl='ring'``).

    Experts stay put on their 'model'-axis shard (stationary A); token
    shards ride ``ppermute`` hops around the expert ring, each rank applies
    its local experts to every passing shard, and the partial outputs ride
    along with the tokens — after R hops everything is home and fully
    accumulated.  Communication is 2·d per token per hop, nearest-neighbour
    only (vs. the all-to-all of the default dispatch: ~2·k·d per token but
    through the switch fabric) — exactly the trade the paper studies.

    Requires an ambient mesh with a 'model' axis whose size divides
    n_experts, and T divisible by that size; falls back to
    :func:`moe_forward` otherwise (e.g. single-device smoke tests).
    """
    mesh = jax.sharding.get_abstract_mesh()
    m = cfg.moe
    b, t, d = x.shape
    if (mesh is None or mesh.empty or MODEL_AXIS not in mesh.axis_names):
        return moe_forward(p, x, cfg)
    R = mesh.shape[MODEL_AXIS]
    if R < 2 or m.n_experts % R or t % R:
        return moe_forward(p, x, cfg)
    el = m.n_experts // R
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    all_axes = batch_axes + (MODEL_AXIS,)
    nl = b * (t // R) // max(
        1, _axes_size(mesh, batch_axes))  # tokens per device (for capacity)
    cap = max(int(m.capacity_factor * nl * m.top_k * el / m.n_experts),
              m.top_k)

    def body(xs, router, wg, wu, wd):
        # xs: [B_l, T/R, d] local token shard; w*: [el, d, f] local experts
        bl, tl, _ = xs.shape
        n_loc = bl * tl
        xf = xs.reshape(n_loc, d)
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, m.top_k)
        top_p = (top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
                 ).astype(xs.dtype)
        r = jax.lax.axis_index(MODEL_AXIS)
        perm = [((i + 1) % R, i) for i in range(R)]

        def step(carry, _):
            xc, te, tp, acc = carry
            # prefetch the next shard (paper SS3.3: overlap with compute)
            nxt = [jax.lax.ppermute(v, MODEL_AXIS, perm)
                   for v in (xc, te, tp, acc)]
            # tokens of this shard routed to MY experts
            mine = (te // el) == r
            le = jnp.where(mine, te - r * el, el)      # el = overflow slot
            onehot = jax.nn.one_hot(le, el + 1, dtype=jnp.int32)
            flat = onehot.reshape(n_loc * m.top_k, el + 1)
            ranks_ = jnp.cumsum(flat, axis=0) - flat
            slot = jnp.einsum("ne,ne->n", ranks_, flat).reshape(
                n_loc, m.top_k)
            keep = mine & (slot < cap)
            ie = jnp.where(keep, le, el)
            ic = jnp.where(keep, slot, 0)
            buf = jnp.zeros((el + 1, cap, d), xs.dtype)
            buf = buf.at[ie.reshape(-1), ic.reshape(-1)].add(
                jnp.repeat(xc[:, None, :], m.top_k, 1).reshape(-1, d))
            act = jax.nn.silu if cfg.mlp_kind != "geglu" else (
                lambda v: jax.nn.gelu(v, approximate=True))
            g = jnp.einsum("ecd,edf->ecf", buf[:el], wg.astype(xs.dtype))
            u = jnp.einsum("ecd,edf->ecf", buf[:el], wu.astype(xs.dtype))
            ye = jnp.einsum("ecf,efd->ecd", act(g) * u, wd.astype(xs.dtype))
            ye = jnp.concatenate([ye, jnp.zeros((1, cap, d), ye.dtype)])
            part = jnp.einsum("nkd,nk->nd", ye[ie, ic],
                              jnp.where(keep, tp, 0.0))
            acc_out = acc + part
            nxt[3] = jax.lax.ppermute(          # pass the updated partials
                acc_out, MODEL_AXIS, perm)
            return tuple(nxt), None

        acc0 = jax.lax.pcast(jnp.zeros((n_loc, d), xs.dtype), all_axes,
                             to="varying")
        (xc, te, tp, acc), _ = jax.lax.scan(
            step, (xf, top_e, top_p, acc0), None, length=R)
        # aux losses, reduced over the whole mesh
        me = jax.lax.pmean(probs.mean(0), all_axes)
        ce = jax.lax.pmean(
            jax.nn.one_hot(top_e, m.n_experts).sum(1).mean(0), all_axes)
        aux_vec = jnp.stack([
            m.aux_loss * m.n_experts * jnp.sum(me * ce),
            m.router_z_loss * jax.lax.pmean(jnp.mean(
                jnp.square(jax.nn.logsumexp(logits, -1))), all_axes),
        ])
        return acc.reshape(bl, tl, d), aux_vec

    from jax.sharding import PartitionSpec as _P
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(_P(batch_axes or None, MODEL_AXIS, None),
                  _P(None, None),
                  _P(MODEL_AXIS, None, None), _P(MODEL_AXIS, None, None),
                  _P(MODEL_AXIS, None, None)),
        out_specs=(_P(batch_axes or None, MODEL_AXIS, None), _P(None)),
        check_vma=False)
    y, aux_vec = f(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    aux = {"moe_aux": aux_vec[0], "moe_z": aux_vec[1],
           "moe_dropped": jnp.zeros((), jnp.float32)}
    return y, aux


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def selftest_ring(n_devices: int) -> bool:
    """ring dispatch == dense_onehot dispatch (no drops), on an EP mesh."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .config import MoEConfig

    cfg = ModelConfig(
        name="moe-ring-selftest", family="moe", n_layers=1, d_model=16,
        n_heads=2, n_kv_heads=1, d_ff=32, vocab_size=64,
        compute_dtype="float32",
        moe=MoEConfig(n_experts=n_devices * 2, top_k=2, d_ff_expert=32,
                      capacity_factor=16.0))
    p = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, n_devices * 4, 16))
    y_ref, _ = moe_forward(p, x, cfg)

    mesh = jax.make_mesh((1, n_devices), ("data", MODEL_AXIS),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.sharding.set_mesh(mesh):
        p_sh = {k: jax.device_put(
            v, NamedSharding(mesh, P(MODEL_AXIS, None, None)
                             if k != "router" else P(None, None)))
            for k, v in p.items()}
        y_ring, _ = jax.jit(
            lambda pp, xx: ring_moe_forward(pp, xx, cfg))(p_sh, x)
    err = float(np.max(np.abs(np.asarray(y_ref) - np.asarray(y_ring))))
    return err < 1e-4
