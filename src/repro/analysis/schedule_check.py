"""Schedule check: host-side verification of plan metadata.

Everything a :class:`repro.core.api.MatmulPlan` will execute is decided at
plan-build time — ppermute permutations, the steal3d assignment + pair
lists + move/reduce rounds, packed-wire consume maps, balance
permutations.  This pass re-derives the *contracts* those artifacts must
satisfy (independently of the planners that built them) and proves them
before the plan ever runs — the trust-a-fresh-plan-without-a-reference-
multiply primitive the elastic-replanning work needs.

Rules (stable ids):

* ``schedule.ppermute-bijection`` — every permutation the schedule hands
  to ``lax.ppermute`` is a complete bijection on the ring axis with no
  self-sends (a missing source deadlocks the neighbour exchange; a
  duplicate destination silently drops a tile).
* ``schedule.steal-exactly-once`` — decoding the steal3d pair lists
  against the LPT assignment and A's structure, every (i, k, j) work
  item's real block products are accumulated exactly once across all
  devices/segments, with consistent joins and output slots.
* ``schedule.steal-conservation`` — steal3d's moved-tile gather indices,
  reduce-round slot/row selectors and pool layout conserve blocks: every
  needed tile ships, every off-owner partial rides home, inert padding
  references guaranteed-zero pool entries, pair lists stay slot-sorted
  with full coverage.
* ``schedule.wire-contract`` — packed-wire ``pack_idx``/consume
  maps/``slot_map``/``dmap`` satisfy the ``bsr_spmm_raw(augment=False)``
  contract (rows sorted, every block-row present, real blocks exactly
  once, inert padding proven structurally zero) and the per-step maps
  match the algorithm's published tile schedule.
* ``schedule.sparse-pairs-exactly-once`` — sparse-output pair lists
  accumulate every structural block product exactly once, slot-sorted
  with full coverage, and the step->k schedule is a bijection.
* ``schedule.balance-identity`` — balance permutations on the operands
  compose to identity through the epilogue's inverse.

A decode failure on corrupted metadata is itself a detection: each rule
converts unexpected decode errors into a finding rather than raising.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional, Tuple

import numpy as np

from .findings import Finding

_MAX_PER_RULE = 8      # cap repeated findings per rule (keep errors readable)


def _perm_problems(perm, g: int) -> List[str]:
    perm = list(perm)
    out = []
    if len(perm) != g:
        out.append(f"has {len(perm)} pairs for a {g}-device axis")
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    if sorted(srcs) != list(range(g)):
        out.append(f"sources {sorted(srcs)} are not a complete cover of "
                   f"0..{g - 1} (a missing source deadlocks the exchange; "
                   "a duplicate sends twice)")
    if sorted(dsts) != list(range(g)):
        out.append(f"destinations {sorted(dsts)} are not a complete cover "
                   f"of 0..{g - 1} (a dropped destination loses a tile)")
    if g > 1 and any(s == d for s, d in perm):
        out.append(f"contains self-sends {[p for p in perm if p[0] == p[1]]}"
                   " (a device must not be its own neighbour on a ring "
                   "of size > 1)")
    return out


_RING_SIGNS = {"ring_c": (1,), "ring_a": (1,), "ring_c_bidir": (1, -1)}


def plan_perms(plan) -> List[Tuple[str, tuple]]:
    """Every ppermute permutation the plan's body uses, labelled: the
    ring hops of the ring schedules and steal3d's rounds."""
    from repro.core import schedules as _schedules
    g = plan.geom.g
    perms: List[Tuple[str, tuple]] = []
    if plan.steal is not None:
        sp = plan.steal
        for what, deltas in (("a_move", sp.a_deltas), ("b_move", sp.b_deltas),
                             ("row_reduce", sp.row_deltas),
                             ("col_reduce", sp.col_deltas)):
            for delta in deltas:
                perms.append((f"steal3d {what} delta={delta}",
                              _schedules._steal3d_perm(g, delta)))
    for sign in _RING_SIGNS.get(plan.algorithm.name, ()):
        perms.append((f"{plan.algorithm.name} ring sign={sign:+d}",
                      _schedules._ring_perm(g, sign)))
    return perms


def check_perms(plan) -> List[Finding]:
    """schedule.ppermute-bijection over every perm the plan's body uses."""
    findings = []
    for label, perm in plan_perms(plan):
        for prob in _perm_problems(perm, plan.geom.g):
            findings.append(Finding(
                "schedule.ppermute-bijection",
                f"{label} permutation {tuple(perm)} {prob}",
                subject=plan.algorithm.name))
    return findings


def check_balance(plan, a_h, b_h) -> List[Finding]:
    """schedule.balance-identity: epilogue inverses undo the perms."""
    findings = []
    for h, who, attr, inv_fn in (
            (a_h, "left", "row_block_perm", "inv_row_perm"),
            (b_h, "right", "col_block_perm", "inv_col_perm")):
        perm = getattr(h, attr, None)
        if not perm:
            continue
        p = np.asarray(perm)
        n = len(p)
        if sorted(p.tolist()) != list(range(n)):
            findings.append(Finding(
                "schedule.balance-identity",
                f"{who} operand's {attr} {tuple(perm)} is not a "
                f"permutation of 0..{n - 1}; the epilogue cannot undo it",
                subject=who))
            continue
        inv = np.asarray(getattr(h, inv_fn)())
        if not (np.array_equal(p[inv], np.arange(n))
                and np.array_equal(inv[p], np.arange(n))):
            findings.append(Finding(
                "schedule.balance-identity",
                f"{who} operand's {attr} does not compose to identity "
                f"with {inv_fn}() — the epilogue would return permuted "
                "output",
                subject=who))
    return findings


# ---------------------------------------------------------------------------
# packed-wire contract
# ---------------------------------------------------------------------------
def _check_po_contract(po, sa, who: str) -> List[Finding]:
    """Per-tile PackedOperand contract against the operand structure."""
    findings = []
    g = sa.real.shape[0]
    wc, nbr = po.wire_capacity, po.tile_nbr
    for i in range(g):
        for j in range(g):
            if len(findings) >= _MAX_PER_RULE:
                return findings
            real = np.nonzero(sa.real[i, j])[0]
            nr = len(real)
            pk = po.pack_idx[i, j]
            if not np.array_equal(np.sort(pk[:nr]), real):
                findings.append(Finding(
                    "schedule.wire-contract",
                    f"{who} tile ({i},{j}): pack_idx prefix {pk[:nr]} does "
                    f"not select the tile's {nr} real stored slots "
                    f"{real} exactly once — blocks would ship "
                    "duplicated/dropped",
                    subject=f"{who}[{i},{j}]"))
                continue
            if nr < wc and sa.real[i, j][pk[nr:]].any():
                findings.append(Finding(
                    "schedule.wire-contract",
                    f"{who} tile ({i},{j}): pack_idx padding gathers a "
                    "real stored slot — the inert tail must be "
                    "structurally zero",
                    subject=f"{who}[{i},{j}]"))
            # slot_map: stored -> packed, inert slots -> guaranteed zero
            sm = po.slot_map[i, j]
            for sl in range(sm.shape[0]):
                if sa.real[i, j][sl]:
                    if pk[sm[sl]] != sl:
                        findings.append(Finding(
                            "schedule.wire-contract",
                            f"{who} tile ({i},{j}): slot_map[{sl}] = "
                            f"{sm[sl]} but pack_idx maps that packed slot "
                            f"to stored slot {pk[sm[sl]]} — remapped pair "
                            "lists would read the wrong block",
                            subject=f"{who}[{i},{j}]"))
                        break
                elif sm[sl] < nr:
                    findings.append(Finding(
                        "schedule.wire-contract",
                        f"{who} tile ({i},{j}): inert stored slot {sl} "
                        f"maps to real packed slot {sm[sl]} — padding "
                        "would alias a real block",
                        subject=f"{who}[{i},{j}]"))
                    break
            # consume lists: bsr_spmm_raw(augment=False) contract
            gx, rw, cl = po.gidx[i, j], po.rows[i, j], po.cols[i, j]
            prob = None
            if (np.diff(rw) < 0).any():
                prob = f"consume rows {rw} are not nondecreasing"
            elif set(range(nbr)) - set(rw.tolist()):
                prob = (f"consume rows miss block-rows "
                        f"{sorted(set(range(nbr)) - set(rw.tolist()))} "
                        "(first-visit zeroing skips them)")
            elif gx.min() < 0 or gx.max() >= wc:
                prob = f"gather index out of the packed range [0, {wc})"
            else:
                seen = Counter()
                for m in range(len(gx)):
                    s = int(gx[m])
                    if s < nr:
                        seen[s] += 1
                        if rw[m] != sa.rows[i, j][pk[s]] \
                                or cl[m] != sa.cols[i, j][pk[s]]:
                            prob = (f"consume entry {m} gathers packed "
                                    f"slot {s} (stored {pk[s]}) but "
                                    f"labels it ({rw[m]},{cl[m]}) instead "
                                    f"of ({sa.rows[i, j][pk[s]]},"
                                    f"{sa.cols[i, j][pk[s]]})")
                            break
                if prob is None and (set(seen) != set(range(nr))
                                     or any(v != 1 for v in seen.values())):
                    prob = (f"real packed slots consumed "
                            f"{dict(seen)} times — exactly-once violated")
            if prob:
                findings.append(Finding(
                    "schedule.wire-contract",
                    f"{who} tile ({i},{j}): {prob}",
                    subject=f"{who}[{i},{j}]"))
            # densify-by-gather map
            dm = po.dmap[i, j]
            lookup = {(int(sa.rows[i, j][sl]), int(sa.cols[i, j][sl])): sl
                      for sl in real}
            for p in range(len(dm)):
                br, bc = divmod(p, po.tile_nbc)
                s = int(dm[p])
                if (br, bc) in lookup:
                    if s >= nr or pk[s] != lookup[(br, bc)]:
                        findings.append(Finding(
                            "schedule.wire-contract",
                            f"{who} tile ({i},{j}): dmap[{p}] does not "
                            f"gather the real block at ({br},{bc}) — "
                            "densified tile would drop it",
                            subject=f"{who}[{i},{j}]"))
                        break
                elif s < nr:
                    findings.append(Finding(
                        "schedule.wire-contract",
                        f"{who} tile ({i},{j}): dmap[{p}] gathers real "
                        f"packed slot {s} into an empty dense position "
                        f"({br},{bc}) — densified tile gains a phantom "
                        "block",
                        subject=f"{who}[{i},{j}]"))
                    break
    return findings


def _wire_schedules(alg_name: str, g: int, a_po, b_po):
    """(a_tiles, a_bases, a_bwd_tiles, b_tiles, b_bases) per algorithm."""
    from repro.core import wire as _wire
    from repro.core.api import _summa_bases
    tbl = {
        "ring_c": (_wire.tiles_ring_c(g), None, None,
                   _wire.tiles_ring_c_b(g), None),
        "ring_c_bidir": (_wire.tiles_ring_c(g), None,
                         _wire.tiles_ring_c_bwd(g), None, None),
        "ring_a": (None, None, None, _wire.tiles_ring_a_b(g), None),
        "summa_ag": (_wire.tiles_summa_a(g),
                     None if a_po is None
                     else _summa_bases(g, a_po.wire_capacity),
                     None, _wire.tiles_summa_b(g),
                     None if b_po is None
                     else _summa_bases(g, b_po.wire_capacity)),
        "summa_bcast": (_wire.tiles_summa_a(g), None, None,
                        _wire.tiles_summa_b(g), None),
    }
    return tbl.get(alg_name)


def check_wire(plan, a_h, b_h) -> List[Finding]:
    """schedule.wire-contract for packed dense-output plans."""
    if plan.wire != "packed" or plan.steal is not None \
            or plan.symbolic is not None:
        return []
    findings = []
    g = plan.geom.g
    a_po = a_h.packed_operand() if "a" in plan._packs else None
    b_po = b_h.packed_operand() if "b" in plan._packs else None
    if a_po is not None:
        findings += _check_po_contract(a_po, a_h.grid_structure(), "A")
    if b_po is not None:
        findings += _check_po_contract(b_po, b_h.grid_structure(), "B")
    sched = _wire_schedules(plan.algorithm.name, g, a_po, b_po)
    if sched is None:
        return findings
    a_tiles, a_bases, a_bwd, b_tiles, b_bases = sched
    aux = {k: np.asarray(v) for k, v in plan._aux.items()}

    def expect_gather(po, arr, tiles, bases):
        out = arr[tiles[..., 0], tiles[..., 1]]
        if bases is not None:
            out = out + bases[..., None].astype(out.dtype)
        return out

    pairs = []
    if a_po is not None and a_tiles is not None:
        pairs += [("a_gidx", a_po, a_po.gidx, a_tiles, a_bases),
                  ("a_rows", a_po, a_po.rows, a_tiles, None),
                  ("a_cols", a_po, a_po.cols, a_tiles, None)]
    if a_po is not None and a_bwd is not None:
        pairs += [("a_gidx_bwd", a_po, a_po.gidx, a_bwd, None),
                  ("a_rows_bwd", a_po, a_po.rows, a_bwd, None),
                  ("a_cols_bwd", a_po, a_po.cols, a_bwd, None)]
    if b_po is not None and b_tiles is not None:
        pairs += [("b_dmap", b_po, b_po.dmap, b_tiles, b_bases)]
    for key, po, arr, tiles, bases in pairs:
        if key not in aux:
            findings.append(Finding(
                "schedule.wire-contract",
                f"packed plan is missing consume map {key!r} — the body "
                "cannot reconstruct the shipped tiles",
                subject=plan.algorithm.name))
            continue
        want = expect_gather(po, arr, tiles, bases)
        if not np.array_equal(aux[key], want):
            bad = np.argwhere(aux[key] != want)
            i, j, t = bad[0][:3]
            findings.append(Finding(
                "schedule.wire-contract",
                f"consume map {key!r} disagrees with the "
                f"{plan.algorithm.name} tile schedule (first mismatch at "
                f"device ({i},{j}) step {t}) — the receiver would "
                "reassemble the wrong tile",
                subject=plan.algorithm.name))
    return findings


# ---------------------------------------------------------------------------
# sparse-output pair lists
# ---------------------------------------------------------------------------
def check_sparse_pairs(plan, a_h, b_h) -> List[Finding]:
    """schedule.sparse-pairs-exactly-once over the committed pair lists."""
    if plan.symbolic is None:
        return []
    findings = []
    sym = plan.symbolic
    g = sym.g
    sa, sb = a_h.grid_structure(), b_h.grid_structure()
    store = sym.store_capacity
    packed = plan.wire == "packed"
    a_po = a_h.packed_operand() if packed else None
    b_po = b_h.packed_operand() if packed else None
    pairs = {k: np.asarray(v) for k, v in plan._pairs.items()}
    k_order = plan.algorithm.k_order

    def decode(po, s_struct, ti, tj, v):
        """(real, stored_slot) of an operand pair value."""
        if po is None:
            return bool(s_struct.real[ti, tj][v]), int(v)
        nr = int(po.n_real[ti, tj])
        return int(v) < nr, int(po.pack_idx[ti, tj][v])

    got: Counter = Counter()
    for i in range(g):
        for j in range(g):
            ks = [int(np.asarray(k_order(i, j, t, g))) for t in range(g)]
            if sorted(ks) != list(range(g)):
                findings.append(Finding(
                    "schedule.sparse-pairs-exactly-once",
                    f"k_order at device ({i},{j}) visits {ks} — not a "
                    "bijection over inner steps, so some k panel is "
                    "consumed twice and another dropped",
                    subject=plan.algorithm.name))
                continue
            for t, k in enumerate(ks):
                pa, pb, ps = (pairs[x][i, j, t] for x in ("pa", "pb", "ps"))
                if (np.diff(ps) < 0).any():
                    findings.append(Finding(
                        "schedule.sparse-pairs-exactly-once",
                        f"pair list at device ({i},{j}) step {t} is not "
                        "slot-sorted — first-visit zeroing would reset "
                        "accumulated slots",
                        subject=plan.algorithm.name))
                if set(range(store)) - set(ps.tolist()):
                    findings.append(Finding(
                        "schedule.sparse-pairs-exactly-once",
                        f"pair list at device ({i},{j}) step {t} misses "
                        "output slots "
                        f"{sorted(set(range(store)) - set(ps.tolist()))[:4]}"
                        " — uninitialized slots survive first-visit "
                        "zeroing",
                        subject=plan.algorithm.name))
                for p in range(pa.shape[0]):
                    ar, asl = decode(a_po, sa, i, k, pa[p])
                    br_, bsl = decode(b_po, sb, k, j, pb[p])
                    if not (ar and br_):
                        continue               # inert coverage/padding pair
                    qa = int(sa.cols[i, k][asl])
                    qb = int(sb.rows[k, j][bsl])
                    s = int(ps[p])
                    if qa != qb:
                        findings.append(Finding(
                            "schedule.sparse-pairs-exactly-once",
                            f"device ({i},{j}) k={k}: pair joins A block "
                            f"col {qa} with B block row {qb} — not a "
                            "structural product",
                            subject=plan.algorithm.name))
                        continue
                    if not sym.c_real[i, j][s] \
                            or sym.c_rows[i, j][s] != sa.rows[i, k][asl] \
                            or sym.c_cols[i, j][s] != sb.cols[k, j][bsl]:
                        findings.append(Finding(
                            "schedule.sparse-pairs-exactly-once",
                            f"device ({i},{j}) k={k}: real product targets "
                            f"slot {s} whose layout entry is "
                            f"({sym.c_rows[i, j][s]},{sym.c_cols[i, j][s]},"
                            f"real={bool(sym.c_real[i, j][s])}) — the "
                            "accumulation lands on the wrong output block",
                            subject=plan.algorithm.name))
                    got[(i, j, k, asl, bsl)] += 1
                if len(findings) >= _MAX_PER_RULE:
                    break

    want: Counter = Counter()
    for i in range(g):
        for j in range(g):
            for k in range(g):
                ra = np.nonzero(sa.real[i, k])[0]
                rb = np.nonzero(sb.real[k, j])[0]
                ca = sa.cols[i, k][ra]
                rb_rows = sb.rows[k, j][rb]
                hit = ca[:, None] == rb_rows[None, :]
                for ai, bi in zip(*np.nonzero(hit)):
                    want[(i, j, k, int(ra[ai]), int(rb[bi]))] += 1
    for key, n in list(want.items()):
        if got.get(key, 0) != n and len(findings) < _MAX_PER_RULE:
            i, j, k, asl, bsl = key
            findings.append(Finding(
                "schedule.sparse-pairs-exactly-once",
                f"structural product A[{i},{k}] slot {asl} x B[{k},{j}] "
                f"slot {bsl} is accumulated {got.get(key, 0)} time(s) "
                f"instead of exactly once on device ({i},{j})",
                subject=plan.algorithm.name))
    for key in got:
        if key not in want and len(findings) < _MAX_PER_RULE:
            i, j, k, asl, bsl = key
            findings.append(Finding(
                "schedule.sparse-pairs-exactly-once",
                f"pair list accumulates A[{i},{k}] slot {asl} x "
                f"B[{k},{j}] slot {bsl}, which is not a structural "
                "product — spurious accumulation",
                subject=plan.algorithm.name))
    return findings


# ---------------------------------------------------------------------------
# steal3d: exactly-once accumulation + conservation
# ---------------------------------------------------------------------------
def _steal_layout(sp, sa):
    """Re-derive the deterministic pool/output layout the builder
    documents (items from the assignment, sorted need lists, pool
    positions, out_idx) — the decode frame the pair lists are checked
    against."""
    g = sp.g
    n_dev = g * g
    dev = np.asarray(sp.assignment.dev)
    items = [[] for _ in range(n_dev)]
    for i in range(g):
        for k in range(g):
            for j in range(g):
                items[int(dev[i, k, j])].append((i, k, j))
    row_js, col_is, need_a, need_b = [], [], [], []
    for d in range(n_dev):
        r, c = divmod(d, g)
        rj, ci, na, nb = set(), set(), set(), set()
        for (i, k, j) in items[d]:
            if i == r and j == c:
                continue
            if i == r:
                rj.add(j)
                nb.add((k, j))
            elif j == c:
                ci.add(i)
                na.add((i, k))
        row_js.append(sorted(rj))
        col_is.append(sorted(ci))
        need_a.append(sorted(na))
        need_b.append(sorted(nb))
    a_lists = {delta: [[t for t in need_a[d]
                        if (d // g - t[0]) % g == delta]
                       for d in range(n_dev)] for delta in sp.a_deltas}
    b_lists = {delta: [[t for t in need_b[d]
                        if (d % g - t[1]) % g == delta]
                       for d in range(n_dev)] for delta in sp.b_deltas}
    packed = sp.wire == "packed"
    wc = sp.a_wire_capacity
    a_pos = [dict() for _ in range(n_dev)]
    b_pos = [dict() for _ in range(n_dev)]
    for d in range(n_dev):
        r, c = divmod(d, g)
        for k in range(g):
            a_pos[d][(r, k)] = k * wc if packed else k
            b_pos[d][(k, c)] = k
    if packed:
        base = g * wc
        for delta, cap, rcap in zip(sp.a_deltas, sp.a_move_cap,
                                    sp.a_round_cap):
            for d in range(n_dev):
                for m, t in enumerate(a_lists[delta][d]):
                    a_pos[d][t] = base + m * rcap
            base += cap * rcap
        a_zero, a_pool_tiles = base, 0
    else:
        base = g
        for delta, cap in zip(sp.a_deltas, sp.a_move_cap):
            for d in range(n_dev):
                for m, t in enumerate(a_lists[delta][d]):
                    a_pos[d][t] = base + m
            base += cap
        a_pool_tiles = base
        a_zero = base * sp.store_a if sp.a_kind == "bsr" else base
    base = g
    for delta, cap in zip(sp.b_deltas, sp.b_move_cap):
        for d in range(n_dev):
            for m, t in enumerate(b_lists[delta][d]):
                b_pos[d][t] = base + m
        base += cap
    n_row_max = max(len(v) for v in row_js)
    out_idx = []
    for d in range(n_dev):
        r, c = divmod(d, g)
        m = {(r, c): 0}
        for t, j in enumerate(row_js[d]):
            m[(r, j)] = 1 + t
        for t, i in enumerate(col_is[d]):
            m[(i, c)] = 1 + n_row_max + t
        out_idx.append(m)
    out_rows = [dict() for _ in range(n_dev)]
    if sa is not None:
        for d in range(n_dev):
            for (i, k, j) in items[d]:
                sl = np.nonzero(sa.real[i, k])[0]
                if len(sl):
                    out_rows[d].setdefault((i, j), set()).update(
                        sa.rows[i, k][sl].tolist())
    return dict(items=items, need_a=need_a, need_b=need_b,
                a_lists=a_lists, b_lists=b_lists, a_pos=a_pos, b_pos=b_pos,
                a_zero=a_zero, a_pool_tiles=a_pool_tiles, out_idx=out_idx,
                out_rows=out_rows, dev=dev)


def _decode_steal_pairs(sp, sa, lay, aux, seg, findings):
    """Decode one pair-list segment into a multiset of executed products.

    ``seg`` is ("", full-pool) for bulk plans, ("0", panel-pool) /
    ("1", full-pool) for overlap plans.  Returns Counter of
    (i, k, j, stored_slot) — stored_slot is 0 for dense A.
    """
    suffix, panel_only = seg
    g = sp.g
    packed = sp.wire == "packed"
    sparse_a = sp.a_kind == "bsr"
    wc = sp.a_wire_capacity
    nbr = sa.real.shape[2] and int(sa.rows.shape[2]) or 1  # unused default
    nbr = int(np.max(sa.rows) + 1) if sparse_a else 1
    if sparse_a:
        nbr = sa.tile_nbr
    pa_arr = aux[f"pa{suffix}"]
    pb_arr = aux[f"pb{suffix}"]
    ps_arr = aux[f"ps{suffix}"]
    if panel_only:
        a_zero = g * wc if packed else (
            g * sp.store_a if sparse_a else g)
    else:
        a_zero = lay["a_zero"]
    # flat packed intervals: (base, stride, tile) in base order
    intervals = []
    if packed:
        for k in range(g):
            intervals.append((k * wc, wc, None, k))   # panel: tile (r, k)
        if not panel_only:
            base = g * wc
            for delta, cap, rcap in zip(sp.a_deltas, sp.a_move_cap,
                                        sp.a_round_cap):
                intervals.append((base, rcap, delta, None))
                base += cap * rcap
    got: Counter = Counter()
    inv_out = [{o: key for key, o in lay["out_idx"][d].items()}
               for d in range(g * g)]
    inv_b = [{pos: t for t, pos in lay["b_pos"][d].items()}
             for d in range(g * g)]
    inv_a = [{pos: t for t, pos in lay["a_pos"][d].items()}
             for d in range(g * g)]
    for d in range(g * g):
        r, c = divmod(d, g)
        ps_dev = ps_arr[r, c]
        if sparse_a and (np.diff(ps_dev) < 0).any():
            findings.append(Finding(
                "schedule.steal-conservation",
                f"device ({r},{c}) pair list (segment {suffix or 'bulk'}) "
                "is not slot-sorted — first-visit zeroing would reset "
                "accumulated slots",
                subject="steal3d"))
        if sparse_a and set(range(sp.n_slots)) - set(ps_dev.tolist()):
            findings.append(Finding(
                "schedule.steal-conservation",
                f"device ({r},{c}) pair list (segment {suffix or 'bulk'}) "
                "misses output slots — uninitialized accumulator slots "
                "survive first-visit zeroing",
                subject="steal3d"))
        for p in range(pa_arr.shape[2]):
            va = int(pa_arr[r, c, p])
            if va == a_zero:
                continue                       # inert coverage/padding
            # --- decode the A side to (tile, stored slot) ---
            if packed:
                tile = off = None
                for base, stride, delta, k in intervals:
                    span = stride * (1 if k is not None else
                                     len(lay["a_lists"][delta][d]) or 1)
                    if k is not None:
                        lo, hi = base, base + stride
                        if lo <= va < hi:
                            tile, off = (r, k), va - lo
                            break
                    else:
                        lst = lay["a_lists"][delta][d]
                        lo, hi = base, base + stride * len(lst)
                        if lo <= va < hi and lst:
                            m, off = divmod(va - lo, stride)
                            tile = lst[m]
                            break
                if tile is None:
                    findings.append(Finding(
                        "schedule.steal-exactly-once",
                        f"device ({r},{c}) pair {p}: packed pool index "
                        f"{va} addresses no gathered or moved tile — "
                        "reads junk as real work",
                        subject="steal3d"))
                    continue
                i, k_a = tile
                nz = np.nonzero(sa.real[i, k_a])[0]
                if off >= len(nz):
                    continue                   # packed zero tail: inert
                stored = int(nz[off])
            elif sparse_a:
                pos, stored = divmod(va, sp.store_a)
                if pos not in inv_a[d] or (panel_only and pos >= g):
                    findings.append(Finding(
                        "schedule.steal-exactly-once",
                        f"device ({r},{c}) pair {p}: pool position {pos} "
                        "addresses no gathered or moved tile — reads "
                        "junk as real work",
                        subject="steal3d"))
                    continue
                i, k_a = inv_a[d][pos]
                if not sa.real[i, k_a][stored]:
                    continue                   # structurally zero: inert
            else:
                if va not in inv_a[d] or (panel_only and va >= g):
                    findings.append(Finding(
                        "schedule.steal-exactly-once",
                        f"device ({r},{c}) pair {p}: pool position {va} "
                        "addresses no gathered or moved tile",
                        subject="steal3d"))
                    continue
                i, k_a = inv_a[d][va]
                stored = 0
            # --- decode output slot and B chunk; check the join ---
            vs = int(ps_arr[r, c, p])
            vb = int(pb_arr[r, c, p])
            o, rhat = divmod(vs, nbr) if sparse_a else (vs, 0)
            if o not in inv_out[d]:
                findings.append(Finding(
                    "schedule.steal-exactly-once",
                    f"device ({r},{c}) pair {p}: output slot {o} maps to "
                    "no (i, j) accumulator on this device",
                    subject="steal3d"))
                continue
            oi, oj = inv_out[d][o]
            bpos, q = divmod(vb, sp.b_chunks) if sparse_a else (vb, 0)
            if bpos not in inv_b[d]:
                findings.append(Finding(
                    "schedule.steal-exactly-once",
                    f"device ({r},{c}) pair {p}: B pool position {bpos} "
                    "addresses no gathered or moved B tile",
                    subject="steal3d"))
                continue
            bk, bj = inv_b[d][bpos]
            ok = (oi == i and bj == oj and bk == k_a)
            if sparse_a:
                ok = ok and q == int(sa.cols[i, k_a][stored]) \
                    and rhat == int(sa.rows[i, k_a][stored])
            if not ok:
                findings.append(Finding(
                    "schedule.steal-exactly-once",
                    f"device ({r},{c}) pair {p}: inconsistent join — A "
                    f"block ({i},{k_a}) slot {stored} paired with B tile "
                    f"({bk},{bj}) chunk {q} into output ({oi},{oj}) row "
                    f"{rhat}",
                    subject="steal3d"))
                continue
            item = (i, k_a, oj)
            if panel_only is not None and suffix == "0" \
                    and not (i == r and oj == c):
                findings.append(Finding(
                    "schedule.steal-conservation",
                    f"device ({r},{c}): stolen item {item} scheduled in "
                    "the own-items segment — it would execute before its "
                    "moved tile arrives",
                    subject="steal3d"))
            if suffix == "1" and (i == r and oj == c):
                findings.append(Finding(
                    "schedule.steal-conservation",
                    f"device ({r},{c}): own item {item} scheduled in the "
                    "stolen segment — serialized behind the move rounds "
                    "for no reason",
                    subject="steal3d"))
            if int(lay["dev"][i, k_a, oj]) != d:
                findings.append(Finding(
                    "schedule.steal-exactly-once",
                    f"item {item} executes on device ({r},{c}) but the "
                    f"assignment placed it on device "
                    f"{divmod(int(lay['dev'][i, k_a, oj]), g)}",
                    subject="steal3d"))
            got[item + (stored,)] += 1
            if len(findings) >= _MAX_PER_RULE:
                return got
    return got


def check_steal(plan, a_h) -> List[Finding]:
    """steal3d exactly-once + conservation over the plan's aux arrays."""
    if plan.steal is None:
        return []
    sp = plan.steal
    g = sp.g
    n_dev = g * g
    sparse_a = sp.a_kind == "bsr"
    sa = a_h.grid_structure() if sparse_a else None
    findings: List[Finding] = []
    lay = _steal_layout(sp, sa)
    aux = sp.aux

    # -- exactly-once: decode every segment, compare against the assignment
    segs = [("0", True), ("1", False)] if sp.overlap else [("", False)]
    got: Counter = Counter()
    for seg in segs:
        got += _decode_steal_pairs(sp, sa, lay, aux, seg, findings)
    want: Counter = Counter()
    for i in range(g):
        for k in range(g):
            for j in range(g):
                if sparse_a:
                    for sl in np.nonzero(sa.real[i, k])[0]:
                        want[(i, k, j, int(sl))] += 1
                else:
                    want[(i, k, j, 0)] += 1
    for key, n in want.items():
        if got.get(key, 0) != n and len(findings) < _MAX_PER_RULE:
            i, k, j, sl = key
            findings.append(Finding(
                "schedule.steal-exactly-once",
                f"work item ({i},{k},{j}) stored slot {sl} is accumulated "
                f"{got.get(key, 0)} time(s) across all devices instead of "
                "exactly once — the result would be "
                f"{'missing' if got.get(key, 0) == 0 else 'double-counted'}"
                " this block product",
                subject="steal3d"))
    for key in got:
        if key not in want and len(findings) < _MAX_PER_RULE:
            findings.append(Finding(
                "schedule.steal-exactly-once",
                f"pair lists accumulate {key[:3]} stored slot {key[3]}, "
                "which is not real structural work",
                subject="steal3d"))

    # -- conservation: move rounds ship exactly the needed tiles ----------
    n_real_tile = sa.real.sum(axis=2) if sparse_a else None
    for d in range(n_dev):
        for t in lay["need_a"][d]:
            delta = (d // g - t[0]) % g
            if delta not in sp.a_deltas and not (
                    sp.wire == "packed" and int(n_real_tile[t]) == 0):
                findings.append(Finding(
                    "schedule.steal-conservation",
                    f"device {divmod(d, g)} needs moved A tile {t} at hop "
                    f"{delta} but no such move round exists — the item "
                    "would compute on a stale pool slot",
                    subject="steal3d"))
        for t in lay["need_b"][d]:
            delta = (d % g - t[1]) % g
            if delta not in sp.b_deltas:
                findings.append(Finding(
                    "schedule.steal-conservation",
                    f"device {divmod(d, g)} needs moved B tile {t} at hop "
                    f"{delta} but no such move round exists",
                    subject="steal3d"))
    for delta in sp.a_deltas:
        arr = aux[f"amk{delta}"]
        for d in range(n_dev):
            s = ((d // g - delta) % g, d % g)
            for m, t in enumerate(lay["a_lists"][delta][d]):
                if int(arr[s[0], s[1], m]) != t[1]:
                    findings.append(Finding(
                        "schedule.steal-conservation",
                        f"A move round delta={delta}: source {s} packs "
                        f"panel position {int(arr[s[0], s[1], m])} into "
                        f"lane {m} but receiver {divmod(d, g)} expects "
                        f"tile {t} (panel position {t[1]}) — the thief "
                        "computes with the wrong tile",
                        subject="steal3d"))
                    break
    for delta in sp.b_deltas:
        arr = aux[f"bmk{delta}"]
        for d in range(n_dev):
            s = (d // g, (d % g - delta) % g)
            for m, t in enumerate(lay["b_lists"][delta][d]):
                if int(arr[s[0], s[1], m]) != t[0]:
                    findings.append(Finding(
                        "schedule.steal-conservation",
                        f"B move round delta={delta}: source {s} packs "
                        f"panel position {int(arr[s[0], s[1], m])} into "
                        f"lane {m} but receiver {divmod(d, g)} expects "
                        f"tile {t} (panel position {t[0]})",
                        subject="steal3d"))
                    break

    # -- conservation: every off-owner partial rides home -----------------
    dummy_idx = sp.n_out - 1
    packed = sp.wire == "packed"
    for d in range(n_dev):
        r, c = divmod(d, g)
        for (i, j), o in lay["out_idx"][d].items():
            if o == 0:
                continue
            if i == r:
                delta, deltas, what = (j - c) % g, sp.row_deltas, "row"
            else:
                delta, deltas, what = (i - r) % g, sp.col_deltas, "col"
            if delta not in deltas and not (
                    packed and not lay["out_rows"][d].get((i, j))):
                findings.append(Finding(
                    "schedule.steal-conservation",
                    f"device ({r},{c}) computes a partial for output tile "
                    f"({i},{j}) but no {what} reduce round at hop {delta} "
                    "exists — the partial never rides home",
                    subject="steal3d"))
    for deltas, key_of, prefix in (
            (sp.row_deltas, lambda r, c, delta: (r, (c + delta) % g), "r"),
            (sp.col_deltas, lambda r, c, delta: ((r + delta) % g, c), "c")):
        for delta in deltas:
            sel = aux[f"{prefix}send{delta}"]
            for d in range(n_dev):
                r, c = divmod(d, g)
                want_o = lay["out_idx"][d].get(key_of(r, c, delta),
                                               dummy_idx)
                if int(sel[r, c]) != want_o:
                    findings.append(Finding(
                        "schedule.steal-conservation",
                        f"{prefix}send{delta}[{r},{c}] selects output "
                        f"slot {int(sel[r, c])} but device ({r},{c})'s "
                        f"partial for that round lives in slot {want_o} — "
                        "the wrong partial (or junk) rides home",
                        subject="steal3d"))
    if packed:
        nbr = sa.tile_nbr
        for deltas, out_of, src_of, prefix in (
                (sp.row_deltas,
                 lambda d, delta: (d // g, (d % g + delta) % g),
                 lambda d, delta: (d // g) * g + (d % g - delta) % g, "r"),
                (sp.col_deltas,
                 lambda d, delta: ((d // g + delta) % g, d % g),
                 lambda d, delta: ((d // g - delta) % g) * g + d % g, "c")):
            for delta in deltas:
                row = aux[f"{prefix}row{delta}"]
                tgt = aux[f"{prefix}tgt{delta}"]
                rows_of = [sorted(lay["out_rows"][d].get(
                    out_of(d, delta), ())) for d in range(n_dev)]
                for d in range(n_dev):
                    r, c = divmod(d, g)
                    mine = rows_of[d]
                    src = rows_of[src_of(d, delta)]
                    ok = list(row[r, c, :len(mine)]) == mine \
                        and list(tgt[r, c, :len(src)]) == src \
                        and (tgt[r, c, len(src):] == nbr).all()
                    if not ok:
                        findings.append(Finding(
                            "schedule.steal-conservation",
                            f"packed reduce round {prefix}{delta} at "
                            f"device ({r},{c}): shipped rows "
                            f"{list(row[r, c])} / targets "
                            f"{list(tgt[r, c])} disagree with the "
                            f"partial's touched rows {mine} (receiver "
                            f"expects {src}; padding must land on the "
                            f"dummy row {nbr})",
                            subject="steal3d"))
                        break
    return findings


def check_survivor_coverage(assignment, g: int,
                            survivors=None) -> List[Finding]:
    """``schedule.survivor-coverage``: a rebuilt assignment matches the
    surviving mesh.

    The elastic-recovery gate (``repro.runtime.replan``): after device
    loss, the steal3d :class:`~repro.core.schedule.Assignment3D` is
    rebuilt for a shrunken ``g x g`` grid.  This rule proves the rebuilt
    assignment covers *exactly* that grid's work: the work grid has the
    new shape, every (i, k, j) item is assigned (no ``-1`` holes), every
    referenced device id is a live position of the new mesh (``[0,
    g^2)``), and — when the surviving device collection is given — the
    new grid actually fits on it.  Locality/makespan invariants stay with
    ``validate_assignment``; this is purely the coverage contract.
    """
    rule = "schedule.survivor-coverage"
    findings: List[Finding] = []
    dev = np.asarray(assignment.dev if hasattr(assignment, "dev")
                     else assignment)
    if dev.shape != (g, g, g):
        return [Finding(rule,
                        f"assignment work grid has shape {dev.shape}, "
                        f"expected {(g, g, g)} for the surviving "
                        f"{g}x{g} mesh", subject="steal3d")]
    if not np.issubdtype(dev.dtype, np.integer):
        return [Finding(rule,
                        f"assignment device ids must be integers, got "
                        f"dtype {dev.dtype}", subject="steal3d")]
    if survivors is not None:
        n_surv = survivors if isinstance(survivors, int) \
            else len(tuple(survivors))
        if g * g > n_surv:
            findings.append(Finding(
                rule,
                f"a {g}x{g} grid needs {g * g} devices but only "
                f"{n_surv} survive", subject="steal3d"))
    unassigned = int((dev < 0).sum())
    if unassigned:
        holes = np.argwhere(dev < 0)[:3].tolist()
        findings.append(Finding(
            rule,
            f"{unassigned} work item(s) unassigned (dev < 0), e.g. "
            f"{holes} — recovery would silently drop their block "
            "products", subject="steal3d"))
    dead = int((dev >= g * g).sum())
    if dead:
        ids = sorted(set(int(d) for d in dev[dev >= g * g].ravel()))[:4]
        findings.append(Finding(
            rule,
            f"{dead} work item(s) assigned to device ids {ids} outside "
            f"the surviving mesh's [0, {g * g}) — those positions no "
            "longer exist", subject="steal3d"))
    return findings


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
RULES = (
    ("schedule.ppermute-bijection",
     "every ppermute permutation is a complete, self-send-free bijection"),
    ("schedule.steal-exactly-once",
     "steal3d pair lists accumulate each (i,k,j) block product exactly "
     "once across devices"),
    ("schedule.steal-conservation",
     "steal3d move/reduce rounds conserve tiles and partials; pair lists "
     "stay sorted with full slot coverage"),
    ("schedule.wire-contract",
     "packed-wire pack_idx/consume maps/slot_map/dmap satisfy the "
     "bsr_spmm_raw(augment=False) contract with inert padding proven "
     "inert"),
    ("schedule.sparse-pairs-exactly-once",
     "sparse-output pair lists accumulate each structural product "
     "exactly once, slot-sorted with full coverage"),
    ("schedule.balance-identity",
     "balance permutations compose to identity through the epilogue"),
    ("schedule.survivor-coverage",
     "a rebuilt steal3d assignment covers exactly the surviving mesh's "
     "work items: every (i,k,j) assigned, only surviving devices "
     "referenced, grid fits the survivor count"),
)


def _guard(rule: str, fn, *args) -> List[Finding]:
    try:
        return fn(*args)
    except Exception as e:                     # noqa: BLE001
        # a decode crash on corrupt metadata is a detection, not a pass
        return [Finding(
            rule,
            f"checker could not decode the plan's metadata "
            f"({type(e).__name__}: {e}) — the arrays do not satisfy the "
            "layout contract's shapes/ranges",
        )]


def check_plan(plan, a=None, b=None) -> List[Finding]:
    """Run every schedule rule that applies to ``plan``.

    ``a`` / ``b`` are the plan's operands (handles preferred); structure-
    dependent rules are skipped when they are absent.
    """
    from repro.core import api as _api
    findings = _guard("schedule.ppermute-bijection", check_perms, plan)
    if a is None or b is None:
        return findings
    a_h, b_h = _api._coerce_pair(a, b, g=plan.geom.g,
                                 allow_pad=plan._allow_pad)
    findings += _guard("schedule.balance-identity", check_balance,
                       plan, a_h, b_h)
    if plan.steal is not None:
        findings += _guard("schedule.steal-exactly-once", check_steal,
                           plan, a_h)
    if plan.symbolic is not None:
        findings += _guard("schedule.sparse-pairs-exactly-once",
                           check_sparse_pairs, plan, a_h, b_h)
    findings += _guard("schedule.wire-contract", check_wire, plan, a_h, b_h)
    return findings
