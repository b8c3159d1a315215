"""Tree-field integration on the host: the BTFI oracle, the recursive FTFI
and ExpMP (numpy references the tests compare against), and the plan
assembly that `repro.ftfi.build` (`core/plan_api.build`) turns into a
`PlanSpec`. The jit plan executor is `plan_api._execute`.

Correctness invariant (proved in comments below, tested in tests/test_core.py):
the *additive* decomposition counts every ordered pair (v, j) exactly once.

  At internal node nu with children L, R sharing pivot p:
    - recursion on L covers pairs L x L; on R covers R x R;
    - the two cross jobs cover (L\\{p}) x (R\\{p}) and (R\\{p}) x (L\\{p})
      (targets and sources both exclude the pivot);
    - the only overlap is the diagonal pair (p, p), counted twice ->
      one correction of -f(0) X[p] per internal node.
  Across the whole IT: two distinct leaves intersect in at most one vertex
  (a shared pivot), so off-diagonal pairs are never double counted by leaves;
  a pair (u, v), u != v is separated at exactly one IT node (their "meet"),
  so it is covered by exactly one cross job or exactly one leaf; diagonal
  pairs (v, v) appear once per leaf containing v = 1 + #(nodes where v is
  pivot), matched by the per-node corrections.

The recursive evaluator follows the paper's Eq. 2-4 verbatim (pivot kept in
the source group, subtracted via the f(left-d[tau(v)]) X'[0] correction); the
plan executor uses the optimized masked-source form. Both are validated
against the dense BTFI oracle.
"""
from __future__ import annotations

from typing import Callable

import numpy as np

from repro.core.cordial import CordialFn
from repro.core.integrator_tree import ITNode, build_integrator_tree
from repro.graphs.graph import WeightedTree
from repro.graphs.traverse import tree_all_pairs


# ----------------------------------------------------------------------------
# BTFI: brute-force oracle (paper's baseline)
# ----------------------------------------------------------------------------


class BTFI:
    """Materialize M_f = f(all-pairs tree distances); multiply densely."""

    def __init__(self, tree: WeightedTree, dtype=np.float64):
        self.dists = tree_all_pairs(tree, dtype=dtype)  # O(N^2) preprocessing

    def integrate(self, fn: Callable, X: np.ndarray) -> np.ndarray:
        return fn(self.dists) @ X


# ----------------------------------------------------------------------------
# FTFI: recursive exact integrator (host / numpy)
# ----------------------------------------------------------------------------


class FTFI:
    """Fast tree-field integrator. Preprocessing = IT construction (once);
    `integrate(fn, X)` is exact for any CordialFn."""

    def __init__(self, tree: WeightedTree, leaf_size: int = 64, seed: int = 0):
        self.n = tree.num_vertices
        self.root = build_integrator_tree(tree, leaf_size=leaf_size, seed=seed)

    def integrate(self, fn: CordialFn, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X)
        squeeze = X.ndim == 1
        if squeeze:
            X = X[:, None]
        # preserve float32 fields: the walk is bandwidth-bound, so dtype
        # halves/doubles end-to-end time for wide fields (e.g. GW plans)
        acc_dtype = X.dtype if X.dtype in (np.float32, np.float64) else np.float64
        out = np.zeros_like(X, dtype=acc_dtype)
        self._walk(self.root, fn, X, out)
        return out[:, 0] if squeeze else out

    def _walk(self, node: ITNode, fn: CordialFn, X: np.ndarray, out: np.ndarray):
        if node.is_leaf:
            out[node.vertex_ids] += fn(node.leaf_dists) @ X[node.vertex_ids]
            return
        p = node.pivot
        # segment layouts are precomputed in build_integrator_tree: ITNode is
        # immutable, so the walk is thread-safe and plans can share one IT
        for src_sorted, starts, tgt_ids, tgt_id_d, tgt_d, src_d in (
            (node.right_sorted_ids, node.right_seg_starts,
             node.left_ids, node.left_id_d, node.left_d, node.right_d),
            (node.left_sorted_ids, node.left_seg_starts,
             node.right_ids, node.right_id_d, node.right_d, node.left_d),
        ):
            # X'[u] = sum over source vertices in distance-group u (Eq. 3);
            # the pivot IS included (group 0), per the paper.
            Xp = np.add.reduceat(X[src_sorted], starts, axis=0).astype(out.dtype)
            # cross values per target distance-group: C @ X' (Eq. 4)
            cross = fn.matvec(tgt_d, src_d, Xp)  # (U_tgt, d)
            # Eq. 4 correction: remove the source-pivot column f(tgt_d) X'[0]
            corr = fn(tgt_d)[:, None] * Xp[0][None, :]
            vals = cross - corr
            # targets exclude the pivot (tgt_ids[0] == pivot by construction)
            out[tgt_ids[1:]] += vals[tgt_id_d[1:]]
        out[p] -= fn.f0 * X[p]  # diagonal (p, p) double-count correction
        self._walk(node.left, fn, X, out)
        self._walk(node.right, fn, X, out)


# ----------------------------------------------------------------------------
# Exponential-kernel specialization: two-pass message passing (beyond-paper)
# ----------------------------------------------------------------------------


class ExpMP:
    """Exact integrator for f(x) = scale * exp(lam * x) on a weighted tree
    via the classic up/down sweep:

      up[v]   = X_v + sum_c e^{lam w_c} up[c]          (subtree mass)
      down[c] = e^{lam w_c} (down[p] + up[p] - e^{lam w_c} up[c])
      out[v]  = up[v] + down[v]

    Two passes over (N, d) — bandwidth-optimal, O(N d) time, no IT needed.
    The multiplicative decomposability that makes this possible is exactly
    the paper's rank-1 cordiality of exp, pushed to its limit."""

    def __init__(self, tree: WeightedTree, root: int = 0):
        from repro.graphs.traverse import tree_bfs_order

        order, parent, parent_w = tree_bfs_order(tree, root)
        self.order = order
        self.parent = parent
        self.parent_w = parent_w

    def integrate(self, lam: float, X: np.ndarray, scale: float = 1.0):
        X = np.asarray(X)
        squeeze = X.ndim == 1
        if squeeze:
            X = X[:, None]
        order, parent, w = self.order, self.parent, self.parent_w
        e = np.exp(lam * w)  # per-vertex edge factor to parent
        up = X.astype(X.dtype if X.dtype in (np.float32, np.float64)
                      else np.float64).copy()
        for v in order[::-1]:
            pv = parent[v]
            if pv >= 0:
                up[pv] += e[v] * up[v]
        down = np.zeros_like(up)
        for v in order[1:]:
            pv = parent[v]
            down[v] = e[v] * (down[pv] + up[pv] - e[v] * up[v])
        out = scale * (up + down)
        return out[:, 0] if squeeze else out


# ----------------------------------------------------------------------------
# Plan assembly: flatten the IT into padded, bucketed, static arrays plus
# concatenated gather/segment/scatter index plans for the fused executor.
# The result is the keyword set of `plan_api.PlanSpec` (`plan_api.build`
# adds the provenance and, for reweightable builds, the reweight tables).
# ----------------------------------------------------------------------------


def _side_job_arrays(side, expand_groups: bool):
    """(ids, id_d, d, rep) for one job side. Default: distance-collapsed
    groups, rep=None (only reweightable builds consume representatives, so
    the hot construction path pays nothing for them). Expanded (reweightable
    builds): every vertex is its own group/representative, so re-deriving
    distances per representative stays exact under ANY edge reweighting —
    two vertices that tie under the build weights need not tie under new
    ones."""
    if not expand_groups:
        return side.ids, side.id_d, side.d, None
    k = side.ids.size
    return (side.ids, np.arange(k, dtype=np.int64), side.d[side.id_d],
            side.ids)


def _upd_tables(flat, job_bucket, job_row, leaf_bucket, leaf_row) -> dict:
    """Update tables: the IT skeleton (children refs + per-tree roots) and
    the (bucket, row) coordinate of every cross job / leaf, which is all
    `ftfi.update_plan` needs to walk a vertex's IT chain and patch the
    affected slots in place. `ghosts` starts empty."""
    root_refs = (flat.root_refs if flat.root_refs is not None
                 else np.array([flat.root_ref], np.int64))
    # int32 end-to-end: node refs are bounded by the node count, and every
    # other PlanSpec index array is already int32 — int64 here doubled the
    # artifact/update-table footprint for nothing (caught by repro.analysis)
    return {"children": flat.children.astype(np.int32),
            "root_refs": np.asarray(root_refs).astype(np.int32),
            "job_bucket": np.asarray(job_bucket, np.int32),
            "job_row": np.asarray(job_row, np.int32),
            "leaf_bucket": np.asarray(leaf_bucket, np.int32),
            "leaf_row": np.asarray(leaf_row, np.int32),
            "ghosts": np.zeros(0, np.int32)}


def _assemble_plan(flat, n: int, detect_grid_spacing: bool,
                   expand_groups: bool = False) -> dict:
    """Flatten a (tree or forest) FlatIT into the executor's arrays: cross
    jobs and leaves from EVERY tree share one global index space and are
    merged into the same size-class buckets, so the executor's dispatch
    count is a function of size diversity, not of how many trees the plan
    covers. Returns `PlanSpec` keywords: per-bucket tuples (`cross_*`,
    `leaf_*`), the four flat executor index arrays, `grid_h` and the
    update tables; `cross_piv` / `cross_*_rep` only when `expand_groups`.

    Vectorized: array ops over the IT's concatenated side CSR
    (`FlatIT.side_cat` / `leaf_cat`) — one stable argsort groups jobs into
    size-class buckets, `np.maximum.reduceat` yields the bucket maxima, and
    every padded bucket array plus all four flat executor index arrays
    fill through `_ranges` scatters, bitwise-identical to the per-node loop
    oracle of tests/test_plan_assembly.py."""
    from repro.core.itree_flat import _ranges

    num_i = flat.num_internal
    J = 2 * num_i
    sc = flat.side_cat
    k, u = sc["k"], sc["u"]
    kptr, uptr = sc["kptr"], sc["uptr"]
    ids_c, idd_c, d_c = sc["ids"], sc["id_d"], sc["d"]
    # job j's target side IS side j (side 2i = left, 2i+1 = right); its
    # source side is the sibling j ^ 1; both jobs of node i share its pivot
    piv_job = np.repeat(flat.pivots, 2)
    g = k if expand_groups else u  # distance-group count per side (incl piv)
    mem = k - 1  # member count per side (targets/sources exclude the pivot)

    cb = {key: [] for key in ("tgt_d", "tgt_mask", "src_d", "src_mask",
                              "src_off", "tgt_off", "piv", "tgt_rep",
                              "src_rep")}
    job_bucket = np.zeros(J, np.int32)
    job_row = np.zeros(J, np.int32)
    src_gather = src_seg = tgt_gather = tgt_scatter = np.zeros(0, np.int64)
    src_goff = tgt_goff = 0
    if J:
        # bucket by ceil(log2(max member count)) => <=2x padding waste;
        # stable sort keeps insertion order within each bucket, matching ref
        bkey = np.ceil(np.log2(np.maximum(
            np.maximum(mem, mem[np.arange(J) ^ 1]), 2))).astype(np.int64)
        order = np.argsort(bkey, kind="stable")
        sib = order ^ 1  # source side of each sorted job
        _, bstarts = np.unique(bkey[order], return_index=True)
        nb = bstarts.size
        bcounts = np.diff(np.r_[bstarts, J])
        Ut = np.maximum.reduceat(g[order], bstarts)
        Us = np.maximum.reduceat(g[sib], bstarts)
        tgt_off = np.zeros(nb + 1, np.int64)
        np.cumsum(bcounts * Ut, out=tgt_off[1:])
        src_off = np.zeros(nb + 1, np.int64)
        np.cumsum(bcounts * Us, out=src_off[1:])
        row = np.arange(J) - np.repeat(bstarts, bcounts)
        bix = np.repeat(np.arange(nb), bcounts)
        job_bucket[order] = bix
        job_row[order] = row

        if expand_groups:  # per-vertex distances: d[id_d], all sides at once
            dvert = d_c[np.repeat(uptr[:-1], k) + idd_c]
        for bi in range(nb):
            lo = int(bstarts[bi])
            hi = lo + int(bcounts[bi])
            js, ss = order[lo:hi], sib[lo:hi]
            B, Utb, Usb = hi - lo, int(Ut[bi]), int(Us[bi])
            tgt_d = np.zeros((B, Utb), dtype=np.float64)
            tgt_mask = np.zeros((B, Utb), dtype=bool)
            src_d = np.zeros((B, Usb), dtype=np.float64)
            src_mask = np.zeros((B, Usb), dtype=bool)
            gt, gs = g[js], g[ss]
            rt = np.repeat(np.arange(B), gt)
            ct = _ranges(np.zeros(B, np.int64), gt)
            rs = np.repeat(np.arange(B), gs)
            cs = _ranges(np.zeros(B, np.int64), gs)
            if expand_groups:
                tgt_d[rt, ct] = dvert[_ranges(kptr[js], gt)]
                src_d[rs, cs] = dvert[_ranges(kptr[ss], gs)]
            else:
                tgt_d[rt, ct] = d_c[_ranges(uptr[js], gt)]
                src_d[rs, cs] = d_c[_ranges(uptr[ss], gs)]
            tgt_mask[rt, ct] = True
            src_mask[rs, cs] = True
            cb["tgt_d"].append(tgt_d)
            cb["tgt_mask"].append(tgt_mask)
            cb["src_d"].append(src_d)
            cb["src_mask"].append(src_mask)
            cb["src_off"].append(int(src_off[bi]))
            cb["tgt_off"].append(int(tgt_off[bi]))
            if expand_groups:  # rep tables: padding repeats the pivot
                pj = piv_job[js]
                tgt_rep = np.repeat(pj, Utb).reshape(B, Utb).astype(np.int32)
                src_rep = np.repeat(pj, Usb).reshape(B, Usb).astype(np.int32)
                tgt_rep[rt, ct] = ids_c[_ranges(kptr[js], gt)]
                src_rep[rs, cs] = ids_c[_ranges(kptr[ss], gs)]
                cb["piv"].append(pj.astype(np.int32))
                cb["tgt_rep"].append(tgt_rep)
                cb["src_rep"].append(src_rep)
        src_goff, tgt_goff = int(src_off[-1]), int(tgt_off[-1])

        # flat executor arrays in (bucket, job) order — one concatenation
        # pass per kind instead of per-job list appends
        mem_t, mem_s = mem[order], mem[sib]
        tjob = tgt_off[bix] + row * Ut[bix]
        sjob = src_off[bix] + row * Us[bix]
        tgt_scatter = ids_c[_ranges(kptr[order] + 1, mem_t)]
        src_gather = ids_c[_ranges(kptr[sib] + 1, mem_s)]
        if expand_groups:  # expanded group index of vertex j is j itself
            tidd = _ranges(np.ones(J, np.int64), mem_t)
            sidd = _ranges(np.ones(J, np.int64), mem_s)
        else:
            tidd = idd_c[_ranges(kptr[order] + 1, mem_t)]
            sidd = idd_c[_ranges(kptr[sib] + 1, mem_s)]
        tgt_gather = np.repeat(tjob, mem_t) + tidd
        src_seg = np.repeat(sjob, mem_s) + sidd

    # --- leaf buckets by ceil(log2(k)): a mixed-size forest pads each leaf
    # to its size class, not to the global maximum (K^2 padding waste would
    # dominate leaf-heavy forest plans)
    lc = flat.leaf_cat
    lk, lptr, ldptr = lc["k"], lc["ptr"], lc["dptr"]
    Lf = lk.size
    leaf_bucket = np.zeros(Lf, np.int32)
    leaf_row = np.zeros(Lf, np.int32)
    leaf_ids, leaf_mask, leaf_dists = [], [], []
    if Lf:
        lkey = np.ceil(np.log2(np.maximum(lk, 2))).astype(np.int64)
        lorder = np.argsort(lkey, kind="stable")
        _, lstarts = np.unique(lkey[lorder], return_index=True)
        lcounts = np.diff(np.r_[lstarts, Lf])
        leaf_bucket[lorder] = np.repeat(np.arange(lstarts.size), lcounts)
        leaf_row[lorder] = np.arange(Lf) - np.repeat(lstarts, lcounts)
        for bi in range(lstarts.size):
            lv = lorder[int(lstarts[bi]):int(lstarts[bi]) + int(lcounts[bi])]
            ks = lk[lv]
            B, K = lv.size, int(ks.max())
            ids = np.full((B, K), n, dtype=np.int32)
            mask = np.zeros((B, K), dtype=bool)
            dists = np.zeros((B, K, K), dtype=np.float64)
            r = np.repeat(np.arange(B), ks)
            c = _ranges(np.zeros(B, np.int64), ks)
            ids[r, c] = lc["ids"][_ranges(lptr[lv], ks)]
            mask[r, c] = True
            # raveled (row, col) targets of every k_i x k_i block at once
            pw = _ranges(np.zeros(B, np.int64), ks * ks)
            kk = np.repeat(ks, ks * ks)
            pos = (np.repeat(np.arange(B) * K * K, ks * ks)
                   + (pw // kk) * K + pw % kk)
            dists.reshape(-1)[pos] = lc["dflat"][_ranges(ldptr[lv],
                                                         ks * ks)]
            leaf_ids.append(ids)
            leaf_mask.append(mask)
            leaf_dists.append(dists)

    h = None
    if detect_grid_spacing:
        from repro.core.cordial import detect_grid
        # one detection over the merged distances reconciles per-tree grids:
        # the common h of a forest is the gcd of its trees' spacings (None if
        # any tree is off-grid or the joint span is FFT-impractical)
        all_d = np.unique(d_c) if d_c.size else np.zeros(1)
        h = detect_grid(all_d, np.zeros(1))
    rep = ({"cross_piv": tuple(cb["piv"]),
            "cross_tgt_rep": tuple(cb["tgt_rep"]),
            "cross_src_rep": tuple(cb["src_rep"])} if expand_groups else {})
    return dict(
        n=n, grid_h=h,
        cross_tgt_mask=tuple(cb["tgt_mask"]),
        cross_src_mask=tuple(cb["src_mask"]),
        cross_src_off=tuple(cb["src_off"]),
        cross_tgt_off=tuple(cb["tgt_off"]),
        cross_tgt_d0=tuple(cb["tgt_d"]),
        cross_src_d0=tuple(cb["src_d"]),
        leaf_ids=tuple(leaf_ids), leaf_mask=tuple(leaf_mask),
        leaf_dists0=tuple(leaf_dists),
        pivots=flat.pivots.astype(np.int32),
        src_gather=src_gather.astype(np.int32),
        src_seg=src_seg.astype(np.int32),
        n_src_groups=src_goff,
        tgt_gather=tgt_gather.astype(np.int32),
        tgt_scatter=tgt_scatter.astype(np.int32),
        n_tgt_groups=tgt_goff,
        num_cross_jobs=J,
        **rep,
        **_upd_tables(flat, job_bucket, job_row, leaf_bucket, leaf_row))


# ----------------------------------------------------------------------------
# reweight tables: everything a differentiable edge_w -> distances map needs
# ----------------------------------------------------------------------------


def _root_path_pairs(trees):
    """(rows, edges): for every vertex v (global packed id), one entry per
    edge on v's root path — so depth[v] = sum of edge_w over v's entries is
    one gather + segment-sum. Edges are numbered in packed per-tree order
    (the concatenation of each tree's `weights` arrays)."""
    from repro.graphs.traverse import tree_bfs_order

    rows_parts, edge_parts = [], []
    voff = eoff = 0
    for t in trees:
        n = t.num_vertices
        _, parent, _ = tree_bfs_order(t, 0)
        eu = t.edges_u.astype(np.int64)
        ev = t.edges_v.astype(np.int64)
        idx = np.arange(eu.size, dtype=np.int64)
        pe = np.full(n, -1, np.int64)  # edge to parent, per non-root vertex
        m = parent[ev] == eu
        pe[ev[m]] = idx[m]
        m = parent[eu] == ev
        pe[eu[m]] = idx[m]
        a = np.flatnonzero(parent >= 0)
        origin = a.copy()
        while a.size:  # climb all root paths one ancestor level at a time
            rows_parts.append(origin + voff)
            edge_parts.append(pe[a] + eoff)
            a = parent[a]
            keep = parent[a] >= 0  # pe[a] valid only for non-root ancestors
            origin, a = origin[keep], a[keep]
        voff += n
        eoff += eu.size
    if not rows_parts:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    return (np.concatenate(rows_parts).astype(np.int32),
            np.concatenate(edge_parts).astype(np.int32))


def _forest_lca_query(lcas, offsets, u, v):
    """Elementwise LCA of global vertex pairs (each pair within one tree)."""
    shape = u.shape
    u = np.asarray(u, np.int64).ravel()
    v = np.asarray(v, np.int64).ravel()
    out = np.empty(u.shape, np.int64)
    tid = np.searchsorted(offsets, u, side="right") - 1
    for t in np.unique(tid):
        sel = tid == t
        off = int(offsets[t])
        out[sel] = lcas[t].lca(u[sel] - off, v[sel] - off) + off
    return out.reshape(shape)


def _reweight_tables(fields: dict, trees) -> dict:
    """The LCA tables (cross + leaf) and root-path CSR of an expanded-group
    assembly (`_assemble_plan(..., expand_groups=True)`), as `PlanSpec`
    keywords: with these, every distance slot is depth[u] + depth[v] -
    2 depth[lca], a pure (differentiable) function of the edge weights."""
    from repro.graphs.traverse import TreeLCA

    sizes = np.array([t.num_vertices for t in trees], np.int64)
    offsets = np.zeros(sizes.size + 1, np.int64)
    np.cumsum(sizes, out=offsets[1:])
    lcas = [TreeLCA(t) for t in trees]
    n = fields["n"]

    ctl, csl = [], []
    for piv, tgt_rep, src_rep in zip(fields["cross_piv"],
                                     fields["cross_tgt_rep"],
                                     fields["cross_src_rep"]):
        pv_t = np.broadcast_to(piv[:, None], tgt_rep.shape)
        pv_s = np.broadcast_to(piv[:, None], src_rep.shape)
        ctl.append(_forest_lca_query(lcas, offsets, pv_t,
                                     tgt_rep).astype(np.int32))
        csl.append(_forest_lca_query(lcas, offsets, pv_s,
                                     src_rep).astype(np.int32))
    ll = []
    for ids in fields["leaf_ids"]:
        B, K = ids.shape
        u = np.broadcast_to(ids[:, :, None].astype(np.int64), (B, K, K))
        v = np.broadcast_to(ids[:, None, :].astype(np.int64), (B, K, K))
        valid = (u < n) & (v < n)
        out = np.full((B, K, K), n, np.int64)  # pad -> sentinel depth row
        if valid.any():
            out[valid] = _forest_lca_query(lcas, offsets, u[valid], v[valid])
        ll.append(out.astype(np.int32))
    rows, edges = _root_path_pairs(trees)
    # packed global edge endpoints + build weights: `update_plan` needs the
    # live edge list to validate leaf deletions and to re-derive distances
    # host-side after structural edits
    eu_parts, ev_parts, ew_parts = [], [], []
    for t, off in zip(trees, offsets[:-1]):
        eu_parts.append(t.edges_u.astype(np.int64) + off)
        ev_parts.append(t.edges_v.astype(np.int64) + off)
        ew_parts.append(t.weights.astype(np.float64))
    return {"cross_tgt_lca": tuple(ctl), "cross_src_lca": tuple(csl),
            "leaf_lca": tuple(ll), "path_rows": rows, "path_edges": edges,
            "num_edges": int(sum(t.num_edges for t in trees)),
            "edges_u": (np.concatenate(eu_parts).astype(np.int32)
                        if eu_parts else np.zeros(0, np.int32)),
            "edges_v": (np.concatenate(ev_parts).astype(np.int32)
                        if ev_parts else np.zeros(0, np.int32)),
            "edge_w0": (np.concatenate(ew_parts)
                        if ew_parts else np.zeros(0, np.float64))}
