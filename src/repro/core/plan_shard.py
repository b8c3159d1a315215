"""Multi-device FTFI: leaf-block partitioner + shard_map plan executor.

The fused executor in `plan_api._execute` is a single-device program: one
gather + segment-sum over the whole source index space, one cross dispatch
per size bucket, one gather + scatter-add over the whole target space. This
module partitions that global index space into per-device *leaf blocks* and
re-expresses the same computation as a shard_map program whose collectives
are exact:

  - the vertex space [0, n) is cut into `num_shards` equal contiguous
    blocks (the `plan_leaves` logical axis). Trees in a packed `Forest`
    occupy contiguous id ranges, so forest plans shard naturally per tree —
    only trees straddling a block boundary contribute halo traffic;
  - every *contribution* (leaf-bucket row, cross job, pivot correction) is
    assigned to the shard owning its output vertices, so scatter-adds stay
    block-local up to the final reduction;
  - cross buckets / leaf rows that straddle shards read remote field rows
    through a host-precomputed **halo/exchange table**: each device gathers
    the rows its neighbours need, one `all_to_all` swaps them, and local
    indices into the received pool are baked into the per-shard index
    arrays (no full-field gather, ever);
  - per-shard partial outputs meet in one `psum_scatter` over the block
    axis — an exact reduction, so `apply_sharded` matches the single-device
    `plan_api.apply` to float round-off (tests pin 1e-6 relative).

Everything the partitioner emits is static numpy, stacked per shard along a
leading `(num_shards, ...)` axis that shard_map splits — each device only
ever holds its own slice of the plan index arrays.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.lru import BoundedLRU
from repro.core.plan_api import (EXACT, PlanParams, PlanSpec, _fspec,
                                 select_cross)

# bumped whenever the per-shard table layout below changes: recorded into
# sharded artifacts' provenance and rejected by plan_guard when a newer
# artifact meets an older codebase
SHARD_LAYOUT_VERSION = 1

_PART_CACHE = BoundedLRU(8)


# ----------------------------------------------------------------------------
# ShardPlan: host-side per-device tables
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False, repr=False)
class ShardPlan:
    """Per-device decomposition of one PlanSpec. All arrays are numpy and
    stacked along a leading (D,) shard axis; `block` is the per-device
    vertex count (the padded global field is (D * block, d)). Index
    conventions inside a shard's local field buffer `xfull`:

      [0, block)                     owned vertex rows
      block                          zero pad row
      [block + 1, block + 1 + D*Emax) halo rows received via all_to_all
    """

    num_shards: int
    block: int
    halo_width: int  # Emax: max rows exchanged per (sender, receiver) pair
    halo_total: int  # sum of remote rows referenced across shards
    send_idx: np.ndarray  # (D, D, Emax) local row ids to send (pad=block)
    # leaf buckets (tuples over bucket index)
    leaf_sel: tuple  # (D, Rmax_i) row ids into the bucket (pad=0)
    leaf_gather: tuple  # (D, Rmax_i, K) xfull indices (pad=block)
    leaf_mask: tuple  # (D, Rmax_i, K) bool
    leaf_scatter: tuple  # (D, Rmax_i, K) out rows (pad/masked=dump)
    # cross buckets
    job_sel: tuple  # (D, Jmax_i) row ids into the bucket (pad=0)
    job_tmask: tuple  # (D, Jmax_i, Ut)
    job_smask: tuple  # (D, Jmax_i, Us)
    loff_src: tuple  # local flat source-group offset per bucket
    loff_tgt: tuple
    n_src_loc: int
    n_tgt_loc: int
    src_gather_l: np.ndarray  # (D, Smax) xfull indices (pad=block)
    src_seg_l: np.ndarray  # (D, Smax) local groups (pad=n_src_loc)
    tgt_gather_l: np.ndarray  # (D, Tmax) local target groups (pad=0)
    tgt_scatter_l: np.ndarray  # (D, Tmax) out rows (pad=dump)
    # pivot diagonal corrections
    piv_gather_l: np.ndarray  # (D, Pmax) xfull indices (pad=block)
    piv_scatter_l: np.ndarray  # (D, Pmax) out rows (pad=dump)
    # grid/Hankel engine: per-shard static integer grid indices + global
    # (shard-invariant) transform sizes; None unless the spec is grid-aligned
    hankel_it: tuple | None
    hankel_isrc: tuple | None
    hankel_LM: tuple | None  # of (L_i, Ms_i)

    @property
    def stats(self) -> dict:
        return {"num_shards": self.num_shards, "block": self.block,
                "halo_width": self.halo_width,
                "halo_total": self.halo_total,
                # per-device flat work (padded gather lengths): the
                # weak-scaling gate checks these shrink vs the global plan
                "src_rows": int(self.src_gather_l.shape[1]),
                "tgt_rows": int(self.tgt_gather_l.shape[1]),
                "shard_layout": SHARD_LAYOUT_VERSION}


def _owner(v, block, D):
    return np.minimum(np.asarray(v, np.int64) // block, D - 1)


def _greedy_assign(w, D):
    """LPT scheduling: heaviest item first onto the least-loaded shard.
    Deterministic (stable sort, lowest-index tie-break); near-optimal
    makespan, which is what bounds the padded per-shard table width."""
    import heapq
    w = np.asarray(w, np.int64)
    out = np.zeros(w.size, np.int64)
    if D <= 1 or not w.size:
        return out
    heap = [(0, k) for k in range(D)]
    heapq.heapify(heap)
    for j in np.argsort(-w, kind="stable"):
        load, k = heapq.heappop(heap)
        out[j] = k
        heapq.heappush(heap, (load + int(w[j]), k))
    return out


def partition_plan(spec: PlanSpec, num_shards: int) -> ShardPlan:
    """Split `spec`'s global index space into `num_shards` leaf blocks.

    Pure host-side numpy; memoized on (spec digest, num_shards). Cross jobs
    and leaf rows are load-balanced across shards by their flat entry
    counts (greedy LPT — vertex ids carry no locality, so ownership-based
    placement would pile everything on the low blocks); every remote
    *input* row a shard needs is routed through the exchange table, and the
    partial outputs meet in one exact psum_scatter."""
    key = (spec.digest, int(num_shards))
    hit = _PART_CACHE.get(key)
    if hit is not None:
        return hit
    D = int(num_shards)
    n = spec.n
    block = max(-(-n // D), 1)
    dump = D * block  # scatter row that is dropped before the reduction

    nb = len(spec.cross_src_mask)
    Bs = np.array([m.shape[0] for m in spec.cross_src_mask], np.int64)
    Us = np.array([m.shape[1] for m in spec.cross_src_mask], np.int64)
    Ut = np.array([m.shape[1] for m in spec.cross_tgt_mask], np.int64)
    soff = np.asarray(spec.cross_src_off, np.int64)
    toff = np.asarray(spec.cross_tgt_off, np.int64)
    jbase = np.zeros(nb + 1, np.int64)
    np.cumsum(Bs, out=jbase[1:])
    total_jobs = int(jbase[-1])

    # ---- decompose the global flat entry tables -------------------------
    tg = np.asarray(spec.tgt_gather, np.int64)
    tv = np.asarray(spec.tgt_scatter, np.int64)
    tb = np.searchsorted(toff, tg, side="right") - 1 if tg.size else tg
    trel = tg - toff[tb] if tg.size else tg
    trow = trel // Ut[tb] if tg.size else tg
    tcol = trel - trow * Ut[tb] if tg.size else tg

    sg = np.asarray(spec.src_gather, np.int64)
    ss = np.asarray(spec.src_seg, np.int64)
    sb = np.searchsorted(soff, ss, side="right") - 1 if ss.size else ss
    srel = ss - soff[sb] if ss.size else ss
    srow = srel // Us[sb] if ss.size else ss
    scol = srel - srow * Us[sb] if ss.size else ss

    # ---- assign jobs to shards: greedy balance on flat entry counts -----
    w_job = np.ones(total_jobs, np.int64)  # +1 spreads zero-weight jobs
    if tg.size:
        w_job += np.bincount(jbase[tb] + trow, minlength=total_jobs)
    if sg.size:
        w_job += np.bincount(jbase[sb] + srow, minlength=total_jobs)
    job_shard = _greedy_assign(w_job, D)

    # per-bucket shard membership -> padded (D, Jmax) selections
    job_sel, job_valid, job_slot = [], [], np.zeros(total_jobs, np.int64)
    Jmax = np.zeros(nb, np.int64)
    for i in range(nb):
        shards = job_shard[jbase[i]:jbase[i + 1]]
        counts = np.bincount(shards, minlength=D)
        Jmax[i] = max(int(counts.max()) if counts.size else 0, 1)
        sel = np.zeros((D, Jmax[i]), np.int32)
        val = np.zeros((D, Jmax[i]), bool)
        order = np.argsort(shards, kind="stable")
        slot = np.arange(shards.size) - np.concatenate(
            [[0], np.cumsum(counts)])[shards[order]]
        job_slot[jbase[i] + order] = slot
        sel[shards[order], slot] = order.astype(np.int32)
        val[shards[order], slot] = True
        job_sel.append(sel)
        job_valid.append(val)

    loff_src = np.zeros(nb + 1, np.int64)
    np.cumsum(Jmax * Us, out=loff_src[1:])
    loff_tgt = np.zeros(nb + 1, np.int64)
    np.cumsum(Jmax * Ut, out=loff_tgt[1:])
    n_src_loc = int(loff_src[-1])
    n_tgt_loc = int(loff_tgt[-1])

    # ---- leaf rows: greedy balance on live-entry counts -----------------
    nlb = len(spec.leaf_ids)
    leaf_live, leaf_w = [], []
    for i in range(nlb):
        mask = np.asarray(spec.leaf_mask[i], bool)
        rows = np.flatnonzero(mask.any(axis=1))
        leaf_live.append(rows)
        leaf_w.append(mask[rows].sum(axis=1).astype(np.int64) + 1)
    lsh = _greedy_assign(np.concatenate(leaf_w) if nlb else
                         np.zeros(0, np.int64), D)
    leaf_rows, off = [], 0  # (rows, shard) per leaf bucket
    for rows in leaf_live:
        leaf_rows.append((rows, lsh[off:off + rows.size]))
        off += rows.size

    # ---- halo: remote vertex rows each shard reads ----------------------
    need = [[] for _ in range(D)]  # remote global vertex ids per shard
    if sg.size:
        esh = job_shard[jbase[sb] + srow]
        rem = (sg < n) & (_owner(sg, block, D) != esh)
        for k in range(D):
            m = rem & (esh == k)
            if m.any():
                need[k].append(sg[m])
    for i in range(nlb):
        rows, rs = leaf_rows[i]
        if not rows.size:
            continue
        ids = np.asarray(spec.leaf_ids[i], np.int64)[rows]
        mask = np.asarray(spec.leaf_mask[i], bool)[rows]
        own = _owner(ids, block, D)
        for k in range(D):
            m = mask & (own != k) & (rs[:, None] == k) & (ids < n)
            if m.any():
                need[k].append(ids[m])
    need = [np.unique(np.concatenate(v)) if v else np.zeros(0, np.int64)
            for v in need]
    halo_total = int(sum(v.size for v in need))

    # send lists per (owner j -> shard k); Emax pads the exchange uniform
    send_lists = [[None] * D for _ in range(D)]
    Emax = 0
    for k in range(D):
        own = _owner(need[k], block, D)
        for j in range(D):
            sl = need[k][own == j]
            send_lists[j][k] = sl
            Emax = max(Emax, sl.size)
    send_idx = np.full((D, D, Emax), block, np.int32)
    for j in range(D):
        for k in range(D):
            sl = send_lists[j][k]
            send_idx[j, k, :sl.size] = (sl - j * block).astype(np.int32)

    def xidx(k, vs):
        """xfull indices on shard k for global vertex ids `vs` (pad id n
        and out-of-range -> the zero row)."""
        vs = np.asarray(vs, np.int64)
        res = np.full(vs.shape, block, np.int32)
        pad = vs >= n
        own = _owner(vs, block, D)
        mine = (own == k) & ~pad
        res[mine] = (vs[mine] - k * block).astype(np.int32)
        rem = ~mine & ~pad
        for j in range(D):
            mj = rem & (own == j)
            if mj.any():
                pos = np.searchsorted(send_lists[j][k], vs[mj])
                res[mj] = (block + 1 + j * Emax + pos).astype(np.int32)
        return res

    # ---- per-shard flat source entries ----------------------------------
    if sg.size:
        esh = job_shard[jbase[sb] + srow]
        lseg = loff_src[sb] + job_slot[jbase[sb] + srow] * Us[sb] + scol
        counts = np.bincount(esh, minlength=D)
        Smax = max(int(counts.max()), 1)
        src_gather_l = np.full((D, Smax), block, np.int32)
        src_seg_l = np.full((D, Smax), n_src_loc, np.int32)
        for k in range(D):
            m = esh == k
            src_gather_l[k, :int(m.sum())] = xidx(k, sg[m])
            src_seg_l[k, :int(m.sum())] = lseg[m].astype(np.int32)
    else:
        src_gather_l = np.full((D, 1), block, np.int32)
        src_seg_l = np.full((D, 1), n_src_loc, np.int32)

    # ---- per-shard flat target entries ----------------------------------
    if tg.size:
        esh = job_shard[jbase[tb] + trow]
        lgat = loff_tgt[tb] + job_slot[jbase[tb] + trow] * Ut[tb] + tcol
        lsca = np.where(tv < n, tv, dump)
        counts = np.bincount(esh, minlength=D)
        Tmax = max(int(counts.max()), 1)
        tgt_gather_l = np.zeros((D, Tmax), np.int32)
        tgt_scatter_l = np.full((D, Tmax), dump, np.int32)
        for k in range(D):
            m = esh == k
            tgt_gather_l[k, :int(m.sum())] = lgat[m].astype(np.int32)
            tgt_scatter_l[k, :int(m.sum())] = lsca[m].astype(np.int32)
    else:
        tgt_gather_l = np.zeros((D, 1), np.int32)
        tgt_scatter_l = np.full((D, 1), dump, np.int32)

    # ---- pivots (always owned by their shard) ---------------------------
    piv = np.asarray(spec.pivots, np.int64)
    live_p = piv[piv < n]
    psh = _owner(live_p, block, D)
    counts = np.bincount(psh, minlength=D) if live_p.size else np.zeros(
        D, np.int64)
    Pmax = max(int(counts.max()) if live_p.size else 0, 1)
    piv_gather_l = np.full((D, Pmax), block, np.int32)
    piv_scatter_l = np.full((D, Pmax), dump, np.int32)
    for k in range(D):
        pv = live_p[psh == k]
        piv_gather_l[k, :pv.size] = (pv - k * block).astype(np.int32)
        piv_scatter_l[k, :pv.size] = pv.astype(np.int32)

    # ---- leaf tables ----------------------------------------------------
    leaf_sel, leaf_gather, leaf_mask_sh, leaf_scatter = [], [], [], []
    for i in range(nlb):
        rows, rs = leaf_rows[i]
        ids = np.asarray(spec.leaf_ids[i], np.int64)
        mask = np.asarray(spec.leaf_mask[i], bool)
        K = ids.shape[1]
        counts = np.bincount(rs, minlength=D) if rows.size else np.zeros(
            D, np.int64)
        Rmax = max(int(counts.max()) if rows.size else 0, 1)
        sel = np.zeros((D, Rmax), np.int32)
        gat = np.full((D, Rmax, K), block, np.int32)
        msk = np.zeros((D, Rmax, K), bool)
        sca = np.full((D, Rmax, K), dump, np.int32)
        for k in range(D):
            rk = rows[rs == k]
            sel[k, :rk.size] = rk.astype(np.int32)
            if rk.size:
                gat[k, :rk.size] = xidx(k, ids[rk])
                msk[k, :rk.size] = mask[rk]
                sca[k, :rk.size] = np.where(mask[rk], ids[rk],
                                            dump).astype(np.int32)
        leaf_sel.append(sel)
        leaf_gather.append(gat)
        leaf_mask_sh.append(msk)
        leaf_scatter.append(sca)

    # ---- cross masks (padded job rows keep slot 0 live so the engines'
    # masked reductions stay finite; their outputs are never gathered) ----
    job_tmask, job_smask = [], []
    for i in range(nb):
        tm = np.asarray(spec.cross_tgt_mask[i], bool)[job_sel[i]]
        sm = np.asarray(spec.cross_src_mask[i], bool)[job_sel[i]]
        pad = ~job_valid[i]
        tm[pad] = False
        sm[pad] = False
        tm[pad, 0] = True
        sm[pad, 0] = True
        job_tmask.append(tm)
        job_smask.append(sm)

    # ---- grid/Hankel static integer indices -----------------------------
    hankel_it = hankel_isrc = hankel_LM = None
    if spec.grid_h is not None and not spec.reweightable:
        h = spec.grid_h
        hankel_it, hankel_isrc, hankel_LM = [], [], []
        for i in range(nb):
            it_g = np.rint(np.asarray(spec.cross_tgt_d0[i]) / h).astype(
                np.int64)
            is_g = np.rint(np.asarray(spec.cross_src_d0[i]) / h).astype(
                np.int64)
            Ms = int(is_g.max()) + 1 if is_g.size else 1
            L = (int(it_g.max()) if it_g.size else 0) + Ms
            hankel_it.append(it_g[job_sel[i]].astype(np.int32))
            hankel_isrc.append(is_g[job_sel[i]].astype(np.int32))
            hankel_LM.append((L, Ms))
        hankel_it = tuple(hankel_it)
        hankel_isrc = tuple(hankel_isrc)
        hankel_LM = tuple(hankel_LM)

    sp = ShardPlan(
        num_shards=D, block=block, halo_width=int(Emax),
        halo_total=halo_total, send_idx=send_idx,
        leaf_sel=tuple(leaf_sel), leaf_gather=tuple(leaf_gather),
        leaf_mask=tuple(leaf_mask_sh), leaf_scatter=tuple(leaf_scatter),
        job_sel=tuple(job_sel), job_tmask=tuple(job_tmask),
        job_smask=tuple(job_smask),
        loff_src=tuple(int(o) for o in loff_src[:-1]),
        loff_tgt=tuple(int(o) for o in loff_tgt[:-1]),
        n_src_loc=n_src_loc, n_tgt_loc=n_tgt_loc,
        src_gather_l=src_gather_l, src_seg_l=src_seg_l,
        tgt_gather_l=tgt_gather_l, tgt_scatter_l=tgt_scatter_l,
        piv_gather_l=piv_gather_l, piv_scatter_l=piv_scatter_l,
        hankel_it=hankel_it, hankel_isrc=hankel_isrc, hankel_LM=hankel_LM)
    _PART_CACHE.put(key, sp)
    return sp


# ----------------------------------------------------------------------------
# sharded cross engine for the grid/Hankel path (traced integer indices)
# ----------------------------------------------------------------------------


def _hankel_sharded(fn_eval, h, it, isrc, Xp, L, Ms):
    """`plan_api.hankel_batched_matvec` with *traced* per-shard integer grid
    indices; the transform sizes (L, Ms) are global and static, so the same
    SPMD program runs on every device."""
    F = fn_eval(h * jnp.arange(L, dtype=Xp.dtype))
    B, Us, d = Xp.shape
    bidx = jnp.arange(B)[:, None]
    Pm = jnp.zeros((B, Ms, d), Xp.dtype).at[bidx, isrc].add(Xp)
    nfft = 1 << int(np.ceil(np.log2(max(L + Ms, 2))))
    Ff = jnp.fft.rfft(F, n=nfft)
    Pf = jnp.fft.rfft(Pm[:, ::-1], n=nfft, axis=1)
    full = jnp.fft.irfft(Ff[None, :, None] * Pf, n=nfft, axis=1)
    out_full = full[:, Ms - 1:Ms - 1 + L]
    return jnp.take_along_axis(out_full, it[:, :, None], axis=1)


# ----------------------------------------------------------------------------
# the shard_map executor
# ----------------------------------------------------------------------------


def _plan_axis(mesh):
    from repro.launch import sharding

    return sharding.plan_axis(mesh)


def check_mesh(spec: PlanSpec, mesh) -> None:
    """Reject a sharded artifact on a mismatched mesh with a clear error
    (instead of a gather-time crash deep inside the executor)."""
    from repro.core.plan_guard import PlanValidationError

    if getattr(spec, "shard_layout", 0) > SHARD_LAYOUT_VERSION:
        raise PlanValidationError(
            f"plan artifact uses shard layout v{spec.shard_layout}, this "
            f"codebase supports <= v{SHARD_LAYOUT_VERSION}")
    nd = getattr(spec, "mesh_devices", 0)
    if nd and mesh is not None and mesh.devices.size != nd:
        raise PlanValidationError(
            f"sharded plan artifact was laid out for {nd} devices "
            f"(axes {tuple(getattr(spec, 'mesh_axes', ()) or ())}), but the "
            f"target mesh has {mesh.devices.size} devices "
            f"(axes {tuple(mesh.axis_names)}); re-save the artifact on the "
            f"serving mesh or pass a matching mesh")


def _execute_sharded(spec, sp: ShardPlan, params: PlanParams, fn_eval,
                     cross_multiply, use_hankel, X, mesh, axis):
    X = jnp.asarray(X)
    squeeze = X.ndim == 1
    if squeeze:
        X = X[:, None]
    d = X.shape[1]
    D, block, Emax = sp.num_shards, sp.block, sp.halo_width
    nb = len(sp.job_sel)
    nlb = len(sp.leaf_sel)
    Us = [m.shape[1] for m in spec.cross_src_mask]
    Ut = [m.shape[1] for m in spec.cross_tgt_mask]
    dump = D * block

    Xg = jnp.zeros((dump, d), X.dtype).at[:spec.n].set(X)
    ops = {
        "x": Xg,
        "send": sp.send_idx,
        "sgl": sp.src_gather_l, "ssl": sp.src_seg_l,
        "tgl": sp.tgt_gather_l, "tsl": sp.tgt_scatter_l,
        "pvg": sp.piv_gather_l, "pvs": sp.piv_scatter_l,
        # per-shard slices of the dynamic distances: a row-gather on the
        # (replicated) params, stacked along the shard axis
        "leaf_d": tuple(params.leaf_dists[i][sp.leaf_sel[i]]
                        for i in range(nlb)),
        "leaf_g": sp.leaf_gather, "leaf_m": sp.leaf_mask,
        "leaf_s": sp.leaf_scatter,
        "tgt_d": tuple(params.cross_tgt_d[i][sp.job_sel[i]]
                       for i in range(nb)),
        "src_d": tuple(params.cross_src_d[i][sp.job_sel[i]]
                       for i in range(nb)),
        "tmask": sp.job_tmask, "smask": sp.job_smask,
    }
    if use_hankel:
        ops["h_it"] = sp.hankel_it
        ops["h_isrc"] = sp.hankel_isrc
    in_specs = jax.tree.map(lambda a: P(axis), ops)

    def local_fn(o):
        x = o["x"]  # (block, d)
        xl = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
        if Emax:
            send = xl[o["send"][0]]  # (D, Emax, d)
            recv = jax.lax.all_to_all(send, axis, 0, 0)
            xfull = jnp.concatenate([xl, recv.reshape(D * Emax, d)], axis=0)
        else:
            xfull = xl
        outp = jnp.zeros((dump + 1, d), x.dtype)

        for i in range(nlb):
            m = jnp.asarray(o["leaf_m"][i][0])
            Xl = xfull[o["leaf_g"][i][0]]  # (Rmax, K, d)
            M = fn_eval(o["leaf_d"][i][0])
            pm = m[:, :, None] & m[:, None, :]
            M = jnp.where(pm, M, 0.0)
            contrib = jnp.einsum("bij,bjd->bid", M, Xl, precision=EXACT)
            outp = outp.at[o["leaf_s"][i][0]].add(contrib * m[:, :, None])

        if sp.n_src_loc:
            Xp_loc = jax.ops.segment_sum(
                xfull[o["sgl"][0]], o["ssl"][0],
                num_segments=sp.n_src_loc + 1)[:-1]
            parts = []
            for i in range(nb):
                J = sp.job_sel[i].shape[1]
                off = sp.loff_src[i]
                Xp = Xp_loc[off:off + J * Us[i]].reshape(J, Us[i], d)
                if use_hankel:
                    L_i, Ms_i = sp.hankel_LM[i]
                    res = _hankel_sharded(fn_eval, spec.grid_h,
                                          o["h_it"][i][0], o["h_isrc"][i][0],
                                          Xp, L_i, Ms_i)
                else:
                    res = cross_multiply(
                        i, o["tgt_d"][i][0], jnp.asarray(o["tmask"][i][0]),
                        o["src_d"][i][0], jnp.asarray(o["smask"][i][0]), Xp)
                parts.append(res.reshape(J * Ut[i], d))
            cflat = (jnp.concatenate(parts, axis=0) if len(parts) > 1
                     else parts[0])
            outp = outp.at[o["tsl"][0]].add(cflat[o["tgl"][0]])

        f0 = fn_eval(jnp.zeros((1,), x.dtype))[0]
        outp = outp.at[o["pvs"][0]].add(-f0 * xfull[o["pvg"][0]])
        # exact meeting point of all cross-shard contributions
        return jax.lax.psum_scatter(outp[:-1], axis, scatter_dimension=0,
                                    tiled=True)

    out = jax.shard_map(local_fn, mesh=mesh, in_specs=(in_specs,),
                        out_specs=P(axis), check_vma=False)(ops)
    res = out[:spec.n]
    if params.tree_w is not None:
        w = jnp.repeat(jnp.asarray(params.tree_w),
                       np.asarray(spec.tree_sizes, np.int64),
                       total_repeat_length=spec.n)
        res = res * w[:, None].astype(res.dtype)
    return res[:, 0] if squeeze else res


def apply_sharded(spec: PlanSpec, params: PlanParams, fn, X, *,
                  mesh=None, axis: str | None = None, backend: str = "plan",
                  degree: int = 32, pallas_opts: dict | None = None):
    """Multi-device `plan_api.apply`: Y = M_f X with the plan's index space
    partitioned into per-device leaf blocks under shard_map.

    `mesh` defaults to the active `launch.sharding.use_sharding` mesh;
    `axis` to the mesh axis bound to the `plan_leaves` logical axis (the
    `data` axis on the standard meshes). Exact: halo rows move through one
    all_to_all, partial outputs through one psum_scatter — parity with the
    single-device executor is float round-off only. Differentiable in
    `params` and `X` like `apply`."""
    from repro.launch import sharding

    if mesh is None:
        mesh = sharding.current_mesh()
    if mesh is None:
        raise ValueError(
            "apply_sharded needs a mesh: pass mesh=... or call under "
            "launch.sharding.use_sharding(mesh)")
    check_mesh(spec, mesh)
    if axis is None:
        axis = _plan_axis(mesh)
    D = int(mesh.shape[axis])
    sp = partition_plan(spec, D)
    fspec = _fspec(fn)
    name, cross = select_cross(spec, fspec, backend=backend, degree=degree,
                               pallas_opts=pallas_opts)
    use_hankel = name == "hankel_fft"
    if use_hankel and sp.hankel_it is None:  # pragma: no cover - guard
        raise ValueError("grid engine selected but shard plan lacks grid "
                         "tables")
    return _execute_sharded(spec, sp, params, fspec.fn_eval, cross,
                            use_hankel, X, mesh, axis)


def sharded_fastmult(spec: PlanSpec, fn, *, mesh, axis: str | None = None,
                     backend: str = "plan", degree: int = 32,
                     pallas_opts: dict | None = None):
    """Jittable (params, X) -> Y closure over `apply_sharded` with the mesh
    and engine choice baked in (the sharded face of `plan_api.fastmult`)."""

    def fm(params, X):
        if isinstance(X, jax.core.Tracer):
            from repro.analysis import trace_guard

            trace_guard.record("ftfi.sharded_fastmult",
                               detail=spec.digest[:12])
        return apply_sharded(spec, params, fn, X, mesh=mesh, axis=axis,
                             backend=backend, degree=degree,
                             pallas_opts=pallas_opts)

    return fm


def shard_stats(spec: PlanSpec, num_shards: int) -> dict:
    """Partition diagnostics: per-device block size, halo width/total (the
    halo-exchange cost model's inputs: one all_to_all moves
    `num_shards * halo_width` rows per device)."""
    return partition_plan(spec, num_shards).stats
