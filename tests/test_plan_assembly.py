"""Vectorized plan assembly vs the per-node loop oracle.

`_assemble_plan` (level-wide numpy array ops over the FlatIT) must emit
`PlanSpec` fields BITWISE identical to `_assemble_plan_ref` (the original
per-internal-node Python loop, kept here as the oracle): same buckets in the
same order, same padded arrays, same flat gather/segment/scatter plans,
same update tables. The battery sweeps topologies x leaf sizes x
expand_groups and the fused forest path.
"""
import hashlib

import numpy as np
import pytest

from repro.core.integrate import (_assemble_plan, _side_job_arrays,
                                  _upd_tables)
from repro.core.itree_flat import build_flat_forest, build_flat_it
from repro.core.plan_api import PlanSpec, _birth_params
from repro.graphs.graph import (Forest, WeightedTree, caterpillar_tree,
                                path_graph, random_tree, star_tree)


def _assemble_plan_ref(flat, n: int, detect_grid_spacing: bool,
                       expand_groups: bool = False) -> dict:
    """Reference (per-node Python loop) plan assembly, emitting the same
    `PlanSpec` fields as `_assemble_plan`. The oracle the vectorized
    assembly is tested bitwise-equal against."""
    # one job per (node, direction): targets/sources both exclude the pivot
    # (masked-source optimization); distance arrays keep the pivot group 0
    jobs = []
    for i in range(flat.num_internal):
        L, R = flat.left[i], flat.right[i]
        piv = int(L.ids[0])
        for t, s in ((L, R), (R, L)):
            t_ids, t_idd, t_d, t_rep = _side_job_arrays(t, expand_groups)
            s_ids, s_idd, s_d, s_rep = _side_job_arrays(s, expand_groups)
            jobs.append((t_ids[1:], t_idd[1:], t_d, s_ids[1:], s_idd[1:],
                         s_d, t_rep, s_rep, piv))

    # --- bucket cross jobs by ceil(log2(max dim)) => <=2x padding waste
    def bkey(job):
        m = max(job[0].size, job[3].size, 2)
        return int(np.ceil(np.log2(m)))

    buckets: dict[int, list] = {}
    for ji, job in enumerate(jobs):
        buckets.setdefault(bkey(job), []).append((ji, job))

    job_bucket = np.zeros(len(jobs), np.int32)
    job_row = np.zeros(len(jobs), np.int32)
    cb = {key: [] for key in ("tgt_d", "tgt_mask", "src_d", "src_mask",
                              "src_off", "tgt_off", "piv", "tgt_rep",
                              "src_rep")}
    src_gather_parts, src_seg_parts = [], []
    tgt_gather_parts, tgt_scatter_parts = [], []
    src_goff = tgt_goff = 0
    for bi, key_b in enumerate(sorted(buckets)):
        bjobs = buckets[key_b]
        Ut = max(j[2].size for _, j in bjobs)
        Us = max(j[5].size for _, j in bjobs)
        B = len(bjobs)
        tgt_d = np.zeros((B, Ut), dtype=np.float64)
        tgt_mask = np.zeros((B, Ut), dtype=bool)
        src_d = np.zeros((B, Us), dtype=np.float64)
        src_mask = np.zeros((B, Us), dtype=bool)
        piv_b = np.zeros(B, dtype=np.int32)
        tgt_rep = np.zeros((B, Ut), dtype=np.int32)
        src_rep = np.zeros((B, Us), dtype=np.int32)
        for b, (ji, (t_ids, t_idd, t_d, s_ids, s_idd, s_d, t_rep, s_rep,
                     piv)) in enumerate(bjobs):
            job_bucket[ji] = bi
            job_row[ji] = b
            tgt_d[b, :t_d.size] = t_d
            tgt_mask[b, :t_d.size] = True
            src_d[b, :s_d.size] = s_d
            src_mask[b, :s_d.size] = True
            if expand_groups:
                piv_b[b] = piv
                tgt_rep[b, :] = piv  # padding: dist(piv, piv) == 0
                tgt_rep[b, :t_rep.size] = t_rep
                src_rep[b, :] = piv
                src_rep[b, :s_rep.size] = s_rep
            src_gather_parts.append(s_ids)
            src_seg_parts.append(src_goff + b * Us + s_idd)
            tgt_gather_parts.append(tgt_goff + b * Ut + t_idd)
            tgt_scatter_parts.append(t_ids)
        for key, val in (("tgt_d", tgt_d), ("tgt_mask", tgt_mask),
                         ("src_d", src_d), ("src_mask", src_mask),
                         ("src_off", src_goff), ("tgt_off", tgt_goff),
                         ("piv", piv_b), ("tgt_rep", tgt_rep),
                         ("src_rep", src_rep)):
            cb[key].append(val)
        src_goff += B * Us
        tgt_goff += B * Ut

    def _cat(parts, dtype):
        return (np.concatenate(parts).astype(dtype) if parts
                else np.zeros(0, dtype))

    # --- leaf buckets by ceil(log2(k)): a mixed-size forest pads each leaf
    # to its size class, not to the global maximum (K^2 padding waste would
    # dominate leaf-heavy forest plans)
    leaf_groups: dict[int, list] = {}
    for li, (ids, D) in enumerate(zip(flat.leaf_ids, flat.leaf_dists)):
        leaf_groups.setdefault(
            int(np.ceil(np.log2(max(ids.size, 2)))), []).append((li, ids, D))
    leaf_bucket = np.zeros(len(flat.leaf_ids), np.int32)
    leaf_row = np.zeros(len(flat.leaf_ids), np.int32)
    leaf_ids, leaf_mask, leaf_dists = [], [], []
    for bi, key_b in enumerate(sorted(leaf_groups)):
        leaves = leaf_groups[key_b]
        K = max(ids.size for _, ids, _ in leaves)
        B = len(leaves)
        lids = np.full((B, K), n, dtype=np.int32)
        lmask = np.zeros((B, K), dtype=bool)
        ldists = np.zeros((B, K, K), dtype=np.float64)
        for b, (li, ids, D) in enumerate(leaves):
            leaf_bucket[li] = bi
            leaf_row[li] = b
            k = ids.size
            lids[b, :k] = ids
            lmask[b, :k] = True
            ldists[b, :k, :k] = D
        leaf_ids.append(lids)
        leaf_mask.append(lmask)
        leaf_dists.append(ldists)

    h = None
    if detect_grid_spacing:
        from repro.core.cordial import detect_grid
        # one detection over the merged distances reconciles per-tree grids:
        # the common h of a forest is the gcd of its trees' spacings (None if
        # any tree is off-grid or the joint span is FFT-impractical)
        all_d = np.unique(np.concatenate(
            [s.d for i in range(flat.num_internal)
             for s in (flat.left[i], flat.right[i])] or [np.zeros(1)]))
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
        src_gather=_cat(src_gather_parts, np.int32),
        src_seg=_cat(src_seg_parts, np.int32),
        n_src_groups=src_goff,
        tgt_gather=_cat(tgt_gather_parts, np.int32),
        tgt_scatter=_cat(tgt_scatter_parts, np.int32),
        n_tgt_groups=tgt_goff,
        num_cross_jobs=len(jobs),
        **rep,
        **_upd_tables(flat, job_bucket, job_row, leaf_bucket, leaf_row))


def _mix(h, x):
    if x is None:
        h.update(b"\x00none")
    elif isinstance(x, np.ndarray):
        a = np.ascontiguousarray(x)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    elif isinstance(x, dict):
        for k in sorted(x):
            h.update(str(k).encode())
            _mix(h, x[k])
    elif isinstance(x, (list, tuple)):
        h.update(f"[{len(x)}".encode())
        for v in x:
            _mix(h, v)
    else:
        h.update(repr(x).encode())


def plan_digest(fields: dict) -> str:
    """Content hash over EVERY emitted `PlanSpec` field (bucket tuples,
    flat index arrays, grid_h, rep and update tables), with dtypes and
    shapes."""
    h = hashlib.sha1()
    _mix(h, fields)
    return h.hexdigest()


def _trees():
    cases = [
        ("path12", path_graph(12)),
        ("path100", path_graph(100)),
        ("star40", star_tree(40, seed=3)),
        ("caterpillar64", caterpillar_tree(64, seed=1)),
        ("two", WeightedTree(2, [0], [1], [0.5])),
    ]
    cases += [(f"random{n}s{s}", random_tree(n, seed=s))
              for n, s in ((30, 0), (77, 1), (128, 2), (200, 5))]
    return cases


@pytest.mark.parametrize("leaf_size", [4, 8, 64])
@pytest.mark.parametrize("expand_groups", [False, True])
def test_vectorized_assembly_bitwise_equals_oracle(leaf_size, expand_groups):
    for name, tree in _trees():
        flat = build_flat_it(tree, leaf_size=leaf_size, use_cache=False)
        ref = _assemble_plan_ref(flat, tree.num_vertices,
                                 detect_grid_spacing=not expand_groups,
                                 expand_groups=expand_groups)
        got = _assemble_plan(flat, tree.num_vertices,
                             detect_grid_spacing=not expand_groups,
                             expand_groups=expand_groups)
        assert plan_digest(got) == plan_digest(ref), (
            f"{name}: vectorized assembly diverges from the loop oracle "
            f"(leaf_size={leaf_size}, expand_groups={expand_groups})")


@pytest.mark.parametrize("expand_groups", [False, True])
def test_forest_assembly_bitwise_equals_oracle(expand_groups):
    rng = np.random.default_rng(4)
    trees = [random_tree(int(s), seed=i)
             for i, s in enumerate(rng.integers(6, 40, size=9))]
    trees.append(path_graph(25))
    n = sum(t.num_vertices for t in trees)
    flat = build_flat_forest(trees, leaf_size=8, use_cache=False)
    ref = _assemble_plan_ref(flat, n, detect_grid_spacing=not expand_groups,
                             expand_groups=expand_groups)
    got = _assemble_plan(flat, n, detect_grid_spacing=not expand_groups,
                         expand_groups=expand_groups)
    assert plan_digest(got) == plan_digest(ref)


def test_update_tables_shapes_and_consistency():
    """The upd tables must index every cross job and leaf: job j lives at
    (job_bucket[j], job_row[j]) with matching pivot, and the IT skeleton's
    refs cover exactly the internal nodes + leaves."""
    tree = random_tree(90, seed=7)
    flat = build_flat_it(tree, leaf_size=8, use_cache=False)
    upd = _assemble_plan(flat, 90, detect_grid_spacing=False,
                         expand_groups=True)
    I = upd["pivots"].shape[0]
    assert upd["children"].shape == (I, 2)
    assert upd["job_bucket"].shape == (2 * I,)
    assert upd["job_row"].shape == (2 * I,)
    assert upd["leaf_bucket"].shape == (flat.num_leaves,)
    for j in range(2 * I):
        bi, row = int(upd["job_bucket"][j]), int(upd["job_row"][j])
        assert 0 <= row < upd["cross_tgt_d0"][bi].shape[0]
        assert int(upd["cross_piv"][bi][row]) == int(upd["pivots"][j // 2])
    for li in range(flat.num_leaves):
        bi, row = int(upd["leaf_bucket"][li]), int(upd["leaf_row"][li])
        assert 0 <= row < upd["leaf_ids"][bi].shape[0]
    # the skeleton reaches every internal node and every leaf exactly once
    seen_nodes, seen_leaves = set(), set()
    stack = list(upd["root_refs"])
    while stack:
        ref = int(stack.pop())
        if ref < 0:
            seen_leaves.add(-ref - 1)
        else:
            assert ref not in seen_nodes
            seen_nodes.add(ref)
            stack += [int(upd["children"][ref, 0]),
                      int(upd["children"][ref, 1])]
    assert seen_nodes == set(range(I))
    assert seen_leaves == set(range(flat.num_leaves))


def test_assembly_plans_execute_identically():
    """Belt and braces on top of the digest: both assemblies make the same
    `PlanSpec` (content digest) and integrate to the same output through
    the real executor."""
    from repro.core import plan_api
    from repro.core.cordial import Exponential

    tree = random_tree(64, seed=9)
    flat = build_flat_it(tree, leaf_size=8, use_cache=False)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 3)).astype(np.float32)
    outs, digests = [], []
    for assemble in (_assemble_plan_ref, _assemble_plan):
        spec = PlanSpec(num_trees=1, tree_sizes=(64,), leaf_size=8, seed=0,
                        fingerprint="", reweightable=False,
                        **assemble(flat, 64, detect_grid_spacing=True))
        params = _birth_params(spec)
        digests.append(spec.digest)
        outs.append(np.asarray(plan_api.apply(
            spec, params, Exponential(-0.5), X)))
    assert digests[0] == digests[1]
    np.testing.assert_array_equal(outs[0], outs[1])
