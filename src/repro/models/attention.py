"""Attention variants: full softmax (GQA/MQA/local/MLA), Performer (FAVOR+
deterministic phi), and Topological Performer — the paper's technique
(Sec 4.4 / Alg. 1) as a first-class option.

Sequence topological masks are f(|i-j|) with f = g(sum_t a_t x^t):
  - train/prefill: exact — separable decay path (g=exp, t<=1) or the
    Toeplitz-FFT Algorithm-1 path (any g, t) chunked over feature columns;
  - decode: O(1)-state cordial recurrences; non-separable f uses a Chebyshev
    rank-R separable expansion (spectral accuracy) — beyond-paper (DESIGN §3).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.sharding import shard, shard_q_heads
from repro.models.layers import apply_rope, dense_init, rms_norm, softcap


# ----------------------------------------------------------------------------
# params
# ----------------------------------------------------------------------------


def attn_init(key, cfg, dtype=jnp.float32):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, KV * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, KV * hd), dtype=dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    return p


def mla_init(key, cfg, dtype=jnp.float32):
    d, H = cfg.d_model, cfg.num_heads
    nope, rope, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    ks = jax.random.split(key, 9)
    p = {
        "w_dkv": dense_init(ks[0], (d, r_kv), dtype=dtype),
        "kv_norm": jnp.zeros((r_kv,), dtype),
        "w_ukv": dense_init(ks[1], (r_kv, H * (nope + vdim)), dtype=dtype),
        "w_kr": dense_init(ks[2], (d, rope), dtype=dtype),
        "wo": dense_init(ks[3], (H * vdim, d), dtype=dtype),
    }
    if r_q > 0:
        p["w_dq"] = dense_init(ks[4], (d, r_q), dtype=dtype)
        p["q_norm"] = jnp.zeros((r_q,), dtype)
        p["w_uq"] = dense_init(ks[5], (r_q, H * (nope + rope)), dtype=dtype)
    else:
        p["wq"] = dense_init(ks[6], (d, H * (nope + rope)), dtype=dtype)
    return p


def topo_init(key, cfg, dtype=jnp.float32):
    """3 learnable scalars (synced) or 3/head (asynced): [a_0..a_t] + scale."""
    t = cfg.topo_degree
    lead = () if cfg.topo_synced else (cfg.num_heads,)
    coeffs = np.zeros(lead + (t + 1,), dtype=np.float32)
    coeffs[..., 0] = 0.0
    if t >= 1:
        coeffs[..., 1] = -1.0  # init: decaying mask
    return {"coeffs": jnp.asarray(coeffs, dtype),
            "logit_scale": jnp.zeros(lead, dtype)}


# ----------------------------------------------------------------------------
# full softmax attention (GQA / MQA; optional local window)
# ----------------------------------------------------------------------------


def _positions_vec(pos, B):
    """Decode positions as a (B,) int32 vector. A scalar () broadcasts to the
    whole batch (lockstep decode); a (B,) vector passes through unchanged —
    per-slot positions are what make mid-wave admission legal in the serve
    engine (each slot writes/masks its own KV row independently)."""
    p = jnp.asarray(pos, jnp.int32)
    if p.ndim == 0:
        p = jnp.broadcast_to(p, (B,))
    return p


def _rope(cfg, x, positions):
    """RoPE on the leading `cfg.rotary_dim` dims of each head (all of them
    where it is 0); the rest pass through."""
    rd = getattr(cfg, "rotary_dim", 0) or x.shape[-1]
    if rd == x.shape[-1]:
        return apply_rope(x, positions, cfg.rope_theta)
    return jnp.concatenate([apply_rope(x[..., :rd], positions, cfg.rope_theta),
                            x[..., rd:]], axis=-1)


def _project_qkv(cfg, p, x, positions, rope=True):
    B, L, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, L, H, hd)
    k = k.reshape(B, L, KV, hd)
    v = v.reshape(B, L, KV, hd)
    if rope:
        q = _rope(cfg, q, positions)
        k = _rope(cfg, k, positions)
    q = shard_q_heads(q)
    k = shard(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = shard(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def _sdpa(cfg, q, k, v, mask):
    """q: (B,Lq,H,hd); k,v: (B,Lk,KV,hd); mask: (1|B, 1, Lq, Lk) bool."""
    B, Lq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Lq, KV, G, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    logits = softcap(logits, cfg.attn_logit_softcap)
    logits = jnp.where(mask[:, :, None], logits, -1e30)  # mask: (B,1,Lq,Lk)->(B,1,1,Lq,Lk)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v.dtype), v)
    return out.reshape(B, Lq, H, hd)


def _sdpa_chunked(cfg, q, k, v, causal: bool, window: int, blk: int = 512):
    """Flash-style attention in plain XLA: `lax.map` over query blocks, each
    an online softmax (fp32 statistics) over a `lax.scan` of key blocks.
    A key block wholly after the query block (causal) or outside its window
    is skipped by a `cond`, so causal work is triangular; the (Lq, Lk) score
    matrix never exists (peak temp O(blk^2)). Matmul inputs keep their
    dtype and accumulate in fp32. Differentiable. Query and key positions
    are taken as aranges from 0. This is the dry-run/CPU twin of
    kernels/flash_attention (Pallas is the TPU hot path); selected via
    cfg.attn_impl == 'chunked'."""
    B, Lq, H, hd = q.shape
    Lk = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    vd = v.shape[-1]  # may differ from hd (MLA: qk 192, v 128)
    bk = min(blk, Lk)
    if Lk % bk:  # fall back when blocks don't tile
        return None
    bq = min(blk, Lq)
    if Lq % bq:
        bq = Lq
    nk, nq = Lk // bk, Lq // bq
    scale = 1.0 / math.sqrt(hd)
    qb = q.reshape(B, nq, bq, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(B, nk, bk, KV, hd).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nk, bk, KV, vd).transpose(1, 0, 3, 2, 4)

    def qblock(qi_i):
        qi, i = qi_i  # (B, KV, G, bq, hd)
        qpos = i * bq + jnp.arange(bq)

        def attend(carry, kc, vc, j):
            m, l, acc = carry
            s = jnp.einsum("bkgqh,bksh->bkgqs", qi, kc,
                           preferred_element_type=jnp.float32) * scale
            s = softcap(s, cfg.attn_logit_softcap)
            kpos = j * bk + jnp.arange(bk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask = mask & (qpos[:, None] >= kpos[None, :])
            if window and window > 0:
                mask = mask & (qpos[:, None] - kpos[None, :] < window)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            pexp = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bksh->bkgqh", pexp.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return m_new, l * corr + jnp.sum(pexp, axis=-1), acc

        def step(carry, inp):
            kc, vc, j = inp
            live = True
            if causal:
                live = j * bk <= i * bq + bq - 1
            if window and window > 0:
                live = live & (i * bq - (j * bk + bk - 1) < window)
            if live is True:
                return attend(carry, kc, vc, j), ()
            return jax.lax.cond(live, lambda c: attend(c, kc, vc, j),
                                lambda c: c, carry), ()

        m0 = jnp.full((B, KV, G, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, vd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      (kb, vb, jnp.arange(nk)))
        l = jnp.where(l == 0.0, 1.0, l)
        return acc / l[..., None]

    out = jax.lax.map(qblock, (qb, jnp.arange(nq)))  # (nq,B,KV,G,bq,vd)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Lq, H, vd).astype(
        q.dtype)


def full_attention_train(cfg, p, x, positions, causal=True, window=0,
                         rope=True, kv_x=None, kv_positions=None):
    """Training/prefill attention; kv_x enables cross-attention."""
    B, L, _ = x.shape
    if kv_x is None:
        q, k, v = _project_qkv(cfg, p, x, positions, rope=rope)
        Lk = L
        kpos = positions
    else:
        q, _, _ = _project_qkv(cfg, p, x, positions, rope=rope)  # reuse wq
        # cross: keys/values from encoder memory
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        k = (kv_x @ p["wk"]).reshape(kv_x.shape[0], kv_x.shape[1], KV, hd)
        v = (kv_x @ p["wv"]).reshape(kv_x.shape[0], kv_x.shape[1], KV, hd)
        Lk = kv_x.shape[1]
        kpos = kv_positions
    if getattr(cfg, "attn_impl", "naive") == "chunked":
        # positions are contiguous aranges at every call site, so the
        # chunked path's internally-derived masks are equivalent
        out = _sdpa_chunked(cfg, q, k, v, causal, window)
        if out is not None:
            return out.reshape(x.shape[0], L, -1) @ p["wo"]
    qi = positions[..., :, None] if positions.ndim > 1 else positions[:, None]
    ki = (kpos[..., None, :] if kpos.ndim > 1 else kpos[None, :])
    mask = jnp.ones((1, L, Lk), bool)
    if causal:
        mask = mask & (qi >= ki)
    if window and window > 0:
        mask = mask & (qi - ki < window)
    mask = jnp.broadcast_to(mask, (x.shape[0],) + mask.shape[1:]) if mask.shape[0] != x.shape[0] else mask
    out = _sdpa(cfg, q, k, v, mask[:, None] if mask.ndim == 3 else mask)
    B_, Lq, H, hd = out.shape
    return out.reshape(B_, Lq, H * hd) @ p["wo"]


def full_attention_decode(cfg, p, x, pos, cache, window=0, rope=True):
    """One-token decode. cache: {'k','v'} (B,S,KV,hd); pos: () or (B,) int32
    (per-slot positions — each batch row writes and masks its own row)."""
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos_v = _positions_vec(pos, B)
    positions = pos_v[:, None]
    q, k_new, v_new = _project_qkv(cfg, p, x, positions, rope=rope)
    S = cache["k"].shape[1]
    rows = jnp.arange(B)
    k = cache["k"].at[rows, pos_v].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[rows, pos_v].set(v_new[:, 0].astype(cache["v"].dtype))
    idx = jnp.arange(S)
    mask = idx[None, None, :] <= pos_v[:, None, None]  # (B,1,S)
    if window and window > 0:
        mask = mask & (idx[None, None, :] > pos_v[:, None, None] - window)
    out = _sdpa(cfg, q, k, v, mask[:, None])
    out = out.reshape(B, 1, H * hd) @ p["wo"]
    return out, {"k": k, "v": v}


def full_attention_prefill(cfg, p, x, positions, lengths, cache,
                           window=0, rope=True):
    """Whole-prompt prefill that writes KV rows [0, Lp) straight into the
    decode cache (the fused replacement for replaying prompt tokens through
    decode). x: (B, Lp, d); lengths: (B,) — rows with lengths[b] == 0 keep
    their cache untouched (they belong to other live slots). Rows at or past
    lengths[b] may hold junk keys: decode at position q rewrites row q before
    its own causal mask can see it, so they are always overwritten-before-
    read. Returns (out (B, Lp, d), new_cache)."""
    B, Lp, _ = x.shape
    q, k_new, v_new = _project_qkv(cfg, p, x, positions, rope=rope)
    out = None
    if getattr(cfg, "attn_impl", "naive") == "chunked":
        out = _sdpa_chunked(cfg, q, k_new, v_new, True, window)
    if out is None:
        idx = jnp.arange(Lp)
        mask = (idx[:, None] >= idx[None, :])[None]  # causal (1,Lp,Lp)
        if window and window > 0:
            mask = mask & (idx[None, :, None] - idx[None, None, :] < window)
        out = _sdpa(cfg, q, k_new, v_new, mask[:, None])
    out = out.reshape(B, Lp, -1) @ p["wo"]
    valid = (lengths > 0)[:, None, None, None]
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), 0, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), 0, axis=1)
    return out, {"k": jnp.where(valid, k, cache["k"]),
                 "v": jnp.where(valid, v, cache["v"])}


def local_attention_decode_init(cfg, B, dtype):
    W = cfg.local_window
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((B, W, KV, hd), dtype),
            "v": jnp.zeros((B, W, KV, hd), dtype),
            "kpos": jnp.full((B, W), -1, jnp.int32)}


def local_attention_decode(cfg, p, x, pos, cache):
    """Sliding-window decode with a per-slot ring buffer of size W (positions
    stored alongside keys; RoPE applied at write time with the true
    position). pos: () or (B,) int32."""
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    W = cfg.local_window
    pos_v = _positions_vec(pos, B)
    positions = pos_v[:, None]
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    slot = jnp.mod(pos_v, W)
    rows = jnp.arange(B)
    k = cache["k"].at[rows, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[rows, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    kpos = cache["kpos"].at[rows, slot].set(pos_v)
    mask = (kpos >= 0) & (kpos <= pos_v[:, None])  # ring enforces the window
    out = _sdpa(cfg, q, k, v, mask[:, None, None, :])
    out = out.reshape(B, 1, H * hd) @ p["wo"]
    return out, {"k": k, "v": v, "kpos": kpos}


def local_attention_prefill(cfg, p, x, positions, lengths, cache):
    """Fused prefill for the sliding-window ring buffer: attention over the
    prompt with the window mask, then the last min(W, lengths[b]) tokens of
    each valid row are scattered into their ring slots (position p lives at
    p % W) with kpos = -1 everywhere else. Unlike the (B, S) cache, junk
    rows here WOULD be visible to later decode steps, so the ring is built
    explicitly from valid tokens only."""
    B, Lp, _ = x.shape
    W = cfg.local_window
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    idx = jnp.arange(Lp)
    mask = ((idx[:, None] >= idx[None, :])
            & (idx[:, None] - idx[None, :] < W))[None]
    out = _sdpa(cfg, q, k_new, v_new, mask[:, None])
    out = out.reshape(B, Lp, -1) @ p["wo"]
    widx = lengths[:, None] - W + jnp.arange(W)[None, :]  # (B, W) positions
    valid_w = (widx >= 0) & (lengths[:, None] > 0)
    gidx = jnp.clip(widx, 0, max(Lp - 1, 0))
    rows = jnp.arange(B)[:, None]
    kg = jnp.where(valid_w[..., None, None], k_new[rows, gidx], 0.0)
    vg = jnp.where(valid_w[..., None, None], v_new[rows, gidx], 0.0)
    # W consecutive positions hit W distinct ring slots: scatter is safe
    slot_idx = jnp.mod(widx, W)
    ring_k = jnp.zeros_like(cache["k"]).at[rows, slot_idx].set(
        kg.astype(cache["k"].dtype))
    ring_v = jnp.zeros_like(cache["v"]).at[rows, slot_idx].set(
        vg.astype(cache["v"].dtype))
    ring_p = jnp.full_like(cache["kpos"], -1).at[rows, slot_idx].set(
        jnp.where(valid_w, widx, -1).astype(jnp.int32))
    valid = lengths > 0
    return out, {
        "k": jnp.where(valid[:, None, None, None], ring_k, cache["k"]),
        "v": jnp.where(valid[:, None, None, None], ring_v, cache["v"]),
        "kpos": jnp.where(valid[:, None], ring_p, cache["kpos"]),
    }


# ----------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ----------------------------------------------------------------------------


def _mla_q(cfg, p, x, positions):
    B, L, _ = x.shape
    H, nope, rope = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank > 0:
        ql = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps, plus_one=True)
        q = ql @ p["w_uq"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, L, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_attention_train(cfg, p, x, positions, causal=True):
    B, L, _ = x.shape
    H = cfg.num_heads
    nope, rope, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    ckv = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps, plus_one=True)
    kv = (ckv @ p["w_ukv"]).reshape(B, L, H, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = apply_rope((x @ p["w_kr"]).reshape(B, L, 1, rope), positions,
                        cfg.rope_theta)
    k_nope = shard(k_nope, ("batch", "seq", "heads", None))
    if getattr(cfg, "attn_impl", "naive") == "chunked":
        # pack nope+rope into one head_dim and run the flash path (§Perf B3):
        # identical math, no (L, L) logits in HBM
        q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_cat = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, L, H, rope))], axis=-1)
        out = _sdpa_chunked(cfg, q_cat, k_cat, v, causal, 0)
        if out is not None:
            return out.reshape(B, L, H * vdim) @ p["wo"]
    scale = 1.0 / math.sqrt(nope + rope)
    logits = (jnp.einsum("blhn,bshn->bhls", q_nope.astype(jnp.float32),
                         k_nope.astype(jnp.float32))
              + jnp.einsum("blhr,bsxr->bhls", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))) * scale
    if causal:
        qi = jnp.arange(L)
        logits = jnp.where(qi[None, None, :, None] >= qi[None, None, None, :],
                           logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhls,bshv->blhv", w.astype(v.dtype), v)
    return out.reshape(B, L, H * vdim) @ p["wo"]


def mla_attention_decode(cfg, p, x, pos, cache):
    """Absorbed-matmul decode: cache holds only (c_kv, k_rope) — the MLA win.

    q_nope is absorbed through W_uk so scores and values are computed in the
    r_kv-dim latent space; per-step cost is O(S * (r_kv + rope) * H).
    """
    B = x.shape[0]
    H = cfg.num_heads
    nope, rope, vdim, r_kv = (cfg.qk_nope_dim, cfg.qk_rope_dim,
                              cfg.v_head_dim, cfg.kv_lora_rank)
    pos_v = _positions_vec(pos, B)
    positions = pos_v[:, None]
    q_nope, q_rope = _mla_q(cfg, p, x, positions)  # (B,1,H,*)
    ckv_new = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps, plus_one=True)
    krope_new = apply_rope((x @ p["w_kr"]).reshape(B, 1, 1, rope), positions,
                           cfg.rope_theta)
    rows = jnp.arange(B)
    ckv = cache["ckv"].at[rows, pos_v].set(
        ckv_new[:, 0].astype(cache["ckv"].dtype))
    krope = cache["krope"].at[rows, pos_v].set(
        krope_new[:, 0, 0].astype(cache["krope"].dtype))
    # absorb: W_ukv columns split into per-head W_uk (r,nope) and W_uv (r,vdim)
    w_ukv = p["w_ukv"].reshape(r_kv, H, nope + vdim)
    w_uk, w_uv = w_ukv[..., :nope], w_ukv[..., nope:]
    q_lat = jnp.einsum("blhn,rhn->blhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))  # (B,1,H,r_kv)
    scale = 1.0 / math.sqrt(nope + rope)
    logits = (jnp.einsum("blhr,bsr->bhls", q_lat, ckv.astype(jnp.float32))
              + jnp.einsum("blhr,bsr->bhls", q_rope.astype(jnp.float32),
                           krope.astype(jnp.float32))) * scale
    S = ckv.shape[1]
    mask = jnp.arange(S)[None, None, None, :] <= pos_v[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out_lat = jnp.einsum("bhls,bsr->blhr", w, ckv.astype(jnp.float32))
    out = jnp.einsum("blhr,rhv->blhv", out_lat, w_uv.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, 1, H * vdim) @ p["wo"]
    return out, {"ckv": ckv, "krope": krope}


def mla_attention_prefill(cfg, p, x, positions, lengths, cache):
    """Fused MLA prefill: train-path attention over the prompt plus a direct
    write of the latent (c_kv, k_rope) rows [0, Lp) into the decode cache.
    Junk rows past lengths[b] are overwritten-before-read exactly as in
    `full_attention_prefill`; rows with lengths[b] == 0 are untouched."""
    B, Lp, _ = x.shape
    rope = cfg.qk_rope_dim
    out = mla_attention_train(cfg, p, x, positions, causal=True)
    ckv_new = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps,
                       plus_one=True)
    krope_new = apply_rope((x @ p["w_kr"]).reshape(B, Lp, 1, rope), positions,
                           cfg.rope_theta)[:, :, 0]
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), 0, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache["krope"], krope_new.astype(cache["krope"].dtype), 0, axis=1)
    valid = (lengths > 0)[:, None, None]
    return out, {"ckv": jnp.where(valid, ckv, cache["ckv"]),
                 "krope": jnp.where(valid, krope, cache["krope"])}


# ----------------------------------------------------------------------------
# Performer features (deterministic phi, paper Table 1)
# ----------------------------------------------------------------------------


def phi_features(x, kind: str):
    """Elementwise nonneg feature map applied to hd^-1/4-scaled q/k."""
    hd = x.shape[-1]
    x = x.astype(jnp.float32) * (hd ** -0.25)
    if kind == "relu":
        return jax.nn.relu(x) + 1e-6
    if kind == "sq":
        return jnp.square(x)
    if kind == "quart":
        return jnp.square(jnp.square(x))
    if kind == "exp":
        return jnp.exp(jnp.clip(x, -20.0, 8.0))
    raise ValueError(kind)


def causal_linear_attention(qf, kf, v, log_gamma=None, chunk=256):
    """Unmasked (or gamma-decayed) causal linear attention, chunked scan.

    qf/kf: (B,L,H,m) nonneg; v: (B,L,H,hd); log_gamma: per-head () or (H,)
    log decay (mask gamma^(i-j), the separable g=exp,t=1 topological mask).
    Returns (num (B,L,H,hd), den (B,L,H)).
    """
    B, L, H, m = qf.shape
    hd = v.shape[-1]
    C = min(chunk, L)
    assert L % C == 0, f"L={L} must be divisible by chunk={C}"
    nC = L // C
    qf_ = qf.reshape(B, nC, C, H, m).transpose(1, 0, 2, 3, 4)
    kf_ = kf.reshape(B, nC, C, H, m).transpose(1, 0, 2, 3, 4)
    v_ = v.reshape(B, nC, C, H, hd).transpose(1, 0, 2, 3, 4)
    i = jnp.arange(C, dtype=jnp.float32)
    if log_gamma is None:
        lg = jnp.zeros((H,), jnp.float32)
    else:
        lg = jnp.broadcast_to(jnp.asarray(log_gamma, jnp.float32), (H,))
    # within-chunk decay factors
    dmat = jnp.exp(lg[None, None, :] * (i[:, None, None] - i[None, :, None]))  # (C,C,H)
    tri = (i[:, None] >= i[None, :])[..., None]
    dmat = jnp.where(tri, dmat, 0.0)
    q_in = jnp.exp(lg[None, :] * i[:, None])  # decay of state across chunk (C,H)
    k_out = jnp.exp(lg[None, :] * (C - i[:, None]))  # contribution into next state

    def step(carry, inp):
        S, z = carry  # (B,H,m,hd), (B,H,m)
        qc, kc, vc = inp  # (B,C,H,m/hd)
        qcf = qc.astype(jnp.float32)
        kcf = kc.astype(jnp.float32)
        vcf = vc.astype(jnp.float32)
        # intra-chunk masked quadratic
        scores = jnp.einsum("bchm,bdhm->bcdh", qcf, kcf) * dmat[None]
        num_in = jnp.einsum("bcdh,bdhv->bchv", scores, vcf)
        den_in = jnp.sum(scores, axis=2)  # (B,C,H)
        # inter-chunk from carried state
        num_x = jnp.einsum("bchm,bhmv->bchv", qcf * q_in[None, :, :, None], S)
        den_x = jnp.einsum("bchm,bhm->bch", qcf * q_in[None, :, :, None], z)
        # update state
        gC = jnp.exp(lg * C)
        S = S * gC[None, :, None, None] + jnp.einsum(
            "bchm,bchv->bhmv", kcf * k_out[None, :, :, None], vcf)
        z = z * gC[None, :, None] + jnp.sum(kcf * k_out[None, :, :, None], axis=1)
        return (S, z), (num_in + num_x, den_in + den_x)

    S0 = jnp.zeros((B, H, m, hd), jnp.float32)
    z0 = jnp.zeros((B, H, m), jnp.float32)
    _, (num, den) = jax.lax.scan(step, (S0, z0), (qf_, kf_, v_))
    num = num.transpose(1, 0, 2, 3, 4).reshape(B, L, H, hd)
    den = den.transpose(1, 0, 2, 3).reshape(B, L, H)
    return num, den


def linear_attention_output(num, den, eps=1e-6):
    den = jnp.where(jnp.abs(den) < eps, eps, den)
    return (num / den[..., None]).astype(num.dtype)


# ----------------------------------------------------------------------------
# Topological Performer: masks f(|i-j|) on the token path metric
# ----------------------------------------------------------------------------


def topo_mask_coeffs(cfg, p_topo):
    """Effective coefficients (H, t+1) and per-head scale, stability-shaped:
    the degree-1 coefficient is forced <= 0 (decay) via -softplus."""
    c = p_topo["coeffs"].astype(jnp.float32)
    if c.ndim == 1:
        c = jnp.broadcast_to(c[None], (cfg.num_heads, c.shape[0]))
    out = [c[:, 0]]
    if c.shape[1] > 1:
        out.append(-jax.nn.softplus(c[:, 1]))
    for t in range(2, c.shape[1]):
        out.append(-jax.nn.softplus(c[:, t]) if cfg.topo_g == "exp" else c[:, t])
    return jnp.stack(out, axis=1)  # (H, t+1)


def topo_logit_scale(cfg, p_topo):
    """Per-head feature temperature e^{logit_scale} — the remaining learnable
    mask scalar. Applied to q BEFORE phi (a post-phi score scale would cancel
    exactly in the num/den normalization); identity at init (logit_scale=0)."""
    ls = p_topo["logit_scale"].astype(jnp.float32)
    return jnp.broadcast_to(jnp.exp(ls), (cfg.num_heads,))


def resolve_topo_backend(cfg, backend: str | None = None) -> str:
    """Integrator/plan backend for tree- and grid-based topological masks,
    shared by the ViT grid path and plan-serving. Resolution follows the
    topo impl axis: explicit `backend` arg > cfg.topo_backend >
    cfg.topo_attn_impl ("pallas" -> the fused fdist_matvec executor
    backend, anything else -> "plan") — then filtered through the
    degradation ladder, so a rung that already failed a health probe
    (`ladder.block_backend`) is never selected again this process."""
    from repro.core import ladder

    req = (backend or getattr(cfg, "topo_backend", None)
           or ("pallas" if getattr(cfg, "topo_attn_impl", "fft") == "pallas"
               else "plan"))
    return ladder.effective_backend(req) if req in ladder.LADDER else req


def topo_attention_train(cfg, p, p_topo, x, positions, causal=True):
    """Masked linear attention (Alg. 1) with the sequence topological mask.

    Impl dispatch (cfg.topo_attn_impl):
      ref    — dense (L, L) mask oracle, O(L^2) (tests/tiny L);
      fft    — separable-decay chunked scan (g=exp, deg<=1) or the
               Toeplitz-FFT Algorithm-1 path chunked over feature columns;
      pallas — fused kernels/topo_linear_attention step (Pallas on TPU, its
               XLA chunked-scan twin elsewhere).
    """
    B, L, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions, rope=False)
    k, v = _expand_kv(cfg, k, v)
    scale = topo_logit_scale(cfg, p_topo)  # (H,)
    qf = phi_features(q * scale[None, None, :, None], cfg.performer_phi)
    kf = phi_features(k, cfg.performer_phi)
    # multi-device: the masked linear-attention sweep is independent per
    # (batch, head) — keep the phi fields partitioned batch-over-data and
    # heads-over-model so pjit never gathers the full (B, L, H, m) field
    qf = shard(qf, ("field_batch", None, "heads", None))
    kf = shard(kf, ("field_batch", None, "heads", None))
    v = shard(v, ("field_batch", None, "heads", None))
    coeffs = topo_mask_coeffs(cfg, p_topo)  # (H, t+1)
    s = cfg.topo_dist_scale
    impl = getattr(cfg, "topo_attn_impl", "fft")
    if impl not in ("ref", "fft", "pallas"):
        raise ValueError(f"cfg.topo_attn_impl={impl!r}: expected one of "
                         "'ref', 'fft', 'pallas'")
    if impl in ("pallas", "ref"):
        if impl == "pallas":
            from repro.kernels.topo_linear_attention.ops import (
                topo_linear_attention as fn)
        else:
            from repro.kernels.topo_linear_attention.ref import (
                topo_linear_attention_ref as fn)
        out = fn(qf.transpose(0, 2, 1, 3), kf.transpose(0, 2, 1, 3),
                 v.transpose(0, 2, 1, 3).astype(jnp.float32), coeffs,
                 g=cfg.topo_g, dist_scale=s,
                 causal=causal).transpose(0, 2, 1, 3)
    elif cfg.topo_g == "exp" and cfg.topo_degree <= 1:
        # separable: mask = e^{a0} gamma^(i-j). The e^{a0} factor cancels in
        # the normalization EXCEPT where the eps denominator clamp binds —
        # fold it into kf so num/den match the other impls bit-for-bit there
        kf = kf * jnp.exp(coeffs[:, 0])[None, None, :, None]
        log_gamma = coeffs[:, 1] * s if coeffs.shape[1] > 1 else jnp.zeros(cfg.num_heads)
        if causal:
            num, den = causal_linear_attention(qf, kf, v, log_gamma)
        else:
            nf, df = causal_linear_attention(qf, kf, v, log_gamma)
            nb, db = causal_linear_attention(qf[:, ::-1], kf[:, ::-1], v[:, ::-1], log_gamma)
            # forward + backward - diagonal (counted twice)
            diag = jnp.einsum("blhm,blhm->blh", qf, kf)
            num = nf + nb[:, ::-1] - diag[..., None] * v.astype(jnp.float32)
            den = df + db[:, ::-1] - diag
        out = linear_attention_output(num, den)
    else:
        out = _topo_fft_attention(cfg, qf, kf, v, coeffs, causal)
    out = shard(out, ("field_batch", None, "heads", None))
    H, hd = cfg.num_heads, cfg.head_dim
    out = out.astype(x.dtype).reshape(B, L, H * hd) @ p["wo"]
    return out


def _expand_kv(cfg, k, v):
    G = cfg.num_heads // cfg.num_kv_heads
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    return k, v


# the Toeplitz mask's diagonals |i - j| < FFT_NEAR are multiplied exactly
# (shifted products in f32) and the FFT takes only the far field: an FFT
# product's rounding error is absolute (a few ulp of the whole signal), so a
# row whose normalizer gathers few, small terms (the first rows of a causal
# mask) lost its relative accuracy to it
FFT_NEAR = 8


def _near_far_fastmult(F, causal: bool):
    """X (B, H, L, c) -> the Toeplitz product of F (1, H, L) with X: the
    band |i - j| < FFT_NEAR as shifted products, the rest by FFT."""
    from repro.core.toeplitz import causal_toeplitz_matvec, symmetric_toeplitz_matvec

    L = F.shape[-1]
    w = min(FFT_NEAR, L)
    far = jnp.where(jnp.arange(L) >= w, F, 0.0)
    fft = causal_toeplitz_matvec if causal else symmetric_toeplitz_matvec

    def shift(X, d):  # row i gets X[i - d] (0 outside)
        if d > 0:
            return jnp.pad(X[..., :L - d, :], ((0, 0), (0, 0), (d, 0), (0, 0)))
        return jnp.pad(X[..., -d:, :], ((0, 0), (0, 0), (0, -d), (0, 0)))

    def mult(X):
        y = F[..., 0, None, None] * X
        for d in range(1, w):
            fd = F[..., d, None, None]
            y = y + fd * shift(X, d)
            if not causal:
                y = y + fd * shift(X, -d)
        if w < L:
            yf = fft(far, X)
            if causal:  # rows i < w have no far field: keep its error out
                yf = jnp.where((jnp.arange(L) >= w)[:, None], yf, 0.0)
            y = y + yf
        return y

    return mult


def _topo_fft_attention(cfg, qf, kf, v, coeffs, causal, col_chunk=8):
    """Algorithm 1 with Toeplitz-FFT FastMult (the near band exact, see
    `FFT_NEAR`), chunked over feature columns.

    Exact for any g/degree; memory O(B L H chunk*hd) instead of O(B L H m hd).
    Accumulation is float32 end-to-end: inputs are upcast once, the single
    `num` accumulator is allocated once in fp32, and the denominator needs no
    column chunking at all — one fastmult over the m feature columns (only
    the k⊗v expansion is chunked, since that is what blows up memory).
    """
    from repro.core.masks import sequence_mask_values

    B, L, H, m = qf.shape
    hd = v.shape[-1]
    F = sequence_mask_values(cfg.topo_g, coeffs, L, cfg.topo_dist_scale)  # (H, L)
    mult = _near_far_fastmult(F[None], causal)
    qf32, kf32, v32 = (t.astype(jnp.float32) for t in (qf, kf, v))
    d2 = mult(kf32.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    den = jnp.einsum("blhm,blhm->blh", qf32, d2)
    num = jnp.zeros((B, L, H, hd), jnp.float32)
    for c0 in range(0, m, col_chunk):
        c1 = min(c0 + col_chunk, m)
        kc = kf32[..., c0:c1]  # (B,L,H,c)
        v1 = kc[..., None] * v32[..., None, :]  # (B,L,H,c,hd)
        v1 = v1.reshape(B, L, H, -1).transpose(0, 2, 1, 3)  # (B,H,L,c*hd)
        d1 = mult(v1).transpose(0, 2, 1, 3).reshape(B, L, H, c1 - c0, hd)
        num = num + jnp.einsum("blhc,blhcv->blhv", qf32[..., c0:c1], d1)
    assert num.dtype == jnp.float32 and den.dtype == jnp.float32, (
        "topo fft accumulators must stay fp32")
    return linear_attention_output(num, den)


# --- decode: cordial / Chebyshev-separable O(1) states -----------------------


def topo_decomposition(cfg, coeffs, L: int, rank: int = 24):
    """f(i-j) = sum_r alpha_r(i) beta_r(j) for i,j in [0,L), for masks that
    are not separable (separable ones decode by the relative recurrence of
    `topo_attention_decode`): the Chebyshev rank-`rank`
    expansion of (i,j) -> f(i-j) on [0,L)^2 (spectral accuracy for smooth f)
    shared with the fused attention kernel
    (core.masks.chebyshev_separable_expansion) — decode states and the fused
    train/prefill path are built from the SAME node grid and Bmat, but decode
    Lagrange-evaluates only the single queried position (O(1) per token, not
    an O(L) table rebuild per step).
    Returns (alpha(pos)->(H,R), beta(pos)->(H,R)).
    """
    from repro.core.masks import chebyshev_separable_expansion

    s = cfg.topo_dist_scale
    H = coeffs.shape[0]
    nodes, Bmat = chebyshev_separable_expansion(cfg.topo_g, coeffs, L, s, rank)
    nodes = jnp.asarray(nodes)

    def lagr(pos):  # pos: () -> (rank,)
        from repro.core.engines.plan import _lagrange_batched
        pts = jnp.reshape(jnp.asarray(pos, jnp.float32), (1, 1))
        return _lagrange_batched(pts, nodes[None, :])[0, 0]

    def alpha(pos):
        return jnp.einsum("r,hrq->hq", lagr(pos), Bmat)  # (H, rank)

    def beta(pos):
        return jnp.broadcast_to(lagr(pos)[None], (H, rank))

    return alpha, beta, rank


def _separable(cfg) -> bool:
    return cfg.topo_g == "exp" and cfg.topo_degree <= 1


def _decay_terms(cfg, coeffs):
    """(e^{a0}, log gamma) per head of a separable mask e^{a0} gamma^(i-j),
    gamma = e^{a1 * dist_scale} <= 1."""
    H = coeffs.shape[0]
    a1 = coeffs[:, 1] if coeffs.shape[1] > 1 else jnp.zeros(H)
    return jnp.exp(coeffs[:, 0]), a1 * cfg.topo_dist_scale


def topo_decode_init(cfg, B, L, dtype=jnp.float32, rank: int = 24):
    H, hd = cfg.num_heads, cfg.head_dim
    m = hd  # deterministic elementwise phi keeps feature dim = head_dim
    R = 1 if _separable(cfg) else rank
    return {
        "S": jnp.zeros((B, H, R, m, hd), dtype),
        "z": jnp.zeros((B, H, R, m), dtype),
    }


def topo_attention_decode(cfg, p, p_topo, x, pos, cache, L: int, rank: int = 24):
    """O(1)-state masked linear attention decode step. pos: () or (B,).

    Separable masks (g = exp, degree <= 1) carry the relative recurrence
    S_n = gamma S_{n-1} + e^{a0} kf_n (x) v_n (and z alike): every factor is
    <= 1, so the state stays finite at any position. Other masks carry the
    Chebyshev rank-R moments, alpha/beta evaluated per slot position
    (vmapped), so slots at different sequence depths share one step."""
    B = x.shape[0]
    H, hd = cfg.num_heads, cfg.head_dim
    pos_v = _positions_vec(pos, B)
    positions = pos_v[:, None]
    q, k, v = _project_qkv(cfg, p, x, positions, rope=False)
    k, v = _expand_kv(cfg, k, v)
    scale = topo_logit_scale(cfg, p_topo)  # (H,)
    qf = phi_features(q[:, 0] * scale[None, :, None], cfg.performer_phi)
    kf = phi_features(k[:, 0], cfg.performer_phi)
    vf = v[:, 0].astype(jnp.float32)
    coeffs = topo_mask_coeffs(cfg, p_topo)
    if _separable(cfg):
        e0, lg = _decay_terms(cfg, coeffs)
        g = jnp.exp(lg)[None, :, None]
        kf = kf * e0[None, :, None]
        S = (cache["S"] * g[..., None, None]
             + (kf[..., None] * vf[:, :, None, :])[:, :, None])
        z = cache["z"] * g[..., None] + kf[:, :, None]
        num = jnp.einsum("bhm,bhmv->bhv", qf, S[:, :, 0])
        den = jnp.einsum("bhm,bhm->bh", qf, z[:, :, 0])
    else:
        alpha, beta, R = topo_decomposition(cfg, coeffs, L, rank)
        pos_f = pos_v.astype(jnp.float32)
        b = jax.vmap(beta)(pos_f)  # (B,H,R)
        S = cache["S"] + b[:, :, :, None, None] * (
            kf[:, :, None, :, None] * vf[:, :, None, None, :])
        z = cache["z"] + b[:, :, :, None] * kf[:, :, None, :]
        a = jax.vmap(alpha)(pos_f)  # (B,H,R)
        num = jnp.einsum("bhm,bhrmv,bhr->bhv", qf, S, a)
        den = jnp.einsum("bhm,bhrm,bhr->bh", qf, z, a)
    den = jnp.where(jnp.abs(den) < 1e-6, 1e-6, den)
    out = (num / den[..., None]).astype(x.dtype).reshape(B, 1, H * hd) @ p["wo"]
    return out, {"S": S, "z": z}


def topo_attention_prefill(cfg, p, p_topo, x, positions, lengths, cache,
                           L: int, rank: int = 24, tree_mask=None):
    """Fused topo prefill: exact train-path attention over the prompt plus
    the decode state for the prompt tokens, written (set, not accumulated)
    into the cache so a reused slot never inherits a previous request's
    state. Rows with lengths[b] == 0 keep their state untouched.

    Separable masks take the state the relative recurrence reaches after
    token len_b - 1, S = sum_{j < len_b} e^{a0} gamma^(len_b - 1 - j)
    kf_j (x) v_j (every exponent <= 0); other masks the Chebyshev moments
    S = sum_{j < len_b} beta(j) kf_j (x) v_j (and z alike).

    `tree_mask` (optional) replaces the sequence Toeplitz mask with a
    per-request tree mask served from a packed forest plan (see
    serve.forest_masks): {'make_fastmult': coeffs -> FastMult over the
    packed row space, 'pack': (N,) packed-row -> flat b*Lp+l token index
    (-1 = foreign block), 'unpack': (B*Lp,) token -> packed row (-1 = not
    in a tree)}. The prompt attends bidirectionally under the tree metric
    (prefix-LM style — the prompt is completed context); generated tokens
    continue through the causal recurrence."""
    B, Lp, _ = x.shape
    if tree_mask is None:
        out = topo_attention_train(cfg, p, p_topo, x, positions, causal=True)
    else:
        out = _topo_tree_masked_attention(cfg, p, p_topo, x, positions,
                                          tree_mask)
    q, k, v = _project_qkv(cfg, p, x, positions, rope=False)
    k, v = _expand_kv(cfg, k, v)
    kf = phi_features(k, cfg.performer_phi)  # (B,Lp,H,m)
    coeffs = topo_mask_coeffs(cfg, p_topo)
    if _separable(cfg):
        e0, lg = _decay_terms(cfg, coeffs)
        w = e0 * _decay_weights(lg, lengths, Lp)  # (B,Lp,H)
        S = jnp.einsum("blhm,blhv,blh->bhmv", kf, v.astype(jnp.float32),
                       w)[:, :, None]
        z = jnp.einsum("blhm,blh->bhm", kf, w)[:, :, None]
    else:
        alpha, beta, R = topo_decomposition(cfg, coeffs, L, rank)
        bet = jax.vmap(beta)(jnp.arange(Lp, dtype=jnp.float32))  # (Lp,H,R)
        vm = (jnp.arange(Lp)[None, :] < lengths[:, None]).astype(
            jnp.float32)  # (B,Lp)
        S = jnp.einsum("blhm,blhv,lhr,bl->bhrmv", kf,
                       v.astype(jnp.float32), bet, vm)
        z = jnp.einsum("blhm,lhr,bl->bhrm", kf, bet, vm)
    valid = lengths > 0
    return out, {
        "S": jnp.where(valid[:, None, None, None, None],
                       S.astype(cache["S"].dtype), cache["S"]),
        "z": jnp.where(valid[:, None, None, None],
                       z.astype(cache["z"].dtype), cache["z"]),
    }


def _decay_weights(lg, lengths, Lp: int):
    """gamma^(len_b - 1 - j) for j < len_b, else 0: (B, Lp, H) from the
    per-head log decays lg (H,) <= 0. The exponent is clamped at 0 before
    exp, so a row past the prompt never overflows on its way to 0."""
    back = (lengths[:, None] - 1 - jnp.arange(Lp)[None, :])  # (B,Lp)
    w = jnp.exp(lg[None, None, :]
                * jnp.maximum(back, 0)[..., None].astype(jnp.float32))
    return jnp.where((back >= 0)[..., None], w, 0.0)


def _topo_tree_masked_attention(cfg, p, p_topo, x, positions, tree_mask):
    """Masked linear attention (Alg. 1) under per-request TREE masks: tokens
    are packed into their forest rows, ONE block-diagonal plan execution
    applies every request's own M_t = [f(dist_{T_t}(i, j))], and outputs
    scatter back to (B, Lp). Tokens outside any tree block get zero
    attention output (their rows are junk padding by construction)."""
    from repro.core.masks import masked_linear_attention

    B, Lp, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _project_qkv(cfg, p, x, positions, rope=False)
    k, v = _expand_kv(cfg, k, v)
    scale = topo_logit_scale(cfg, p_topo)
    qf = phi_features(q * scale[None, None, :, None], cfg.performer_phi)
    kf = phi_features(k, cfg.performer_phi)
    m = qf.shape[-1]
    pack = tree_mask["pack"]      # (N,) packed row -> flat token (or -1)
    unpack = tree_mask["unpack"]  # (B*Lp,) flat token -> packed row (or -1)
    take = jnp.clip(pack, 0)
    in_tree = (pack >= 0).astype(jnp.float32)[:, None, None]
    qp = jnp.moveaxis(qf.reshape(B * Lp, H, m)[take] * in_tree, 1, 0)
    kp = jnp.moveaxis(kf.reshape(B * Lp, H, m)[take] * in_tree, 1, 0)
    vp = jnp.moveaxis(
        v.astype(jnp.float32).reshape(B * Lp, H, hd)[take] * in_tree, 1, 0)
    coeffs = topo_mask_coeffs(cfg, p_topo)  # (H, t+1)
    mk = tree_mask["make_fastmult"]
    if cfg.topo_synced:
        out_p = masked_linear_attention(qp, kp, vp, mk(coeffs[0]))
    else:
        out_p = jnp.stack([
            masked_linear_attention(qp[h], kp[h], vp[h], mk(coeffs[h]))
            for h in range(H)])
    sel = jnp.clip(unpack, 0)
    out_tok = jnp.moveaxis(out_p, 0, 1)[sel]  # (B*Lp, H, hd)
    out_tok = out_tok * (unpack >= 0).astype(out_tok.dtype)[:, None, None]
    out = out_tok.reshape(B, Lp, H, hd)
    return out.astype(x.dtype).reshape(B, Lp, H * hd) @ p["wo"]


# --- lightning attention (MiniMax-Text-01): the unnormalized decay mask ----
#
# A lightning layer is the sequence topological mask with g = exp, degree 1
# and fixed per-head coefficients [0, -s_{h,l}] on SiLU features, with no
# 1/sqrt(d) and no denominator:
#   [q|k|v]_h = SiLU(x W_qkv)      (W_qkv columns per head: q, k, v)
#   o_{h,i}   = sum_{j<=i} exp(-s_{h,l} (i-j)) (q_{h,i} . k_{h,j}) v_{h,j}
#   y         = W_out(sigmoid(x W_g) * RMSNorm(concat_h o_h))
# with s_{h,l} = 2^(-8(h+1)/H) (1 - l/(N-1) + 1e-5) at published layer l of
# N. The norm spans all H*hd channels, so it crosses head shards.


def lightning_init(key, cfg, dtype=jnp.float32):
    d, H, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    ks = jax.random.split(key, 3)
    return {"w_qkv": dense_init(ks[0], (d, 3 * H * hd), dtype=dtype),
            "w_gate": dense_init(ks[1], (d, H * hd), dtype=dtype),
            "wo": dense_init(ks[2], (H * hd, d), dtype=dtype),
            "out_norm": jnp.zeros((H * hd,), dtype)}


def lightning_slopes(cfg, layer):
    """Per-head decays s_{h,l} (H,) of published layer `layer` (an int or a
    traced int) of `cfg.lightning_decay_layers`."""
    H, N = cfg.num_heads, cfg.lightning_decay_layers
    base = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    frac = 1.0 - jnp.asarray(layer, jnp.float32) / (N - 1) + 1e-5
    return jnp.asarray(base, jnp.float32) * frac


def _lightning_qkv(cfg, p, x):
    B, L, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    qkv = jax.nn.silu((x @ p["w_qkv"]).astype(jnp.float32)).astype(x.dtype)
    qkv = shard(qkv.reshape(B, L, H, 3, hd),
                ("batch", "seq", "heads", None, None))
    return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]


def _lightning_out(cfg, p, x, o):
    """W_out(sigmoid(x W_g) * RMSNorm(o)), o: (B, L, H, hd) f32."""
    B, L = o.shape[:2]
    o = shard(o.reshape(B, L, -1), ("batch", "seq", "heads"))
    o = rms_norm(o, p["out_norm"], cfg.norm_eps, plus_one=True)
    gate = jax.nn.sigmoid((x @ p["w_gate"]).astype(jnp.float32))
    y = shard((gate * o).astype(x.dtype), ("batch", "seq", "heads"))
    return y @ p["wo"]


def lightning_core(q, k, v, slopes):
    """sum_{j<=i} exp(-s_h (i-j)) (q_i . k_j) v_j for (B, L, H, hd) q/k/v:
    the topo kernel's decay sweep, unnormalized (the Pallas kernel on TPU,
    its XLA twin elsewhere; heads over the mesh's model axis)."""
    from repro.kernels.topo_linear_attention.ops import (
        topo_linear_attention, topo_linear_attention_sharded)
    from repro.launch.sharding import current_mesh

    coeffs = jnp.stack([jnp.zeros_like(slopes), -slopes], axis=1)  # (H, 2)
    args = [t.transpose(0, 2, 1, 3) for t in (q, k, v)]
    kw = dict(g="exp", dist_scale=1.0, causal=True, normalize=False)
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        out = topo_linear_attention_sharded(*args, coeffs, mesh=mesh, **kw)
    else:
        out = topo_linear_attention(*args, coeffs, **kw)
    return out.transpose(0, 2, 1, 3)


def lightning_attention_train(cfg, p, x, layer):
    """The lightning layer over a whole sequence. x: (B, L, d)."""
    with jax.named_scope("lm.lightning"):
        q, k, v = _lightning_qkv(cfg, p, x)
        with jax.named_scope("lm.lightning.core"):
            o = lightning_core(q, k, v, lightning_slopes(cfg, layer))
        return _lightning_out(cfg, p, x, o)


def lightning_decode_init(cfg, B):
    H, hd = cfg.num_heads, cfg.head_dim
    return {"S": jnp.zeros((B, H, hd, hd), jnp.float32)}


def lightning_attention_prefill(cfg, p, x, lengths, cache, layer):
    """The lightning layer over right-padded prompts, and the state the
    recurrence S_n = gamma S_{n-1} + k_n v_n^T reaches after each row's
    last token: S = sum_{j < len_b} gamma^(len_b - 1 - j) k_j v_j^T with
    gamma = exp(-s) (every exponent <= 0). Rows with lengths[b] == 0 keep
    their state. Returns (out (B, Lp, d), new_cache)."""
    with jax.named_scope("lm.lightning"):
        q, k, v = _lightning_qkv(cfg, p, x)
        slopes = lightning_slopes(cfg, layer)
        with jax.named_scope("lm.lightning.core"):
            o = lightning_core(q, k, v, slopes)
        out = _lightning_out(cfg, p, x, o)
        w = _decay_weights(-slopes, lengths, x.shape[1])  # (B, Lp, H)
        S = jnp.einsum("blhm,blhv,blh->bhmv", k.astype(jnp.float32),
                       v.astype(jnp.float32), w)
        S = shard(S, ("batch", "heads", None, None))
        valid = (lengths > 0)[:, None, None, None]
        return out, {"S": jnp.where(valid, S, cache["S"])}


def lightning_attention_decode(cfg, p, x, cache, layer):
    """One token through the recurrence. x: (B, 1, d)."""
    with jax.named_scope("lm.lightning"):
        q, k, v = _lightning_qkv(cfg, p, x)
        g = jnp.exp(-lightning_slopes(cfg, layer))[None, :, None, None]
        q, k, v = (t[:, 0].astype(jnp.float32) for t in (q, k, v))
        S = cache["S"] * g + k[..., None] * v[..., None, :]
        o = jnp.einsum("bhm,bhmv->bhv", q, S)[:, None]
        return _lightning_out(cfg, p, x, o), {"S": S}


# --- plain performer decode (unmasked linear attention state) ----------------


def performer_decode_init(cfg, B, dtype=jnp.float32):
    H, hd = cfg.num_heads, cfg.head_dim
    return {"S": jnp.zeros((B, H, hd, hd), dtype), "z": jnp.zeros((B, H, hd), dtype)}


def performer_attention_train(cfg, p, x, positions, causal=True):
    B, L, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions, rope=False)
    k, v = _expand_kv(cfg, k, v)
    qf = phi_features(q, cfg.performer_phi)
    kf = phi_features(k, cfg.performer_phi)
    if causal:
        num, den = causal_linear_attention(qf, kf, v)
    else:
        kv = jnp.einsum("blhm,blhv->bhmv", kf, v.astype(jnp.float32))
        num = jnp.einsum("blhm,bhmv->blhv", qf, kv)
        den = jnp.einsum("blhm,bhm->blh", qf, jnp.sum(kf, axis=1))
    out = linear_attention_output(num, den)
    return out.astype(x.dtype).reshape(B, L, -1) @ p["wo"]


def performer_attention_decode(cfg, p, x, pos, cache):
    B = x.shape[0]
    H, hd = cfg.num_heads, cfg.head_dim
    positions = _positions_vec(pos, B)[:, None]
    q, k, v = _project_qkv(cfg, p, x, positions, rope=False)
    k, v = _expand_kv(cfg, k, v)
    qf = phi_features(q[:, 0], cfg.performer_phi)
    kf = phi_features(k[:, 0], cfg.performer_phi)
    S = cache["S"] + kf[..., None] * v[:, 0].astype(jnp.float32)[..., None, :]
    z = cache["z"] + kf
    num = jnp.einsum("bhm,bhmv->bhv", qf, S)
    den = jnp.einsum("bhm,bhm->bh", qf, z)
    den = jnp.where(jnp.abs(den) < 1e-6, 1e-6, den)
    out = (num / den[..., None]).astype(x.dtype).reshape(B, 1, H * hd) @ p["wo"]
    return out, {"S": S, "z": z}


def performer_attention_prefill(cfg, p, x, positions, lengths, cache):
    """Fused performer prefill: train-path attention over the prompt plus the
    closed-form linear-attention state (beta = 1) for the prompt tokens,
    overwriting any stale state in reused slots."""
    B, Lp, _ = x.shape
    out = performer_attention_train(cfg, p, x, positions, causal=True)
    _, k, v = _project_qkv(cfg, p, x, positions, rope=False)
    k, v = _expand_kv(cfg, k, v)
    kf = phi_features(k, cfg.performer_phi)  # (B,Lp,H,m)
    vmask = (jnp.arange(Lp)[None, :] < lengths[:, None]).astype(jnp.float32)
    S = jnp.einsum("blhm,blhv,bl->bhmv", kf, v.astype(jnp.float32), vmask)
    z = jnp.einsum("blhm,bl->bhm", kf, vmask)
    valid = lengths > 0
    return out, {
        "S": jnp.where(valid[:, None, None, None],
                       S.astype(cache["S"].dtype), cache["S"]),
        "z": jnp.where(valid[:, None, None],
                       z.astype(cache["z"].dtype), cache["z"]),
    }
