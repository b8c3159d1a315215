"""Decoder-only LM assembly for all assigned families.

Layers are lax.scan-stacked (compile time and HLO size O(1) in depth) with
optional per-layer remat. Families:
  dense   — [norm->attn, norm->mlp] x L
  moe     — first_dense_layers dense blocks, then MoE blocks (scan)
  ssm     — mamba blocks (no MLP, as mamba-1)
  hybrid  — superblocks (rec, rec, attn) x num_superblocks + tail rec blocks,
            or (lightning x 7, softmax) periods with MoE and post-norm
            residuals (minimax)
  vlm     — dense backbone over [projected patch embeddings ; text tokens]
Attention variant per config: full | performer | topo (the paper's technique).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.launch.sharding import shard
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import ssm as SSM
from repro.models.layers import (cross_entropy_loss, dense_init, dtype_of,
                                 embed_init, gated_mlp, gated_mlp_init,
                                 maybe_remat, rms_norm)


# ----------------------------------------------------------------------------
# block init/apply by kind
# ----------------------------------------------------------------------------


def _block_init(key, cfg, kind: str, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p = {}
    if kind in ("attn_mlp", "attn_local_mlp", "attn_only"):
        p["attn_norm"] = {"scale": jnp.zeros((d,), dtype)}
        p["attn"] = (A.mla_init(ks[0], cfg, dtype) if cfg.mla
                     else A.attn_init(ks[0], cfg, dtype))
        if cfg.attention_variant == "topo":
            p["topo"] = A.topo_init(ks[1], cfg, dtype)
        if kind != "attn_only":
            p["mlp_norm"] = {"scale": jnp.zeros((d,), dtype)}
            p["mlp"] = gated_mlp_init(ks[2], d, cfg.d_ff, dtype)
    elif kind == "moe":
        p["attn_norm"] = {"scale": jnp.zeros((d,), dtype)}
        p["attn"] = (A.mla_init(ks[0], cfg, dtype) if cfg.mla
                     else A.attn_init(ks[0], cfg, dtype))
        if cfg.attention_variant == "topo":
            p["topo"] = A.topo_init(ks[1], cfg, dtype)
        p["mlp_norm"] = {"scale": jnp.zeros((d,), dtype)}
        p["moe"] = MOE.moe_init(ks[2], cfg, dtype)
    elif kind in ("lightning", "softmax"):
        p["attn_norm"] = {"scale": jnp.zeros((d,), dtype)}
        p["attn"] = (A.lightning_init(ks[0], cfg, dtype) if kind == "lightning"
                     else A.attn_init(ks[0], cfg, dtype))
        p["mlp_norm"] = {"scale": jnp.zeros((d,), dtype)}
        if cfg.moe:
            p["moe"] = MOE.moe_init(ks[2], cfg, dtype)
        else:
            p["mlp"] = gated_mlp_init(ks[2], d, cfg.d_ff, dtype)
    elif kind == "mamba":
        p["norm"] = {"scale": jnp.zeros((d,), dtype)}
        p["ssm"] = SSM.ssm_init(ks[0], cfg, dtype)
    elif kind == "rec_mlp":
        p["norm"] = {"scale": jnp.zeros((d,), dtype)}
        p["lru"] = RG.lru_init(ks[0], cfg, dtype)
        p["mlp_norm"] = {"scale": jnp.zeros((d,), dtype)}
        p["mlp"] = gated_mlp_init(ks[2], d, cfg.d_ff, dtype)
    else:
        raise ValueError(kind)
    return p


def _attn_train(cfg, p, x, positions, causal=True, window=0):
    h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps, plus_one=True)
    if cfg.mla:
        return A.mla_attention_train(cfg, p["attn"], h, positions, causal=causal)
    if cfg.attention_variant == "topo":
        return A.topo_attention_train(cfg, p["attn"], p["topo"], h, positions,
                                      causal=causal)
    if cfg.attention_variant == "performer":
        return A.performer_attention_train(cfg, p["attn"], h, positions,
                                           causal=causal)
    return A.full_attention_train(cfg, p["attn"], h, positions, causal=causal,
                                  window=window)


def _residual(cfg, x, h, y):
    """x + y, or with `cfg.postnorm` alpha * h + beta * y (the residual
    taken after the norm)."""
    if cfg.postnorm:
        return cfg.residual_alpha * h + cfg.residual_beta * y
    return x + y


def _ffn(cfg, p, x):
    """The second half of a lightning / softmax block: norm, then the MoE
    (or MLP), then the residual. Returns (x, aux, ids): ids (B, L, top_k)
    are the experts a chip-share MoE routed each token to, else None."""
    h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
    ids = None
    if "moe" in p and cfg.moe_held:
        y, aux, ids = MOE.held_moe_block(cfg, p["moe"], h)
    elif "moe" in p:
        y, aux = MOE.moe_block(cfg, p["moe"], h)
    else:
        y, aux = gated_mlp(p["mlp"], h, cfg.mlp_act), jnp.zeros((), jnp.float32)
    return _residual(cfg, x, h, y), aux, ids


def _log_routes(log, ids, pos=None, lengths=None):
    """The route log of a chip-share MoE block's cache: `log` (B, S, top_k)
    holds the experts each cached position was routed to. A decode step
    writes ids (B, 1, top_k) at `pos` (() or (B,)); a prefill writes ids
    (B, Lp, top_k) at [0, Lp), but not in rows with lengths[b] == 0."""
    if lengths is None:
        B = ids.shape[0]
        return log.at[jnp.arange(B), A._positions_vec(pos, B)].set(ids[:, 0])
    new = jax.lax.dynamic_update_slice_in_dim(log, ids, 0, axis=1)
    return jnp.where((lengths > 0)[:, None, None], new, log)


def _block_train(cfg, kind, p, x, positions, window=0, layer=0):
    """Returns (x, aux). `layer` is the block's published layer index (the
    lightning decays depend on it)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("lightning", "softmax"):
        h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps, plus_one=True)
        if kind == "lightning":
            y = A.lightning_attention_train(cfg, p["attn"], h, layer)
        else:
            with jax.named_scope("lm.softmax"):
                y = A.full_attention_train(cfg, p["attn"], h, positions)
        x, aux, _ = _ffn(cfg, p, _residual(cfg, x, h, y))
    elif kind in ("attn_mlp", "attn_local_mlp", "attn_only", "moe"):
        w = window if kind == "attn_local_mlp" else 0
        x = x + _attn_train(cfg, p, x, positions, window=w)
        if kind == "moe":
            h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
            y, aux = MOE.moe_block(cfg, p["moe"], h)
            x = x + y
        elif kind != "attn_only":
            h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
            x = x + gated_mlp(p["mlp"], h, cfg.mlp_act)
    elif kind == "mamba":
        h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps, plus_one=True)
        x = x + SSM.mamba_block_train(cfg, p["ssm"], h)
    elif kind == "rec_mlp":
        h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps, plus_one=True)
        x = x + RG.lru_block_train(cfg, p["lru"], h)
        h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
        x = x + gated_mlp(p["mlp"], h, cfg.mlp_act)
    else:
        raise ValueError(kind)
    seq_name = ("seq_sp" if getattr(cfg, "seq_sharded_residuals", False)
                else "seq")
    x = shard(x, ("batch", seq_name, "embed"))
    return x, aux


def _block_decode(cfg, kind, p, x, pos, cache, S, window=0, layer=0):
    """x: (B, 1, d). Returns (x, new_cache)."""
    if kind in ("lightning", "softmax"):
        log = cache.get("routes")
        h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps, plus_one=True)
        if kind == "lightning":
            y, cache = A.lightning_attention_decode(cfg, p["attn"], h, cache,
                                                    layer)
        else:
            with jax.named_scope("lm.softmax"):
                y, cache = A.full_attention_decode(cfg, p["attn"], h, pos,
                                                   cache)
        x, _, ids = _ffn(cfg, p, _residual(cfg, x, h, y))
        if log is not None:
            cache = dict(cache, routes=_log_routes(log, ids, pos=pos))
    elif kind in ("attn_mlp", "attn_local_mlp", "attn_only", "moe"):
        h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps, plus_one=True)
        if cfg.mla:
            y, cache = A.mla_attention_decode(cfg, p["attn"], h, pos, cache)
        elif cfg.attention_variant == "topo":
            y, cache = A.topo_attention_decode(cfg, p["attn"], p["topo"], h,
                                               pos, cache, L=S)
        elif cfg.attention_variant == "performer":
            y, cache = A.performer_attention_decode(cfg, p["attn"], h, pos, cache)
        elif kind == "attn_local_mlp":
            y, cache = A.local_attention_decode(cfg, p["attn"], h, pos, cache)
        else:
            y, cache = A.full_attention_decode(cfg, p["attn"], h, pos, cache)
        x = x + y
        if kind == "moe":
            h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
            y, _ = MOE.moe_block(cfg, p["moe"], h)
            x = x + y
        elif kind != "attn_only":
            h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
            x = x + gated_mlp(p["mlp"], h, cfg.mlp_act)
    elif kind == "mamba":
        h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps, plus_one=True)
        y, cache = SSM.mamba_block_decode(cfg, p["ssm"], h, cache)
        x = x + y
    elif kind == "rec_mlp":
        h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps, plus_one=True)
        y, cache = RG.lru_block_decode(cfg, p["lru"], h, cache)
        x = x + y
        h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
        x = x + gated_mlp(p["mlp"], h, cfg.mlp_act)
    return x, cache


def _block_prefill(cfg, kind, p, x, positions, lengths, cache, S, window=0,
                   tree_mask=None, layer=0):
    """Whole-prompt forward (same math as `_block_train`) that also writes
    the decode cache for positions [0, lengths[b]). x: (B, Lp, d) right-
    padded; rows with lengths[b] == 0 leave their cache untouched (they
    belong to other live serve slots). Returns (x, new_cache)."""
    if kind in ("lightning", "softmax"):
        log = cache.get("routes")
        h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps, plus_one=True)
        if kind == "lightning":
            y, cache = A.lightning_attention_prefill(cfg, p["attn"], h,
                                                     lengths, cache, layer)
        else:
            with jax.named_scope("lm.softmax"):
                y, cache = A.full_attention_prefill(cfg, p["attn"], h,
                                                    positions, lengths, cache)
                cache = {k: shard(c, ("batch", None, "kv_heads", None))
                         for k, c in cache.items()}
        x, _, ids = _ffn(cfg, p, _residual(cfg, x, h, y))
        if log is not None:
            cache = dict(cache, routes=_log_routes(log, ids, lengths=lengths))
    elif kind in ("attn_mlp", "attn_local_mlp", "attn_only", "moe"):
        h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps, plus_one=True)
        if cfg.mla:
            y, cache = A.mla_attention_prefill(cfg, p["attn"], h, positions,
                                               lengths, cache)
        elif cfg.attention_variant == "topo":
            y, cache = A.topo_attention_prefill(cfg, p["attn"], p["topo"], h,
                                                positions, lengths, cache,
                                                L=S, tree_mask=tree_mask)
        elif cfg.attention_variant == "performer":
            y, cache = A.performer_attention_prefill(cfg, p["attn"], h,
                                                     positions, lengths, cache)
        elif kind == "attn_local_mlp":
            y, cache = A.local_attention_prefill(cfg, p["attn"], h, positions,
                                                 lengths, cache)
        else:
            y, cache = A.full_attention_prefill(cfg, p["attn"], h, positions,
                                                lengths, cache)
        x = x + y
        if kind == "moe":
            h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
            y, _ = MOE.moe_block(cfg, p["moe"], h)
            x = x + y
        elif kind != "attn_only":
            h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
            x = x + gated_mlp(p["mlp"], h, cfg.mlp_act)
    elif kind == "mamba":
        h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps, plus_one=True)
        y, cache = SSM.mamba_block_prefill(cfg, p["ssm"], h, lengths, cache)
        x = x + y
    elif kind == "rec_mlp":
        h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps, plus_one=True)
        y, cache = RG.lru_block_prefill(cfg, p["lru"], h, lengths, cache)
        x = x + y
        h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
        x = x + gated_mlp(p["mlp"], h, cfg.mlp_act)
    else:
        raise ValueError(kind)
    return x, cache


def _block_cache_init(cfg, kind, B, S, dtype):
    if kind in ("lightning", "softmax"):
        c = (A.lightning_decode_init(cfg, B) if kind == "lightning" else
             {"k": jnp.zeros((B, S, cfg.num_kv_heads, cfg.head_dim), dtype),
              "v": jnp.zeros((B, S, cfg.num_kv_heads, cfg.head_dim), dtype)})
        if cfg.moe and cfg.moe_held:
            # the chip-share MoE's route log (`_log_routes`)
            c["routes"] = jnp.zeros((B, S, cfg.top_k), jnp.int32)
        return c
    if kind in ("attn_mlp", "attn_local_mlp", "attn_only", "moe"):
        if cfg.mla:
            return {"ckv": jnp.zeros((B, S, cfg.kv_lora_rank), dtype),
                    "krope": jnp.zeros((B, S, cfg.qk_rope_dim), dtype)}
        if cfg.attention_variant == "topo":
            return A.topo_decode_init(cfg, B, S)
        if cfg.attention_variant == "performer":
            return A.performer_decode_init(cfg, B)
        if kind == "attn_local_mlp":
            return A.local_attention_decode_init(cfg, B, dtype)
        return {"k": jnp.zeros((B, S, cfg.num_kv_heads, cfg.head_dim), dtype),
                "v": jnp.zeros((B, S, cfg.num_kv_heads, cfg.head_dim), dtype)}
    if kind == "mamba":
        return SSM.mamba_decode_init(cfg, B, dtype)
    if kind == "rec_mlp":
        return RG.lru_decode_init(cfg, B, dtype)
    raise ValueError(kind)


_SB_KINDS = {"rec": "rec_mlp", "attn": "attn_local_mlp",
             "lightning": "lightning", "softmax": "softmax"}


def _sb_kind(bkind: str) -> str:
    """The block kind of one entry of a hybrid config's `superblock`."""
    return _SB_KINDS[bkind]


def _layer(cfg, j, bi: int):
    """Published layer index of block `bi` of superblock `j`."""
    return j * len(cfg.superblock) + bi


# ----------------------------------------------------------------------------
# layer stack description per family
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StackDesc:
    """(kind, count, scanned) segments, executed in order."""
    segments: tuple  # of (kind, count, scan: bool)


def stack_desc(cfg) -> StackDesc:
    if cfg.family in ("dense", "vlm"):
        return StackDesc((("attn_mlp", cfg.num_layers, cfg.scan_layers),))
    if cfg.family == "moe":
        segs = []
        if cfg.first_dense_layers:
            segs.append(("attn_mlp", cfg.first_dense_layers, False))
        segs.append(("moe", cfg.num_layers - cfg.first_dense_layers,
                     cfg.scan_layers))
        return StackDesc(tuple(segs))
    if cfg.family == "ssm":
        return StackDesc((("mamba", cfg.num_layers, cfg.scan_layers),))
    if cfg.family == "hybrid":
        segs = []
        for _ in range(len(cfg.superblock)):
            pass
        # scan over superblocks: represented as alternating scanned segments
        return StackDesc((("hybrid_superblocks", cfg.num_superblocks,
                           cfg.scan_layers),
                          ("hybrid_tail", len(cfg.tail_blocks), False)))
    raise ValueError(cfg.family)


# ----------------------------------------------------------------------------
# params init
# ----------------------------------------------------------------------------


def init_params(cfg, key):
    dtype = dtype_of(cfg)
    V = cfg.padded_vocab()
    keys = jax.random.split(key, 16)
    params = {"embed": embed_init(keys[0], V, cfg.d_model, dtype)}

    def stacked_init(k, kind, n):
        return jax.vmap(lambda kk: _block_init(kk, cfg, kind, dtype))(
            jax.random.split(k, n))

    ki = iter(jax.random.split(keys[1], 32))
    for si, (kind, count, scanned) in enumerate(stack_desc(cfg).segments):
        if count == 0:
            continue
        if kind == "hybrid_superblocks":
            sb = {}
            for bi, bkind in enumerate(cfg.superblock):
                kk = next(ki)
                sb[f"b{bi}_{bkind}"] = (
                    jax.vmap(lambda x: _block_init(
                        x, cfg, _sb_kind(bkind), dtype))(
                            jax.random.split(kk, count)))
            params[f"blocks{si}"] = sb
        elif kind == "hybrid_tail":
            for bi, bkind in enumerate(cfg.tail_blocks):
                params[f"tail{bi}"] = _block_init(
                    next(ki), cfg,
                    _sb_kind(bkind), dtype)
        else:
            # params are ALWAYS stacked; cfg.scan_layers only selects the
            # execution strategy (lax.scan vs unrolled indexing)
            params[f"blocks{si}"] = stacked_init(next(ki), kind, count)
    params["final_norm"] = {"scale": jnp.zeros((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense_init(keys[2], (cfg.d_model, V),
                                                  dtype=dtype)}
    if cfg.family == "vlm":
        params["mm_projector"] = {
            "w1": dense_init(keys[3], (1024, cfg.d_model), dtype=dtype),
            "w2": dense_init(keys[4], (cfg.d_model, cfg.d_model), dtype=dtype),
        }
    if cfg.mtp_depth > 0:
        params["mtp_proj"] = {"kernel": dense_init(
            keys[5], (2 * cfg.d_model, cfg.d_model), dtype=dtype)}
        params["mtp_block"] = _block_init(keys[6], cfg, "attn_mlp", dtype)
        params["mtp_norm"] = {"scale": jnp.zeros((cfg.d_model,), dtype)}
    return params


# ----------------------------------------------------------------------------
# forward (train / prefill)
# ----------------------------------------------------------------------------


def _run_stack(cfg, params, x, positions):
    """Shared trunk for train/prefill. Returns (x, total_aux)."""
    total_aux = jnp.zeros((), jnp.float32)
    for si, (kind, count, scanned) in enumerate(stack_desc(cfg).segments):
        if count == 0:
            continue
        if kind == "hybrid_superblocks":
            sb = params[f"blocks{si}"]

            def superblock(x, pj):
                layer_p, j = pj
                aux = jnp.zeros((), jnp.float32)
                for bi, bkind in enumerate(cfg.superblock):
                    bk = _sb_kind(bkind)
                    x, a = _block_train(cfg, bk, layer_p[f"b{bi}_{bkind}"], x,
                                        positions, window=cfg.local_window,
                                        layer=_layer(cfg, j, bi))
                    aux = aux + a
                return x, aux

            body = maybe_remat(superblock, cfg)
            if scanned:
                x, auxs = jax.lax.scan(body, x, (sb, jnp.arange(count)))
                total_aux = total_aux + jnp.sum(auxs)
            else:
                for j in range(count):
                    x, a = body(x, (jax.tree.map(lambda t: t[j], sb), j))
                    total_aux = total_aux + a
        elif kind == "hybrid_tail":
            for bi, bkind in enumerate(cfg.tail_blocks):
                bk = _sb_kind(bkind)
                x, a = _block_train(cfg, bk, params[f"tail{bi}"], x, positions,
                                    window=cfg.local_window)
                total_aux = total_aux + a
        else:
            def body_fn(x, layer_p, _kind=kind):
                return _block_train(cfg, _kind, layer_p, x, positions)

            body = maybe_remat(body_fn, cfg)
            if scanned:
                x, auxs = jax.lax.scan(body, x, params[f"blocks{si}"])
                total_aux = total_aux + jnp.sum(auxs)
            else:
                for j in range(count):
                    x, a = body(x, jax.tree.map(lambda t: t[j],
                                                params[f"blocks{si}"]))
                    total_aux = total_aux + a
    return x, total_aux


@jax.named_scope("lm.embed")
def embed_tokens(cfg, params, tokens):
    x = params["embed"]["table"][tokens]
    if cfg.emb_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


@jax.named_scope("lm.head")
def unembed(cfg, params, x):
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["lm_head"]["kernel"])
    if cfg.tie_embeddings:
        logits = x @ table.T
    else:
        logits = x @ table
    return shard(logits, ("batch", "seq", "vocab"))


def forward_train(cfg, params, batch):
    """batch: {'tokens': (B, L)} (+ 'patch_embeds' (B, P, 1024) for vlm).
    Returns (loss, metrics)."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    if cfg.family == "vlm":
        patches = batch["patch_embeds"]
        pe = jax.nn.gelu(patches.astype(dtype_of(cfg)) @ params["mm_projector"]["w1"])
        pe = pe @ params["mm_projector"]["w2"]
        te = embed_tokens(cfg, params, tokens)
        x = jnp.concatenate([pe, te], axis=1)
        P = patches.shape[1]
    else:
        x = embed_tokens(cfg, params, tokens)
        P = 0
    L = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))
    x = shard(x, ("batch", "seq", "embed"))
    x, aux = _run_stack(cfg, params, x, positions)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps, plus_one=True)
    logits = unembed(cfg, params, x)
    # next-token loss over the text region
    txt_logits = logits[:, P:, :]
    loss = cross_entropy_loss(txt_logits[:, :-1], tokens[:, 1:],
                              cfg.padded_vocab())
    if cfg.mtp_depth > 0:
        loss = loss + 0.3 * _mtp_loss(cfg, params, x[:, P:], tokens, positions[:, P:])
    loss = loss + aux
    return loss, {"aux": aux}


def _mtp_loss(cfg, params, h, tokens, positions):
    """DeepSeek-V3-style 1-step multi-token prediction head."""
    emb_next = embed_tokens(cfg, params, tokens)
    # combine h_t with emb(t+1) to predict t+2
    hcat = jnp.concatenate([h[:, :-1], emb_next[:, 1:]], axis=-1)
    hp = hcat @ params["mtp_proj"]["kernel"]
    hp, _ = _block_train(cfg, "attn_mlp", params["mtp_block"], hp,
                         positions[:, :-1])
    hp = rms_norm(hp, params["mtp_norm"]["scale"], cfg.norm_eps, plus_one=True)
    logits = unembed(cfg, params, hp)
    return cross_entropy_loss(logits[:, :-1], tokens[:, 2:], cfg.padded_vocab())


def forward_prefill(cfg, params, batch):
    """Prefill: logits for the last position (cacheless dry-run form —
    cache construction is exercised by serve.engine)."""
    cfgp = cfg
    tokens = batch["tokens"]
    B = tokens.shape[0]
    if cfg.family == "vlm":
        patches = batch["patch_embeds"]
        pe = jax.nn.gelu(patches.astype(dtype_of(cfg)) @ params["mm_projector"]["w1"])
        pe = pe @ params["mm_projector"]["w2"]
        x = jnp.concatenate([pe, embed_tokens(cfg, params, tokens)], axis=1)
    else:
        x = embed_tokens(cfg, params, tokens)
    L = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))
    x, _ = _run_stack(cfgp, params, x, positions)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps, plus_one=True)
    return unembed(cfg, params, x[:, -1:, :])


# ----------------------------------------------------------------------------
# decode
# ----------------------------------------------------------------------------


def init_decode_cache(cfg, B, S):
    dtype = dtype_of(cfg)
    cache = {}
    for si, (kind, count, scanned) in enumerate(stack_desc(cfg).segments):
        if count == 0:
            continue
        if kind == "hybrid_superblocks":
            sb = {}
            for bi, bkind in enumerate(cfg.superblock):
                bk = _sb_kind(bkind)
                one = _block_cache_init(cfg, bk, B, S, dtype)
                sb[f"b{bi}_{bkind}"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one)
            cache[f"blocks{si}"] = sb
        elif kind == "hybrid_tail":
            for bi, bkind in enumerate(cfg.tail_blocks):
                bk = _sb_kind(bkind)
                cache[f"tail{bi}"] = _block_cache_init(cfg, bk, B, S, dtype)
        else:
            one = _block_cache_init(cfg, kind, B, S, dtype)
            cache[f"blocks{si}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one)
    return cache


def forward_decode(cfg, params, cache, token, pos, S):
    """token: (B, 1) int32; pos: () int32. Returns (logits (B,1,V), cache)."""
    x = embed_tokens(cfg, params, token)
    new_cache = {}
    for si, (kind, count, scanned) in enumerate(stack_desc(cfg).segments):
        if count == 0:
            continue
        if kind == "hybrid_superblocks":
            sb_p = params[f"blocks{si}"]
            sb_c = cache[f"blocks{si}"]

            def sb_body(x, pcj):
                layer_p, layer_c, j = pcj
                new_c = {}
                for bi, bkind in enumerate(cfg.superblock):
                    bk = _sb_kind(bkind)
                    key = f"b{bi}_{bkind}"
                    x, c = _block_decode(cfg, bk, layer_p[key], x, pos,
                                         layer_c[key], S, window=cfg.local_window,
                                         layer=_layer(cfg, j, bi))
                    new_c[key] = c
                return x, new_c

            if scanned:
                x, nc = jax.lax.scan(sb_body, x,
                                     (sb_p, sb_c, jnp.arange(count)))
            else:
                ncs = []
                for j in range(count):
                    x, c = sb_body(x, jax.tree.map(lambda t: t[j], (sb_p, sb_c))
                                   + (j,))
                    ncs.append(c)
                nc = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
            new_cache[f"blocks{si}"] = nc
        elif kind == "hybrid_tail":
            for bi, bkind in enumerate(cfg.tail_blocks):
                bk = _sb_kind(bkind)
                x, c = _block_decode(cfg, bk, params[f"tail{bi}"], x, pos,
                                     cache[f"tail{bi}"], S,
                                     window=cfg.local_window)
                new_cache[f"tail{bi}"] = c
        else:
            def body(x, pc, _kind=kind):
                layer_p, layer_c = pc
                return _block_decode(cfg, _kind, layer_p, x, pos, layer_c, S)

            if scanned:
                x, nc = jax.lax.scan(body, x, (params[f"blocks{si}"],
                                               cache[f"blocks{si}"]))
            else:
                ncs = []
                for j in range(count):
                    x, c = body(x, jax.tree.map(
                        lambda t: t[j], (params[f"blocks{si}"],
                                         cache[f"blocks{si}"])))
                    ncs.append(c)
                nc = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
            new_cache[f"blocks{si}"] = nc
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps, plus_one=True)
    logits = unembed(cfg, params, x)
    return logits, new_cache


def forward_prefill_into_cache(cfg, params, cache, tokens, lengths, S,
                               tree_mask=None):
    """Fused prefill: run the whole (right-padded) prompt batch through one
    forward pass AND write each row's KV / recurrent state into the decode
    cache — replacing the token-by-token decode replay loop.

    tokens: (B, Lp) int32, right-padded; lengths: (B,) int32 — rows with
    lengths[b] == 0 are not part of this prefill group and keep their cache
    untouched (they may belong to other live serve slots). tree_mask (topo
    only) applies a packed-forest FTFI mask over the prompt region. Returns
    (logits (B, V) for each row's last real token, new_cache)."""
    B, Lp = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(Lp, dtype=jnp.int32)[None], (B, Lp))
    x = shard(x, ("batch", "seq", "embed"))
    new_cache = {}
    for si, (kind, count, scanned) in enumerate(stack_desc(cfg).segments):
        if count == 0:
            continue
        if kind == "hybrid_superblocks":
            sb_p = params[f"blocks{si}"]
            sb_c = cache[f"blocks{si}"]

            def sb_body(x, pcj):
                layer_p, layer_c, j = pcj
                new_c = {}
                for bi, bkind in enumerate(cfg.superblock):
                    bk = _sb_kind(bkind)
                    key = f"b{bi}_{bkind}"
                    x, c = _block_prefill(cfg, bk, layer_p[key], x, positions,
                                          lengths, layer_c[key], S,
                                          window=cfg.local_window,
                                          layer=_layer(cfg, j, bi))
                    new_c[key] = c
                return x, new_c

            if scanned:
                x, nc = jax.lax.scan(sb_body, x,
                                     (sb_p, sb_c, jnp.arange(count)))
            else:
                ncs = []
                for j in range(count):
                    x, c = sb_body(x, jax.tree.map(lambda t: t[j], (sb_p, sb_c))
                                   + (j,))
                    ncs.append(c)
                nc = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
            new_cache[f"blocks{si}"] = nc
        elif kind == "hybrid_tail":
            for bi, bkind in enumerate(cfg.tail_blocks):
                bk = _sb_kind(bkind)
                x, c = _block_prefill(cfg, bk, params[f"tail{bi}"], x,
                                      positions, lengths, cache[f"tail{bi}"],
                                      S, window=cfg.local_window)
                new_cache[f"tail{bi}"] = c
        else:
            def body(x, pc, _kind=kind):
                layer_p, layer_c = pc
                return _block_prefill(cfg, _kind, layer_p, x, positions,
                                      lengths, layer_c, S,
                                      tree_mask=tree_mask)

            if scanned:
                x, nc = jax.lax.scan(body, x, (params[f"blocks{si}"],
                                               cache[f"blocks{si}"]))
            else:
                ncs = []
                for j in range(count):
                    x, c = body(x, jax.tree.map(
                        lambda t: t[j], (params[f"blocks{si}"],
                                         cache[f"blocks{si}"])))
                    ncs.append(c)
                nc = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
            new_cache[f"blocks{si}"] = nc
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps, plus_one=True)
    last = jnp.clip(lengths - 1, 0, Lp - 1)
    x_last = x[jnp.arange(B), last][:, None, :]  # (B, 1, d)
    logits = unembed(cfg, params, x_last)[:, 0]
    return logits, new_cache
