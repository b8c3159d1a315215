"""Plain float32 reference of MiniMax-Text-01's prefill, the seeded weights
and prompts both sides start from, and the numbers `correct` compares.
Imports nothing of `repro`.

The model (the public `minimax_text_01` modeling code and config.json), at
published layer l of N, residual stream x:

    lightning (attn_type 0), H heads of hd:
        [q|k|v]_h = SiLU(x W_qkv)        (columns per head: q, k, v)
        s_{h,l}   = 2^(-8(h+1)/H) (1 - l/(N-1) + 1e-5)
        o_{h,i}   = sum_{j<=i} exp(-s_{h,l}(i-j)) (q_{h,i}.k_{h,j}) v_{h,j}
        y         = W_out(sigmoid(x W_g) * RMSNorm(concat_h o_h))
    softmax (attn_type 1): GQA, RoPE (theta, halves) on the first
        rotary_dim dims of each head, causal softmax scaled by 1/sqrt(hd)
    block (postnorm): h = RMSNorm(x); x = alpha h + beta Attn(h);
                      h = RMSNorm(x); x = alpha h + beta MoE(h)
    MoE: p = softmax(h W_r) over all E experts, top k renormalized to 1;
         sum over the chosen experts that are held of p W2(SiLU(W1 h) W3 h)

then a final RMSNorm and the untied head over the vocabulary slice.
RMSNorm: x / sqrt(mean(x^2) + eps) * (1 + g), the gain stored as its offset
from 1 (the program's layout).

It computes in blocks of `rows` rows, with each layer's weights upcast to
float32 when the layer runs, so that it fits beside the program's weights:
the lightning layer is the masked quadratic form inside each block, and the
exact decayed sum sum_{j < b0} exp(-s(i-j)) k_j v_j^T of the rows before
block b0 is carried from block to block (every exponent <= 0); the softmax
layer runs each query block over the key blocks at or before it with a
running maximum and sum. A chosen expert's rows are gathered on the host's
count of them, in tiles of TILE rows (padding rows of weight 0). Every
matmul goes through `mm`: float32 at `highest`, or with its inputs rounded
to float8 (e4m3) for the fp8 control. The other controls change the
lightning layer: `no_decay` sets s = 0, `normalized` divides o_i by
q_i . sum_{j<=i} exp(-s(i-j)) k_j.

Routing takes the experts it is given, where it is given them: the
program's own top-k choices, read from its route log. Rounding moves a
router logit by a little, and where a token's k-th and (k+1)-th experts lie
that close, the program and an independent reference choose differently,
and that token's output differs by an expert's share. So the comparison
follows the program's choices, and the route gap checks them: by how much
the logit of a chosen expert falls below the reference's k-th largest.
"""
from __future__ import annotations

import numpy as np

VOCAB_PAD = 256  # the program pads its embedding and head rows to this
ROUTER_STD = 0.02  # the program's router init (`moe.moe_init`), before
# `balance_routers`


def prng_key(seed: int, stream: int):
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


# ----------------------------------------------------------------------------
# the configuration's sizes and the program's weight layout
# ----------------------------------------------------------------------------


def sizes(c: dict) -> dict:
    n = c["num_hidden_layers"]
    kinds = ["lightning" if t == 0 else "softmax"
             for t in c["attn_type_list"][:n]]
    V = c["vocab_size"]
    return {"d": c["hidden_size"], "H": c["num_attention_heads"],
            "KV": c["num_key_value_heads"], "hd": c["head_dim"],
            "ff": c["intermediate_size"], "held": c["num_local_experts"],
            "E": c["published"]["num_local_experts"],
            "N": c["published"]["num_hidden_layers"],
            "K": c["num_experts_per_tok"], "V": V,
            "Vp": -(-V // VOCAB_PAD) * VOCAB_PAD, "kinds": kinds,
            "eps": c["rms_norm_eps"], "theta": c["rope_theta"],
            "rd": c["rotary_dim"],
            "alpha": c["layernorm_linear_attention_alpha"],
            "beta": c["layernorm_linear_attention_beta"]}


def block_key(l: int, kind: str) -> str:
    return f"b{l}_{kind}"


def init_params(seed: int, c: dict, dtype, shardings=None):
    """The weights in the program's layout (one period, each block's leaves
    stacked on a leading axis of 1), made on the device in one jitted call:
    normal / sqrt(fan_in) matrices in `dtype`, the router in float32 at
    ROUTER_STD-normal (`balance_routers` then balances it), the embedding
    0.02-normal, norm gains
    1 + 0.1-normal.
    `shardings`, a matching tree of shardings, places them."""
    import jax
    import jax.numpy as jnp

    z = sizes(c)
    d, H, KV, hd, ff, n = z["d"], z["H"], z["KV"], z["hd"], z["ff"], \
        z["held"]

    def gen(key):
        ks = iter(jax.random.split(key, 16 * len(z["kinds"]) + 8))

        def dense(shape):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    / np.sqrt(shape[-2])).astype(dtype)

        def gain(shape):
            return (0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                    ).astype(dtype)

        blocks = {}
        for l, kind in enumerate(z["kinds"]):
            if kind == "lightning":
                attn = {"w_qkv": dense((1, d, 3 * H * hd)),
                        "w_gate": dense((1, d, H * hd)),
                        "wo": dense((1, H * hd, d)),
                        "out_norm": gain((1, H * hd))}
            else:
                attn = {"wq": dense((1, d, H * hd)),
                        "wk": dense((1, d, KV * hd)),
                        "wv": dense((1, d, KV * hd)),
                        "wo": dense((1, H * hd, d))}
            blocks[block_key(l, kind)] = {
                "attn": attn, "attn_norm": {"scale": gain((1, d))},
                "mlp_norm": {"scale": gain((1, d))},
                "moe": {"router": ROUTER_STD
                        * jax.random.normal(next(ks), (1, d, z["E"]),
                                            jnp.float32),
                        "experts_w_gate": dense((1, n, d, ff)),
                        "experts_w_in": dense((1, n, d, ff)),
                        "experts_w_out": dense((1, n, ff, d))}}
        return {"embed": {"table": (0.02 * jax.random.normal(
                    next(ks), (z["Vp"], d), jnp.float32)).astype(dtype)},
                "blocks0": blocks,
                "final_norm": {"scale": gain((d,))},
                "lm_head": {"kernel": dense((d, z["Vp"]))}}

    return jax.jit(gen, out_shardings=shardings)(prng_key(seed, 0))


def call_order(seed: int, shapes: list) -> list:
    """The seeded order in which the window cycles the call shapes."""
    rng = np.random.default_rng([seed, 1])
    return [tuple(shapes[i]) for i in rng.permutation(len(shapes))]


def prompts(seed: int, c: dict, shape, pool: int) -> np.ndarray:
    """`pool` prompts of `shape` (B, L), ids uniform over the slice."""
    rng = np.random.default_rng([seed, 2, shape[0], shape[1]])
    return rng.integers(0, c["vocab_size"], (pool,) + tuple(shape),
                        dtype=np.int32)


def next_ids(seed: int, c: dict, call: int, B: int, n: int) -> np.ndarray:
    """The ids fed to the `n` decode steps after call number `call`."""
    rng = np.random.default_rng([seed, 3, call])
    return rng.integers(0, c["vocab_size"], (B, n), dtype=np.int32)


def balance_routers(params, c: dict, seed: int, rows: int):
    """The weights with each layer's router balanced over the experts, as a
    trained router is (MiniMax-Text-01 trains with a load-balancing loss).
    A random router is not: the SiLU features give every token's router
    input h a common part, whose product with each expert's column sends up
    to 2.5 times the mean load to some experts, and the seed decides which,
    so that the held experts' work changed from seed to seed. The reference
    runs `rows` seeded ids layer by layer; at each layer the router's
    columns lose their component along the mean of h over those ids and are
    scaled so that each expert's logits spread over the ids as the experts'
    mean does (the init's scale), and the layer then routes with them."""
    import jax

    ids = np.random.default_rng([seed, 4]).integers(
        0, c["vocab_size"], rows, dtype=np.int32)
    routers: dict = {}
    with jax.default_matmul_precision("highest"):
        Reference(c, rows=rows, max_len=rows)._row(params, ids, 1, None,
                                                   routers)
    blocks = dict(params["blocks0"])
    for l, kind in enumerate(sizes(c)["kinds"]):
        blk = blocks[block_key(l, kind)]
        router = jax.device_put(routers[l], blk["moe"]["router"].sharding)
        blocks[block_key(l, kind)] = {**blk, "moe": {**blk["moe"],
                                                     "router": router}}
    return {**params, "blocks0": blocks}


# ----------------------------------------------------------------------------
# the reference, in blocks of rows
# ----------------------------------------------------------------------------


def matmul(rounding: str):
    import jax.numpy as jnp

    def mm(subscripts, a, b):
        if rounding == "fp8":
            a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return jnp.einsum(subscripts, a, b)

    return mm


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + g)


def slopes(z: dict, layer: int) -> np.ndarray:
    base = 2.0 ** (-8.0 * (np.arange(z["H"]) + 1) / z["H"])
    return (base * (1.0 - layer / (z["N"] - 1) + 1e-5)).astype(np.float32)


def _lightning(z, mm, rows: int, normalized: bool):
    """(p, x (Lp, d), s (H,), nb) -> x after the attention half of the block
    in its first nb blocks of `rows` rows (the rest stay 0)."""
    import jax
    import jax.numpy as jnp

    H, hd, d, R = z["H"], z["hd"], z["d"], rows
    a, b, eps = z["alpha"], z["beta"], z["eps"]

    def f(p, x, s, nb):
        i = jnp.arange(R, dtype=jnp.float32)
        dist = i[:, None] - i[None, :]
        intra = jnp.where(dist >= 0, jnp.exp(-s[:, None, None]
                                             * jnp.maximum(dist, 0)), 0.0)
        dec_q = jnp.exp(-s[None, :] * (i[:, None] + 1))      # (R, H)
        dec_k = jnp.exp(-s[None, :] * (R - 1 - i[:, None]))  # (R, H)
        g_R = jnp.exp(-s * R)

        def block(n, carry):
            S, zk, out = carry  # (H, hd, hd), (H, hd), (Lp, d)
            xr = jax.lax.dynamic_slice_in_dim(x, n * R, R)
            h = _rms(xr, p["attn_norm"]["scale"], eps)
            qkv = jax.nn.silu(mm("rd,de->re", h, p["attn"]["w_qkv"]))
            qkv = qkv.reshape(R, H, 3, hd)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            sc = mm("ihd,jhd->hij", q, k) * intra
            qd = q * dec_q[:, :, None]
            o = mm("hij,jhd->ihd", sc, v) + mm("ihd,hde->ihe", qd, S)
            if normalized:
                den = jnp.sum(sc, axis=-1).T + jnp.einsum("ihd,hd->ih", qd, zk)
                den = jnp.where(jnp.abs(den) < 1e-6, 1e-6, den)
                o = o / den[:, :, None]
            kd = k * dec_k[:, :, None]
            S = g_R[:, None, None] * S + mm("jhd,jhe->hde", kd, v)
            zk = g_R[:, None] * zk + jnp.sum(kd, axis=0)
            o = _rms(o.reshape(R, H * hd), p["attn"]["out_norm"], eps)
            gate = jax.nn.sigmoid(mm("rd,de->re", h, p["attn"]["w_gate"]))
            y = mm("re,ed->rd", gate * o, p["attn"]["wo"])
            out = jax.lax.dynamic_update_slice_in_dim(out, a * h + b * y,
                                                      n * R, 0)
            return S, zk, out

        carry = (jnp.zeros((H, hd, hd), jnp.float32),
                 jnp.zeros((H, hd), jnp.float32), jnp.zeros_like(x))
        return jax.lax.fori_loop(0, nb, block, carry)[2]

    return f


def _rope(x, pos, z):
    import jax.numpy as jnp

    rd = z["rd"]
    inv = 1.0 / z["theta"] ** (np.arange(0, rd, 2, dtype=np.float64) / rd)
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(inv, jnp.float32)
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rd:]],
                           axis=-1)


def _softmax(z, mm, rows: int):
    """(p, x (Lp, d), nb) -> x after the attention half of the block in its
    first nb blocks of `rows` rows (the rest stay 0)."""
    import jax
    import jax.numpy as jnp

    H, KV, hd, d, R = z["H"], z["KV"], z["hd"], z["d"], rows
    G = H // KV
    a, b, eps = z["alpha"], z["beta"], z["eps"]

    def f(p, x, nb):
        Lp = x.shape[0]
        h = _rms(x, p["attn_norm"]["scale"], eps)
        pos = jnp.arange(Lp)
        k = _rope(mm("ld,de->le", h, p["attn"]["wk"]).reshape(Lp, KV, hd),
                  pos, z)
        v = mm("ld,de->le", h, p["attn"]["wv"]).reshape(Lp, KV, hd)
        tri = jnp.arange(R)[:, None] >= jnp.arange(R)[None, :]

        def qblock(i, out):
            hi = jax.lax.dynamic_slice_in_dim(h, i * R, R)
            q = _rope(mm("rd,de->re", hi, p["attn"]["wq"]).reshape(R, H, hd),
                      i * R + jnp.arange(R), z) / np.sqrt(hd)
            q = q.reshape(R, KV, G, hd)

            def body(j, carry):
                m, l, acc = carry
                kj = jax.lax.dynamic_slice_in_dim(k, j * R, R)
                vj = jax.lax.dynamic_slice_in_dim(v, j * R, R)
                s = mm("qkgd,skd->kgqs", q, kj)
                s = jnp.where((j < i) | tri, s, -1e30)
                m2 = jnp.maximum(m, jnp.max(s, axis=-1))
                e = jnp.exp(s - m2[..., None])
                c = jnp.exp(m - m2)
                return (m2, l * c + jnp.sum(e, axis=-1),
                        acc * c[..., None] + mm("kgqs,skd->kgqd", e, vj))

            m0 = jnp.full((KV, G, R), -1e30, jnp.float32)
            zero = jnp.zeros((KV, G, R), jnp.float32)
            _, l, acc = jax.lax.fori_loop(
                0, i + 1, body, (m0, zero, jnp.zeros((KV, G, R, hd),
                                                     jnp.float32)))
            o = (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(R, H * hd)
            return jax.lax.dynamic_update_slice_in_dim(
                out, a * hi + b * mm("re,ed->rd", o, p["attn"]["wo"]),
                i * R, 0)

        return jax.lax.fori_loop(0, nb, qblock, jnp.zeros_like(x))

    return f


def _router(z, mm):
    """(p, x) -> (h = RMSNorm(x), router logits h W_r (Lp, E))."""

    def f(p, x):
        h = _rms(x, p["mlp_norm"]["scale"], z["eps"])
        return h, mm("ld,de->le", h, p["moe"]["router"])

    return f


def _top(logits, k: int):
    """(values, ids) of the k largest logits of each row, by k passes of
    argmax (a sort compiles slowly on the chip)."""
    import jax.numpy as jnp

    vals, ids = [], []
    for _ in range(k):
        i = jnp.argmax(logits, axis=-1)
        vals.append(jnp.take_along_axis(logits, i[:, None], axis=-1)[:, 0])
        ids.append(i)
        logits = jnp.where(jnp.arange(logits.shape[-1]) == i[:, None],
                           -jnp.inf, logits)
    return jnp.stack(vals, -1), jnp.stack(ids, -1).astype(jnp.int32)


def _gates(z):
    """(logits (Lp, E), ids (Lp, k), Lx) -> (weight of each held expert per
    row (Lp, held), the route gap): the top-k weights of softmax(logits) at
    `ids`, renormalized to 1, 0 past row Lx; the gap is the most by which
    the logit of an expert in `ids` falls below the k-th largest logit,
    over the rows before Lx."""
    import jax
    import jax.numpy as jnp

    def f(logits, ids, Lx):
        pick = ids[:, :, None] == jnp.arange(logits.shape[-1])  # (Lp, k, E)
        probs = jax.nn.softmax(logits, axis=-1)
        g = jnp.sum(jnp.where(pick, probs[:, None, :], 0.0), axis=-1)
        g = g / jnp.sum(g, axis=-1, keepdims=True)
        w = jnp.sum(jnp.where(pick[:, :, :z["held"]], g[:, :, None], 0.0),
                    axis=1)
        valid = jnp.arange(logits.shape[0]) < Lx
        kth = _top(logits, z["K"])[0][:, -1]
        low = jnp.min(jnp.sum(jnp.where(pick, logits[:, None, :], 0.0),
                              axis=-1), axis=-1)
        return (jnp.where(valid[:, None], w, 0.0),
                jnp.max(jnp.where(valid, kth - low, 0.0)))

    return f


def _experts(z, mm):
    """(h, y, rows (held, T), weights (held, T), stacked expert weights)
    -> y + sum_e weight * W2(SiLU(W1 h) W3 h) over one tile of each
    expert's rows; padding rows carry weight 0. One expert at a time, its
    weights upcast to float32 as it runs."""
    import jax
    import jax.numpy as jnp

    def f(h, y, idx, wt, w1, w3, w2):
        def one(y, ex):
            i, w, a, b, c = ex
            a, b, c = (t.astype(jnp.float32) for t in (a, b, c))
            xr = h[i]
            o = mm("rf,fd->rd", jax.nn.silu(mm("rd,df->rf", xr, a))
                   * mm("rd,df->rf", xr, b), c)
            return y.at[i].add(o * w[:, None]), None

        return jax.lax.scan(one, y, (idx, wt, w1, w3, w2))[0]

    return f


_CACHE: dict = {}
TILE = 1024  # rows of one expert per call of the experts piece


class Reference:
    """The reference for one configuration and one variant ("f32", "fp8",
    "no_decay", "normalized"). Every sequence is held in buffers of
    `max_len` tokens (rounded up to `rows`) and computed in its first
    blocks only, so that each jitted piece (the attention half of each
    layer kind, the router, the gates, the experts, the embedding and the
    head) compiles once for all prompt lengths, and is shared by every
    instance of the same sizes, variant, rows and length."""

    def __init__(self, c: dict, variant: str = "f32", rows: int = 2048,
                 max_len: int = 0):
        import json

        import jax
        import jax.numpy as jnp

        self.z = z = sizes(c)
        self.variant, self.rows, self.max_len = variant, rows, max_len
        key = (json.dumps(z, sort_keys=True), variant, rows)
        if key in _CACHE:
            self.fns = _CACHE[key]
            return
        mm = matmul("fp8" if variant == "fp8" else "f32")
        light = _lightning(z, mm, rows, variant == "normalized")
        soft = _softmax(z, mm, rows)

        def up(t):
            return jax.tree.map(lambda a: a[0].astype(jnp.float32), t)

        self.fns = _CACHE[key] = {
            "lightning": jax.jit(lambda p, x, s, nb: light(up(p), x, s, nb)),
            "softmax": jax.jit(lambda p, x, s, nb: soft(up(p), x, nb)),
            "router": jax.jit(lambda p, x: _router(z, mm)(up(p), x)),
            "top": jax.jit(lambda lg: _top(lg, z["K"])[1]),
            "gates": jax.jit(_gates(z)),
            "experts": jax.jit(_experts(z, mm), donate_argnums=(1,)),
            "residual": jax.jit(lambda h, y: z["alpha"] * h + z["beta"] * y),
            "embed": jax.jit(lambda t, ids: t[ids].astype(jnp.float32)),
            "head": jax.jit(lambda x, at, g, w, n: mm(
                "ld,dv->lv", _rms(jax.lax.dynamic_slice_in_dim(x, at, n),
                                  g.astype(jnp.float32), z["eps"]),
                w.astype(jnp.float32)), static_argnums=(4,)),
        }

    def forward(self, params, tokens: np.ndarray, n_last: int,
                routes: list | None = None):
        """(logits (B, n_last, vocab) at each row's last `n_last` positions,
        float64 on the host; the expert ids each row's tokens took in each
        layer, [row][layer] (Lx, k); the route gap). tokens: (B, Lx) ids.
        `routes`, in the same layout, gives the ids to take (the program's
        choices, which the route gap then measures); else the reference
        takes its own top k."""
        import jax

        out = []
        with jax.default_matmul_precision("highest"):
            for b, t in enumerate(tokens):
                out.append(self._row(params, np.asarray(t), n_last,
                                     None if routes is None else routes[b]))
        return (np.stack([o[0] for o in out]), [o[1] for o in out],
                max(o[2] for o in out))

    def last_logits(self, params, tokens: np.ndarray, n_last: int
                    ) -> np.ndarray:
        return self.forward(params, tokens, n_last)[0]

    def _row(self, params, ids, n_last, given, balanced=None):
        """One row's forward. `balanced`, a dict, asks for `balance_routers`'
        routers: each layer routes with its balanced router, stored there
        under the layer's index."""
        import jax
        import jax.numpy as jnp

        z, R, fns = self.z, self.rows, self.fns
        Lx = ids.shape[0]
        Lp = -(-max(Lx, self.max_len) // R) * R
        nb = jnp.asarray(-(-Lx // R), jnp.int32)
        toks = np.zeros(Lp, np.int32)
        toks[:Lx] = ids
        x = fns["embed"](params["embed"]["table"], jnp.asarray(toks))
        took, worst = [], 0.0
        for l, kind in enumerate(z["kinds"]):
            blk = params["blocks0"][block_key(l, kind)]
            s = slopes(z, l) * (0.0 if self.variant == "no_decay" else 1.0)
            x = fns[kind]({k: blk[k] for k in ("attn", "attn_norm")}, x,
                          jnp.asarray(s), nb)
            router = blk["moe"]["router"]
            h, logits = fns["router"](
                {"mlp_norm": blk["mlp_norm"], "moe": {"router": router}}, x)
            if balanced is not None:
                m = jnp.mean(h[:Lx], axis=0)
                w = router[0].astype(jnp.float32)
                w = w - jnp.outer(m, m @ w) / (m @ m)
                sd = jnp.std(h[:Lx] @ w, axis=0)
                w = w * (jnp.mean(sd) / sd)
                balanced[l] = router = w[None].astype(router.dtype)
                h, logits = fns["router"](
                    {"mlp_norm": blk["mlp_norm"], "moe": {"router": router}},
                    x)
            del x  # (Lp, d) float32 buffers are the reference's largest
            if given is None:
                top = fns["top"](logits)
            else:
                top = np.zeros((Lp, z["K"]), np.int32)
                top[:Lx] = given[l]
                top = jnp.asarray(top)
            w, gap = fns["gates"](logits, top, jnp.asarray(Lx, jnp.int32))
            took.append(np.asarray(jax.device_get(top))[:Lx])
            worst = max(worst, float(gap))
            w_host = np.asarray(jax.device_get(w))
            rows = [np.nonzero(w_host[:, e] > 0)[0] for e in range(z["held"])]
            m = blk["moe"]
            y = jnp.zeros_like(h)
            for t0 in range(0, max(r.size for r in rows), TILE):
                idx = np.zeros((z["held"], TILE), np.int32)
                wt = np.zeros((z["held"], TILE), np.float32)
                for e, r in enumerate(rows):
                    r = r[t0:t0 + TILE]
                    idx[e, :r.size], wt[e, :r.size] = r, w_host[r, e]
                y = fns["experts"](h, y, jnp.asarray(idx), jnp.asarray(wt),
                                   m["experts_w_gate"][0],
                                   m["experts_w_in"][0],
                                   m["experts_w_out"][0])
            x = fns["residual"](h, y)
        out = fns["head"](x, jnp.asarray(Lx - n_last, jnp.int32),
                          params["final_norm"]["scale"],
                          params["lm_head"]["kernel"], n_last)
        return (np.asarray(jax.device_get(out), np.float64)[:, :z["V"]],
                took, worst)


def gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """max |prog - ref| over max |ref|."""
    prog = np.asarray(prog, np.float64)
    return float(np.max(np.abs(prog - ref)) / np.max(np.abs(ref)))
