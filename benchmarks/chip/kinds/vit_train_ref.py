"""Plain float32 reference of one TopoViT training run, and the seeded
weights and batches both sides start from. Imports nothing of `repro`.

The model (FTFI paper Sec. 4.4, Topological Performer ViT): patches are
projected and given learned position embeddings; each layer is pre-norm
(RMSNorm with a (1 + scale) gain) masked linear attention and a gated GELU
MLP, each added to the residual stream; a final RMSNorm, mean pooling and a
linear head give the logits. Attention per head:

    phi(x) = relu(x * hd^-1/4) + 1e-6,   q scaled by e^{logit_scale} first
    A = (phi(Q) phi(K)^T) * M,           M_ij = exp(poly(dist_T(i, j) / 16))
    out = (A V) / rowsum(A)              (rowsums below 1e-6 read 1e-6)

poly(x) = a0 - softplus(a1) x - softplus(a2) x^2 from three learned scalars
per layer shared by the heads, and T is the minimum spanning tree of the
14 x 14 patch grid with unit edge weights, chosen by Kruskal with ties
broken by edge index (horizontal edges row by row, then vertical ones).
The dense mask is built here from exact tree distances. The loss is the
mean softmax cross-entropy; AdamW with global-norm clipping and a warm-up
plus cosine schedule updates the weights.

Every matmul goes through `mm`: float32 at `highest` for the reference,
inputs rounded to float8 (e4m3) for the control.
"""
from __future__ import annotations

import numpy as np

LEAF_SEP = "/"


# ----------------------------------------------------------------------------
# seeded weights and batches (the inputs of both sides)
# ----------------------------------------------------------------------------


def prng_key(seed: int, stream: int):
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def init_params(seed: int, c: dict, dtype):
    """The weights, made on the device in one jitted call, in `dtype`:
    normal / sqrt(fan_in) matrices, 0.02-normal position embeddings, zero
    norm gains and biases, mask scalars (0, -1, 0) and logit scale 0."""
    import jax
    import jax.numpy as jnp

    d, H, KV, hd, ff = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                        c["head_dim"], c["d_ff"])
    nl, L, pd, nc = (c["num_layers"], c["num_prefix_embeddings"],
                     c["patch_dim"], c["num_classes"])

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(shape[-2])).astype(dtype)

    @jax.jit
    def gen(key):
        ks = iter(jax.random.split(key, 16))
        zeros = lambda *s: jnp.zeros(s, dtype)  # noqa: E731
        coeffs = np.zeros((nl, c["topo_degree"] + 1), np.float32)
        coeffs[:, 1] = -1.0
        return {
            "patch_proj": {"kernel": dense(next(ks), (pd, d)),
                           "bias": zeros(d)},
            "pos_embed": (jax.random.normal(next(ks), (L, d), jnp.float32)
                          * 0.02).astype(dtype),
            "blocks": {
                "attn_norm": {"scale": zeros(nl, d)},
                "attn": {"wq": dense(next(ks), (nl, d, H * hd)),
                         "wk": dense(next(ks), (nl, d, KV * hd)),
                         "wv": dense(next(ks), (nl, d, KV * hd)),
                         "wo": dense(next(ks), (nl, H * hd, d))},
                "topo": {"coeffs": jnp.asarray(coeffs, dtype),
                         "logit_scale": zeros(nl)},
                "mlp_norm": {"scale": zeros(nl, d)},
                "mlp": {"w_gate": dense(next(ks), (nl, d, ff)),
                        "w_in": dense(next(ks), (nl, d, ff)),
                        "w_out": dense(next(ks), (nl, ff, d))},
            },
            "final_norm": {"scale": zeros(d)},
            "head": {"kernel": dense(next(ks), (d, nc)), "bias": zeros(nc)},
        }

    return gen(prng_key(seed, 0))


def make_batches(seed: int, c: dict, pool: int, batch: int):
    """`pool` seeded batches: (patches (pool, B, L, patch_dim) f32, labels
    (pool, B) int32), made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    shape = (pool, batch, c["num_prefix_embeddings"], c["patch_dim"])

    @jax.jit
    def gen(key):
        kp, kl = jax.random.split(key)
        return (jax.random.normal(kp, shape, jnp.float32),
                jax.random.randint(kl, (pool, batch), 0, c["num_classes"],
                                   jnp.int32))

    return gen(prng_key(seed, 1))


# ----------------------------------------------------------------------------
# the patch grid's tree
# ----------------------------------------------------------------------------


def grid_tree_distances(side: int) -> np.ndarray:
    """(L, L) float64 path distances through the Kruskal MST of the
    side x side unit-weight grid graph."""
    idx = np.arange(side * side).reshape(side, side)
    u = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    v = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    parent = list(range(side * side))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    n = side * side
    adj = [[] for _ in range(n)]
    for a, b in zip(u.tolist(), v.tolist()):  # equal weights: edge order
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb
            adj[a].append(b)
            adj[b].append(a)
    D = np.zeros((n, n))
    for s in range(n):
        seen, frontier = {s}, [s]
        while frontier:
            nxt = []
            for a in frontier:
                for b in adj[a]:
                    if b not in seen:
                        seen.add(b)
                        D[s, b] = D[s, a] + 1.0
                        nxt.append(b)
            frontier = nxt
    return D


# ----------------------------------------------------------------------------
# the model, the loss and the optimizer
# ----------------------------------------------------------------------------


def matmul(rounding: str):
    """`mm(subscripts, a, b)`: an einsum at float32 `highest`, with its
    inputs first cast to float8 e4m3 for rounding="fp8" (no scaling: the
    cast's gradient casts the backward pass's cotangents too)."""
    import jax
    import jax.numpy as jnp

    def mm(subscripts, a, b):
        if rounding == "fp8":
            a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return jnp.einsum(subscripts, a, b,
                          precision=jax.lax.Precision.HIGHEST)

    return mm


def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale)


def _gelu(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def _softplus(x):
    import jax.numpy as jnp

    return jnp.logaddexp(x, 0.0)


def logits_fn(c: dict, D, mm):
    """(params, patches) -> logits, all in float32."""
    import jax
    import jax.numpy as jnp

    H, hd, eps = c["num_heads"], c["head_dim"], c["norm_eps"]
    scale_d = c["topo_dist_scale"]
    Ds = jnp.asarray(D * scale_d, jnp.float32)

    def phi(x):
        return jax.nn.relu(x * hd ** -0.25) + 1e-6

    def attention(p, h):
        B, L, _ = h.shape
        q = mm("bld,de->ble", h, p["attn"]["wq"]).reshape(B, L, H, hd)
        k = mm("bld,de->ble", h, p["attn"]["wk"]).reshape(B, L, H, hd)
        v = mm("bld,de->ble", h, p["attn"]["wv"]).reshape(B, L, H, hd)
        a = p["topo"]["coeffs"]
        poly = a[0] - _softplus(a[1]) * Ds
        for t in range(2, a.shape[0]):
            poly = poly - _softplus(a[t]) * Ds ** t
        M = jnp.exp(poly)
        qf = phi(q * jnp.exp(p["topo"]["logit_scale"]))
        kf = phi(k)
        A = mm("bihm,bjhm->bhij", qf, kf) * M
        den = jnp.sum(A, axis=-1)
        den = jnp.where(jnp.abs(den) < 1e-6, 1e-6, den)
        out = mm("bhij,bjhd->bihd", A, v) / den.transpose(0, 2, 1)[..., None]
        return mm("ble,ed->bld", out.reshape(B, L, H * hd), p["attn"]["wo"])

    def mlp(p, h):
        g = mm("bld,df->blf", h, p["w_gate"])
        i = mm("bld,df->blf", h, p["w_in"])
        return mm("blf,fd->bld", _gelu(g) * i, p["w_out"])

    def logits(params, patches):
        x = mm("blp,pd->bld", patches, params["patch_proj"]["kernel"])
        x = x + params["patch_proj"]["bias"] + params["pos_embed"][None]
        blocks = params["blocks"]
        for layer in range(blocks["attn"]["wq"].shape[0]):
            p = jax.tree.map(lambda a, i=layer: a[i], blocks)
            x = x + attention(p, _rms_norm(x, p["attn_norm"]["scale"], eps))
            x = x + mlp(p["mlp"], _rms_norm(x, p["mlp_norm"]["scale"], eps))
        x = _rms_norm(x, params["final_norm"]["scale"], eps)
        pooled = jnp.mean(x, axis=1)
        return (mm("bd,dc->bc", pooled, params["head"]["kernel"])
                + params["head"]["bias"])

    return logits


def loss_fn(c: dict, D, mm):
    import jax
    import jax.numpy as jnp

    logits = logits_fn(c, D, mm)

    def loss(params, patches, labels):
        lg = logits(params, patches)
        picked = jnp.take_along_axis(lg, labels[:, None], axis=1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(lg, axis=1) - picked)

    return loss


def adamw_step(o: dict):
    """(params, mu, nu, step, grads) -> (params, mu, nu, clipped grads):
    global-norm clipping, then AdamW with the warm-up + cosine schedule."""
    import jax
    import jax.numpy as jnp

    def step_fn(params, mu, nu, step, grads):
        leaves = jax.tree.leaves(grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9)),
            grads)
        warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
        prog = jnp.clip((step - o["warmup_steps"])
                        / max(o["total_steps"] - o["warmup_steps"], 1),
                        0.0, 1.0)
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
        lr = o["lr"] * warm * (o["min_lr_ratio"]
                               + (1 - o["min_lr_ratio"]) * cos)
        b1, b2 = o["b1"], o["b2"]
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2)
                                                   + o["eps"])
                                      + o["weight_decay"] * p),
            params, mu, nu)
        return params, mu, nu, grads

    return step_fn


def leaf_norms(tree) -> np.ndarray:
    """float64 norm of every leaf, in `jax.tree.leaves` order."""
    import jax

    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)))
                     for a in jax.tree.leaves(tree)])


def leaf_names(tree) -> list[str]:
    import jax

    return [LEAF_SEP.join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def reference_run(c: dict, o: dict, seed: int, pool: int, batch: int,
                  steps: int, rounding: str = "f32") -> dict:
    """The reference's first `steps` training steps from the seeded
    weights on batches 0 .. steps-1: each step's loss, the norm of every
    leaf of the first (clipped) gradient, and the norm of every leaf's
    change after the last step."""
    import jax
    import jax.numpy as jnp

    side = int(round(np.sqrt(c["num_prefix_embeddings"])))
    D = grid_tree_distances(side)
    mm = matmul(rounding)
    p0 = jax.tree.map(lambda a: a.astype(jnp.float32),
                      init_params(seed, c, jnp.dtype(c["dtype"])))
    patches, labels = make_batches(seed, c, pool, batch)
    grad = jax.jit(jax.value_and_grad(loss_fn(c, D, mm)))
    upd = jax.jit(adamw_step(o))
    params = p0
    mu = jax.tree.map(jnp.zeros_like, p0)
    nu = jax.tree.map(jnp.zeros_like, p0)
    losses, g1 = [], None
    for s in range(steps):
        loss, g = grad(params, patches[s], labels[s])
        params, mu, nu, gc = upd(params, mu, nu, jnp.float32(s + 1), g)
        losses.append(float(loss))
        if s == 0:
            g1 = leaf_norms(gc)
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return {"losses": losses, "grad_norms": g1, "change_norms": delta,
            "leaves": leaf_names(p0)}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers `correct` compares (each a gap relative to the
    reference):

    loss_gap      the largest |loss - ref| / |ref| over the steps
    grad_gap      the worst leaf's |norm - ref norm| of the first gradient,
                  over the larger of that leaf's ref norm and the median
                  leaf's
    change_gap    the same for each leaf's change after the steps, leaving
                  out leaves whose ref gradient is under a thousandth of
                  the median leaf's (nought to rounding: they move under
                  Adam by round-off alone)
    """
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    gp, gr = prog["grad_norms"], ref["grad_norms"]
    cp, cr = prog["change_norms"], ref["change_norms"]
    g_med, c_med = np.median(gr), np.median(cr)
    moved = gr >= 1e-3 * g_med
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": float(np.max(np.abs(gp - gr) / np.maximum(gr, g_med))),
        "change_gap": float(np.max((np.abs(cp - cr)
                                    / np.maximum(cr, c_med))[moved])),
    }
