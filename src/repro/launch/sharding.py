"""Logical-axis sharding: MaxText-style rules mapping logical names to mesh axes.

Models annotate activations with `shard(x, ("batch", "seq", "model_ff"))` and
declare parameter specs by path-regex. With no active rules (CPU unit tests)
everything is a no-op, so model code runs unchanged on one device.
"""
from __future__ import annotations

import contextlib
import contextvars
import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis name -> mesh axis (or tuple of mesh axes, or None)
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_shard": "data",  # long-context decode: sequence over data axis
    "embed": None,  # activation d_model stays unsharded (megatron style)
    "seq_sp": "model",  # sequence-parallel residual stream (opt-in per cfg)
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_capacity": None,
    "inner": "model",  # ssm / lru inner channels
    "state": None,
    "kv_lora": None,
    "frames": None,
    # FTFI plan axes (core.plan_shard): the plan's vertex index space is
    # cut into per-device leaf blocks over `data`; cross-bucket source /
    # target group spaces follow their jobs onto the same axis; whole trees
    # of a packed Forest land per shard ("tree"); batched field columns ride
    # the batch axes
    "plan_leaves": "data",
    "cross_src": "data",
    "cross_tgt": "data",
    "tree": "data",
    "field_batch": ("pod", "data"),
}

_rules_var: contextvars.ContextVar = contextvars.ContextVar("rules", default=None)
_mesh_var: contextvars.ContextVar = contextvars.ContextVar("mesh", default=None)


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: dict | None = None, overrides: dict | None = None):
    r = dict(DEFAULT_RULES if rules is None else rules)
    if overrides:
        r.update(overrides)
    # drop mesh axes that don't exist (e.g. "pod" on the single-pod mesh)
    axis_names = set(mesh.axis_names)

    def _filter(ax):
        if ax is None:
            return None
        if isinstance(ax, tuple):
            ax = tuple(a for a in ax if a in axis_names)
            return ax if ax else None
        return ax if ax in axis_names else None

    r = {k: _filter(v) for k, v in r.items()}
    t1 = _rules_var.set(r)
    t2 = _mesh_var.set(mesh)
    try:
        yield
    finally:
        _rules_var.reset(t1)
        _mesh_var.reset(t2)


def logical_to_spec(logical: tuple) -> P:
    rules = _rules_var.get()
    if rules is None:
        return P()
    axes = []
    used = set()
    for name in logical:
        ax = rules.get(name) if name is not None else None
        # an axis may be consumed only once per spec
        if ax is not None:
            key = tuple(ax) if isinstance(ax, tuple) else (ax,)
            if any(a in used for a in key):
                ax = None
            else:
                used.update(key)
        axes.append(ax)
    return P(*axes)


def shard(x, logical: tuple):
    """with_sharding_constraint by logical names; no-op without active rules.

    Axes whose mesh extent does not divide the array dim are dropped (e.g.
    kv_heads=8 on a 16-way model axis -> left to SPMD propagation), which
    avoids GSPMD's 'involuntary full rematerialization' fallback."""
    rules = _rules_var.get()
    mesh = _mesh_var.get()
    if rules is None or mesh is None:
        return x
    spec = logical_to_spec(logical)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fixed = []
    for i, ax in enumerate(spec):
        if ax is None or i >= x.ndim:
            fixed.append(None)
            continue
        total = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            total *= sizes[a]
        fixed.append(ax if x.shape[i] % total == 0 else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


# ----------------------------------------------------------------------------
# parameter specs by path pattern
# ----------------------------------------------------------------------------

# Order matters: first match wins. Patterns run against '/'-joined param paths.
# Leading layer-stack dims are handled by `stacked` markers in the model's
# param builders (they prepend None).
PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed/table", ("vocab", "embed")),
    (r"lm_head/kernel", ("embed", "vocab")),
    (r"(attn|cross_attn)/(wq|wkv|wk|wv)\b.*", ("embed", "heads")),
    (r"(attn|cross_attn)/wo", ("heads", "embed")),
    # lightning: W_qkv columns are per head [q|k|v], so a head shard holds
    # its heads' q, k and v; the output norm spans the heads' channels
    (r"attn/(w_qkv|w_gate)", ("embed", "heads")),
    (r"attn/out_norm", ("heads",)),
    (r"attn/w_dq", ("embed", None)),
    (r"attn/w_uq", (None, "heads")),
    (r"attn/w_dkv", ("embed", None)),
    (r"attn/w_ukv", (None, "heads")),
    (r"attn/w_kr", ("embed", None)),
    (r"mlp/w_(in|gate)", ("embed", "ff")),
    (r"mlp/w_out", ("ff", "embed")),
    (r"moe/router", ("embed", "experts")),
    (r"moe/experts_w_(in|gate)", ("experts", "embed", None)),
    (r"moe/experts_w_out", ("experts", None, "embed")),
    (r"moe/shared_w_(in|gate)", ("embed", "ff")),
    (r"moe/shared_w_out", ("ff", "embed")),
    (r"ssm/in_proj", ("embed", "inner")),
    (r"ssm/conv_w", ("inner", None)),
    (r"ssm/x_proj", ("inner", None)),
    (r"ssm/dt_proj", (None, "inner")),
    (r"ssm/(A_log|D|conv_b|dt_bias)", ("inner",)),
    (r"ssm/out_proj", ("inner", "embed")),
    (r"lru/in_proj", ("embed", "inner")),
    (r"lru/conv_w", ("inner", None)),
    (r"lru/(a_param|gate_w|gate_b|input_w|input_b)", ("inner",)),
    (r"lru/gates", ("inner", None)),
    (r"lru/out_proj", ("inner", "embed")),
    (r"topo/.*", (None,)),  # 3 scalars/layer: replicated
    (r".*(norm|scale|bias)\b.*", (None,)),
    (r".*", (None,)),
]


def param_spec_for_path(path: str, ndim: int, stacked: bool) -> P:
    rules = _rules_var.get()
    if rules is None:
        return P()
    for pat, logical in PARAM_RULES:
        if re.search(pat, path):
            names = list(logical)
            break
    else:  # pragma: no cover
        names = []
    # pad/trim to ndim (minus the stack dim)
    eff = ndim - (1 if stacked else 0)
    if len(names) < eff:
        names = names + [None] * (eff - len(names))
    names = names[:eff]
    if stacked:
        names = [None] + names
    axes = [logical_to_spec((n,))[0] if n else None for n in names]
    return P(*axes)


def tree_param_specs(params, stacked_prefixes=("blocks",)):
    """PartitionSpec pytree matching `params` (path-based rules).
    Non-divisible dims fall back to replication."""
    mesh = _mesh_var.get()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else {}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    specs = []
    for path, leaf in flat:
        spath = "/".join(
            p.key if hasattr(p, "key") else str(p) for p in path)
        stacked = any(spath.startswith(pfx) for pfx in stacked_prefixes)
        spec = param_spec_for_path(spath, leaf.ndim, stacked)
        fixed = []
        for i, ax in enumerate(spec):
            if ax is None:
                fixed.append(None)
                continue
            total = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                total *= sizes.get(a, 1)
            fixed.append(ax if leaf.shape[i] % total == 0 else None)
        specs.append(P(*fixed))
    return jax.tree_util.tree_unflatten(treedef, specs)


def shard_q_heads(x):
    """Attention-query sharding with context-parallel fallback: prefer heads
    over the model axis; if num_heads doesn't divide it (llava 56, qwen2 12,
    recurrentgemma 10), shard the QUERY sequence dim instead — rows of the
    attention matrix are independent, so Lq-sharding is always legal and
    keeps the (B, H, Lq, Lk) logits partitioned. x: (B, L, H, hd)."""
    rules = _rules_var.get()
    mesh = _mesh_var.get()
    if rules is None or mesh is None:
        return x
    dp = rules.get("batch")
    model_ax = rules.get("heads")
    if model_ax is None:
        return shard(x, ("batch", None, None, None))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msize = 1
    for a in (model_ax if isinstance(model_ax, tuple) else (model_ax,)):
        msize *= sizes[a]
    B, L, H = x.shape[0], x.shape[1], x.shape[2]
    if H % msize == 0:
        spec = P(dp, None, model_ax, None)
    elif L % msize == 0 and L > 1:
        spec = P(dp, model_ax, None, None)
    else:
        spec = P(dp, None, None, None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_axes():
    """Mesh axes bound to the logical 'batch' axis (tuple), or None."""
    rules = _rules_var.get()
    if rules is None:
        return None
    ax = rules.get("batch")
    if ax is None:
        return None
    return ax if isinstance(ax, tuple) else (ax,)


def named_sharding(spec: P):
    mesh = _mesh_var.get()
    return NamedSharding(mesh, spec)


def current_mesh():
    return _mesh_var.get()


def plan_axis(mesh=None) -> str | None:
    """Mesh axis carrying the FTFI `plan_leaves` logical axis (leaf-block
    sharding of the plan executor). Falls back to "data" (or the mesh's
    first axis) when the active rules don't bind it."""
    rules = _rules_var.get()
    ax = (rules or DEFAULT_RULES).get("plan_leaves", "data")
    if isinstance(ax, tuple):
        ax = ax[0] if ax else None
    mesh = mesh if mesh is not None else _mesh_var.get()
    if mesh is not None and ax not in mesh.axis_names:
        ax = "data" if "data" in mesh.axis_names else mesh.axis_names[0]
    return ax
