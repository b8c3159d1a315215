"""Roofline analysis from compiled dry-run artifacts (TPU v5e targets).

Terms (seconds), from the per-device SPMD program:
  compute    = HLO_flops / peak_flops          (197 TFLOP/s bf16 / chip)
  memory     = HLO_bytes_accessed / HBM_bw     (819 GB/s / chip)
  collective = collective operand bytes / ICI  (~50 GB/s / link)
collective bytes are parsed from the compiled HLO text (cost_analysis does
not report them): sum of operand sizes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops.
"""
from __future__ import annotations

import re

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# result-shape form: %all-reduce.5 = bf16[16,512]{1,0} all-reduce(
# also matches tuple-result async starts: ... = (bf16[..], bf16[..]) all-gather-start(
# and TPU tiled layouts: ... = f32[641,64]{1,0:T(8,128)S(1)} all-reduce(
_COLL_LINE_RE = re.compile(
    r"= *(\(?[A-Za-z0-9, \[\]{}():]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_TENSOR_RE = re.compile(r"\b([a-z]?[a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _tensor_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [num_groups, group_size]
    return 1


def _iter_collectives(hlo: str):
    """Yields (kind, operand_bytes) per collective instruction.

    Result shapes are parsed from the instruction's LHS (operand types are
    not printed in optimized HLO); operand size is reconstructed from the
    result and the replica-group size: all-gather operand = result/g,
    reduce-scatter operand = result*g, others operand = result. `-done` ops
    are skipped so async pairs are not double counted."""
    for line in hlo.splitlines():
        m = _COLL_LINE_RE.search(line)
        if not m:
            continue
        result_spec, kind = m.group(1), m.group(2)
        sizes = [_tensor_bytes(d, s) for d, s in _TENSOR_RE.findall(result_spec)]
        if not sizes:
            continue
        g = _group_size(line)
        is_start = bool(m.group(3)) and len(sizes) >= 2
        if is_start:
            # async start tuples carry (operand, result): the operand is the
            # smaller entry for all-gather, equal for all-reduce, larger for
            # reduce-scatter
            op_bytes = max(sizes) if kind == "reduce-scatter" else min(sizes)
        else:
            res_bytes = sum(sizes)
            if kind == "all-gather":
                op_bytes = res_bytes // max(g, 1)
            elif kind == "reduce-scatter":
                op_bytes = res_bytes * g
            else:
                op_bytes = res_bytes
        yield kind, op_bytes


def collective_bytes_from_hlo(hlo: str) -> float:
    """Sum of operand bytes over all collective ops (per-device program)."""
    return float(sum(b for _, b in _iter_collectives(hlo)))


def collective_breakdown(hlo: str) -> dict:
    per_kind: dict[str, float] = {}
    counts: dict[str, int] = {}
    for kind, b in _iter_collectives(hlo):
        per_kind[kind] = per_kind.get(kind, 0) + b
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes": per_kind, "counts": counts}


def count_params(cfg) -> tuple[int, int]:
    """(total, active) parameter counts straight from the config."""
    import jax
    from repro.models import api

    shapes = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = sum(l.size for _, l in flat)
    inactive = 0
    for path, leaf in flat:
        spath = "/".join(str(getattr(p, "key", p)) for p in path)
        if "experts_w" in spath:
            frac_active = cfg.top_k / max(cfg.num_experts, 1)
            inactive += int(leaf.size * (1.0 - frac_active))
    return total, total - inactive


def model_flops(cfg, shape: dict) -> float:
    """Ideal matmul flops: 6·N·tokens (train) / 2·N·tokens (inference),
    charging each parameter group for the tokens that actually flow through
    it: embedding lookups are free; the LM head runs per *logit* position
    (all tokens in training, one per sequence at prefill/decode); encoder
    params see src frames and only when the encoder runs."""
    import jax
    from repro.models import api

    B, L, kind = shape["global_batch"], shape["seq_len"], shape["kind"]
    mult = 6.0 if kind == "train" else 2.0
    shapes = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    enc = head = embed = body = 0
    frac_active = cfg.top_k / max(cfg.num_experts, 1) if cfg.moe else 1.0
    for path, leaf in flat:
        spath = "/".join(str(getattr(p, "key", p)) for p in path)
        if "blocks_enc" in spath or "frontend_proj" in spath:
            enc += leaf.size
        elif "lm_head" in spath:
            head += leaf.size
        elif spath.startswith("embed"):
            embed += leaf.size
        elif "experts_w" in spath:
            body += int(leaf.size * frac_active)
        else:
            body += leaf.size
    if cfg.tie_embeddings:
        head = embed  # tied: the unembed matmul reuses the table
    tokens = B * (L if kind != "decode" else 1)
    logit_pos = B * L if kind == "train" else B
    total = mult * body * tokens + mult * head * logit_pos
    if cfg.is_encdec and kind != "decode":
        total += mult * enc * B * cfg.max_source_len
    return float(total)


def roofline_terms(rec: dict, cfg, shape: dict, n_chips: int) -> dict:
    compute_s = rec["flops"] / PEAK_FLOPS
    memory_s = rec["bytes_accessed"] / HBM_BW
    collective_s = rec["collective_bytes"] / ICI_BW
    dominant = max(
        [("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)], key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape)
    hlo_total = rec["flops"] * n_chips
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "model_flops": mf,
        "useful_flops_ratio": (mf / hlo_total) if hlo_total else 0.0,
        "roofline_bound_s": max(compute_s, memory_s, collective_s),
    }
