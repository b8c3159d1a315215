"""Work counts computed from shapes, kept with the benchmark so that every
change is measured with the same arithmetic: the operations and bytes a
call needs, which the per-layer readers divide by device time or rates."""
from __future__ import annotations

F32 = 4


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


# ----------------------------------------------------------------------------
# tree-field integrate
# ----------------------------------------------------------------------------


def integrate_floor_bytes(n: int, d: int, itemsize: int = F32) -> int:
    """Least bytes of any exact integrate of an (n, d) field: read X once
    and write Y once, whatever implements it."""
    return 2 * n * d * itemsize


def fdist_matvec_work(B: int, a: int, b: int, d: int) -> tuple[int, int]:
    """(flops, bytes) of one batched `fdist_matvec` call over B jobs of a
    targets and b sources: the (a, b) f-tile times the (b, d) field per
    job, and reading the two distance vectors and the field and writing
    the output once, in f32."""
    flops = 2 * B * a * b * d
    nbytes = F32 * (B * a + B * b + B * b * d + B * a * d)
    return flops, nbytes


def cross_buckets(spec) -> list[tuple[int, int, int]]:
    """(B, a, b) of every cross bucket of a plan, as the plan hands them to
    its cross engine (before any padding the kernel adds to its tiles)."""
    return [(int(t.shape[0]), int(t.shape[1]), int(s.shape[1]))
            for t, s in zip(spec.cross_tgt_mask, spec.cross_src_mask)]


# ----------------------------------------------------------------------------
# TopoViT training (the 6·N·tokens rule)
# ----------------------------------------------------------------------------


def vit_matmul_params(c: dict) -> dict:
    """Matmul parameters of a ViT configuration by the group of tokens that
    flows through them. `c` holds the configuration's sizes."""
    d, H, KV, hd = c["d_model"], c["num_heads"], c["num_kv_heads"], \
        c["head_dim"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * c["d_ff"]  # gated: gate, in, out
    return {"per_layer": attn + mlp, "patch_proj": c["patch_dim"] * d,
            "head": d * c["num_classes"]}


def vit_train_flops_per_image(c: dict) -> float:
    """6 x matmul parameters x the tokens that flow through them, per image
    (`roofline.analysis.model_flops`'s rule): every layer and the patch
    projection see all L patches, the head sees one pooled vector. The
    attention scores and Alg. 1's mask multiply are not counted."""
    p = vit_matmul_params(c)
    L = c["num_prefix_embeddings"]
    return 6.0 * (c["num_layers"] * p["per_layer"] * L
                  + p["patch_proj"] * L + p["head"])
