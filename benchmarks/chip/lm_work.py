"""Work counts of the language-model prefill cells, computed from the
configuration's shapes and kept with the benchmark: the model operations
of one prefill call and the operations and bytes of the lightning decay
sweep, which the per-layer readers divide by rates or device time."""
from __future__ import annotations

import topolm_prefill_ref as ref

F32 = 4
CHUNK = 128  # rows per step of the topo kernel's decay sweep


def prefill_flops(c: dict, shape) -> float:
    """Model operations of one prefill call of `shape` (B, L) over the share
    the configuration holds: 2 x matmul parameters x tokens for every
    projection, the experts at their expected load (T k held / E
    assignments), the lightning recurrence (4 hd^2 per token and head), the
    causal softmax scores and values (4 hd per query-key pair and head), and
    the head at the last positions."""
    z = ref.sizes(c)
    B, L = shape
    T = B * L
    d, H, KV, hd, ff = z["d"], z["H"], z["KV"], z["hd"], z["ff"]
    flops = 0.0
    for kind in z["kinds"]:
        if kind == "lightning":
            flops += 2.0 * T * (d * 3 * H * hd + d * H * hd + H * hd * d)
            flops += 4.0 * T * H * hd * hd
        else:
            flops += 2.0 * T * (2 * d * H * hd + 2 * d * KV * hd)
            flops += 4.0 * hd * H * B * L * (L + 1) / 2
        flops += 2.0 * T * d * z["E"]
        flops += (T * z["K"] * z["held"] / z["E"]) * 2.0 * 3 * d * ff
    return flops + 2.0 * B * d * z["V"]


def lightning_sweep_work(c: dict, shape) -> tuple[float, float]:
    """(operations, bytes) of one call's decay sweeps, all lightning layers:
    per chunk of CHUNK rows and head, the masked q k^T (2 C^2 hd), its
    product with v (2 C^2 hd), the state read (2 C hd^2) and update
    (2 C hd^2); reading q, k and v once in the configuration's dtype (the
    kernel upcasts them in its tiles) and writing the unnormalized sum and
    the row sums once in float32. The same for every call shape of one
    token count."""
    import jax.numpy as jnp

    z = ref.sizes(c)
    B, L = shape
    n = sum(k == "lightning" for k in z["kinds"])
    hd = z["hd"]
    per = 4.0 * CHUNK * hd + 4.0 * hd * hd  # per token and head
    rows = float(B * L * z["H"] * n)
    qkv = jnp.dtype(c["dtype"]).itemsize
    return rows * per, rows * (3 * hd * qkv + F32 * (hd + 1))
