"""prefill_softmax_ms: device ms per prefill call of the ops under the
program's `lm.softmax` scope (the GQA layer: projections, RoPE, the causal
block attention, the KV cache write), the mean over the chips."""
from scopes import per_call_ms

SCOPES = ("lm.lightning", "lm.softmax", "lm.moe", "lm.moe.exchange")


def read(ctx):
    ms = per_call_ms(ctx, SCOPES, "lm.softmax")
    return None if ms is None else ms / ctx["chips"]
