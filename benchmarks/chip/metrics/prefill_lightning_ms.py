"""prefill_lightning_ms: device ms per prefill call of the ops under the
program's `lm.lightning` scope (the projections, the decay sweep, the output
norm and gate, and the all-reduce after W_out), the mean over the chips."""
from scopes import per_call_ms

SCOPES = ("lm.lightning", "lm.softmax", "lm.moe", "lm.moe.exchange")


def read(ctx):
    ms = per_call_ms(ctx, SCOPES, "lm.lightning")
    return None if ms is None else ms / ctx["chips"]
