"""prefill_rest_ms: device ms per prefill call of the ops in none of the
four scopes above (`lm.embed`, `lm.head`, the norms and residuals, the
cache's zeros), the mean over the chips, so that the five add up to the
busy time per call."""
from scopes import per_call_ms

SCOPES = ("lm.lightning", "lm.softmax", "lm.moe", "lm.moe.exchange")


def read(ctx):
    ms = per_call_ms(ctx, SCOPES, None)
    return None if ms is None else ms / ctx["chips"]
