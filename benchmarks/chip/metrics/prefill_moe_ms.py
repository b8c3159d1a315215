"""prefill_moe_ms: device ms per prefill call of the ops under the
program's `lm.moe` scope outside `lm.moe.exchange` (the router, the held
experts' tiles), the mean over the chips."""
from scopes import per_call_ms

SCOPES = ("lm.lightning", "lm.softmax", "lm.moe", "lm.moe.exchange")


def read(ctx):
    ms = per_call_ms(ctx, SCOPES, "lm.moe")
    return None if ms is None else ms / ctx["chips"]
