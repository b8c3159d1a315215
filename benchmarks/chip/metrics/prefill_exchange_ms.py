"""prefill_exchange_ms: device ms per prefill call of the ops under the
program's `lm.moe.exchange` scope (the all-reduce that sums the held
experts' parts over the chips), the mean over the chips."""
from scopes import per_call_ms

SCOPES = ("lm.lightning", "lm.softmax", "lm.moe", "lm.moe.exchange")


def read(ctx):
    ms = per_call_ms(ctx, SCOPES, "lm.moe.exchange")
    return None if ms is None else ms / ctx["chips"]
