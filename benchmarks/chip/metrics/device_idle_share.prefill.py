"""device_idle_share.prefill: 100 x (1 - device busy union / traced
window), averaged over the chips used, in the prefill cells."""
from trace_reader import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx)
