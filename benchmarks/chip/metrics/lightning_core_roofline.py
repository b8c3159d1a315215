"""lightning_core_roofline: the decay sweeps' least time (the larger of
their operations over the chips' peak FLOP/s and their bytes over the
chips' peak bytes/s, from `lm_work.lightning_sweep_work`) over their device
time per call (the ops under `lm.lightning.core`, summed over the chips),
in %."""
from scopes import per_call_ms
from work import least_time

SCOPES = ("lm.lightning.core",)


def read(ctx):
    ms = per_call_ms(ctx, SCOPES, "lm.lightning.core")
    sweep = ctx["work"].get("sweep")
    if not ms or sweep is None:
        return None
    n = ctx["chips"]
    least = least_time(sweep[0] / n, sweep[1] / n, ctx["peak"])
    return 100.0 * least / (ms * 1e-3 / n)
