"""prefill_mfu: model operations of the calls completed in the traced
window (`lm_work.prefill_flops`, the held share) over the chips' peak bf16
FLOP/s times the window's length, in %: the operations of a mean call over
chips x peak x the mean call time."""


def read(ctx):
    tr = ctx.get("trace")
    w = ctx["window"]
    flops = ctx["work"].get("call_flops")
    if not tr or not w["calls"] or not flops:
        return None
    return (100.0 * sum(flops[:w["calls"]])
            / (ctx["peak"]["bf16_flops"] * ctx["chips"] * w["seconds"]))
