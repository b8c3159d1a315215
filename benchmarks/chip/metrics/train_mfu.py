"""train_mfu: model flops (6 x matmul parameters x tokens, the head once
per image; Alg. 1 and the attention scores not counted) x images/s over
the traced window, over the chip's peak bf16 FLOP/s, in %."""


def read(ctx):
    tr = ctx.get("trace")
    w = ctx["window"]
    if not tr or not w["calls"]:
        return None
    rate = w["calls"] * ctx["work"]["images_per_call"] / w["seconds"]
    return (100.0 * ctx["work"]["flops_per_image"] * rate
            / (ctx["peak"]["bf16_flops"] * ctx["chips"]))
