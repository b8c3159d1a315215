"""step_attn_ms: device ms per train step of the ops under the program's
`vit.attn` scope outside `vit.alg1`, forward and backward: the attention's
projections, features and output projection."""
from scopes import STEP, per_call_ms


def read(ctx):
    return per_call_ms(ctx, STEP, "vit.attn")
