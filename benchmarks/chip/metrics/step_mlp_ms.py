"""step_mlp_ms: device ms per train step of the ops under the program's
`vit.mlp` scope, forward and backward."""
from scopes import STEP, per_call_ms


def read(ctx):
    return per_call_ms(ctx, STEP, "vit.mlp")
