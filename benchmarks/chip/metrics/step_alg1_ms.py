"""step_alg1_ms: device ms per train step of the ops under the program's
`vit.alg1` scope, forward and backward: Alg. 1 (the tree mask's fastmult
and the masked linear attention)."""
from scopes import STEP, per_call_ms


def read(ctx):
    return per_call_ms(ctx, STEP, "vit.alg1")
