"""step_adamw_ms: device ms per train step of the ops under the program's
`adamw` scope: the optimizer update with its global-norm clip."""
from scopes import STEP, per_call_ms


def read(ctx):
    return per_call_ms(ctx, STEP, "adamw")
