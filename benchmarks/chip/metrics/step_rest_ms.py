"""step_rest_ms: device ms per train step of the ops in none of the
step's four scopes above (`vit.layer_params`, embed, head, norms, the
gradient accumulation), so that the five add up to the busy time per
step."""
from scopes import STEP, per_call_ms


def read(ctx):
    return per_call_ms(ctx, STEP, None)
