"""exec_rest_ms: device ms per integrate call of the ops in none of the
executor's four scopes above (`ftfi.diag`, the harness's `finite` check,
unnamed ops), so that the five add up to the busy time per call."""
from scopes import EXEC, per_call_ms


def read(ctx):
    return per_call_ms(ctx, EXEC, None)
