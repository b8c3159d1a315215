"""exec_scatter_ms: device ms per integrate call of the ops under the
program's `ftfi.scatter` scope: Eq. 4, the gather of each target's group
value + scatter-add into the output."""
from scopes import EXEC, per_call_ms


def read(ctx):
    return per_call_ms(ctx, EXEC, "ftfi.scatter")
