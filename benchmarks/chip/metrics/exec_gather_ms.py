"""exec_gather_ms: device ms per integrate call of the ops under the
program's `ftfi.gather` scope: Eq. 3, the gather + segment-sum of the
field into source groups."""
from scopes import EXEC, per_call_ms


def read(ctx):
    return per_call_ms(ctx, EXEC, "ftfi.gather")
