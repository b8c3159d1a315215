"""exec_leaf_ms: device ms per integrate call of the ops under the
program's `ftfi.leaf` scope: the plan executor's leaf blocks (gather,
f on the leaf distances, einsum, scatter-add)."""
from scopes import EXEC, per_call_ms


def read(ctx):
    return per_call_ms(ctx, EXEC, "ftfi.leaf")
