"""exec_cross_ms: device ms per integrate call of the ops under the
program's `ftfi.cross` scope: every bucket's cross multiply (on the pallas
backend the `fdist_matvec` kernel with its pads and copies)."""
from scopes import EXEC, per_call_ms


def read(ctx):
    return per_call_ms(ctx, EXEC, "ftfi.cross")
