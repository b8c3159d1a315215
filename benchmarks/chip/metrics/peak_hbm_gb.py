"""peak_hbm_gb: `memory_stats()["peak_bytes_in_use"]` of the fullest chip
after the window and before the check, in GB (1e9 bytes)."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return None if b is None else b / 1e9
