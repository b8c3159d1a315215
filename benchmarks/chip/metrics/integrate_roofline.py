"""integrate_roofline: the plan executor as a whole against the least
bytes any exact integrate moves (read X, write Y: 2·n·d·4 bytes) at the
chip's peak HBM bandwidth, over the device busy time per call, in %."""


def read(ctx):
    tr = ctx.get("trace")
    calls = ctx["window"]["calls"]
    if not tr or tr["busy_s"] <= 0 or not calls:
        return None
    least = ctx["work"]["floor_bytes"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (tr["busy_s"] / calls)
