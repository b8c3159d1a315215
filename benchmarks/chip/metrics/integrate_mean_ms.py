"""integrate_mean_ms: the window's length over the integrate calls
completed in it, each call dispatch to `block_until_ready`, one caller.
A host that stands still for seconds in the window moves it by as much,
so it is a per-layer reading beside the end-to-end tail."""


def read(ctx):
    w = ctx["window"]
    return 1e3 * w["seconds"] / w["calls"]
