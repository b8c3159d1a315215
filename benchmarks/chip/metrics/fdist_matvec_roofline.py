"""fdist_matvec_roofline: the `fdist_matvec` kernel's least time (the larger
of its flops over peak FLOP/s and its bytes over peak bytes/s, summed over
the cross buckets the plan hands it, read from the spec at run time) over
the summed device time of its custom-call events per call, in %."""
from trace_reader import kernel_seconds
from work import fdist_matvec_work, least_time

# the trace names the kernel's custom call after the jitted wrapper around
# its pallas_call (`fdist_matvec_batched_pallas.<n>`), not after the kernel
# body `_fdist_kernel`
KERNEL = "fdist_matvec_batched_pallas"


def read(ctx):
    tr = ctx.get("trace")
    buckets = ctx["work"].get("kernel_buckets")
    calls = ctx["window"]["calls"]
    if not tr or not buckets or not calls:
        return None
    t = kernel_seconds(tr, KERNEL)
    if t <= 0:
        return None
    d = ctx["work"]["d"]
    least = sum(least_time(*fdist_matvec_work(B, a, b, d), ctx["peak"])
                for B, a, b in buckets)
    return 100.0 * least / (t / calls)
