"""plan_decompose_s: host seconds of the program's `ftfi.build.decompose`
span in set-up (`trace_guard.seconds`): the IT decomposition of the
cell's tree. Reads 0 where no such span closed, as
in a program that keeps no span seconds."""


def read(ctx):
    from repro.analysis import trace_guard

    seconds = getattr(trace_guard, "seconds", None)
    got = None if seconds is None else seconds("ftfi.build.decompose")
    return 0.0 if got is None else got
