"""plan_assemble_s: host seconds of the program's `ftfi.build.assemble`
span in set-up (`trace_guard.seconds`): the plan assembly from the IT
decomposition. Reads 0 where no such span closed, as
in a program that keeps no span seconds."""


def read(ctx):
    from repro.analysis import trace_guard

    seconds = getattr(trace_guard, "seconds", None)
    got = None if seconds is None else seconds("ftfi.build.assemble")
    return 0.0 if got is None else got
