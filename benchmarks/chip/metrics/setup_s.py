"""setup_s: seconds from the start of the process to the start of the
measured window: loading, the plan build, data, compiling, warming up."""


def read(ctx):
    return ctx["setup_s"]
