"""plan_build_s: host seconds of `ftfi.build` in set-up (the IT
decomposition and the plan assembly of the cell's tree)."""


def read(ctx):
    return ctx["host_s"].get("plan_build")
