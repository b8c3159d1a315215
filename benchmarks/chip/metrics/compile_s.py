"""compile_s: host seconds of `.lower().compile()` of the cell's jitted
entry (and the small programs beside it) in set-up; a hit in the
persistent compile cache shows as a short one."""


def read(ctx):
    return ctx["host_s"].get("compile")
