"""XLA compilations (persistent-cache loads included) that the harness
counted inside the measured window; warm-up should leave none."""


def read(ctx):
    return ctx.get("compiles_in_window")
