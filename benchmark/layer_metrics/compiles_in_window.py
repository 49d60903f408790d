"""kernels / compile: `jax.monitoring` backend-compile events between the
window's start and end. Every shape is warmed in set-up, so: 0."""
UNIT = "count"


def read(ctx):
    return ctx.compiles_in_window
