"""Device time per step of the lookup program (its XLA module's events in
the trace), summed over the cell's chips."""

PROGRAM = "_lookup_batch_jit"


def read(ctx):
    r = ctx.reduced
    s = r and r["program_s"].get(PROGRAM)
    if not s or not ctx.steps:
        return None
    return 1e3 * s / ctx.steps
