"""Device time per image of the chain's eager glue: the operations of
every executable not named ``jit_n3h_*`` (jnp's own, such as
``jit_multiply``), in a trace where the program names its executables
by role (the trace's op labels are ``<executable>/<op>``)."""


def read(ctx):
    ops = ctx.trace.op_seconds
    if not ctx.images or not any(k.startswith("jit_n3h_") for k in ops):
        return None
    glue = sum(sec for key, sec in ops.items()
               if not key.startswith("jit_n3h_"))
    return 1e3 * glue / ctx.images
