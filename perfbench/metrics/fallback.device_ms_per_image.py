"""Device time per image of the layers that run under XLA in place of
the kernel: the operations of the ``jit_n3h_<conv|gemm>_xla_vmem`` and
``jit_n3h_<conv|gemm>_xla_depthwise`` executables (the trace's op
labels are ``<executable>/<op>``)."""
import re

FALLBACK = re.compile(r"^jit_n3h_(conv|gemm)_(xla_vmem|xla_depthwise)/")


def read(ctx):
    secs = [sec for key, sec in ctx.trace.op_seconds.items()
            if FALLBACK.match(key)]
    if not ctx.images or not secs:
        return None
    return 1e3 * sum(secs) / ctx.images
