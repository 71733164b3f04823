"""Device time per image of the Pallas kernels (``tpu_custom_call``)."""


def read(ctx):
    if not ctx.images or ctx.trace.kernel_s <= 0:
        return None
    return 1e3 * ctx.trace.kernel_s / ctx.images
