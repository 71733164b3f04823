"""Device time per image of every operation that is not a Pallas kernel:
the wrappers' padding and packing, the fallbacks and the chain's glue."""


def read(ctx):
    if not ctx.images or ctx.trace.busy_s <= 0:
        return None
    return 1e3 * ctx.trace.other_s / ctx.images
