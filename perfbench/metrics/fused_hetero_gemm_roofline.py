"""For the layers that ran on the kernel: the sum of each layer's least
time (``work.least_time_s``) over the kernels' device time, per image."""
from work import least_time_s


def read(ctx):
    least = sum(least_time_s(w, ctx.peaks)
                for w, path in zip(ctx.layer_work, ctx.layer_paths)
                if path == "kernel")
    if not ctx.images or ctx.trace.kernel_s <= 0 or least <= 0:
        return None
    return 100.0 * least / (ctx.trace.kernel_s / ctx.images)
