"""The whole image's share of the chip's int8 peak: 2 x MACs per image
(every layer, as ``work.layer_work`` counts them) x images per second of
the traced run, over the peak."""


def read(ctx):
    ops = sum(w.ops for w in ctx.layer_work)
    if not ctx.images or ops <= 0:
        return None
    return 100.0 * ops * ctx.images_per_s / ctx.peaks["int8_ops_per_s"]
