"""For the layers whose fused conv launch has a 1x1 window (named
``fused_conv_gemm_1x1_L<index>[_<index>...]`` in the trace): the sum of
each layer's least time (``work.least_time_s``) over those launches'
device time, per image."""
from conv_launches import roofline


def read(ctx):
    return roofline(ctx, pointwise=True)
