"""For the layers whose fused conv launch has a window wider than 1x1
(``fused_conv_gemm_<k>x<k>_L<index>[_<index>...]``, k > 1, in the
trace): the sum of
each layer's least time (``work.least_time_s``) over those launches'
device time, per image."""
from conv_launches import roofline


def read(ctx):
    return roofline(ctx, pointwise=False)
