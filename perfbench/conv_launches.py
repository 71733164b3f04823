"""The fused conv kernel's launches in a device trace, by layer.

The executor launches the fused conv kernel under a name that gives its
window and the indices of the layers that launch it,
``fused_conv_gemm_<k>x<k>_L<i>[_<j>...]``: layers of one geometry share
one launch and its name. A layer's index in the program is the index of
its reference layer too. The name is the instruction's in the trace, so
an op label (``<executable>/<instruction> <type>``, ``tracing.op_label``)
tells the layers. A program that does not name its launches so has
none here.
"""
from __future__ import annotations

import re

from work import least_time_s

_LAUNCH = re.compile(r"/fused_conv_gemm_(\d+)x\1_L(\d+(?:_\d+)*) ")


def launches(op_seconds: dict) -> dict:
    """(window, layer indices) -> device seconds of those launches."""
    out: dict = {}
    for key, sec in op_seconds.items():
        m = _LAUNCH.search(key)
        if m is not None:
            name = (int(m.group(1)),
                    tuple(int(i) for i in m.group(2).split("_")))
            out[name] = out.get(name, 0.0) + sec
    return out


def roofline(ctx, pointwise: bool):
    """For the launches whose window is 1x1 (``pointwise``) or wider: the
    sum of their layers' least time (``work.least_time_s``) over their
    device time per image, in %; None where the trace has none."""
    picked = {name: sec for name, sec in
              launches(ctx.trace.op_seconds).items()
              if (name[0] == 1) == pointwise
              and max(name[1]) < len(ctx.layer_work)}
    secs = sum(picked.values())
    if not ctx.images or secs <= 0:
        return None
    layers = {i for _, indices in picked for i in indices}
    least = sum(least_time_s(ctx.layer_work[i], ctx.peaks) for i in layers)
    return 100.0 * least / (secs / ctx.images)
