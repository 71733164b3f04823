"""MobileNetV2 (width 1.0) as a reference graph, built from the sizes in
``mobilenet_v2.json``: stem, inverted residual blocks (1x1 expansion
when t > 1, 3x3 depthwise, linear 1x1 projection that adds the block
input back where stride is 1 and the channels match), the last 1x1
conv with the global mean pool, and the classifier."""
from qcnn import Layer


def layers(cfg: dict) -> list[Layer]:
    bits = cfg["bits_a"]
    stem = Layer("conv0", cfg["in_channels"], cfg["stem_channels"],
                 cfg["stem_kernel"], cfg["stem_stride"], cfg["in_hw"],
                 act=cfg["stem_act"], out_bits=bits)
    out = [stem]
    hw, c_in = stem.out_hw, cfg["stem_channels"]
    bi = 0
    for t, c, n, s in cfg["inverted_residual_setting"]:
        for r in range(n):
            stride = s if r == 0 else 1
            x = len(out) - 1  # the block input
            hidden = c_in * t
            if t != 1:
                out.append(Layer(f"b{bi}_exp", c_in, hidden, 1, 1, hw,
                                 src=len(out) - 1, act=cfg["expand_act"],
                                 out_bits=bits))
            dw = Layer(f"b{bi}_dw", hidden, hidden, cfg["dw_kernel"], stride,
                       hw, depthwise=True, src=len(out) - 1,
                       act=cfg["dw_act"], out_bits=bits)
            out.append(dw)
            hw = dw.out_hw
            res = stride == 1 and c_in == c
            out.append(Layer(f"b{bi}_pw", hidden, c, 1, 1, hw,
                             src=len(out) - 1, add=x if res else None,
                             out_bits=bits))
            c_in = c
            bi += 1
    out.append(Layer("conv_last", c_in, cfg["last_channel"], 1, 1, hw,
                     src=len(out) - 1, act=cfg["last_act"],
                     pool=cfg["head_pool"], out_bits=bits))
    out.append(Layer("fc", cfg["last_channel"], cfg["num_classes"], 1, 1, 1,
                     src=len(out) - 1))
    return out
