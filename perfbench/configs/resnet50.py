"""ResNet-50 v1.5 (Bottleneck, [3, 4, 6, 3], the stride on the 3x3 conv)
as a reference graph, built from the sizes in ``resnet50.json``, in the
layer order the program uses: per block conv_a (1x1), conv_b (3x3),
conv_c (1x1 to ``expansion`` x the width), then the 1x1 projection where
the block changes shape. An identity block's conv_c adds the block
input; a projection reads the block input and adds conv_c's output."""
from qcnn import Layer


def layers(cfg: dict) -> list[Layer]:
    bits = cfg["bits_a"]
    act = cfg["act"]
    stem = Layer("conv1", cfg["in_channels"], cfg["stem_channels"],
                 cfg["stem_kernel"], cfg["stem_stride"], cfg["in_hw"],
                 act=act, pool=cfg["stem_pool"], out_bits=bits)
    out = [stem]
    hw = stem.pooled_hw
    c_in = cfg["stem_channels"]
    for width, n_blocks, stride0 in zip(cfg["stage_channels"],
                                        cfg["stage_blocks"],
                                        cfg["stage_strides"]):
        c_out = cfg["expansion"] * width
        for b in range(n_blocks):
            stride = stride0 if b == 0 else 1
            proj = stride != 1 or c_in != c_out
            x = len(out) - 1  # the block input
            a = Layer(f"conv{len(out) + 1}", c_in, width, 1, 1, hw, src=x,
                      act=act, out_bits=bits)
            out.append(a)
            bl = Layer(f"conv{len(out) + 1}", width, width, 3, stride, hw,
                       src=len(out) - 1, act=act, out_bits=bits)
            out.append(bl)
            out.append(Layer(f"conv{len(out) + 1}", width, c_out, 1, 1,
                             bl.out_hw, src=len(out) - 1,
                             act="" if proj else act,
                             add=None if proj else x, out_bits=bits))
            if proj:
                out.append(Layer(f"conv{len(out) + 1}_ds", c_in, c_out, 1,
                                 stride, hw, src=x, add=len(out) - 1,
                                 act=act, out_bits=bits))
            hw = bl.out_hw
            c_in = c_out
    last = out[-1]
    out[-1] = Layer(last.name, last.c_in, last.c_out, last.kernel,
                    last.stride, last.in_hw, src=last.src, add=last.add,
                    act=last.act, pool=cfg["head_pool"], out_bits=bits)
    out.append(Layer("fc", c_in, cfg["num_classes"], 1, 1, 1,
                     src=len(out) - 1))
    return out
