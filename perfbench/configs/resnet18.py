"""ResNet-18 (BasicBlock, [2, 2, 2, 2]) as a reference graph, built from
the sizes in ``resnet18.json``, in the layer order the program uses:
per block conv_a, conv_b, then the 1x1 downsample projection where the
block changes shape (that projection adds conv_b's output back)."""
from qcnn import Layer


def layers(cfg: dict) -> list[Layer]:
    bits = cfg["bits_a"]
    hw = cfg["in_hw"]
    stem = Layer("conv1", cfg["in_channels"], cfg["stem_channels"],
                 cfg["stem_kernel"], cfg["stem_stride"], hw,
                 act=cfg["act"], pool=cfg["stem_pool"], out_bits=bits)
    out = [stem]
    hw = stem.pooled_hw
    c_in = cfg["stem_channels"]
    kk = cfg["block_kernel"]
    for c, n_blocks, stride0 in zip(cfg["stage_channels"], cfg["stage_blocks"],
                                    cfg["stage_strides"]):
        for b in range(n_blocks):
            stride = stride0 if b == 0 else 1
            ds = stride != 1 or c_in != c
            x = len(out) - 1  # the block input
            a = Layer(f"conv{len(out) + 1}", c_in, c, kk, stride, hw, src=x,
                      act=cfg["act"], out_bits=bits)
            out.append(a)
            out.append(Layer(f"conv{len(out) + 1}", c, c, kk, 1, a.out_hw,
                             src=len(out) - 1,
                             act="" if ds else cfg["act"],
                             add=None if ds else x, out_bits=bits))
            if ds:
                out.append(Layer(f"conv{len(out) + 1}_ds", c_in, c, 1, stride,
                                 hw, src=x, add=len(out) - 1, act=cfg["act"],
                                 out_bits=bits))
            hw = a.out_hw
            c_in = c
    last = out[-1]
    out[-1] = Layer(last.name, last.c_in, last.c_out, last.kernel,
                    last.stride, last.in_hw, src=last.src, add=last.add,
                    act=last.act, pool=cfg["head_pool"], out_bits=bits)
    out.append(Layer("fc", c_in, cfg["num_classes"], 1, 1, 1,
                     src=len(out) - 1))
    return out
