"""End-to-end compiler walkthrough: compile → optimize (-O1) →
inspect → simulate → execute on the golden model → verify against the
deployed integer path → re-execute on the batched Pallas backend.

    PYTHONPATH=src python examples/compile_and_execute.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.compiler import (
    GemmLayer,
    GoldenExecutor,
    PallasExecutor,
    bind_synthetic,
    compile_network,
    disassemble,
    lower_network,
    optimize_program,
    to_binary,
)
from repro.core.hetero_linear import (
    HeteroLinearConfig,
    deploy,
    init_hetero_linear,
)
from repro.core.scheduler import (
    XC7Z020,
    DspCoreConfig,
    GemmDims,
    LutCoreConfig,
    simulate_program,
)
from repro.quant.hybrid import LayerQuantConfig
from repro.quant.uniform import fit_scale, qrange


def main() -> None:
    # 1. Compile a registry arch and look at the program-level numbers.
    prog = compile_network("llama3.2-1b", seq_len=32)
    s = prog.stats()
    print(f"[compile] {prog.name}: {len(prog.layers)} layers, "
          f"{s.n_instructions} instrs, image {s.image_bytes} B, "
          f"{s.bytes_moved / 1e6:.2f} MB DDR traffic")
    print(f"[compile] binary image: {len(to_binary(prog))} B; "
          f"first asm lines:")
    print("\n".join(disassemble(prog).splitlines()[:8]))

    # 2. Optimize: the -O1 pass pipeline (weight-tile prefetch
    #    reordering, sync elision, fused result/fetch DMA pairs).
    opt = optimize_program(prog, 1)
    for pstat in opt.opt_stats:
        print(f"[optimize] {pstat.render()}")

    # 3. Simulate both — the Fig. 5 decomposition from the same
    #    streams; optimized streams are what gets timed at -O1.
    ps = simulate_program(prog)
    ps1 = simulate_program(opt)
    gain = 100.0 * (ps.total_cycles - ps1.total_cycles) / ps.total_cycles
    print(f"[simulate] -O0 {ps.total_cycles} cycles = "
          f"{prog.device.cycles_to_ms(ps.total_cycles):.3f} ms @ "
          f"{prog.device.freq_mhz:.0f} MHz")
    print(f"[simulate] -O1 {ps1.total_cycles} cycles "
          f"({gain:+.2f}% latency gain)")
    for core in ("lut", "dsp"):
        print(f"[simulate]   {core}: {ps1.decomposition(core)}")

    # 4. Golden-execute one quantized layer and check bit-exactness
    #    against the deployed HeteroLinear integer path.
    M, K, N = 32, 48, 64
    cfg = HeteroLinearConfig(K, N, quant=LayerQuantConfig(
        w_bits_lut=6, a_bits=4, ratio=0.5))
    d = deploy(init_hetero_linear(jax.random.PRNGKey(0), cfg), cfg)
    n_lut = d.wq_serial.shape[1]

    layer_prog = lower_network(
        "hetero_fc", [GemmLayer("fc", GemmDims(M, K, N))],
        LutCoreConfig(m=8, n=16, k=128),
        DspCoreConfig(n_reg_row_a=DspCoreConfig.rows_for_device(XC7Z020)),
        XC7Z020, bits_w_lut=6, bits_a=4, n_luts=[n_lut])
    ex = GoldenExecutor(layer_prog)
    ex.bind_deployed(0, d)

    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    s_a = fit_scale(x, 4)
    lo, hi = qrange(4)
    x_q = jnp.clip(jnp.round(x / s_a), lo, hi).astype(jnp.int8)

    got = np.asarray(ex.run_layer(0, x_q))
    want = np.asarray(kernels.fused_matmul(
        x_q, d.wq_serial, d.s_serial, d.bits_serial,
        d.wq_parallel, d.s_parallel))
    exact = (got == want).all()
    print(f"[execute] golden model vs fused_matmul on [{M},{K}]x[{K},{N}] "
          f"(n_lut={n_lut}): bit-exact={bool(exact)}")
    assert exact

    # 5. Same layer on the batched Pallas backend: one fused_matmul
    #    call for both partitions instead of the interpreter's per-tile
    #    Python loop — bit-identical output.
    fast = PallasExecutor(layer_prog)
    fast.bind_deployed(0, d)
    t0 = time.time()
    got_fast = np.asarray(fast.run_layer(0, x_q))
    dt_fast = time.time() - t0
    t0 = time.time()
    ex.run_layer(0, x_q)
    dt_golden = time.time() - t0
    assert (got_fast == got).all()
    print(f"[execute] pallas backend bit-exact vs golden; "
          f"{dt_golden * 1e3:.1f} ms golden -> {dt_fast * 1e3:.1f} ms "
          f"pallas on one layer")

    # 6. Whole-CNN inference: a reduced mobilenet_v2 (depthwise layers
    #    included) chained end to end through the spatial im2col path —
    #    grouped per-channel GEMMs, pool glue, inter-layer requant.
    cnn_prog = compile_network("mobilenet_v2", in_hw=28, width=0.25)
    golden_cnn = GoldenExecutor(cnn_prog)
    fast_cnn = PallasExecutor(cnn_prog)
    for lp in cnn_prog.layers:
        bind_synthetic(golden_cnn, lp, seed=lp.index)
        bind_synthetic(fast_cnn, lp, seed=lp.index)
    geo0 = cnn_prog.layers[0].geometry
    img = np.random.default_rng(0).integers(
        -8, 8, geo0.in_shape).astype(np.int8)
    logits_g = np.asarray(golden_cnn.run(img))
    logits_p = np.asarray(fast_cnn.run(img))
    n_dw = sum(lp.depthwise for lp in cnn_prog.layers)
    assert (logits_g == logits_p).all()
    print(f"[execute] mobilenet_v2@{geo0.in_hw}px end to end: "
          f"{len(cnn_prog.layers)} layers ({n_dw} depthwise) -> logits "
          f"{logits_g.shape}, golden == pallas bit-exact")


if __name__ == "__main__":
    main()
