"""NN→ISA compiler toolchain: lowering, round-trips, golden execution.

Covers the acceptance surface of the compiler subsystem:
  * assembly/binary round-trips are bit-exact for all four instruction
    kinds (and canonical: re-render is byte-identical);
  * the golden executor matches `core/hetero_linear.py`'s deployed
    integer path bit-exactly on quantized layers for both core types;
  * simulating compiled programs reproduces the seed per-engine latency
    decomposition (golden numbers recorded from the pre-compiler
    scheduler) for identical GemmDims/core configs;
  * every registry arch + CNN workload compiles end to end.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as kref
from repro.compiler import (
    GemmLayer,
    GoldenExecutor,
    assemble,
    compile_network,
    disassemble,
    from_binary,
    list_networks,
    lower_network,
    network_layers,
    to_binary,
)
from repro.compiler.runtime import ExecutionError
from repro.core.hetero_linear import (
    HeteroLinearConfig,
    apply_deploy,
    deploy,
    init_hetero_linear,
)
from repro.core.scheduler import (
    XC7Z020,
    DspCoreConfig,
    GemmDims,
    LutCoreConfig,
    dsp_core_streams,
    lut_core_streams,
    simulate,
    simulate_dsp_core,
    simulate_lut_core,
    simulate_program,
)
from repro.quant.hybrid import LayerQuantConfig
from repro.quant.uniform import fit_scale, qrange

LUT = LutCoreConfig(m=8, n=16, k=128)
DSP = DspCoreConfig(n_reg_row_a=13)


def _tiny_program(m=24, k=32, n=40, n_lut=18, bits_w=6, bits_a=4,
                  name="tiny"):
    return lower_network(name, [GemmLayer("fc", GemmDims(m, k, n))],
                         LUT, DSP, XC7Z020, bits_w_lut=bits_w,
                         bits_a=bits_a, n_luts=[n_lut])


# ---------------------------------------------------------------------------
# Round-trips
# ---------------------------------------------------------------------------


def test_asm_roundtrip_bit_exact_all_instruction_kinds():
    prog = _tiny_program()
    words = set(type(op.instr).__name__
                for lp in prog.layers for cp in lp.cores()
                for op in cp.ops())
    # the tiny program exercises all four instruction kinds
    assert words == {"FetchInstr", "ExecuteInstr", "ResultInstr",
                     "SyncInstr"}
    text = disassemble(prog)
    prog2 = assemble(text)
    assert prog2 == prog
    # canonical: assemble -> disassemble -> assemble is byte-identical
    assert disassemble(prog2) == text


def test_binary_roundtrip_bit_exact():
    prog = _tiny_program()
    blob = to_binary(prog)
    prog2 = from_binary(blob)
    assert prog2 == prog
    assert to_binary(prog2) == blob


def test_binary_matches_isa_encode():
    """Every 128-bit word in the image is the isa.py encoding."""
    prog = _tiny_program()
    blob = to_binary(prog)
    words = prog.words()
    # the packed stream section ends with the last instruction record
    tail = blob.rsplit(words[-1].to_bytes(16, "little"), 1)
    assert len(tail) == 2 and len(tail[1]) == 4  # trailing u32 cycles


def test_corrupt_binary_rejected():
    prog = _tiny_program()
    blob = to_binary(prog)
    with pytest.raises(ValueError):
        from_binary(b"XXXXXXXX" + blob[8:])
    with pytest.raises(ValueError):
        from_binary(blob + b"\x00\x00\x00\x00")


def test_multi_layer_roundtrip_with_barriers():
    layers = [GemmLayer("fc1", GemmDims(16, 24, 32)),
              GemmLayer("fc2", GemmDims(16, 32, 48)),
              GemmLayer("fc3", GemmDims(16, 48, 16))]
    prog = lower_network("mlp", layers, LUT, DSP, XC7Z020,
                         bits_w_lut=4, bits_a=4, n_luts=[16, 24, 8])
    # barrier wait opens each later layer's fetch streams
    for lp in prog.layers[1:]:
        for cp in lp.cores():
            first = cp.streams["fetch"][0]
            assert first.channel in ("lut.bar", "dsp.bar")
            assert first.instr.is_wait == 1
    assert assemble(disassemble(prog)) == prog
    assert from_binary(to_binary(prog)) == prog


# ---------------------------------------------------------------------------
# Golden executor vs hetero_linear reference
# ---------------------------------------------------------------------------


def _quantize_acts(x, bits):
    s_a = fit_scale(x, bits)
    lo, hi = qrange(bits)
    return jnp.clip(jnp.round(x / s_a), lo, hi).astype(jnp.int8), s_a


@pytest.mark.parametrize("ratio,bits_w", [(0.45, 6), (1.0, 5), (0.0, 4)])
def test_golden_executor_bit_exact_vs_hetero_linear(ratio, bits_w):
    M, K, N = 24, 32, 40
    cfg = HeteroLinearConfig(K, N, quant=LayerQuantConfig(
        w_bits_lut=bits_w, a_bits=4, ratio=ratio))
    params = init_hetero_linear(jax.random.PRNGKey(0), cfg)
    d = deploy(params, cfg)
    n_lut = d.wq_serial.shape[1]

    prog = _tiny_program(M, K, N, n_lut=n_lut, bits_w=bits_w)
    ex = GoldenExecutor(prog)
    ex.bind_deployed(0, d)

    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    x_q, s_a = _quantize_acts(x, 4)

    got = np.asarray(ex.run_layer(0, x_q))
    want = np.asarray(kref.fused_hetero_gemm_ref(
        x_q, d.wq_serial, d.s_serial, d.bits_serial,
        d.wq_parallel, d.s_parallel))
    assert (got == want).all()

    # and through the full deployed path (permutation + act scale)
    full = got[:, np.asarray(d.inv_perm)] * float(s_a)
    assert (full == np.asarray(apply_deploy(d, x))).all()


def test_golden_executor_chains_fc_network():
    layers = [GemmLayer("fc1", GemmDims(8, 16, 24)),
              GemmLayer("fc2", GemmDims(8, 24, 12))]
    prog = lower_network("mlp", layers, LUT, DSP, XC7Z020,
                         bits_w_lut=4, bits_a=4, n_luts=[12, 6])
    ex = GoldenExecutor(prog)
    rng = np.random.default_rng(0)
    for i, lp in enumerate(prog.layers):
        k, n_lut, n_dsp = lp.dims.k, lp.n_lut, lp.dims.n - lp.n_lut
        ex.bind_layer(
            i,
            w_lut=rng.integers(-8, 8, (k, n_lut)), s_lut=np.ones(n_lut),
            w_dsp=rng.integers(-8, 8, (k, n_dsp)), s_dsp=np.ones(n_dsp))
    x_q = rng.integers(-8, 8, (8, 16)).astype(np.int8)
    out = np.asarray(ex.run(x_q))
    assert out.shape == (8, 12)
    assert np.isfinite(out).all()


def test_golden_executor_validates_contract():
    prog = _tiny_program()
    ex = GoldenExecutor(prog)
    with pytest.raises(ExecutionError):
        ex.run_layer(0, jnp.zeros((24, 32), jnp.int8))  # no weights bound
    rng = np.random.default_rng(1)
    ex.bind_layer(0, w_lut=rng.integers(-32, 32, (32, 18)),
                  s_lut=np.ones(18), w_dsp=rng.integers(-8, 8, (32, 22)),
                  s_dsp=np.ones(22))
    with pytest.raises(ExecutionError):
        ex.run_layer(0, jnp.zeros((24, 99), jnp.int8))  # wrong K
    with pytest.raises(ValueError):
        ex.bind_layer(0, w_lut=np.full((32, 18), 99), s_lut=np.ones(18),
                      w_dsp=rng.integers(-8, 8, (32, 22)), s_dsp=np.ones(22))


def test_depthwise_executes_grouped():
    from repro.compiler import bind_synthetic
    prog = lower_network(
        "dw", [GemmLayer("dw", GemmDims(64, 9, 32), depthwise=True)],
        LUT, DSP, XC7Z020, n_luts=[16])
    ex = GoldenExecutor(prog)
    bind_synthetic(ex, prog.layers[0])
    x = np.random.default_rng(0).integers(-8, 8, (64, 9, 32)).astype(np.int8)
    out = np.asarray(ex.run_layer(0, x))
    assert out.shape == (64, 32)
    assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# Compiled-program simulation == seed latency decomposition
# ---------------------------------------------------------------------------

# Golden numbers recorded from the pre-compiler scheduler (seed commit),
# (total, l_wait, l_run, l_sig, l_rst, n_instructions):
SEED_GOLDEN = [
    ("lut", (3136, 576, 96), 4, 4, False,
     (375743, 166361, 206976, 4739, 84672, 9455)),
    ("lut", (784, 1152, 144), 6, 3, False,
     (215685, 64801, 149940, 1817, 30870, 3599)),
    ("lut", (12544, 9, 32), 5, 4, True,
     (128671, 12616, 62720, 6283, 112896, 12559)),
    ("dsp", (3136, 576, 160), 0, 0, False,
     (1661403, 3179, 1655280, 5808, 94380, 10891)),
    ("dsp", (196, 2304, 80), 0, 0, False,
     (221264, 2153, 218880, 382, 3120, 638)),
    ("dsp", (12544, 9, 32), 0, 0, True,
     (77306, 81, 42460, 7720, 75270, 12546)),
]


@pytest.mark.parametrize("which,dims,bw,ba,dw,expect", SEED_GOLDEN)
def test_compiled_streams_reproduce_seed_decomposition(which, dims, bw, ba,
                                                       dw, expect):
    g = GemmDims(*dims)
    if which == "lut":
        r = simulate_lut_core(g, LUT, XC7Z020, bw, ba, dw)
    else:
        r = simulate_dsp_core(g, DSP, XC7Z020, dw)
    assert (r.total_cycles, r.l_wait, r.l_run, r.l_sig, r.l_rst,
            r.n_instructions) == expect


def test_program_simulation_matches_raw_streams():
    """simulate_program over a compiled single-layer Program == simulate
    of the wrapper streams (the compiler is the single source)."""
    g = GemmDims(784, 1152, 144)
    n_lut = 60
    prog = lower_network("one", [GemmLayer("l0", g)], LUT, DSP, XC7Z020,
                         bits_w_lut=6, bits_a=3, n_luts=[n_lut])
    ps = simulate_program(prog)
    lut_raw = simulate(*lut_core_streams(
        GemmDims(g.m, g.k, n_lut), LUT, XC7Z020, 6, 3))
    dsp_raw = simulate(*dsp_core_streams(
        GemmDims(g.m, g.k, g.n - n_lut), DSP, XC7Z020))
    assert ps.layers[0].lut.total_cycles == lut_raw.total_cycles
    assert ps.layers[0].dsp.total_cycles == dsp_raw.total_cycles
    assert ps.layers[0].lut.l_wait == lut_raw.l_wait
    assert ps.layers[0].dsp.l_run == dsp_raw.l_run
    assert ps.total_cycles == max(lut_raw.total_cycles,
                                  dsp_raw.total_cycles)


def test_network_simulation_is_interlayer_synchronous():
    prog = compile_network("resnet18")
    ps = simulate_program(prog)
    assert ps.total_cycles == sum(ls.cycles for ls in ps.layers)
    assert len(ps.layers) == 21
    for core in ("lut", "dsp"):
        d = ps.decomposition(core)
        assert d["l_run"] > 0 and d["l_rst"] > 0


# ---------------------------------------------------------------------------
# Whole-registry compilation + CLI
# ---------------------------------------------------------------------------


def test_every_network_compiles():
    for name in list_networks():
        prog = compile_network(name, seq_len=8)
        assert prog.n_instructions > 0
        s = prog.stats()
        assert s.by_opcode["EXECUTE"] > 0
        assert s.by_opcode["SYNC"] > 0
        assert s.bytes_fetched > 0
        # split sanity: every layer's n_lut within range
        for lp in prog.layers:
            assert 0 <= lp.n_lut <= lp.dims.n


def test_memory_map_is_disjoint_and_aligned():
    prog = compile_network("llama3.2-1b", seq_len=8)
    segs = prog.memory.segments
    for a, b in zip(segs, segs[1:]):
        assert b.base >= a.end
        assert b.base % 64 == 0
    assert prog.memory.footprint >= sum(s.size for s in segs)


def test_cli_summary_and_asm(tmp_path, capsys):
    from repro.compiler.cli import main
    assert main(["llama3.2-1b", "--seq-len", "8"]) == 0
    out = capsys.readouterr().out
    assert "program   llama3.2-1b" in out and "instrs" in out
    asm_path = tmp_path / "p.asm"
    assert main(["llama3.2-1b", "--seq-len", "8", "--format", "asm",
                 "-o", str(asm_path)]) == 0
    prog = assemble(asm_path.read_text())
    assert prog.name == "llama3.2-1b"
    bin_path = tmp_path / "p.n3h"
    assert main(["llama3.2-1b", "--seq-len", "8", "--format", "bin",
                 "-o", str(bin_path)]) == 0
    assert from_binary(bin_path.read_bytes()) == prog


def test_fixed_ratio_override():
    prog = compile_network("llama3.2-1b", seq_len=8, ratio=0.25)
    for lp in prog.layers:
        assert lp.n_lut == int(round(0.25 * lp.dims.n))


def test_network_layers_shapes_make_sense():
    layers = network_layers("llama3.2-1b", seq_len=16)
    # 2 smoke blocks x (4 attn + 3 mlp) + lm_head
    assert len(layers) == 15
    assert all(gl.dims.m == 16 for gl in layers)
    assert layers[-1].name == "lm_head"
