"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_hetero_gemm import dsp_blocks, fused_hetero_gemm
from repro.kernels import ops

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# representation helpers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [2, 3, 4, 5, 6, 7, 8])
def test_bitplane_roundtrip(bits):
    q = RNG.integers(-(2 ** (bits - 1)), 2 ** (bits - 1), (17, 23))
    planes = ref.bitplane_decompose(jnp.asarray(q), bits)
    assert planes.shape == (bits, 17, 23)
    assert set(np.unique(np.asarray(planes))) <= {0, 1}
    rec = ref.bitplane_reconstruct(planes)
    np.testing.assert_array_equal(np.asarray(rec), q)


def test_int4_pack_roundtrip():
    q = RNG.integers(-8, 8, (9, 24))
    packed = ref.pack_int4(jnp.asarray(q))
    assert packed.shape == (9, 12)
    np.testing.assert_array_equal(np.asarray(ref.unpack_int4(packed)), q)


# ---------------------------------------------------------------------------
# fused kernel, one region at a time: the LUT (bitplane) sweep
# ---------------------------------------------------------------------------


def _lut_only(x, planes, scale, bits):
    """The fused kernel with every column block in the LUT region and
    one dummy packed block that is never read."""
    k = planes.shape[1]
    return fused_hetero_gemm(x, planes, jnp.zeros((1, k, 32), jnp.int8),
                             scale,
                             bits, planes.shape[2] // 64, 0,
                             bm=64, bn=64, bk=64, interpret=True)


def _dsp_only(x, packed, scale):
    """The fused kernel with every column block in the DSP region and
    dummy planes that are never read."""
    k = packed.shape[0]
    return fused_hetero_gemm(x, jnp.zeros((1, k, 64), jnp.int8),
                             dsp_blocks(packed, 64), scale, 0, 0,
                             packed.shape[1] // 32,
                             bm=64, bn=64, bk=64, interpret=True)


@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (128, 256, 128),
                                   (64, 128, 192)])
@pytest.mark.parametrize("bits", [2, 5, 8])
def test_bitserial_kernel_vs_oracle(m, k, n, bits):
    x = RNG.integers(-8, 8, (m, k)).astype(np.int8)
    wq = RNG.integers(-(2 ** (bits - 1)), 2 ** (bits - 1),
                      (k, n)).astype(np.int32)
    scale = RNG.uniform(0.01, 0.2, n).astype(np.float32)
    planes = ref.bitplane_decompose(jnp.asarray(wq), bits)
    out = _lut_only(jnp.asarray(x), planes, jnp.asarray(scale), bits)
    want = ref.bitserial_gemm_ref(jnp.asarray(x), jnp.asarray(wq),
                                  jnp.asarray(scale), bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


def test_bitserial_exact_integer_semantics():
    """fp32 output must equal exact integer GEMM x scale."""
    bits = 6
    x = RNG.integers(-8, 8, (64, 64)).astype(np.int8)
    wq = RNG.integers(-32, 32, (64, 64)).astype(np.int32)
    scale = np.ones(64, np.float32)
    planes = ref.bitplane_decompose(jnp.asarray(wq), bits)
    out = _lut_only(jnp.asarray(x), planes, jnp.asarray(scale), bits)
    exact = x.astype(np.int64) @ wq.astype(np.int64)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64), exact)


# ---------------------------------------------------------------------------
# fused kernel, DSP (packed int4) region only
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (64, 128, 128),
                                   (128, 64, 128)])
def test_int4_kernel_vs_oracle(m, k, n):
    x = RNG.integers(-8, 8, (m, k)).astype(np.int8)
    wq = RNG.integers(-8, 8, (k, n)).astype(np.int32)
    packed = ref.pack_int4(jnp.asarray(wq), block=64)
    scale = RNG.uniform(0.01, 0.2, n).astype(np.float32)
    out = _dsp_only(jnp.asarray(x), packed, jnp.asarray(scale))
    want = ref.int4_gemm_ref(jnp.asarray(x), packed, jnp.asarray(scale),
                             block=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("planes_n,n_lut_blocks,n_dsp_blocks,match", [
    (64, 0, 0, "at least one column block"),
    (32, 1, 1, "at least one weight block"),
])
def test_fused_hetero_gemm_rejects_a_region_without_a_block(
        planes_n, n_lut_blocks, n_dsp_blocks, match):
    x = jnp.zeros((64, 64), jnp.int8)
    planes = jnp.zeros((2, 64, planes_n), jnp.int8)
    packed = jnp.zeros((1, 64, 32), jnp.int8)
    with pytest.raises(ValueError, match=match):
        fused_hetero_gemm(x, planes, packed, jnp.ones(128, jnp.float32), 2,
                          n_lut_blocks, n_dsp_blocks, bm=64, bn=64, bk=64,
                          interpret=True)


# ---------------------------------------------------------------------------
# flash attention sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,h,s,d", [(1, 2, 128, 32), (2, 3, 192, 64)])
def test_flash_vs_oracle(b, h, s, d, causal):
    q = (RNG.standard_normal((b, h, s, d)) * 0.3).astype(np.float32)
    k = (RNG.standard_normal((b, h, s, d)) * 0.3).astype(np.float32)
    v = RNG.standard_normal((b, h, s, d)).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, bq=64, bkv=64, interpret=True)
    want = ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_flash_decode_offset():
    b, h, s, d = 2, 2, 128, 32
    q = (RNG.standard_normal((b, h, 1, d)) * 0.3).astype(np.float32)
    k = (RNG.standard_normal((b, h, s, d)) * 0.3).astype(np.float32)
    v = RNG.standard_normal((b, h, s, d)).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, kv_offset=s - 1, bq=1, bkv=64,
                          interpret=True)
    want = ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=True,
                                   kv_offset=s - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# ops wrappers (padding + split)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_serial", [0, 16, 40])
def test_fused_matmul_equals_dense(n_serial):
    """Through the kernel: 0 and 40 are the one-sided splits, the same
    launch with a dummy block in the empty region."""
    m, k, n = 32, 48, 40
    x = RNG.integers(-8, 8, (m, k)).astype(np.int8)
    wq = RNG.integers(-8, 8, (k, n)).astype(np.int32)
    s = np.full(n, 0.05, np.float32)
    out = ops.fused_matmul(jnp.asarray(x), jnp.asarray(wq[:, :n_serial]),
                           jnp.asarray(s[:n_serial]), 6,
                           jnp.asarray(wq[:, n_serial:]),
                           jnp.asarray(s[n_serial:]), mode="kernel")
    want = (x.astype(np.int64) @ wq) * 0.05
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


def test_attention_wrapper_gqa():
    b, hq, hkv, s, d = 2, 8, 2, 96, 32
    q = jnp.asarray(RNG.standard_normal((b, hq, s, d)) * 0.3, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)) * 0.3, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    out = ops.attention(q, k, v, causal=True)
    kr = jnp.repeat(k, hq // hkv, axis=1)
    vr = jnp.repeat(v, hq // hkv, axis=1)
    want = ref.flash_attention_ref(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
