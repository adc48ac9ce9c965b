"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# popcount_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,words", [(4, 4, 1), (16, 8, 2), (130, 70, 3),
                                       (256, 128, 4)])
@pytest.mark.parametrize("mode", ["and", "xnor"])
def test_popcount_matmul(m, n, words, mode):
    r = rng(m * 7 + n)
    x = r.integers(0, 2**32, size=(m, words), dtype=np.uint32)
    w = r.integers(0, 2**32, size=(n, words), dtype=np.uint32)
    kb = words * 32
    got = ops.popcount_matmul(jnp.asarray(x), jnp.asarray(w), mode=mode,
                              k_bits=kb)
    want = ref.popcount_matmul_ref(jnp.asarray(x), jnp.asarray(w), mode=mode,
                                   k_bits=kb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_popcount_matmul_matches_integer_dot():
    r = rng(3)
    K = 64
    xb = r.integers(0, 2, size=(5, K)).astype(np.uint8)
    wb = r.integers(0, 2, size=(7, K)).astype(np.uint8)

    def pack(bits):
        out = np.zeros((bits.shape[0], K // 32), dtype=np.uint32)
        for k in range(K):
            out[:, k // 32] |= (bits[:, k].astype(np.uint32)) << (k % 32)
        return out

    got = ops.popcount_matmul(jnp.asarray(pack(xb)), jnp.asarray(pack(wb)),
                              mode="and")
    want = xb.astype(np.int32) @ wb.T.astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# lut_eval
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,nlanes", [(8, 2, 4), (64, 3, 16), (300, 4, 8),
                                        (1000, 5, 2)])
def test_lut_eval(m, k, nlanes):
    r = rng(m + k)
    ins = r.integers(0, 2**32, size=(m, k, nlanes), dtype=np.uint32)
    tts = r.integers(0, 2**(2**k), size=(m,),
                     dtype=np.uint64).astype(np.uint32) \
        if k < 5 else r.integers(0, 2**32, size=(m,), dtype=np.uint32)
    got = ops.lut_eval(jnp.asarray(ins), jnp.asarray(tts))
    want = ref.lut_eval_ref(jnp.asarray(ins), jnp.asarray(tts))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lut_eval_known_functions():
    # AND2 / XOR2 bit-parallel
    ins = np.zeros((2, 2, 1), dtype=np.uint32)
    ins[0, 0, 0] = 0b1100
    ins[0, 1, 0] = 0b1010
    ins[1, 0, 0] = 0b1100
    ins[1, 1, 0] = 0b1010
    tts = np.array([0b1000, 0b0110], dtype=np.uint32)  # AND2, XOR2
    got = np.asarray(ops.lut_eval(jnp.asarray(ins), jnp.asarray(tts)))
    assert got[0, 0] == 0b1000
    assert got[1, 0] == 0b0110


@pytest.mark.parametrize("m,nlanes", [(8, 4), (300, 8), (513, 2)])
def test_lut_eval6_fused_layout(m, nlanes):
    r = rng(m * 7)
    ins = r.integers(0, 2**32, size=(m, 6, nlanes), dtype=np.uint32)
    tt_lo = r.integers(0, 2**32, size=(m,), dtype=np.uint32)
    tt_hi = r.integers(0, 2**32, size=(m,), dtype=np.uint32)
    got = ops.lut_eval6(jnp.asarray(ins), jnp.asarray(tt_lo),
                        jnp.asarray(tt_hi))
    want = ref.lut_eval6_ref(jnp.asarray(ins), jnp.asarray(tt_lo),
                             jnp.asarray(tt_hi))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,nlanes", [(257, 4), (2330, 8), (300, 130)])
def test_lut_eval6_partial_tiles_match_ref(m, nlanes):
    """Row (and lane) counts that are not a multiple of the kernel's
    tile: the last tile is partial and must not leak into real rows."""
    from repro.kernels.lut_eval import BLOCK_M, lut_eval6

    assert m % BLOCK_M
    r = rng(m + nlanes)
    ins = r.integers(0, 2**32, size=(m, 6, nlanes), dtype=np.uint32)
    tt_lo = r.integers(0, 2**32, size=(m,), dtype=np.uint32)
    tt_hi = r.integers(0, 2**32, size=(m,), dtype=np.uint32)
    got = lut_eval6(jnp.asarray(ins), jnp.asarray(tt_lo), jnp.asarray(tt_hi),
                    interpret=True)
    want = ref.lut_eval6_ref(jnp.asarray(ins), jnp.asarray(tt_lo),
                             jnp.asarray(tt_hi))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lut_eval6_shannon_select():
    # pin5 selects between the lo/hi table words: table = XOR2 in lo,
    # AND2 in hi, pin5 toggling per lane bit
    ins = np.zeros((1, 6, 1), dtype=np.uint32)
    ins[0, 0, 0] = 0b1100
    ins[0, 1, 0] = 0b1010
    ins[0, 5, 0] = 0b0011  # vector bits 0-1 read hi, bits 2-3 read lo
    lo = np.array([0x66666666], dtype=np.uint32)  # XOR2 replicated
    hi = np.array([0x88888888], dtype=np.uint32)  # AND2 replicated
    got = np.asarray(ops.lut_eval6(jnp.asarray(ins), jnp.asarray(lo),
                                   jnp.asarray(hi)))
    # bits 2,3 (lo): XOR2(1,0)=1, XOR2(1,1)=0; bits 0,1 (hi): AND2=0
    assert got[0, 0] == 0b0100


# ---------------------------------------------------------------------------
# bitplane_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n,b", [(4, 8, 4, 2), (32, 64, 16, 4),
                                     (128, 256, 128, 3), (65, 130, 70, 8)])
def test_bitplane_matmul(m, k, n, b):
    r = rng(m + k + n + b)
    x = r.standard_normal((m, k)).astype(np.float32)
    planes = r.integers(0, 2, size=(b, k, n)).astype(np.float32)
    scale = (r.standard_normal(n).astype(np.float32)) * 0.1
    got = ops.bitplane_matmul(jnp.asarray(x), jnp.asarray(planes),
                              jnp.asarray(scale))
    want = ref.bitplane_matmul_ref(jnp.asarray(x), jnp.asarray(planes),
                                   jnp.asarray(scale))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_bitplane_matmul_matches_int_quantized():
    """The kernel must equal a real two's-complement quantized matmul."""
    r = rng(5)
    m, k, n, b = 8, 16, 8, 4
    w_int = r.integers(-(2 ** (b - 1)), 2 ** (b - 1), size=(k, n))
    planes = np.zeros((b, k, n), dtype=np.float32)
    w_uint = (w_int % (2 ** b)).astype(np.uint32)
    for bit in range(b):
        planes[bit] = (w_uint >> bit) & 1
    x = r.standard_normal((m, k)).astype(np.float32)
    scale = np.full(n, 0.5, dtype=np.float32)
    got = ops.bitplane_matmul(jnp.asarray(x), jnp.asarray(planes),
                              jnp.asarray(scale))
    want = (x @ w_int.astype(np.float32)) * 0.5
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


CASES = [
    # B, Hq, Hkv, S, T, D, causal, window, softcap
    (1, 2, 2, 64, 64, 32, True, None, None),
    (2, 4, 2, 128, 128, 64, True, None, None),       # GQA
    (1, 8, 1, 64, 64, 32, True, None, None),         # MQA
    (1, 2, 2, 64, 64, 32, True, 32, None),           # sliding window
    (1, 2, 2, 64, 64, 32, True, None, 30.0),         # softcap (gemma2)
    (1, 2, 1, 16, 128, 32, True, None, None),        # decode: S < T
    (1, 2, 2, 64, 64, 32, False, None, None),        # bidirectional
]


@pytest.mark.parametrize("case", CASES)
def test_flash_attention(case):
    B, Hq, Hkv, S, T, D, causal, window, softcap = case
    r = rng(sum(case[:6]))
    q = r.standard_normal((B, Hq, S, D)).astype(np.float32)
    k = r.standard_normal((B, Hkv, T, D)).astype(np.float32)
    v = r.standard_normal((B, Hkv, T, D)).astype(np.float32)
    got = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=causal, window=window, softcap=softcap)
    want = ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=causal,
                                   window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    r = rng(9)
    q = r.standard_normal((1, 2, 64, 32)).astype(np.float32)
    k = r.standard_normal((1, 2, 64, 32)).astype(np.float32)
    v = r.standard_normal((1, 2, 64, 32)).astype(np.float32)
    got = ops.flash_attention(jnp.asarray(q, dtype=jnp.bfloat16),
                              jnp.asarray(k, dtype=jnp.bfloat16),
                              jnp.asarray(v, dtype=jnp.bfloat16))
    want = ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_flash_attention_grad_matches_ref():
    r = rng(11)
    q = jnp.asarray(r.standard_normal((1, 2, 32, 16)).astype(np.float32))
    k = jnp.asarray(r.standard_normal((1, 2, 32, 16)).astype(np.float32))
    v = jnp.asarray(r.standard_normal((1, 2, 32, 16)).astype(np.float32))

    def f_pallas(q, k, v):
        return ops.flash_attention(q, k, v).sum()

    def f_ref(q, k, v):
        return ref.flash_attention_ref(q, k, v).sum()

    g1 = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bb,L,H,P,N,chunk_note", [
    (1, 128, 2, 16, 8, "single chunk"),
    (2, 256, 2, 32, 16, "two chunks"),
    (1, 512, 4, 16, 32, "four chunks"),
])
def test_ssd_scan(bb, L, H, P, N, chunk_note):
    r = rng(L + H + P)
    x = r.standard_normal((bb, L, H, P)).astype(np.float32) * 0.5
    dt = (0.001 + 0.05 * r.random((bb, L, H))).astype(np.float32)
    A = (-0.5 - r.random(H)).astype(np.float32)
    B = r.standard_normal((bb, L, N)).astype(np.float32) * 0.5
    C = r.standard_normal((bb, L, N)).astype(np.float32) * 0.5
    got = ops.ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                       jnp.asarray(B), jnp.asarray(C))
    want = ref.ssd_scan_ref(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                            jnp.asarray(B), jnp.asarray(C))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_ssd_scan_state_continuity():
    """Splitting a sequence into chunks must match one long chunk —
    the carried VMEM state is doing its job."""
    r = rng(21)
    x = r.standard_normal((1, 256, 1, 8)).astype(np.float32) * 0.3
    dt = (0.01 + 0.02 * r.random((1, 256, 1))).astype(np.float32)
    A = np.array([-1.0], dtype=np.float32)
    B = r.standard_normal((1, 256, 4)).astype(np.float32)
    C = r.standard_normal((1, 256, 4)).astype(np.float32)
    got = ops.ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                       jnp.asarray(B), jnp.asarray(C))       # CHUNK=128 → 2
    want = ref.ssd_scan_ref(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                            jnp.asarray(B), jnp.asarray(C))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)
