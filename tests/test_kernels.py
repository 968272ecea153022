"""Per-kernel allclose sweeps vs the pure-jnp oracles (shapes x dtypes):
every Pallas kernel, explicitly in interpret mode, against ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.fir import fir_filter_bank
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mriq import mriq_compute_q
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssm_scan import ssm_scan

KEY = jax.random.PRNGKey(0)


def test_interpret_mode_comes_from_the_backend(monkeypatch):
    from repro.kernels import resolve_interpret
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True


# ---------------------------------------------------------------------------
# FIR
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,k,block_n,unroll", [
    (2, 256, 16, 128, 1),
    (4, 1024, 64, 256, 1),
    (4, 1024, 64, 512, 4),
    (1, 512, 128, 256, 2),
    (8, 2048, 32, 512, 8),
])
def test_fir_kernel_matches_ref(m, n, k, block_n, unroll):
    kx, kh = jax.random.split(KEY)
    x = (jax.random.normal(kx, (m, n)) + 1j * jax.random.normal(kh, (m, n))
         ).astype(jnp.complex64)
    h = (jax.random.normal(kh, (m, k)) + 1j * jax.random.normal(kx, (m, k))
         ).astype(jnp.complex64)
    out = fir_filter_bank(x, h, block_n=block_n, tap_unroll=unroll,
                          interpret=True)
    ref = R.fir_ref(x, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_fir_ref_matches_c_loop_structure():
    kx, kh = jax.random.split(KEY)
    x = (jax.random.normal(kx, (3, 48)) + 1j * jax.random.normal(kh, (3, 48))
         ).astype(jnp.complex64)
    h = (jax.random.normal(kh, (3, 8)) + 1j * jax.random.normal(kx, (3, 8))
         ).astype(jnp.complex64)
    ref = R.fir_ref(x, h)
    loopy = R.fir_ref_loopy(np.asarray(x), np.asarray(h))
    np.testing.assert_allclose(np.asarray(ref), loopy, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# MRI-Q
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("num_x,num_k,bx,bk", [
    (128, 128, 128, 128),
    (300, 200, 128, 128),     # non-multiples exercise padding
    (1024, 512, 256, 512),
])
def test_mriq_kernel_matches_ref(num_x, num_k, bx, bk):
    ks = jax.random.split(KEY, 7)
    x, y, z = (jax.random.normal(ks[i], (num_x,)) for i in range(3))
    kx, ky, kz = (jax.random.normal(ks[3 + i], (num_k,)) * 0.1 for i in range(3))
    pm = jax.random.uniform(ks[6], (num_k,))
    qr, qi = mriq_compute_q(x, y, z, kx, ky, kz, pm, block_x=bx, block_k=bk,
                            interpret=True)
    qr_ref, qi_ref = R.mriq_ref(x, y, z, kx, ky, kz, pm)
    np.testing.assert_allclose(np.asarray(qr), np.asarray(qr_ref),
                               rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(qi), np.asarray(qi_ref),
                               rtol=3e-3, atol=3e-3)


def test_mriq_ref_matches_c_loop_structure():
    ks = jax.random.split(KEY, 7)
    args = [np.asarray(jax.random.normal(ks[i], (40,))) for i in range(3)]
    kargs = [np.asarray(jax.random.normal(ks[3 + i], (24,)) * 0.1)
             for i in range(3)]
    pm = np.asarray(jax.random.uniform(ks[6], (24,)))
    qr_ref, qi_ref = R.mriq_ref(*[jnp.asarray(a) for a in args],
                                *[jnp.asarray(a) for a in kargs], jnp.asarray(pm))
    qr_l, qi_l = R.mriq_ref_loopy(*args, *kargs, pm)
    np.testing.assert_allclose(np.asarray(qr_ref), qr_l, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(qi_ref), qi_l, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,s,d,causal,window,dtype", [
    (2, 4, 2, 256, 32, True, 0, jnp.float32),
    (1, 8, 2, 512, 64, True, 128, jnp.float32),
    (2, 2, 2, 256, 32, False, 0, jnp.float32),
    (1, 4, 1, 256, 64, True, 0, jnp.bfloat16),
    (1, 16, 4, 128, 128, True, 0, jnp.float32),
])
def test_flash_attention_matches_ref(b, hq, hkv, s, d, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128, interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# RG-LRU / SSM scans
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,d,bc,tc", [
    (2, 256, 256, 128, 64),
    (1, 128, 128, 128, 128),
    (4, 512, 384, 128, 64),
])
def test_rglru_kernel_matches_seq(b, s, d, bc, tc):
    a = jax.random.uniform(KEY, (b, s, d), jnp.float32, 0.5, 0.99)
    bb = jax.random.normal(KEY, (b, s, d), jnp.float32) * 0.1
    h0 = jax.random.normal(KEY, (b, d), jnp.float32)
    y, hf = rglru_scan(a, bb, h0, block_c=bc, time_chunk=tc, interpret=True)
    y_ref, hf_ref = R.rglru_scan_seq(a, bb, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,s,d,n,bc,tc", [
    (2, 128, 256, 8, 128, 32),
    (1, 64, 128, 16, 128, 64),
])
def test_ssm_kernel_matches_seq(b, s, d, n, bc, tc):
    a = jax.random.uniform(KEY, (b, s, d, n), jnp.float32, 0.5, 0.99)
    bx = jax.random.normal(KEY, (b, s, d, n), jnp.float32) * 0.1
    c = jax.random.normal(KEY, (b, s, n), jnp.float32)
    h0 = jnp.zeros((b, d, n), jnp.float32)
    y, hf = ssm_scan(a, bx, c, h0, block_c=bc, time_chunk=tc, interpret=True)
    y_ref, hf_ref = R.ssm_scan_seq(a, bx, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_ref),
                               rtol=1e-4, atol=1e-4)


# chunked associative-scan refs (model path) vs sequential oracle
def test_model_ssm_chunked_scan_matches_seq():
    from repro.models.ssm import ssm_scan_ref
    b, s, d, n = 2, 200, 64, 8
    a = jax.random.uniform(KEY, (b, s, d, n), jnp.float32, 0.5, 0.99)
    bx = jax.random.normal(KEY, (b, s, d, n), jnp.float32) * 0.1
    c = jax.random.normal(KEY, (b, s, n), jnp.float32)
    h0 = jnp.zeros((b, d, n), jnp.float32)
    y, hf = ssm_scan_ref(a, bx, c, h0, chunk=64)
    y_ref, hf_ref = R.ssm_scan_seq(a, bx, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_model_rglru_chunked_scan_matches_seq():
    from repro.models.rglru import rglru_scan_ref
    b, s, d = 2, 200, 64
    a = jax.random.uniform(KEY, (b, s, d), jnp.float32, 0.5, 0.99)
    bb = jax.random.normal(KEY, (b, s, d), jnp.float32) * 0.1
    h0 = jax.random.normal(KEY, (b, d), jnp.float32)
    y, hf = rglru_scan_ref(a, bb, h0, chunk=64)
    y_ref, hf_ref = R.rglru_scan_seq(a, bb, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype", [
    ((4, 100, 512), jnp.bfloat16),
    ((8, 256), jnp.float32),
    ((2, 3, 5, 128), jnp.float32),
])
def test_rmsnorm_kernel_matches_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(KEY, (shape[-1],), jnp.float32) * 0.1
    out = rmsnorm(x, w, interpret=True)
    ref = R.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Model chunked attention (XLA ref path) vs dense oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,window", [(192, 0), (256, 64), (100, 0)])
def test_chunked_attention_matches_dense(s, window):
    from repro.models.layers import chunked_attention
    b, hq, hkv, d = 2, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, window=window,
                            q_chunk=64, k_chunk=64)
    ref = R.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Decode attention (single token vs KV cache)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,s,d,window,bk", [
    (2, 8, 2, 256, 64, 0, 128),
    (1, 4, 4, 300, 32, 0, 128),     # non-multiple cache length
    (2, 8, 4, 256, 64, 128, 128),   # sliding window
    (1, 16, 8, 512, 128, 0, 512),
])
def test_decode_attention_kernel_matches_ref(b, hq, hkv, s, d, window, bk):
    from repro.kernels.decode_attention import decode_attention
    from repro.models.layers import decode_attention as decode_ref

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    cur = jnp.array([s // 2 + 7] * b, jnp.int32)
    slot = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    slot = jnp.where(slot <= cur[:, None], slot, -1)
    out = decode_attention(q, kc, vc, slot, cur, window=window, block_k=bk,
                           interpret=True)
    ref = decode_ref(q, kc, vc, slot, cur, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-6, atol=5e-6)


@pytest.mark.parametrize("s,bk", [
    (96, 256),     # cache shorter than one block
    (40, 128),     # much shorter, non-multiple of the lane width
    (130, 128),    # one full block + a 2-slot tail
])
def test_decode_attention_short_sequences(s, bk):
    """Regression: the autotuner may propose any block_k, including one
    larger than (or not dividing) the cache length — the kernel must clamp
    and pad, never assert, and still match the dense oracle."""
    from repro.kernels.decode_attention import decode_attention
    from repro.models.layers import decode_attention as decode_ref

    b, hq, hkv, d = 2, 8, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    cur = jnp.array([s - 1] * b, jnp.int32)
    slot = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    out = decode_attention(q, kc, vc, slot, cur, block_k=bk, interpret=True)
    ref = decode_ref(q, kc, vc, slot, cur)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-6, atol=5e-6)


def test_decode_attn_ref_variant_matches_dense_oracle():
    """The registered planner-side ref variant computes the same dense
    masked softmax as the model-layer oracle (windowed and unwindowed)."""
    from repro.kernels.ops import decode_attn_ref
    from repro.models.layers import decode_attention as decode_ref

    b, hq, hkv, s, d = 2, 8, 2, 192, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    cur = jnp.array([s // 2 + 5] * b, jnp.int32)
    slot = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    slot = jnp.where(slot <= cur[:, None], slot, -1)
    for window in (0, 64):
        out = decode_attn_ref(q, kc, vc, slot, cur, window=window)
        ref = decode_ref(q, kc, vc, slot, cur, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-6, atol=5e-6)


# ---------------------------------------------------------------------------
# FIR tile-knob clamping (the kernel degrades gracefully; legality lives in
# the TuningSpace predicate, so an illegal proposed point must still run)
# ---------------------------------------------------------------------------
def test_largest_divisor():
    from repro.kernels.fir import largest_divisor
    assert largest_divisor(96, 64) == 48
    assert largest_divisor(12, 8) == 6
    assert largest_divisor(7, 3) == 1
    assert largest_divisor(128, 512) == 128    # cap beyond n clamps to n
    assert largest_divisor(10, 0) == 1         # degenerate cap


def test_fir_clamps_invalid_block_n_and_warns():
    kx, kh = jax.random.split(KEY)
    x = (jax.random.normal(kx, (2, 96)) + 1j * jax.random.normal(kh, (2, 96))
         ).astype(jnp.complex64)
    h = (jax.random.normal(kh, (2, 8)) + 1j * jax.random.normal(kx, (2, 8))
         ).astype(jnp.complex64)
    with pytest.warns(UserWarning, match="block_n=64 invalid"):
        out = fir_filter_bank(x, h, block_n=64, interpret=True)
    ref = R.fir_ref(x, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_fir_clamps_invalid_tap_unroll_and_warns():
    kx, kh = jax.random.split(KEY)
    x = (jax.random.normal(kx, (2, 128)) + 1j * jax.random.normal(kh, (2, 128))
         ).astype(jnp.complex64)
    h = (jax.random.normal(kh, (2, 12)) + 1j * jax.random.normal(kx, (2, 12))
         ).astype(jnp.complex64)
    with pytest.warns(UserWarning, match="tap_unroll=8 invalid"):
        out = fir_filter_bank(x, h, block_n=64, tap_unroll=8, interpret=True)
    ref = R.fir_ref(x, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
