"""Per-kernel allclose vs ref.py: flash attention, grouped GEMM, SSD."""
import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests degrade to skip
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.models import ssm


# -- flash attention -------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 24])
def test_flash_vs_ref(causal, window):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 4, 80, 32), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 80, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 80, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=32, bkv=32, interpret=True)
    want = ref.ref_mha(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.sampled_from([(4, 1), (4, 2), (6, 3)]),
       st.integers(17, 97), st.sampled_from([16, 32, 64]))
def test_flash_shape_sweep(B, heads, S, D):
    Hq, Hkv = heads
    rng = np.random.RandomState(B * 7 + S)
    q = jnp.asarray(rng.randn(B, Hq, S, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, Hkv, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, Hkv, S, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, bq=32, bkv=32,
                              interpret=True)
    want = ref.ref_mha(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_flash_decode_step():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 4, 1, 32), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 64, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 64, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, q_offset=63, bq=8,
                              bkv=32, interpret=True)
    want = ref.ref_mha(q, k, v, causal=True, q_offset=63)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunked_mha_oracle_consistency():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 50, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 50, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 50, 16), jnp.float32)
    for window in (None, 13):
        a = ref.chunked_mha(q, k, v, causal=True, window=window, kv_chunk=16)
        b = ref.ref_mha(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


# -- grouped GEMM ----------------------------------------------------------

def test_batched_gemm():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 24, 96), jnp.float32)
    w = jnp.asarray(rng.randn(4, 96, 56), jnp.float32)
    out = ops.batched_gemm(x, w, interpret=True)
    want = jnp.einsum("gck,gkn->gcn", x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, 40), min_size=2, max_size=5),
       st.sampled_from([32, 96]), st.sampled_from([48, 128]))
def test_ragged_gemm_property(sizes, K, N):
    bm = 8
    G = len(sizes)
    rng = np.random.RandomState(sum(sizes) + K)
    w = jnp.asarray(rng.randn(G, K, N), jnp.float32)
    xs, gids, want_rows = [], [], []
    for g, s in enumerate(sizes):
        p = max(-(s // -bm) * bm, bm)
        blk = rng.randn(p, K).astype(np.float32)
        blk[s:] = 0
        xs.append(blk)
        gids += [g] * (p // bm)
        want_rows.append(blk @ np.asarray(w[g]))
    x = jnp.asarray(np.concatenate(xs), jnp.float32)
    out = ops.ragged_gemm(x, w, jnp.asarray(np.array(gids, np.int32)),
                          bm=bm, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.concatenate(want_rows),
                               rtol=2e-5, atol=2e-4)


# -- Mamba-2 SSD -----------------------------------------------------------

def _ssd_inputs(rng, Bt, S, H, P, N):
    x = jnp.asarray(rng.randn(Bt, S, H, P) * 0.3, jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(Bt, S, H)) * 0.1 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(H)) * 0.5 - 0.1, jnp.float32)
    B = jnp.asarray(rng.randn(Bt, S, 1, N) * 0.3, jnp.float32)
    C = jnp.asarray(rng.randn(Bt, S, 1, N) * 0.3, jnp.float32)
    return x, dt, A, B, C


def test_ssd_chunked_vs_recurrent():
    rng = np.random.RandomState(4)
    x, dt, A, B, C = _ssd_inputs(rng, 2, 96, 3, 16, 24)
    gt = ref.ref_ssd_recurrent(x, dt, A, B, C)
    ck = ref.ref_ssd(x, dt, A, B, C, chunk=32)
    np.testing.assert_allclose(np.asarray(ck), np.asarray(gt),
                               rtol=1e-4, atol=1e-5)


def test_ssd_kernel_vs_recurrent():
    rng = np.random.RandomState(5)
    x, dt, A, B, C = _ssd_inputs(rng, 2, 96, 3, 16, 24)
    gt = ref.ref_ssd_recurrent(x, dt, A, B, C)
    kn = ops.ssd_scan(x, dt, A, B, C, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(kn), np.asarray(gt),
                               rtol=1e-4, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([17, 64, 100]),
       st.sampled_from([16, 32]))
def test_ssd_kernel_shape_sweep(Bt, S, chunk):
    rng = np.random.RandomState(Bt * 31 + S)
    x, dt, A, B, C = _ssd_inputs(rng, Bt, S, 2, 8, 16)
    gt = ref.ref_ssd_recurrent(x, dt, A, B, C)
    kn = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(kn), np.asarray(gt),
                               rtol=1e-4, atol=1e-5)


def test_ssd_state_handoff():
    """Chunked-with-state == recurrent continuation (prefill -> decode)."""
    rng = np.random.RandomState(6)
    x, dt, A, B, C = _ssd_inputs(rng, 1, 33, 2, 8, 16)
    y, h = ref.ref_ssd(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32],
                       chunk=16, return_state=True)
    h2, y2 = ref.ref_ssd_decode_step(
        h, x[:, 32].astype(jnp.float32), dt[:, 32], A,
        B[:, 32, 0], C[:, 32, 0])
    gt = ref.ref_ssd_recurrent(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(gt[:, 32]),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("active", [True, False])
@pytest.mark.parametrize("h0", ["zero", "random"])
@pytest.mark.parametrize("seg", ["full", "partial"])
@pytest.mark.parametrize("C", [1, 8, 32])
def test_ssd_serve_chunked_vs_serial(C, seg, h0, active):
    """The serving recurrence (ssm.serve_ssd) against the serial decode
    step run over the same rows: a prefill chunk (C > 1) takes the
    chunked form and agrees within float32 rounding; the decode step
    (C == 1) is the serial step, bit for bit.  A chunk split in two
    sub-chunks is two calls of ref.ssd_chunk with the state handed on."""
    rng = np.random.RandomState(C * 7 + (seg == "full") * 3
                                + (h0 == "zero") + 11 * active)
    H, P, N = 3, 8, 16
    x, dt, A, B, Cm = _ssd_inputs(rng, 1, C, H, P, N)
    B, Cm = B[:, :, 0], Cm[:, :, 0]
    h = jnp.zeros((1, H, P, N), jnp.float32) if h0 == "zero" else \
        jnp.asarray(rng.randn(1, H, P, N) * 0.5, jnp.float32)
    seg_len = C if seg == "full" else C // 2
    valid = (jnp.arange(C)[None, :] < seg_len) & active

    def step(hc, t):
        dtt = jnp.where(valid[:, t, None], dt[:, t], 0.0)
        return ref.ref_ssd_decode_step(hc, x[:, t], dtt, A, B[:, t],
                                       Cm[:, t])
    h_ser, y_ser = lax.scan(step, h, jnp.arange(C))
    y_ser = y_ser.transpose(1, 0, 2, 3)
    h_got, y_got = ssm.serve_ssd(h, x, dt, A, B, Cm, valid, chunk=16)
    if C == 1:
        np.testing.assert_array_equal(np.asarray(h_got), np.asarray(h_ser))
        np.testing.assert_array_equal(np.asarray(y_got), np.asarray(y_ser))
        return
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_ser),
                               rtol=1e-5, atol=1e-5)
    rows = np.asarray(valid[0])          # masked rows' outputs are unused
    np.testing.assert_allclose(np.asarray(y_got)[:, rows],
                               np.asarray(y_ser)[:, rows],
                               rtol=1e-5, atol=1e-5)
    # ref_ssd from h0 over two sub-chunks == two ssd_chunk calls
    dtm = jnp.where(valid[..., None], dt, 0.0)
    c = C // 2
    y2, h2 = ref.ref_ssd(x, dtm, A, B[:, :, None], Cm[:, :, None], chunk=c,
                         return_state=True, h0=h)
    ha, ya = ref.ssd_chunk(h, x[:, :c], dtm[:, :c], A, B[:, :c], Cm[:, :c])
    hb, yb = ref.ssd_chunk(ha, x[:, c:], dtm[:, c:], A, B[:, c:], Cm[:, c:])
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hb),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y2),
                               np.concatenate([ya, yb], axis=1),
                               rtol=1e-6, atol=1e-6)
