"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs the
pure-jnp oracle, executed via interpret=True on CPU (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.ssm_scan.ssm_scan import ssm_scan

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,K,S,hd,bq,bk",
    [
        (1, 4, 2, 128, 64, 64, 64),
        (2, 2, 1, 256, 32, 128, 64),   # MQA, rectangular blocks
        (1, 8, 8, 128, 128, 128, 128), # MHA, MXU-native tile
        (1, 4, 4, 64, 64, 64, 64),     # single block
    ],
)
def test_flash_attention_sweep(dtype, B, H, K, S, hd, bq, bk):
    q = jnp.asarray(RNG.normal(size=(B, H, S, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, K, S, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, K, S, hd)), dtype)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    q = jnp.asarray(RNG.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 256, 64)), jnp.float32)
    out = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_softcap_noncausal():
    q = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, softcap=20.0,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=False, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,di,N,bd,chunk",
    [
        (1, 64, 64, 8, 32, 32),
        (2, 128, 128, 16, 128, 64),
        (1, 32, 256, 16, 64, 32),
    ],
)
def test_ssm_scan_sweep(dtype, B, S, di, N, bd, chunk):
    u = jnp.asarray(RNG.normal(size=(B, S, di)), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(B, S, di)), dtype)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(di, N)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    out = ssm_scan(u, dt, A, Bm, Cm, block_d=bd, chunk=chunk, interpret=True)
    ref = ssm_scan_ref(u, dt, A, Bm, Cm)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
        rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
    )


def test_ssm_scan_state_carries_across_chunks():
    """With a long memory (small dt), late outputs depend on early inputs —
    catching any bug where scratch state is reset between chunks."""
    B, S, di, N = 1, 128, 32, 4
    u = jnp.zeros((B, S, di)).at[:, 0, :].set(1.0)
    dt = jnp.full((B, S, di), 0.01)
    A = -jnp.full((di, N), 0.1)
    Bm = jnp.ones((B, S, N))
    Cm = jnp.ones((B, S, N))
    out = ssm_scan(u, dt, A, Bm, Cm, block_d=32, chunk=32, interpret=True)
    assert float(jnp.abs(out[0, -1]).max()) > 1e-4  # leakage from t=0 visible


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

from repro.kernels.flash_decode.flash_decode import flash_decode
from repro.kernels.flash_decode.ref import decode_attention_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,K,S,hd,bs",
    [
        (2, 8, 2, 256, 64, 128),
        (1, 4, 4, 512, 128, 256),   # MHA
        (3, 2, 1, 128, 32, 64),     # MQA
    ],
)
def test_flash_decode_sweep(dtype, B, H, K, S, hd, bs):
    q = jnp.asarray(RNG.normal(size=(B, H, hd)), dtype)
    kc = jnp.asarray(RNG.normal(size=(B, K, S, hd)), dtype)
    vc = jnp.asarray(RNG.normal(size=(B, K, S, hd)), dtype)
    pos = jnp.asarray(RNG.integers(1, S, size=(B,)), jnp.int32)
    out = flash_decode(q, kc, vc, pos, block_s=bs, interpret=True)
    ref = decode_attention_ref(q, kc, vc, pos)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


def test_flash_decode_respects_pos_mask():
    """Only cache entries <= pos may contribute."""
    B, H, S, hd = 1, 2, 128, 32
    q = jnp.ones((B, H, hd), jnp.float32)
    kc = jnp.ones((B, H, S, hd), jnp.float32)
    vc = jnp.zeros((B, H, S, hd), jnp.float32).at[:, :, 50:, :].set(1e3)
    pos = jnp.asarray([10], jnp.int32)  # garbage beyond 10 must be masked
    out = flash_decode(q, kc, vc, pos, block_s=64, interpret=True)
    assert float(jnp.abs(out).max()) < 1.0


def test_flash_decode_sliding_window():
    B, H, S, hd = 1, 2, 256, 32
    q = jnp.asarray(RNG.normal(size=(B, H, hd)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(B, H, S, hd)), jnp.float32)
    vc = jnp.asarray(RNG.normal(size=(B, H, S, hd)), jnp.float32)
    pos = jnp.asarray([200], jnp.int32)
    out = flash_decode(q, kc, vc, pos, window=32, block_s=64, interpret=True)
    ref = decode_attention_ref(q, kc, vc, pos, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan (mamba-2)
# ---------------------------------------------------------------------------

from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,N,bh,chunk",
    [
        (1, 64, 4, 16, 8, 4, 32),
        (2, 128, 8, 32, 16, 4, 64),
        (1, 96, 2, 64, 32, 2, 32),
    ],
)
def test_ssd_scan_sweep(dtype, B, S, H, P, N, bh, chunk):
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(B, S, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    out = ssd_scan(x, dt, A, Bm, Cm, block_h=bh, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=8e-2 if dtype == jnp.bfloat16 else 2e-4,
        rtol=8e-2 if dtype == jnp.bfloat16 else 2e-4,
    )


def test_ssd_scan_matches_model_impl():
    """Kernel vs the model's einsum-based chunked SSD (_ssd_chunked) —
    two independent implementations of the same decomposition."""
    from repro.models.ssm import _ssd_chunked

    B, S, H, P, N = 1, 128, 4, 32, 16
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(B, S, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    out_k = ssd_scan(x, dt, A, Bm, Cm, block_h=4, chunk=32, interpret=True)
    out_m = _ssd_chunked(x, dt, A, Bm, Cm, 32)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_m), atol=2e-4, rtol=2e-4
    )


def test_ssd_state_carries_across_chunks():
    B, S, H, P, N = 1, 96, 2, 8, 4
    x = jnp.zeros((B, S, H, P)).at[:, 0].set(1.0)
    dt = jnp.full((B, S, H), 0.01)
    A = -jnp.full((H,), 0.1)
    Bm = jnp.ones((B, S, N))
    Cm = jnp.ones((B, S, N))
    out = ssd_scan(x, dt, A, Bm, Cm, block_h=2, chunk=32, interpret=True)
    assert float(jnp.abs(out[0, -1]).max()) > 1e-5
