"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_hmajor
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_pallas
from repro.kernels.mlstm_chunk.ref import mlstm_ref
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref


# ------------------------------------------------------- flash attention ---
@pytest.mark.parametrize("b,h,kvh,s,d", [
    (2, 4, 2, 256, 64),
    (1, 4, 4, 512, 32),
    (1, 2, 1, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(b, h, kvh, s, d, dtype, causal, window, rng):
    q = jnp.asarray(rng.normal(size=(b, h, s, d))).astype(dtype)
    k = jnp.asarray(rng.normal(size=(b, kvh, s, d))).astype(dtype)
    v = jnp.asarray(rng.normal(size=(b, kvh, s, d))).astype(dtype)
    out, _ = flash_attention_hmajor(q, k, v, causal=causal, window=window,
                                    block_q=128, block_k=128)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_softcap(rng):
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 32)).astype(np.float32))
    out, _ = flash_attention_hmajor(q, k, v, causal=True, softcap=20.0,
                                    block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=True, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_model_layout_and_grad(rng):
    """ops wrapper: (B,S,H,d) layout + the blocked backward runs."""
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 32)).astype(np.float32))
    out = flash_attention(q, k, v, True, 0, 0.0)
    assert out.shape == q.shape
    g = jax.grad(lambda q_: flash_attention(q_, k, v, True, 0, 0.0).sum())(q)
    assert bool(jnp.isfinite(g).all())


# bidirectional, causal and cross-attention (Sq != Skv), grouped queries
FLASH_CASES = [
    # name, Sq, Skv, causal, H, KVH
    ("bidirectional", 64, 64, False, 4, 2),
    ("causal", 64, 64, True, 4, 2),
    ("cross_32_onto_64", 32, 64, False, 4, 2),
    ("cross_64_onto_128", 64, 128, False, 4, 1),
]
# f32 agrees with the dense path to rounding; bf16 rounds the
# probabilities and dS to bf16 before their products (as the dense path,
# whose f32 products run in bf16 passes on a TPU, does not on the CPU)
FLASH_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _flash_inputs(rng, sq, sk, h, kvh, dtype, d=16):
    q = jnp.asarray(rng.normal(size=(2, sq, h, d))).astype(dtype)
    k = jnp.asarray(rng.normal(size=(2, sk, kvh, d))).astype(dtype)
    v = jnp.asarray(rng.normal(size=(2, sk, kvh, d))).astype(dtype)
    ct = jnp.asarray(rng.normal(size=(2, sq, h, d)).astype(np.float32))
    return q, k, v, ct


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("name,sq,sk,causal,h,kvh", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_dense(name, sq, sk, causal, h, kvh, dtype,
                                       rng):
    """The model-layout flash path against ``dense_attention``: the output,
    and the gradients in q, k and v through the blocked backward (kv
    blocks of 32 keys, so the backward takes several)."""
    from repro.kernels.flash_attention import ops
    from repro.models.attention import dense_attention

    q, k, v, ct = _flash_inputs(rng, sq, sk, h, kvh, dtype)

    def flash(q, k, v):
        out, lse = ops._forward(q, k, v, causal, 0, 0.0)
        return out, lse

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=causal, window=0, softcap=0.0)

    out, lse = flash(q, k, v)
    ref, vjp = jax.vjp(dense, q, k, v)
    tol = FLASH_TOL[dtype]
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert _rel_err(out, ref) <= tol
    want = vjp(ct.astype(dtype))
    got = ops.attention_bwd(q, k, v, out, lse, ct.astype(dtype),
                            causal=causal, window=0, softcap=0.0, block_k=32)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _rel_err(g, w) <= tol
    # the custom VJP is the same backward
    loss = lambda f: lambda *a: (f(*a).astype(jnp.float32) * ct).sum()
    grads = jax.grad(loss(lambda *a: flash_attention(*a, causal, 0, 0.0)),
                     argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want):
        assert _rel_err(g, w) <= tol


@pytest.mark.parametrize("name,sq,sk,causal,h,kvh", FLASH_CASES)
def test_flash_attention_lse(name, sq, sk, causal, h, kvh, rng):
    """The kernel's per-row log-sum-exp is ``logsumexp`` of the dense
    path's scaled, masked logits."""
    q, k, v, _ = _flash_inputs(rng, sq, sk, h, kvh, jnp.float32)
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    _, lse = flash_attention_hmajor(qh, kh, vh, causal=causal, block_q=32,
                                    block_k=32)
    kx = jnp.repeat(kh, h // kvh, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kx) / np.sqrt(q.shape[-1])
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), logits,
                           -jnp.inf)
    want = jax.scipy.special.logsumexp(logits, axis=-1)
    assert lse.shape == (2, h, sq) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_window_and_softcap_backward(rng):
    """The blocked backward with a sliding window and a soft cap, against
    autodiff of the dense path."""
    from repro.kernels.flash_attention import ops
    from repro.models.attention import dense_attention

    q, k, v, ct = _flash_inputs(rng, 64, 64, 4, 2, jnp.float32)
    out, lse = ops._forward(q, k, v, True, 16, 5.0)
    ref, vjp = jax.vjp(lambda *a: dense_attention(
        *a, causal=True, window=16, softcap=5.0), q, k, v)
    assert _rel_err(out, ref) <= 1e-5
    got = ops.attention_bwd(q, k, v, out, lse, ct, causal=True, window=16,
                            softcap=5.0, block_k=16)
    for g, w in zip(got, vjp(ct)):
        assert _rel_err(g, w) <= 1e-5


# ------------------------------------------------------------ rglru --------
@pytest.mark.parametrize("b,s,w,bt,bw", [
    (2, 128, 64, 32, 64),
    (1, 256, 512, 64, 256),
    (3, 64, 128, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_kernel_sweep(b, s, w, bt, bw, dtype, rng):
    la = (-jnp.abs(jnp.asarray(rng.normal(size=(b, s, w)))) * 0.1).astype(dtype)
    bb = jnp.asarray(rng.normal(size=(b, s, w))).astype(dtype)
    h0 = jnp.asarray(rng.normal(size=(b, w))).astype(jnp.float32)
    out = rglru_scan_pallas(la, bb, h0, block_t=bt, block_w=bw)
    ref = rglru_scan_ref(la, bb, h0)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_rglru_ops_grad(rng):
    la = -jnp.abs(jnp.asarray(rng.normal(size=(1, 32, 16)).astype(np.float32)))
    bb = jnp.asarray(rng.normal(size=(1, 32, 16)).astype(np.float32))
    h0 = jnp.zeros((1, 16), jnp.float32)
    g = jax.grad(lambda b_: rglru_scan(la, b_, h0).sum())(bb)
    assert bool(jnp.isfinite(g).all())


# ------------------------------------------------------------ mlstm --------
@pytest.mark.parametrize("b,h,s,dh,ck", [
    (2, 2, 128, 32, 32),
    (1, 4, 256, 64, 64),
    (1, 1, 64, 16, 16),
])
def test_mlstm_kernel_sweep(b, h, s, dh, ck, rng):
    q = jnp.asarray(rng.normal(size=(b, h, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, h, s, dh)).astype(np.float32)) / np.sqrt(dh)
    v = jnp.asarray(rng.normal(size=(b, h, s, dh)).astype(np.float32))
    li = jnp.asarray(rng.normal(size=(b, h, s)).astype(np.float32))
    lf = jnp.log(jax.nn.sigmoid(
        jnp.asarray(rng.normal(size=(b, h, s)).astype(np.float32))))
    out = mlstm_chunk_pallas(q, k, v, li, lf, chunk=ck)
    ref = mlstm_ref(q, k, v, li, lf, chunk=ck)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)
