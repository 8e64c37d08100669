"""Recurrent blocks: parallel/chunkwise training forms must match the O(1)
recurrent decode forms step by step."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.models.rglru import rglru_scan_assoc
from repro.models.xlstm import mlstm_chunkwise, mlstm_step


def test_mlstm_chunkwise_matches_recurrent(rng):
    b, s, h, dh = 2, 64, 2, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32)) / np.sqrt(dh)
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    li = jnp.asarray(rng.normal(size=(b, s, h)).astype(np.float32))
    lf = jnp.log(jax.nn.sigmoid(
        jnp.asarray(rng.normal(size=(b, s, h)).astype(np.float32))))

    out_chunk, _ = mlstm_chunkwise(q, k, v, li, lf, chunk=16)

    carry = (jnp.zeros((b, h, dh, dh)), jnp.zeros((b, h, dh)),
             jnp.full((b, h), -1e30))
    outs = []
    for t in range(s):
        o, carry = mlstm_step(q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1],
                              li[:, t:t + 1], lf[:, t:t + 1], carry)
        outs.append(o[:, 0])
    out_rec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_chunk), np.asarray(out_rec),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunks", [(16,), (32,), (64,)])
def test_mlstm_chunk_size_invariance(chunks, rng):
    """The chunk size is an implementation detail, not semantics."""
    b, s, h, dh = 1, 64, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    li = jnp.asarray(rng.normal(size=(b, s, h)).astype(np.float32))
    lf = jnp.log(jax.nn.sigmoid(
        jnp.asarray(rng.normal(size=(b, s, h)).astype(np.float32))))
    ref, _ = mlstm_chunkwise(q, k, v, li, lf, chunk=s)
    out, _ = mlstm_chunkwise(q, k, v, li, lf, chunk=chunks[0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_mlstm_chunkwise_grad_finite_with_strong_forget(rng):
    """Strong forgetting drives g_s - M_t above the diagonal far past exp's
    f32 range (10 per step here); the masked entries must not turn the
    gradient into NaN.  Published widths at seq 1024 hit this at init."""
    b, s, h, dh = 1, 64, 1, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    li = jnp.zeros((b, s, h), jnp.float32)
    lf = jnp.full((b, s, h), -10.0, jnp.float32)

    def loss(q_, li_, lf_):
        return mlstm_chunkwise(q_, k, v, li_, lf_, chunk=s)[0].sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, li, lf)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_rglru_assoc_scan_matches_sequential(rng):
    b, s, w = 2, 48, 16
    log_a = -jnp.abs(jnp.asarray(rng.normal(size=(b, s, w)).astype(np.float32))) * 0.2
    bb = jnp.asarray(rng.normal(size=(b, s, w)).astype(np.float32))
    h = rglru_scan_assoc(log_a, bb)
    href = np.zeros((b, w), np.float32)
    la, bn = np.asarray(log_a), np.asarray(bb)
    outs = []
    for t in range(s):
        href = np.exp(la[:, t]) * href + bn[:, t]
        outs.append(href.copy())
    ref = np.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(h), ref, rtol=1e-4, atol=1e-5)


def test_rglru_with_initial_state(rng):
    b, s, w = 1, 8, 4
    log_a = -jnp.abs(jnp.asarray(rng.normal(size=(b, s, w)).astype(np.float32)))
    bb = jnp.asarray(rng.normal(size=(b, s, w)).astype(np.float32))
    h0 = jnp.asarray(rng.normal(size=(b, w)).astype(np.float32))
    h = rglru_scan_assoc(log_a, bb, h0=h0)
    # sequential with h0
    href = np.asarray(h0).copy()
    la, bn = np.asarray(log_a), np.asarray(bb)
    for t in range(s):
        href = np.exp(la[:, t]) * href + bn[:, t]
    np.testing.assert_allclose(np.asarray(h[:, -1]), href, rtol=1e-4,
                               atol=1e-5)


def test_xlstm_decode_state_bounded():
    """xLSTM/RG-LRU decode caches are O(1) in sequence length — the
    long_500k enabling property."""
    import jax

    from repro.models import build_model
    cfg = get_arch("xlstm-350m").reduced()
    model = build_model(cfg)
    c1 = jax.eval_shape(lambda: model.init_cache(1, 1000, dtype=jnp.float32))
    c2 = jax.eval_shape(lambda: model.init_cache(1, 100000, dtype=jnp.float32))
    from repro.utils.tree import tree_bytes
    assert tree_bytes(c1) == tree_bytes(c2)
