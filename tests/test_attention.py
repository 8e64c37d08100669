"""Attention path consistency: dense vs chunked vs banded vs decode."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.data.synthetic import synthetic_token_batch
from repro.models import build_model
from repro.models.attention import (banded_attention, choose_impl,
                                    chunked_attention, dense_attention,
                                    self_attention)
from repro.models.layers import apply_mrope, apply_rope


def _qkv(rng, b, s, h, kvh, d):
    q = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, kvh, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, kvh, d)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("window", [0, 64])
def test_chunked_matches_dense(window, rng):
    q, k, v = _qkv(rng, 2, 256, 4, 2, 32)
    ref = dense_attention(q, k, v, causal=True, window=window, softcap=0.0)
    out = chunked_attention(q, k, v, causal=True, window=window, softcap=0.0,
                            q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_banded_matches_dense(rng):
    q, k, v = _qkv(rng, 1, 256, 4, 4, 32)
    ref = dense_attention(q, k, v, causal=True, window=64, softcap=0.0)
    out = banded_attention(q, k, v, window=64, softcap=0.0, q_chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,sk,platform,impl", [
    # m4t-medium-round on a TPU: the encoder, the decoder, cross-attention
    (1024, 1024, "tpu", "flash"),
    (512, 512, "tpu", "flash"),
    (512, 1024, "tpu", "flash"),
    (32768, 32768, "tpu", "flash"),
    # decode, lengths off the kernel's 128-row unit, and short lengths,
    # where dense is the faster path
    (1, 1024, "tpu", "dense"),
    (128, 128, "tpu", "dense"),
    (256, 256, "tpu", "dense"),
    (256, 1024, "tpu", "dense"),
    (1, 1, "tpu", "dense"),
    (64, 64, "tpu", "dense"),
    (1000, 1000, "tpu", "dense"),
    # other platforms keep the pure-JAX paths
    (1024, 1024, "cpu", "dense"),
    (512, 1024, "gpu", "dense"),
    (8192, 8192, "cpu", "chunked"),
    (8192, 8000, "cpu", "dense"),
])
def test_choose_impl(sq, sk, platform, impl):
    assert choose_impl(sq, sk, platform) == impl


@pytest.mark.parametrize("causal,sk", [(True, 64), (False, 64), (False, 128)])
def test_auto_on_the_cpu_is_dense(causal, sk, rng):
    """Under ``impl="auto"`` off a TPU the tiny shapes run the dense path,
    bit for bit, cross-attention (Sq != Skv) included."""
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, sk, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, sk, 2, 16)).astype(np.float32))
    out = self_attention(q, k, v, causal=causal)
    ref = dense_attention(q, k, v, causal=causal, window=0, softcap=0.0)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("causal,sk", [(True, 64), (False, 128)])
def test_forced_flash_matches_dense(causal, sk, rng):
    """``impl="flash"`` (the kernel in the Pallas interpreter here) gives
    the dense path's output and gradients."""
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, sk, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, sk, 2, 16)).astype(np.float32))

    def loss(impl):
        return lambda *a: (self_attention(*a, causal=causal, impl=impl)
                           ** 2).sum()

    for impl_out, ref_out in zip(
            jax.value_and_grad(loss("flash"), argnums=(0, 1, 2))(q, k, v),
            jax.value_and_grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)):
        for a, b in zip(jax.tree.leaves(impl_out), jax.tree.leaves(ref_out)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def test_rope_relative_shift_invariance(rng):
    """RoPE: shifting q and k positions together preserves attention logits."""
    d = 32
    q = jnp.asarray(rng.normal(size=(1, 8, 2, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 8, 2, d)).astype(np.float32))
    pos = jnp.arange(8)[None, :]
    q1, k1 = apply_rope(q, pos, 1e4), apply_rope(k, pos, 1e4)
    q2, k2 = apply_rope(q, pos + 13, 1e4), apply_rope(k, pos + 13, 1e4)
    l1 = jnp.einsum("bqhd,bkhd->bhqk", q1, k1)
    l2 = jnp.einsum("bqhd,bkhd->bhqk", q2, k2)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-3, atol=1e-3)


def test_mrope_reduces_to_rope_for_text(rng):
    """With identical (t,h,w) position streams, M-RoPE == RoPE."""
    d = 32
    x = jnp.asarray(rng.normal(size=(1, 8, 2, d)).astype(np.float32))
    pos = jnp.arange(8)[None, :]
    pos3 = jnp.tile(pos[..., None], (1, 1, 3))
    a = apply_rope(x, pos, 1e4)
    b = apply_mrope(x, pos3, 1e4, (8, 4, 4))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("arch", ["mistral-large-123b", "gemma3-12b",
                                  "deepseek-v2-236b", "recurrentgemma-2b",
                                  "xlstm-350m", "seamless-m4t-medium"])
def test_prefill_decode_equivalence(arch, rng):
    """Teacher-forced logits at position t == decode logits after feeding
    tokens 0..t-1 through the cache path."""
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S = 12
    nb = synthetic_token_batch(0, 1, S, cfg.vocab_size)
    batch = {k: jnp.asarray(v) for k, v in nb.items()}
    if cfg.encdec is not None:
        batch["source_embeds"] = 0.02 * jnp.ones(
            (1, cfg.encdec.max_source_len, cfg.d_model), jnp.float32)
    hidden, _ = model.apply(params, batch)
    full_logits = model.logits(params, hidden)        # (1,S,V)

    cache = model.init_cache(1, S, dtype=jnp.float32)
    if cfg.encdec is not None:
        from repro.models import encdec as ed
        memory = ed.encode(params, cfg, batch["source_embeds"])
        cache["cross"] = ed.precompute_cross(params, cfg, memory,
                                             dtype=jnp.float32)
    logits_steps = []
    for t in range(S):
        tok = batch["tokens"][:, t:t + 1]
        lg, cache = model.decode_step(params, tok, cache,
                                      jnp.asarray(t, jnp.int32))
        logits_steps.append(lg[:, 0])
    dec = jnp.stack(logits_steps, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               rtol=5e-3, atol=5e-3)
