"""Shared layer math: norms, activations, RoPE (incl. M-RoPE), gated MLPs."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.telemetry.spans import scope


# ---------------------------------------------------------------- norms ----
def apply_norm(p, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        # plain scale (not gemma's "1+scale" convention; training dynamics we
        # study are insensitive to it)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
        return y.astype(x.dtype)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
        return y.astype(x.dtype)
    raise ValueError(kind)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(name)


def softcap(x, cap: float):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ----------------------------------------------------------------- RoPE ----
def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                  # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]                        # (..., seq, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: tuple[int, int, int]):
    """Qwen2-VL multimodal RoPE [arXiv:2409.12191].

    positions3: (..., seq, 3) int — (temporal, height, width) position ids.
    The rotary spectrum (head_dim/2 frequencies) is split into ``sections``
    (t/h/w); each section rotates by its own position stream.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)                  # (half,)
    # section id per frequency: each of the half rotary frequencies is driven
    # by one of the three (t, h, w) position streams
    sec_id = jnp.concatenate([
        jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)])
    idx = jnp.broadcast_to(sec_id[None, :], positions3.shape[:-1] + (half,))
    pos = jnp.take_along_axis(positions3.astype(jnp.float32), idx, axis=-1)
    ang = pos * freqs                                       # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ gated MLP ----
def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None, dtype=None):
    from repro.models.init_utils import dense
    d_ff = d_ff or cfg.d_ff
    dtype = dtype or jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense(k1, cfg.d_model, d_ff, dtype=dtype),
        "up": dense(k2, cfg.d_model, d_ff, dtype=dtype),
        "down": dense(k3, d_ff, cfg.d_model, dtype=dtype),
    }


def mlp_axes():
    from repro.models.init_utils import dense_axes
    return {
        "gate": dense_axes(("embed", "mlp")),
        "up": dense_axes(("embed", "mlp")),
        "down": dense_axes(("mlp", "embed")),
    }


@scope("mlp")
def mlp_apply(p, x, act_name: str):
    act = activation(act_name)
    h = act(x @ p["gate"]["w"]) * (x @ p["up"]["w"])
    return h @ p["down"]["w"]
