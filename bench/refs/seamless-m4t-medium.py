"""Plain float32 reference of SeamlessM4T-medium's text-decoder training
loss over a speech encoder (arXiv:2308.11596; Vaswani et al. 2017 for the
layers).

The speech frontend is a stub: the batch carries frame embeddings
``source_embeds`` (B, S_src, D), projected by ``src_proj`` into a
pre-LayerNorm transformer encoder (bidirectional self-attention with
rotary positions, then a gated GELU feed-forward).  The decoder embeds the
target tokens and runs causal rotary self-attention, cross-attention onto
the encoder's normalized output (no rotary), and the same feed-forward.

Departures from the paper, as the configuration runs the model: rotary
positions instead of sinusoidal ones, a gated feed-forward, an untied
frozen head, and the loss's softmax over every head column, padding
columns included.

Parameter layout (the program's): ``src_proj.w``, ``encoder`` and
``decoder`` stacked over layers, ``enc_norm``, ``embed.table``,
``final_norm``, ``lm_head.w``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference as R

F32 = jnp.float32


def rope(x, theta):
    """Rotary positions 0..S-1 on x (B, S, H, hd), halves rotated."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq           # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def proj(p, x, cast):
    """x (B, S, D) @ w (D, H, hd) + b."""
    y = R.einsum("bsd,dhk->bshk", x, p["w"], cast)
    return y + p["b"].astype(F32) if "b" in p else y


def attention(p, xq, xkv, cast, *, causal, theta):
    q, k, v = (proj(p[n], x, cast) for n, x in (("q", xq), ("k", xkv),
                                                 ("v", xkv)))
    if theta:
        q, k = rope(q, theta), rope(k, theta)
    s = R.einsum("bqhd,bkhd->bhqk", q, k, cast) / math.sqrt(q.shape[-1])
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = R.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, cast)
    B, S = o.shape[:2]
    return R.mm(o.reshape(B, S, -1), p["o"]["w"], cast)


def ffn(p, x, cast):
    g = R.gelu(R.mm(x, p["gate"]["w"], cast)) * R.mm(x, p["up"]["w"], cast)
    return R.mm(g, p["down"]["w"], cast)


def loss(params, batch, cfg, cast=R.exact):
    """Mean next-token cross-entropy of one batch {"source_embeds" (B,
    S_src, D), "tokens", "labels" (B, S)}; ``cast`` takes every matrix
    product's operands."""
    theta = cfg["rope_theta"]

    @jax.checkpoint
    def enc_layer(x, p):
        h = R.layernorm(x, p["ln1"])
        x = x + attention(p["attn"], h, h, cast, causal=False, theta=theta)
        return x + ffn(p["mlp"], R.layernorm(x, p["ln2"]), cast), None

    x = R.mm(batch["source_embeds"], params["src_proj"]["w"], cast)
    x, _ = jax.lax.scan(enc_layer, x, params["encoder"])
    memory = R.layernorm(x, params["enc_norm"])

    @jax.checkpoint
    def dec_layer(x, p):
        h = R.layernorm(x, p["ln1"])
        x = x + attention(p["self"], h, h, cast, causal=True, theta=theta)
        h = R.layernorm(x, p["lnx"])
        x = x + attention(p["cross"], h, memory, cast, causal=False,
                          theta=0.0)
        return x + ffn(p["mlp"], R.layernorm(x, p["ln2"]), cast), None

    x = params["embed"]["table"][batch["tokens"]].astype(F32)
    x, _ = jax.lax.scan(dec_layer, x, params["decoder"])
    x = R.layernorm(x, params["final_norm"])
    return R.cross_entropy(R.mm(x, params["lm_head"]["w"], cast),
                           batch["labels"])
