"""Plain float32 reference of xLSTM-350M's training loss (arXiv:2405.04517).

mLSTM in its parallel stabilized form (paper, appendix: D~ = exp(F_t - F_s
+ i_s) for s <= t, normalizer max(|sum_s C~_ts|, exp(-m_t))), sLSTM as its
recurrence step by step, a pre-LayerNorm residual around each block.  Layer
k uses the k-th block of the configuration's ``block_pattern``.

Parameter layout (the program's, read by name): ``embed.table``,
``final_norm``, ``lm_head.w``; the first ``n_client_layers`` layers under
``stage0.b<j>``, then the repeating pattern stacked on a leading axis under
``stage1.b<j>``, and any remainder under ``stage2.b<j>``.

Departures from the paper, as the configuration runs the model: the head
is untied from the embedding (the PHSFL frozen head), and the loss takes
its softmax over every head column, padding columns included.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference as R

F32 = jnp.float32


def mlstm(p, x, cfg, cast):
    """x: (B, S, D) float32, already normalized."""
    xl = cfg["xlstm"]
    B, S, D = x.shape
    di = int(D * xl["proj_factor_mlstm"])
    H = xl["num_heads"]
    dh = di // H
    up = R.mm(x, p["up"]["w"], cast)
    xm, z = up[..., :di], up[..., di:]
    w = p["conv"].astype(F32)                      # (K, di), causal taps
    K = w.shape[0]
    xp = jnp.pad(xm, ((0, 0), (K - 1, 0), (0, 0)))
    c = R.silu(sum(xp[:, i:i + S] * w[i] for i in range(K)))
    q = R.mm(c, p["q"]["w"], cast).reshape(B, S, H, dh)
    k = R.mm(c, p["k"]["w"], cast).reshape(B, S, H, dh) / math.sqrt(dh)
    v = R.mm(xm, p["v"]["w"], cast).reshape(B, S, H, dh)
    li = R.mm(c, p["i_gate"]["w"], cast)            # (B, S, H)
    lf = R.log_sigmoid(R.mm(c, p["f_gate"]["w"], cast))
    F = jnp.cumsum(lf, axis=1)
    logd = F[:, :, None, :] - F[:, None, :, :] + li[:, None, :, :]  # b,t,s,h
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    logd = jnp.where(causal, logd, -jnp.inf)
    m = logd.max(axis=2)                            # (B, t, H)
    d = jnp.exp(logd - m[:, :, None, :])
    ct = R.einsum("bthd,bshd->btsh", q, k, cast) * d
    n = jnp.maximum(jnp.abs(ct.sum(axis=2)), jnp.exp(-m))
    h = R.einsum("btsh,bshd->bthd", ct, v, cast) / n[..., None]
    h = R.rmsnorm(h, p["out_norm"]["scale"]).reshape(B, S, di)
    return R.mm(h * R.silu(z), p["down"]["w"], cast)


def slstm(p, x, cfg, cast):
    """x: (B, S, D) float32, already normalized."""
    B, S, D = x.shape
    H = cfg["xlstm"]["num_heads"]
    dh = D // H
    wx = R.mm(x, p["w"]["w"], cast) + p["b"].astype(F32)
    wx = wx.reshape(B, S, H, 4 * dh)
    r = p["r"].astype(F32)                          # (H, dh, 4 dh)

    def cell(state, wx_t):
        c, n, h, m = state
        raw = wx_t + R.einsum("bhd,hdk->bhk", h, r, cast)
        i, f, zt, o = (raw[..., j * dh:(j + 1) * dh] for j in range(4))
        lf = R.log_sigmoid(f)
        m_new = jnp.maximum(lf + m, i)
        ig = jnp.exp(i - m_new)
        fg = jnp.exp(lf + m - m_new)
        c = fg * c + ig * jnp.tanh(zt)
        n = fg * n + ig
        h = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    zero = jnp.zeros((B, H, dh), F32)
    init = (zero, zero, zero, jnp.full((B, H, dh), -1e30, F32))
    _, hs = jax.lax.scan(cell, init, jnp.moveaxis(wx, 1, 0))
    hs = R.rmsnorm(jnp.moveaxis(hs, 0, 1), p["out_norm"]["scale"])
    hs = hs.reshape(B, S, D)
    g = (R.gelu(R.mm(hs, p["up_gate"]["w"], cast))
         * R.mm(hs, p["up"]["w"], cast))
    return R.mm(g, p["down"]["w"], cast)


BLOCKS = {"mlstm": mlstm, "slstm": slstm}


def layer(p, x, kind, cfg, cast):
    return x + BLOCKS[kind](p["block"], R.layernorm(x, p["ln1"]), cfg, cast)


def loss(params, batch, cfg, cast=R.exact):
    """Mean next-token cross-entropy of one batch {"tokens", "labels"},
    each (B, S); ``cast`` takes every matrix product's operands."""
    pattern = cfg["block_pattern"]
    L, lead = cfg["num_layers"], cfg["n_client_layers"]
    x = params["embed"]["table"][batch["tokens"]].astype(F32)
    stage = 0
    if lead:
        for j in range(lead):
            kind = pattern[j % len(pattern)]
            x = jax.checkpoint(
                lambda p, x, kind=kind: layer(p, x, kind, cfg, cast))(
                    params["stage0"][f"b{j}"], x)
        stage = 1
    reps = (L - lead) // len(pattern)
    if reps:
        kinds = [pattern[(lead + j) % len(pattern)]
                 for j in range(len(pattern))]

        @jax.checkpoint
        def period(x, ps):
            for j, kind in enumerate(kinds):
                x = layer(ps[f"b{j}"], x, kind, cfg, cast)
            return x, None

        x, _ = jax.lax.scan(period, x, params[f"stage{stage}"])
        stage += 1
    for j, lid in enumerate(range(lead + reps * len(pattern), L)):
        x = layer(params[f"stage{stage}"][f"b{j}"], x,
                  pattern[lid % len(pattern)], cfg, cast)
    x = R.layernorm(x, params["final_norm"])
    return R.cross_entropy(R.mm(x, params["lm_head"]["w"], cast),
                           batch["labels"])
