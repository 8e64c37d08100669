"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, parallelizable)
and sLSTM (scalar memory with true hidden-to-hidden recurrence).

mLSTM training uses the *chunkwise-parallel* form (intra-chunk quadratic +
inter-chunk recurrent carry) — the same algorithm the Pallas
``mlstm_chunk`` kernel implements with VMEM tiling; decode uses the O(1)
recurrent form.  The two are numerically consistent (tested).

sLSTM is inherently sequential (hidden state feeds the gates); training is a
lax.scan over time — this is honest to the architecture and shows up as a
latency-bound term in the roofline analysis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.init_utils import dense, dense_axes, norm, norm_axes, truncated_normal

MLSTM_CHUNK = 256


# =============================================================== mLSTM ======
def mlstm_init(key, cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    x = cfg.xlstm
    d = cfg.d_model
    di = int(d * x.proj_factor_mlstm)
    h = x.num_heads
    dh = di // h
    ks = jax.random.split(key, 8)
    return {
        "up": dense(ks[0], d, 2 * di, dtype=dtype),        # [x_m ; z-gate]
        "conv": truncated_normal(ks[1], (x.conv_kernel, di), 1.0 / math.sqrt(x.conv_kernel), dtype),
        "q": dense(ks[2], di, di, dtype=dtype),
        "k": dense(ks[3], di, di, dtype=dtype),
        "v": dense(ks[4], di, di, dtype=dtype),
        "i_gate": dense(ks[5], di, h, dtype=jnp.float32),
        "f_gate": dense(ks[6], di, h, dtype=jnp.float32),
        "out_norm": norm(dh, "rmsnorm", dtype),            # per-head group norm
        "down": dense(ks[7], di, d, dtype=dtype),
    }


def mlstm_axes(cfg: ModelConfig):
    return {
        "up": dense_axes(("embed", "mlp")),
        "conv": ("conv", "mlp"),
        "q": dense_axes(("mlp", "mlp")),
        "k": dense_axes(("mlp", "mlp")),
        "v": dense_axes(("mlp", "mlp")),
        "i_gate": dense_axes(("mlp", None)),
        "f_gate": dense_axes(("mlp", None)),
        "out_norm": norm_axes("rmsnorm"),
        "down": dense_axes(("mlp", "embed")),
    }


def causal_conv1d(x, w, state=None):
    """Depthwise causal conv.  x: (B,S,C); w: (K,C).

    state: (B,K-1,C) trailing context from previous tokens (decode); returns
    (y, new_state).
    """
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros(x.shape[:1] + (k - 1,) + x.shape[2:], x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                 # (B, S+K-1, C)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    new_state = xp[:, -(k - 1):] if k > 1 else state
    return y, new_state


def _mlstm_heads(p, cfg: ModelConfig, x_m, conv_state=None):
    """Project the mLSTM branch to per-head q,k,v and scalar gates."""
    xl = cfg.xlstm
    h = xl.num_heads
    conv_out, conv_state = causal_conv1d(x_m, p["conv"], conv_state)
    conv_act = jax.nn.silu(conv_out)
    b, s, di = x_m.shape
    dh = di // h
    q = (conv_act @ p["q"]["w"]).reshape(b, s, h, dh)
    k = (conv_act @ p["k"]["w"]).reshape(b, s, h, dh) / math.sqrt(dh)
    v = (x_m @ p["v"]["w"]).reshape(b, s, h, dh)
    li = (conv_act.astype(jnp.float32) @ p["i_gate"]["w"])          # (B,S,H)
    lf = jax.nn.log_sigmoid(conv_act.astype(jnp.float32) @ p["f_gate"]["w"])
    return q, k, v, li, lf, conv_state


def mlstm_chunkwise(q, k, v, li, lf, carry=None, chunk: int = MLSTM_CHUNK):
    """Chunkwise-parallel stabilized mLSTM.

    q,k,v: (B,S,H,dh); li,lf: (B,S,H) input/forget log-gates.
    carry: optional (C (B,H,dk,dv), n (B,H,dk), m (B,H)).
    Returns (h (B,S,H,dh), carry').
    """
    b, s, h, dh = q.shape
    if s % chunk:  # fall back to one chunk == recurrent-free quadratic path
        chunk = s
    nc = s // chunk
    f32 = jnp.float32
    qc = q.reshape(b, nc, chunk, h, dh).astype(f32)
    kc = k.reshape(b, nc, chunk, h, dh).astype(f32)
    vc = v.reshape(b, nc, chunk, h, dh).astype(f32)
    lic = li.reshape(b, nc, chunk, h).astype(f32)
    lfc = lf.reshape(b, nc, chunk, h).astype(f32)

    if carry is None:
        C0 = jnp.zeros((b, h, dh, dh), f32)
        n0 = jnp.zeros((b, h, dh), f32)
        m0 = jnp.full((b, h), -1e30, f32)
    else:
        C0, n0, m0 = (c.astype(f32) for c in carry)

    def chunk_body(state, inp):
        C, n, m_prev = state
        qb, kb, vb, lib, lfb = inp                          # (B,chunk,H,*)
        a = jnp.cumsum(lfb, axis=1)                         # (B,chunk,H)
        g = lib - a                                         # g_s = li_s - a_s
        run_max = jax.lax.cummax(g, axis=1)
        M = jnp.maximum(m_prev[:, None, :], run_max)        # (B,chunk,H)
        m_t = a + M
        # intra-chunk: D[t,s] = exp(g_s - M_t) for s <= t.  Mask BEFORE the
        # exp: above the diagonal g_s - M_t can exceed f32's exp range over
        # a long chunk, and exp's gradient there (inf * 0) would be NaN
        Dlog = g[:, None, :, :] - M[:, :, None, :]          # (B,t,s,H)
        t_idx = jnp.arange(chunk)
        causal = t_idx[None, :, None, None] >= t_idx[None, None, :, None]
        D = jnp.exp(jnp.where(causal, Dlog, -jnp.inf))
        scores = jnp.einsum("bthd,bshd->btsh", qb, kb) * D
        h_intra = jnp.einsum("btsh,bshd->bthd", scores, vb)
        n_intra = jnp.einsum("btsh,bshd->bthd", D, kb)
        # inter-chunk carry contribution, decayed by exp(m_prev - M_t)
        decay = jnp.exp(m_prev[:, None, :] - M)             # (B,chunk,H)
        h_inter = jnp.einsum("bthd,bhde->bthe", qb, C) * decay[..., None]
        n_inter = n[:, None, :, :] * decay[..., None]
        n_tot = n_intra + n_inter
        denom = jnp.maximum(jnp.abs(jnp.einsum("bthd,bthd->bth", qb, n_tot)),
                            jnp.exp(-m_t))[..., None]
        h_out = (h_intra + h_inter) / denom
        # ---- end-of-chunk carry update ----
        a_L = a[:, -1, :]                                   # (B,H)
        M_L = M[:, -1, :]
        m_new = m_t[:, -1, :]
        w_s = jnp.exp(g - M_L[:, None, :])                  # (B,chunk,H)
        C_new = C * jnp.exp(m_prev - M_L)[:, :, None, None] + \
            jnp.einsum("bsh,bshd,bshe->bhde", w_s, kb, vb)
        n_new = n * jnp.exp(m_prev - M_L)[:, :, None] + \
            jnp.einsum("bsh,bshd->bhd", w_s, kb)
        return (C_new, n_new, m_new), h_out

    (C, n, m), hs = jax.lax.scan(
        chunk_body, (C0, n0, m0),
        (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.moveaxis(lic, 1, 0), jnp.moveaxis(lfc, 1, 0)))
    h_all = jnp.moveaxis(hs, 0, 1).reshape(b, s, h, dh)
    return h_all.astype(q.dtype), (C, n, m)


def mlstm_step(q, k, v, li, lf, carry):
    """O(1) recurrent decode step.  q,k,v: (B,1,H,dh); li,lf: (B,1,H)."""
    C, n, m_prev = carry
    f32 = jnp.float32
    qs, ks, vs = (t[:, 0].astype(f32) for t in (q, k, v))
    lis, lfs = li[:, 0].astype(f32), lf[:, 0].astype(f32)
    m_new = jnp.maximum(lfs + m_prev, lis)
    fgate = jnp.exp(lfs + m_prev - m_new)[..., None]
    igate = jnp.exp(lis - m_new)[..., None]
    C = C * fgate[..., None] + igate[..., None] * ks[..., :, None] * vs[..., None, :]
    n = n * fgate + igate * ks
    h = jnp.einsum("bhd,bhde->bhe", qs, C)
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qs, n)),
                        jnp.exp(-m_new))[..., None]
    h = (h / denom)[:, None].astype(q.dtype)               # (B,1,H,dh)
    return h, (C, n, m_new)


def mlstm_block_apply(p, cfg: ModelConfig, x, *, cache=None, index=None):
    """Full mLSTM residual block.  x: (B,S,D).

    cache: None (training/prefill-from-scratch) or dict with conv/carry
    state for decode.  Returns (out, new_cache).
    """
    from repro.models.layers import apply_norm

    xl = cfg.xlstm
    di = int(cfg.d_model * xl.proj_factor_mlstm)
    up = x @ p["up"]["w"]
    x_m, z = up[..., :di], up[..., di:]
    conv_state = cache["conv"] if cache is not None else None
    q, k, v, li, lf, conv_state = _mlstm_heads(p, cfg, x_m, conv_state)
    if cache is None:
        h, carry = mlstm_chunkwise(q, k, v, li, lf)
    else:
        h, carry = mlstm_step(q, k, v, li, lf, cache["carry"])
    h = apply_norm(p["out_norm"], h, "rmsnorm")            # per-head norm
    b, s = x.shape[:2]
    h = h.reshape(b, s, di)
    out = (h * jax.nn.silu(z)) @ p["down"]["w"]
    new_cache = {"conv": conv_state, "carry": carry} if cache is not None else None
    return out, new_cache


def init_mlstm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    xl = cfg.xlstm
    di = int(cfg.d_model * xl.proj_factor_mlstm)
    h = xl.num_heads
    dh = di // h
    return {
        "conv": jnp.zeros((batch, xl.conv_kernel - 1, di), dtype),
        "carry": (jnp.zeros((batch, h, dh, dh), jnp.float32),
                  jnp.zeros((batch, h, dh), jnp.float32),
                  jnp.full((batch, h), -1e30, jnp.float32)),
    }


# =============================================================== sLSTM ======
def slstm_init(key, cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    xl = cfg.xlstm
    d = cfg.d_model
    h = xl.num_heads
    dh = d // h
    dff = int(d * xl.proj_factor_slstm)
    ks = jax.random.split(key, 6)
    return {
        "w": dense(ks[0], d, 4 * d, dtype=dtype),          # i,f,z,o all heads
        "r": truncated_normal(ks[1], (h, dh, 4 * dh), 1.0 / math.sqrt(dh), dtype),
        "b": jnp.zeros((4 * d,), jnp.float32),
        "out_norm": norm(dh, "rmsnorm", dtype),
        "up_gate": dense(ks[2], d, dff, dtype=dtype),
        "up": dense(ks[3], d, dff, dtype=dtype),
        "down": dense(ks[4], dff, d, dtype=dtype),
    }


def slstm_axes(cfg: ModelConfig):
    return {
        "w": dense_axes(("embed", "mlp")),
        "r": (None, None, None),      # hidden-to-hidden; kept replicated
        "b": (None,),
        "out_norm": norm_axes("rmsnorm"),
        "up_gate": dense_axes(("embed", "mlp")),
        "up": dense_axes(("embed", "mlp")),
        "down": dense_axes(("mlp", "embed")),
    }


def _slstm_cell(p, cfg: ModelConfig, wx_t, state):
    """One sLSTM step.  wx_t: (B,H,4*dh) precomputed W x_t (+ b).

    state: (c, n, h, m) each (B,H,dh) except m (B,H,dh? scalar-per-unit) —
    xLSTM stabilizer is per *unit*: keep (B,H,dh).
    """
    c, n, hid, m = state
    rh = jnp.einsum("bhd,hdk->bhk", hid.astype(wx_t.dtype), p["r"])
    raw = (wx_t + rh).astype(jnp.float32)
    dh = c.shape[-1]
    i_t, f_t, z_t, o_t = (raw[..., j * dh:(j + 1) * dh] for j in range(4))
    lf = jax.nn.log_sigmoid(f_t)
    m_new = jnp.maximum(lf + m, i_t)
    igate = jnp.exp(i_t - m_new)
    fgate = jnp.exp(lf + m - m_new)
    c_new = fgate * c + igate * jnp.tanh(z_t)
    n_new = fgate * n + igate
    h_new = jax.nn.sigmoid(o_t) * c_new / jnp.maximum(n_new, 1e-6)
    return (c_new, n_new, h_new, m_new), h_new


def slstm_scan(p, cfg: ModelConfig, x, state=None):
    """x: (B,S,D) -> (h (B,S,D), final state).  Sequential over S."""
    xl = cfg.xlstm
    b, s, d = x.shape
    h = xl.num_heads
    dh = d // h
    wx = (x @ p["w"]["w"]).astype(jnp.float32) + p["b"]
    wx = wx.reshape(b, s, h, 4 * dh)
    if state is None:
        z = lambda: jnp.zeros((b, h, dh), jnp.float32)
        state = (z(), z(), z(), jnp.full((b, h, dh), -1e30, jnp.float32))

    def body(st, wx_t):
        return _slstm_cell(p, cfg, wx_t, st)

    state, hs = jax.lax.scan(body, state, jnp.moveaxis(wx, 1, 0))
    return jnp.moveaxis(hs, 0, 1).reshape(b, s, d).astype(x.dtype), state


def slstm_block_apply(p, cfg: ModelConfig, x, *, cache=None, index=None):
    """sLSTM residual block with post-up-projection MLP."""
    from repro.models.layers import apply_norm

    b, s, d = x.shape
    h, state = slstm_scan(p, cfg, x, None if cache is None else cache["state"])
    hh = apply_norm(p["out_norm"], h.reshape(b, s, cfg.xlstm.num_heads, -1),
                    "rmsnorm").reshape(b, s, d)
    y = (jax.nn.gelu(hh @ p["up_gate"]["w"]) * (hh @ p["up"]["w"])) @ p["down"]["w"]
    new_cache = {"state": state} if cache is not None else None
    return y, new_cache


def init_slstm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    xl = cfg.xlstm
    dh = cfg.d_model // xl.num_heads
    z = lambda: jnp.zeros((batch, xl.num_heads, dh), jnp.float32)
    return {"state": (z(), z(), z(), jnp.full((batch, xl.num_heads, dh), -1e30,
                                              jnp.float32))}
