"""Recurrent blocks: parallel/chunkwise training forms must match the O(1)
recurrent decode forms step by step, and the sLSTM's hand-written backward
must match autodiff of a plain sequential loop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.models.rglru import rglru_scan_assoc
from repro.models.xlstm import (mlstm_chunkwise, mlstm_step,
                                 slstm_block_apply, slstm_init, slstm_scan)


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


# ------------------------------------------------ sLSTM hand-written VJP ----
SL_CFG = get_arch("xlstm-350m").reduced(d_model=32)


def _ref_cell(r, state, wx_t, branches=None):
    """One plain sLSTM step: (state, W x_t + b) -> (state', h).
    ``branches``, if given, collects where the stabilizer takes the input
    gate."""
    c, n, hid, m = state
    dh = c.shape[-1]
    raw = (wx_t + jnp.einsum("bhd,hdk->bhk", hid.astype(wx_t.dtype),
                             r)).astype(jnp.float32)
    i, f, z, o = (raw[..., j * dh:(j + 1) * dh] for j in range(4))
    lf = jax.nn.log_sigmoid(f)
    m_new = jnp.maximum(lf + m, i)
    if branches is not None:
        branches.append(np.asarray(i > lf + m))
    igate = jnp.exp(i - m_new)
    fgate = jnp.exp(lf + m - m_new)
    c = fgate * c + igate * jnp.tanh(z)
    n = fgate * n + igate
    hid = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1e-6)
    return (c, n, hid, m_new), hid


def _ref_wx(p, x):
    b, s, d = x.shape
    h = SL_CFG.xlstm.num_heads
    return ((x @ p["w"]["w"]).astype(jnp.float32)
            + p["b"]).reshape(b, s, h, 4 * d // h)


def _slstm_loop(p, x, state, branches=None):
    """Plain sequential sLSTM: a Python loop over time, differentiated by
    ordinary autodiff.  Returns (h (B,S,D), final state)."""
    wx = _ref_wx(p, x)
    outs = []
    for t in range(x.shape[1]):
        state, hid = _ref_cell(p["r"], state, wx[:, t], branches)
        outs.append(hid)
    return jnp.stack(outs, axis=1).reshape(x.shape), state


def _autodiff_slstm_scan(p, x, state):
    """The recurrence as a lax.scan differentiated by autodiff (the form the
    hand-written backward replaced), for the forward's bit-identity."""
    state, hs = jax.lax.scan(lambda st, wx_t: _ref_cell(p["r"], st, wx_t),
                             state, jnp.moveaxis(_ref_wx(p, x), 1, 0))
    return jnp.moveaxis(hs, 0, 1).reshape(x.shape).astype(x.dtype), state


def _bf16(p, x):
    """The model's dtypes: bf16 weights and inputs, f32 bias."""
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, p)
    return p, x.astype(jnp.bfloat16)


def _slstm_case(case, rng, k=0, b=2, s=12):
    """f32 parameters, inputs and initial state of one gradient case."""
    d, h = SL_CFG.d_model, SL_CFG.xlstm.num_heads
    dh = d // h
    p = slstm_init(jax.random.PRNGKey(k), SL_CFG, dtype=jnp.float32)
    bias = rng.normal(size=(h, 4, dh)).astype(np.float32)
    scale = 1.0
    if case == "strong_gates":
        # strong input and forget gates on large inputs: the stabilizer
        # switches between the two branches of its maximum
        bias[:, 0] += 4.0
        bias[:, 1] += 3.0
        scale = 3.0
    p["b"] = jnp.asarray(bias.reshape(-1))
    x = jnp.asarray(scale * rng.normal(size=(b, s, d)).astype(np.float32))
    if case == "no_state":
        z = jnp.zeros((b, h, dh), jnp.float32)
        st = (z, z, z, jnp.full((b, h, dh), -1e30, jnp.float32))
    else:
        c, n, hid, m = (rng.normal(size=(b, h, dh)).astype(np.float32)
                        for _ in range(4))
        st = tuple(map(jnp.asarray, (c, np.abs(n) + 0.5, hid, m)))
    return p, x, st


def _slstm_loss(apply):
    """A scalar of every output, so that each state's cotangent is used."""
    def loss(p, x, st):
        hs, final = apply(p, x, st)
        w = jnp.cos(jnp.arange(hs.size, dtype=jnp.float32)).reshape(hs.shape)
        return (hs * w).sum() + sum((0.3 + k) * f.sum()
                                    for k, f in enumerate(final))
    return loss


def _named(grads):
    """(d params, d x, d state) -> the gradients the tests compare."""
    gp, gx, gst = grads
    return {"x": gx, "w": gp["w"]["w"], "b": gp["b"], "r": gp["r"],
            **dict(zip(("c0", "n0", "h0", "m0"), gst))}


@pytest.mark.parametrize("case", ["no_state", "given_state", "strong_gates",
                                  "vmapped"])
def test_slstm_grads_match_sequential_loop(case, rng):
    """Gradients of the hand-written backward (d x, W, b, R and the initial
    state) equal those of autodiff through a plain loop, to f32 round-off."""
    new = _slstm_loss(lambda p, x, st: slstm_scan(p, SL_CFG, x, st))
    ref = _slstm_loss(_slstm_loop)
    if case == "vmapped":
        p, x, st = jax.tree.map(lambda *a: jnp.stack(a),
                                *(_slstm_case(case, rng, k) for k in range(2)))
        new, ref = (lambda p, x, st, f=f: jax.vmap(f)(p, x, st).sum()
                    for f in (new, ref))
    else:
        p, x, st = _slstm_case(case, rng)
    if case == "strong_gates":
        branches = []
        _slstm_loop(p, x, st, branches)
        took_input = np.mean(branches[1:])
        assert 0.05 < took_input < 0.95, took_input
    with jax.default_matmul_precision("highest"):
        g_new, g_ref = (_named(jax.jit(jax.grad(f, argnums=(0, 1, 2)))(p, x, st))
                        for f in (new, ref))
    for name, b in g_ref.items():
        a, b = np.asarray(g_new[name]), np.asarray(b)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6 * np.abs(b).max(),
                                   err_msg=name)


@pytest.mark.parametrize("s", [1, 16])
def test_slstm_forward_bit_identical_to_autodiff_scan(s, rng):
    """Training (s=16) and decode (s=1, a carried state) run the same
    primal: outputs and final state equal the autodiff scan's bit for bit,
    called alone or as the primal of a VJP."""
    p, x, st = _slstm_case("given_state", rng, s=s)
    p, x = _bf16(p, x)
    want = jax.jit(_autodiff_slstm_scan)(p, x, st)
    got = jax.jit(lambda p, x, st: slstm_scan(p, SL_CFG, x, st))(p, x, st)
    primal = jax.jit(lambda p, x, st: jax.vjp(
        lambda p, x, st: slstm_scan(p, SL_CFG, x, st), p, x, st)[0])(p, x, st)
    for out in (got, primal):
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def _loop_carries(jaxpr):
    """Avals of every scan and while carry in a jaxpr, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            k, n = eqn.params["num_consts"], eqn.params["num_carry"]
            out += [v.aval for v in eqn.invars[k:k + n]]
        elif eqn.primitive.name == "while":
            out += [v.aval for v in eqn.invars[eqn.params["cond_nconsts"]
                                               + eqn.params["body_nconsts"]:]]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _loop_carries(sub)
    return out


@pytest.mark.parametrize("clients", [None, 2])
def test_slstm_backward_carries_no_recurrent_weight_gradient(clients, rng):
    """Engagement of the hand-written VJP: no loop of the backward carries a
    cotangent of R's shape (H, dh, 4dh), with or without a client axis;
    R's gradient is formed after the time loop."""
    p, x = _bf16(*_slstm_case("no_state", rng)[:2])

    def loss(p, x):
        y, _ = slstm_block_apply(p, SL_CFG, x)
        return y.astype(jnp.float32).sum()

    grad = jax.grad(loss)
    if clients:
        p, x = jax.tree.map(lambda a: jnp.stack([a] * clients), (p, x))
        grad = jax.vmap(grad)
    carries = _loop_carries(jax.make_jaxpr(grad)(p, x).jaxpr)
    r_shape = p["r"].shape
    assert carries, "no loop traced"
    assert not [a for a in carries if a.shape[-3:] == r_shape[-3:]], carries
