"""The plain reference: float32 layer math and the PHSFL edge round, written
from the papers and imports nothing of the program.

It reads the weights the benchmark made (in the program's parameter
layout, in the configuration's storage type) and computes in float32 at
``highest`` matmul precision.  What it shares with the configuration is
what the configuration states: bfloat16 storage of the parameters, so an
SGD update is rounded to the parameter's type when it is stored, and the
gradient reaches the optimizer in that type.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-6


# ------------------------------------------------------------------ ops ----
def layernorm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + EPS) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def rmsnorm(x, scale):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + EPS) * \
        scale.astype(F32)


def gelu(x):
    """GELU, tanh form (Hendrycks & Gimpel 2016, eq. for the fast
    approximation)."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def log_sigmoid(x):
    return -jnp.logaddexp(0.0, -x)


def exact(x):
    """The reference's own precision: operands as they are, in float32."""
    return x.astype(F32)


def e4m3(y):
    """Round to the nearest float8 e4m3 value (ties to even), |y| <= 448.

    Done in arithmetic, not by a cast pair: XLA may drop a float32 ->
    float8 -> float32 round trip as excess precision."""
    _, ex = jnp.frexp(y)                        # y = m * 2**ex, |m| in [.5, 1)
    step = jnp.exp2(jnp.maximum(ex - 1, -6) - 3.0)  # 3 mantissa bits
    return jnp.round(y / step) * step


def fp8(x):
    """The control's precision: a tensor rounded to float8 e4m3 with a
    per-tensor scale, straight-through (the backward pass sees float32)."""
    xf = x.astype(F32)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.abs(xf).max(), 1e-30) / 448.0)
    return xf + jax.lax.stop_gradient(e4m3(xf / s) * s - xf)


def mm(x, w, cast=exact):
    """x @ w, both operands through ``cast``, accumulated in float32."""
    return jnp.matmul(cast(x), cast(w))


def einsum(eq, a, b, cast=exact):
    return jnp.einsum(eq, cast(a), cast(b))


def cross_entropy(logits, labels):
    """Mean token cross-entropy over every logit column."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (lse - gold).mean()


# ------------------------------------------------------------ the round ----
def make_step(loss_fn, cfg, lr, frozen_keys, cast=exact):
    """One local SGD step of one client: (params, batch) -> (params, loss).

    ``cast`` is the precision: every matrix product's operands and every
    updated parameter pass through it (``exact``: float32 products,
    parameters stored in their own type; ``fp8``: the control).  The
    frozen keys (the PHSFL head) are never updated."""
    def step(params, batch):
        def f(p):
            return loss_fn(p, batch, cfg, cast)

        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(f)(params)
        new = {k: (v if k in frozen_keys else jax.tree.map(
            lambda w, gw: cast(w.astype(F32) - lr * gw.astype(F32))
            .astype(w.dtype), v, g[k]))
            for k, v in params.items()}
        return new, loss
    return jax.jit(step)


@jax.jit
def _aggregate(clients, weights):
    """Weighted mean of the clients' parameters in float32, stored back in
    each leaf's type."""
    def agg(*xs):
        acc = sum(w * x.astype(F32) for w, x in zip(weights, xs))
        return acc.astype(xs[0].dtype)
    return jax.tree.map(agg, *clients)


def edge_round(step, params, batches, mask, alpha):
    """Eqs. 14-15 of the PHSFL paper for one ES: every client takes its
    local steps from the edge model, then the participants' models are
    averaged with weights alpha renormalized over them.  ``batches[c][k]``
    is client c's k-th local batch (a dict of arrays).  Returns the new
    edge model and the mean loss over every client's steps."""
    outs, losses = [], []
    for c, client_batches in enumerate(batches):
        p = params
        for b in client_batches:
            p, loss = step(p, b)
            losses.append(loss)
        if mask[c] > 0:
            outs.append((alpha[c], p))
    loss = float(jnp.stack(losses).mean())
    if not outs:
        return params, loss
    tot = sum(a for a, _ in outs)
    w = [jnp.asarray(a / tot, F32) for a, _ in outs]
    return _aggregate([p for _, p in outs], w), loss


def client_batches(batch_np, c):
    """Client c's local batches from one round's stacked host batch
    (leaves shaped (C, kappa0, micro, ...))."""
    k0 = next(iter(batch_np.values())).shape[1]
    return [{k: jnp.asarray(v[c, i]) for k, v in batch_np.items()}
            for i in range(k0)]


def follow(step, params0, batches_np, masks, alpha, lr, frozen_keys,
           rounds):
    """Run ``rounds`` edge rounds from ``params0``; return the readings
    that the program's first rounds are compared on."""
    C = len(alpha)
    p = params0
    losses, grad1 = [], None
    for r in range(rounds):
        prev = p
        p, loss = edge_round(step, p, [client_batches(batches_np[r], c)
                                       for c in range(C)],
                             masks[r], alpha)
        losses.append(loss)
        if r == 0:
            grad1 = diff_stats(prev, p)[0] / lr
        del prev
    return readings(losses, grad1, diff_stats(p, params0),
                    frozen_mask(params0, frozen_keys))


# ------------------------------------------------------------ readings -----
def _stats(a, b):
    d = [x.astype(F32) - y.astype(F32)
         for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return (jnp.stack([jnp.sqrt((x * x).sum()) for x in d]),
            jnp.stack([jnp.abs(x).max() for x in d]))


def _host(stats):
    return tuple(np.asarray(x, np.float64) for x in stats)


def diff_stats(a, b):
    """Per leaf (jax.tree.leaves order): float32 norm of a - b and largest
    absolute entry."""
    return _host(_diff_stats(a, b))


def first_client_diff_stats(a, b):
    """``diff_stats`` of client 0 of two client-stacked trees."""
    return _host(_first_diff_stats(a, b))


_diff_stats = jax.jit(_stats)
_first_diff_stats = jax.jit(lambda a, b: _stats(
    *(jax.tree.map(lambda x: x[0], t) for t in (a, b))))


def frozen_mask(params, frozen_keys) -> np.ndarray:
    """Per leaf: does it sit under one of the frozen top-level keys."""
    return np.array([getattr(path[0], "key", None) in frozen_keys
                     for path, _ in
                     jax.tree_util.tree_leaves_with_path(params)])


def readings(losses, grad1, delta_stats, frozen) -> dict:
    """What a run is judged on: each round's loss, per leaf the first
    round's update over the learning rate, per leaf the change after the
    rounds, and the largest change of any frozen weight."""
    norms, maxabs = delta_stats
    return {"loss": [float(x) for x in losses], "grad1": grad1,
            "delta": norms,
            "frozen_change": float(maxabs[frozen].max()) if frozen.any()
            else 0.0}


def leaf_gap(prog, ref):
    """Worst leaf of |norm_prog - norm_ref| over the larger of the
    reference's norm of that leaf and the median leaf's.  Leaves whose
    reference norm is under a thousandth of the median's are left out:
    they move by round-off alone."""
    moved = ref > 0
    if not moved.any():
        return 0.0
    med = float(np.median(ref[moved]))
    keep = ref >= 1e-3 * med
    den = np.maximum(ref[keep], med)
    return float((np.abs(prog[keep] - ref[keep]) / den).max())


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``, by name."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["loss"], ref["loss"]))
    return {"loss_gap": loss_gap,
            "grad1_gap": leaf_gap(prog["grad1"], ref["grad1"]),
            "delta_gap": leaf_gap(prog["delta"], ref["delta"]),
            "frozen_change": prog["frozen_change"]}
