"""Parity of the degenerate 1-device launch path (make_host_round) with
make_phsfl_round semantics, plus its participation-mask behavior.

The fast tests check the host round against an explicit per-client loop on a
tiny model (single device).  The slow test runs the mesh path on 8 fake
devices in a child process and asserts the two paths agree numerically.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import HierarchyConfig, TrainConfig
from repro.configs.registry import get_arch
from repro.core import (build_optimizer, edge_aggregate, init_stacked_params,
                        make_host_round)
from repro.data.synthetic import synthetic_token_batch
from repro.models import build_model
from repro.optim import apply_updates


C, K, MICRO, SEQ = 4, 2, 2, 32


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("xlstm-350m").reduced()
    model = build_model(cfg)
    hcfg = HierarchyConfig(num_edge_servers=1, clients_per_es=C, kappa0=K,
                           kappa1=1)
    tcfg = TrainConfig(learning_rate=0.05, freeze_head=True, remat=False)
    params = init_stacked_params(model, jax.random.PRNGKey(0), C)
    opt, _ = build_optimizer(model, tcfg)
    state1 = opt.init(jax.tree.map(lambda x: x[0], params))
    opt_state = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), state1)
    nb = synthetic_token_batch(0, C * K * MICRO, SEQ, cfg.vocab_size)
    batch = {k: jnp.asarray(v).reshape(C, K, MICRO, SEQ)
             for k, v in nb.items()}
    au = jnp.full((C,), 1.0 / C, jnp.float32)
    ab = jnp.ones((C,), jnp.float32)
    return cfg, model, hcfg, tcfg, params, opt_state, batch, au, ab, opt


def _host_reference(model, opt, params, batch, weights):
    """Per-client local SGD loop + Eq. 14-15 weighted aggregation."""
    client_params = []
    for c in range(C):
        p = jax.tree.map(lambda x: x[c], params)
        s = opt.init(p)
        for k in range(K):
            mb = {kk: vv[c, k] for kk, vv in batch.items()}
            loss, g = jax.value_and_grad(lambda q: model.loss(q, mb))(p)
            upd, s = opt.update(g, s, p)
            p = apply_updates(p, upd)
        client_params.append(p)
    return client_params, edge_aggregate(
        [client_params[i] for i in np.flatnonzero(weights)],
        weights[weights > 0] / weights[weights > 0].sum())


def test_host_round_matches_per_client_reference(setup):
    cfg, model, hcfg, tcfg, params, opt_state, batch, au, ab, opt = setup
    rnd = make_host_round(model, hcfg, tcfg, num_clients=C,
                          global_sync=False)
    p2, s2, metrics = jax.jit(rnd.fn)(params, opt_state, batch, au, ab)
    _, ref = _host_reference(model, opt, params, batch,
                             np.full(C, 1.0 / C))
    got = jax.tree.map(lambda x: x[0], p2)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    # head frozen (Eq. 12), all clients synced
    assert jnp.array_equal(params["lm_head"]["w"][0], p2["lm_head"]["w"][0])
    for x in jax.tree.leaves(p2):
        for i in range(1, C):
            assert jnp.array_equal(x[0], x[i])
    assert np.isfinite(float(metrics["loss"]))


def test_host_round_full_mask_bit_identical(setup):
    """The launch-path regression: an all-ones participation mask reproduces
    the unmasked round bit-for-bit (ideal-network trajectory)."""
    cfg, model, hcfg, tcfg, params, opt_state, batch, au, ab, opt = setup
    base = make_host_round(model, hcfg, tcfg, num_clients=C,
                           global_sync=False)
    masked = make_host_round(model, hcfg, tcfg, num_clients=C,
                             global_sync=False, participation=True)
    p_ref, s_ref, _ = jax.jit(base.fn)(params, opt_state, batch, au, ab)
    ones = jnp.ones((C,), jnp.float32)
    p_m, s_m, _ = jax.jit(masked.fn)(params, opt_state, batch, au, ab, ones)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_m)):
        assert jnp.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(s_ref), jax.tree.leaves(s_m)):
        assert jnp.array_equal(a, b)


def test_host_round_partial_mask_renormalizes(setup):
    cfg, model, hcfg, tcfg, params, opt_state, batch, au, ab, opt = setup
    masked = make_host_round(model, hcfg, tcfg, num_clients=C,
                             global_sync=False, participation=True)
    mask = jnp.asarray([1.0, 0.0, 1.0, 0.0], jnp.float32)
    p_m, _, _ = jax.jit(masked.fn)(params, opt_state, batch, au, ab, mask)
    _, ref = _host_reference(model, opt, params, batch,
                             np.array([0.25, 0.0, 0.25, 0.0]))
    got = jax.tree.map(lambda x: x[0], p_m)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_host_round_empty_mask_keeps_previous_edge_model(setup):
    cfg, model, hcfg, tcfg, params, opt_state, batch, au, ab, opt = setup
    masked = make_host_round(model, hcfg, tcfg, num_clients=C,
                             global_sync=False, participation=True)
    zeros = jnp.zeros((C,), jnp.float32)
    p_m, _, _ = jax.jit(masked.fn)(params, opt_state, batch, au, ab, zeros)
    for a, b in zip(jax.tree.leaves(p_m), jax.tree.leaves(params)):
        assert jnp.array_equal(a, b)


# --------------------------- mesh vs host parity (8 fake devices) ----------
_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_disable_hlo_passes=all-reduce-promotion")
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.registry import get_arch
from repro.configs.base import HierarchyConfig, TrainConfig
from repro.models import build_model
from repro.core import (make_phsfl_round, make_host_round,
                        init_stacked_params, build_optimizer)
from repro.data.synthetic import synthetic_token_batch

# model axis size 1: XLA's partial-manual TP subgroup aborts on this
# jax/XLA version; the pod/data manual aggregation is what parity tests.
mesh = jax.make_mesh((2, 4, 1), ("pod", "data", "model"))
cfg = get_arch("mistral-large-123b").reduced()
model = build_model(cfg)
h = HierarchyConfig(num_edge_servers=2, clients_per_es=4, kappa0=2, kappa1=1)
t = TrainConfig(learning_rate=0.05, freeze_head=True, local_steps_in_step=2,
                remat=False)
C = 8
params = init_stacked_params(model, jax.random.PRNGKey(0), C)
opt, _ = build_optimizer(model, t)
state1 = opt.init(jax.tree.map(lambda x: x[0], params))
opt_state = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (C,) + x.shape),
                         state1)
nb = synthetic_token_batch(0, C * 2 * 2, 32, cfg.vocab_size)
batch = {k: jnp.asarray(v).reshape(C, 2, 2, 32) for k, v in nb.items()}
au = jnp.full((C,), 0.25, jnp.float32)
ab = jnp.full((C,), 0.5, jnp.float32)
# ES 0 loses two clients, ES 1 keeps all four
mask = jnp.asarray([1.0, 0.0, 1.0, 0.0, 1.0, 1.0, 1.0, 1.0], jnp.float32)

with mesh:
    rnd = make_phsfl_round(model, h, t, mesh, global_sync=True,
                           participation=True)
    p_mesh, s_mesh, m_mesh = jax.jit(rnd.fn)(params, opt_state, batch,
                                             au, ab, mask)

host = make_host_round(model, h, t, num_clients=C, global_sync=True,
                       participation=True)
p_host, s_host, m_host = jax.jit(host.fn)(params, opt_state, batch,
                                          au, ab, mask)

def maxerr(a, b):
    return max(float(jnp.abs(x.astype(jnp.float32)
                             - y.astype(jnp.float32)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

print(json.dumps({
    "param_err": maxerr(p_mesh, p_host),
    "loss_mesh": float(m_mesh["loss"]),
    "loss_host": float(m_host["loss"]),
}))
"""


@pytest.mark.slow
def test_mesh_and_host_rounds_agree_under_partial_mask():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["param_err"] < 5e-3, rec
    assert abs(rec["loss_mesh"] - rec["loss_host"]) < 1e-5, rec


# ------------------- row-sparse embedding update of the local step ---------
def _row_setup(arch, dtype, optimizer="sgd", freeze_table=False):
    """A reduced model, one client's params, optimizer state and K local
    micro-batches whose tokens repeat no id within a step."""
    import dataclasses

    from repro.optim import make_optimizer, masked

    cfg = dataclasses.replace(get_arch(arch).reduced(), dtype=dtype)
    model = build_model(cfg)
    tcfg = TrainConfig(learning_rate=0.05, optimizer=optimizer,
                       freeze_head=True, remat=False)
    opt, mask = build_optimizer(model, tcfg)
    if freeze_table:
        mask = dict(mask, embed={"table": False})
        opt = masked(make_optimizer(optimizer, tcfg.learning_rate), mask)
    p = model.init(jax.random.PRNGKey(0))
    tokens = jnp.stack([
        jax.random.permutation(jax.random.PRNGKey(k), cfg.vocab_size)
        [:MICRO * SEQ].reshape(MICRO, SEQ) for k in range(K)])
    b = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1)}
    if cfg.encdec is not None:
        b["source_embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(9),
            (K, MICRO, cfg.encdec.max_source_len, cfg.d_model))
    return model, tcfg, opt, mask, p, opt.init(p), b


def _scan(model, tcfg, opt, path, p, s, b):
    from repro.core.phsfl import _local_scan
    return jax.jit(_local_scan(model, tcfg, opt, path))(p, s, b)


def _assert_bit_identical(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and jnp.array_equal(x, y)


ROW_ARCHS = ["xlstm-350m", "seamless-m4t-medium"]


@pytest.mark.parametrize("arch", ROW_ARCHS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_embed_rows_bit_identical_to_dense_without_repeats(arch, dtype):
    """No step repeats an id: each touched row's gradient is one cotangent
    and gets the same SGD formula, so the two paths agree bit for bit;
    rows no step touches come back as they went in; the head is frozen."""
    model, tcfg, opt, _, p, s, b = _row_setup(arch, dtype)
    dense = _scan(model, tcfg, opt, "dense", p, s, b)
    rows = _scan(model, tcfg, opt, "rows", p, s, b)
    _assert_bit_identical(dense, rows)
    table, new = p["embed"]["table"], rows[0]["embed"]["table"]
    untouched = np.setdiff1d(np.arange(table.shape[0]),
                             np.asarray(b["tokens"]).ravel())
    assert untouched.size >= table.shape[0] - K * MICRO * SEQ
    assert jnp.array_equal(table[untouched], new[untouched])
    touched = np.unique(np.asarray(b["tokens"]))
    assert not jnp.array_equal(table[touched], new[touched])
    _assert_bit_identical(p["lm_head"], rows[0]["lm_head"])


@pytest.mark.parametrize("arch", ROW_ARCHS)
def test_embed_rows_with_repeated_ids_match_dense(arch):
    """Repeated ids sum their cotangents in the same dtype, possibly in
    another order: within the round's parity tolerance at f32.  The last
    row is touched too, so a padding id clamped onto it (not dropped)
    would overwrite its update."""
    model, tcfg, opt, _, p, s, b = _row_setup(arch, "float32")
    last = p["embed"]["table"].shape[0] - 1
    tokens = jax.random.randint(jax.random.PRNGKey(3), b["tokens"].shape,
                                0, 20).at[:, :, 0].set(last)
    b = dict(b, tokens=tokens, labels=jnp.roll(tokens, -1, axis=-1))
    dense = _scan(model, tcfg, opt, "dense", p, s, b)
    rows = _scan(model, tcfg, opt, "rows", p, s, b)
    for x, y in zip(jax.tree.leaves(dense), jax.tree.leaves(rows),
                    strict=True):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-5, atol=2e-6)
    table, new = p["embed"]["table"], rows[0]["embed"]["table"]
    assert jnp.array_equal(table[20:last], new[20:last])
    assert not jnp.array_equal(table[last], new[last])
    _assert_bit_identical(p["lm_head"], rows[0]["lm_head"])


@pytest.mark.parametrize("optimizer,freeze_table", [
    ("momentum", False), ("adamw", False), ("sgd", True)])
def test_embed_dense_path_where_rows_are_not_exact(optimizer, freeze_table):
    """Momentum and AdamW keep table-shaped state, and a frozen table has
    nothing to update: those take the dense path, unchanged."""
    from repro.core.phsfl import embed_update_path

    model, tcfg, opt, mask, p, s, b = _row_setup(
        "xlstm-350m", "float32", optimizer, freeze_table)
    path = embed_update_path(tcfg, mask)
    assert path == "dense"
    _assert_bit_identical(_scan(model, tcfg, opt, path, p, s, b),
                          _scan(model, tcfg, opt, "dense", p, s, b))


@pytest.mark.parametrize("optimizer,path", [
    ("sgd", "rows"), ("momentum", "dense"), ("adamw", "dense")])
def test_round_reports_embed_update_path(optimizer, path):
    cfg = get_arch("seamless-m4t-medium").reduced()
    model = build_model(cfg)
    hcfg = HierarchyConfig(num_edge_servers=1, clients_per_es=C, kappa0=K,
                           kappa1=1)
    tcfg = TrainConfig(learning_rate=0.05, optimizer=optimizer,
                       freeze_head=True, remat=False)
    rnd = make_host_round(model, hcfg, tcfg, num_clients=C,
                          global_sync=False, participation=True)
    assert rnd.embed_update == path


def test_embed_rows_share_recorded_with_a_span_sink():
    """With a span sink installed, tracing the row path records the share
    of table rows a step may write."""
    from repro.telemetry import MetricsRegistry, set_span_sink

    model, tcfg, opt, _, p, s, b = _row_setup("xlstm-350m", "float32")
    reg = MetricsRegistry()
    set_span_sink(reg)
    try:
        _scan(model, tcfg, opt, "rows", p, s, b)
    finally:
        set_span_sink(None)
    share = reg.gauge("optimizer.embed_row_share").value
    assert share == MICRO * SEQ / model.cfg.padded_vocab
