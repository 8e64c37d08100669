"""Named layers: host spans (off by default, bit-inert when on) and the
device scopes that label every operation of the compiled edge round.

The load-bearing assertions:

- with no sink, ``span()`` is one shared null context;
- with a sink, each span's count and host seconds land in
  ``span.<name>``, and ``Telemetry(spans=True)`` installs and removes it;
- ``CohortScheduler`` reports and the edge round's outputs are
  bit-identical with the sink on and off;
- every scope of ``SCOPES`` names operations in the ``op_name`` metadata
  of a compiled tiny round, backward operations of the recurrent blocks
  included.
"""

import contextlib
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import (FaultConfig, HierarchyConfig, TrainConfig,
                                WirelessConfig)
from repro.configs.phsfl_cnn import CONFIG as CNN_CFG
from repro.configs.registry import get_arch
from repro.core import build_optimizer, init_stacked_params, make_host_round
from repro.core.comm import comm_for_cnn
from repro.data.synthetic import synthetic_token_batch
from repro.models import build_model
from repro.telemetry import (SCOPES, SPAN_NAMES, MetricsRegistry, Telemetry,
                             get_span_sink, scope, set_span_sink, span)
from repro.wireless.population import Population, make_cohort_scheduler

C, K, MICRO, SEQ = 2, 2, 1, 32


@pytest.fixture(autouse=True)
def _no_sink():
    set_span_sink(None)
    yield
    set_span_sink(None)


# ------------------------------------------------------------ host spans
def test_off_is_one_shared_null_context():
    assert get_span_sink() is None
    a, b = span("sched.price"), span("sched.cohort")
    assert a is b
    assert isinstance(a, contextlib.nullcontext)
    with a:
        pass


def test_on_counts_and_seconds_per_name():
    reg = MetricsRegistry()
    set_span_sink(reg)
    for _ in range(3):
        with span("sched.price"):
            time.sleep(0.01)
    with span("sched.channel"):
        with span("sched.cohort"):
            pass
    set_span_sink(None)
    price = reg["span.sched.price"]
    assert price.count == 3
    assert 0.03 <= price.sum < 1.0
    assert price.max >= 0.01
    assert reg["span.sched.channel"].count == 1
    assert reg["span.sched.cohort"].count == 1
    # the outer span encloses the inner one
    assert reg["span.sched.channel"].sum >= reg["span.sched.cohort"].sum
    assert span("sched.price") is span("sched.report")     # off again


def test_telemetry_spans_knob_installs_until_close():
    tel = Telemetry(spans=True)
    assert get_span_sink() is tel.metrics
    with span("sched.report"):
        pass
    assert tel.metrics["span.sched.report"].count == 1
    tel.close()
    assert get_span_sink() is None
    Telemetry().close()                     # the default installs none
    assert get_span_sink() is None


def test_span_names_are_the_schedulers():
    assert SPAN_NAMES == ("sched.cohort", "sched.channel", "sched.price",
                          "sched.report")


def _cohort_scheduler(faults=None):
    wc = WirelessConfig(model="rayleigh", mean_uplink_mbps=8.0,
                        mean_downlink_mbps=30.0, latency_s=0.01,
                        deadline_s=1.5, energy_budget_j=20.0,
                        heterogeneity=0.5, selection="topk", topk=5,
                        es_uplink_mbps=12.0, contention="proportional",
                        staleness_lambda=0.5, seed=3,
                        faults=faults or FaultConfig())
    comm = comm_for_cnn(CNN_CFG, dataset_size=400, batch_size=16)
    pop = Population(64, num_es=2, seed=3)
    return make_cohort_scheduler(wc, 64, comm, 2, population=pop,
                                 cohort_size=8, sampling="rate")


def _reports_equal(ra, rb):
    import dataclasses
    for f in dataclasses.fields(ra):
        va, vb = getattr(ra, f.name), getattr(rb, f.name)
        if isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
            assert np.array_equal(np.asarray(va), np.asarray(vb)), f.name
        else:
            assert va == vb, f.name


def test_scheduler_bit_identical_with_spans_on_and_off():
    off, on = _cohort_scheduler(), _cohort_scheduler()
    reg = MetricsRegistry()
    for r in range(4):
        ra = off.step(r)
        set_span_sink(reg)
        rb = on.step(r)
        set_span_sink(None)
        _reports_equal(ra, rb)
        assert np.array_equal(off.last_cohort, on.last_cohort)
    assert np.array_equal(off.energy_left, on.energy_left)
    for name in SPAN_NAMES:
        assert reg[f"span.{name}"].count == 4, name


def test_fault_plan_rounds_open_no_pricing_spans():
    faults = FaultConfig(erasure_prob=0.3, max_retries=2, backoff_s=0.02)
    s = _cohort_scheduler(faults)
    reg = MetricsRegistry()
    set_span_sink(reg)
    s.step(0)
    set_span_sink(None)
    assert reg["span.sched.cohort"].count == 1
    for name in ("sched.channel", "sched.price", "sched.report"):
        assert f"span.{name}" not in reg


# ---------------------------------------------------------- device scopes
def test_scope_rejects_unknown_names():
    with pytest.raises(ValueError):
        scope("slstm_typo")


def test_scope_as_decorator_and_context_names_ops():
    @scope("mlp")
    def f(x):
        with scope("embed"):
            y = jnp.sin(x)
        return jnp.cos(y)

    f(jnp.ones(3))                           # re-entered: fresh scopes
    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "mlp/embed/sin" in text and "mlp/cos" in text


def _tiny_round(arch, global_sync):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    hcfg = HierarchyConfig(num_edge_servers=1, clients_per_es=C, kappa0=K,
                           kappa1=1)
    tcfg = TrainConfig(learning_rate=0.05, freeze_head=True, remat=False,
                       local_steps_in_step=K)
    params = init_stacked_params(model, jax.random.PRNGKey(0), C)
    opt, _ = build_optimizer(model, tcfg)
    st = opt.init(jax.tree.map(lambda x: x[0], params))
    opt_state = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), st)
    nb = synthetic_token_batch(0, C * K * MICRO, SEQ, cfg.vocab_size)
    batch = {k: jnp.asarray(v).reshape(C, K, MICRO, SEQ)
             for k, v in nb.items()}
    if cfg.encdec is not None:
        batch["source_embeds"] = jnp.asarray(
            np.random.default_rng(0).standard_normal(
                (C, K, MICRO, cfg.encdec.max_source_len, cfg.d_model)),
            jnp.float32)
    r = make_host_round(model, hcfg, tcfg, num_clients=C,
                        global_sync=global_sync, participation=True)
    args = (params, opt_state, batch, jnp.full((C,), 1.0 / C),
            jnp.ones((C,)), jnp.ones((C,)))
    return jax.jit(r.fn), args


def _op_names(fn, args):
    text = fn.lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.fixture(scope="module")
def round_op_names():
    """op_name metadata of the compiled tiny xLSTM round (with global
    aggregation), of a tiny attention/MLP transformer's and of a tiny
    encoder-decoder's (cross-attention)."""
    names = []
    for arch in ("xlstm-350m", "gemma3-12b", "seamless-m4t-medium"):
        names += _op_names(*_tiny_round(arch, global_sync=True))
    return names


def _components(stack):
    out = []
    for comp in stack.split(";", 1)[0].split("/"):
        while m := re.fullmatch(r"([\w.\-]*)\((.*)\)", comp):
            comp = m.group(2)
        out.append(comp)
    return out


@pytest.mark.parametrize("name", SCOPES)
def test_each_scope_names_ops_of_the_compiled_round(round_op_names, name):
    assert any(name in _components(s) for s in round_op_names), name


def _direction(stack, name):
    """"bwd" or "fwd" where ``name`` is a component of the stack under a
    transposed or a differentiated component (itself or one before it,
    the rule of ``bench/scopes.py``), else None.  A layer-scanned block's
    stack reads ``transpose(jvp())/while/body/closed_call/<scope>/...``;
    an unscanned one's ``transpose(jvp(<scope>))/...``."""
    transposed = differentiated = False
    for comp in stack.split(";", 1)[0].split("/"):
        while m := re.fullmatch(r"([\w.\-]*)\((.*)\)", comp):
            transposed |= m.group(1) == "transpose"
            differentiated |= m.group(1) == "jvp"
            comp = m.group(2)
        if comp == name:
            return "bwd" if transposed else "fwd" if differentiated else None
    return None


@pytest.mark.parametrize("name", ["slstm", "mlstm", "lm_loss",
                                  "cross_attention"])
def test_backward_ops_carry_their_scope(round_op_names, name):
    bwd = [s for s in round_op_names if _direction(s, name) == "bwd"]
    fwd = [s for s in round_op_names if _direction(s, name) == "fwd"]
    assert bwd and fwd, name


def test_rounds_bit_identical_with_sink_on():
    """Scheduler step -> cohort mask -> edge round, twice, from the same
    state: the sink changes no number."""
    from repro.wireless.population import cohort_report

    fn, args = _tiny_round("xlstm-350m", global_sync=False)

    def rounds(sink):
        sched = _cohort_scheduler()
        sched.cohort_size = C
        p, s, batch, au, ab, _ = args
        set_span_sink(sink)
        for r in range(2):
            rep = cohort_report(sched.step(r), sched.last_cohort)
            p, s, m = fn(p, s, batch, au, ab,
                         jnp.asarray(rep.mask, jnp.float32))
        set_span_sink(None)
        return p, s, m

    reg = MetricsRegistry()
    for a, b in zip(jax.tree.leaves(rounds(None)),
                    jax.tree.leaves(rounds(reg))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert reg["span.sched.price"].count == 2


def test_cross_attention_scope_changes_no_output(monkeypatch):
    """The tiny encoder-decoder round with the ``cross_attention`` scope and
    without it: bit-identical outputs."""
    import repro.models.encdec as encdec

    fn, args = _tiny_round("seamless-m4t-medium", global_sync=False)
    scoped = fn(*args)
    real = encdec.scope
    monkeypatch.setattr(encdec, "scope", lambda name: (
        contextlib.nullcontext() if name == "cross_attention"
        else real(name)))
    fn2, args2 = _tiny_round("seamless-m4t-medium", global_sync=False)
    assert not any("cross_attention" in s for s in _op_names(fn2, args2))
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(fn2(*args2))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
