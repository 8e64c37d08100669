"""Device time by scope and idle gaps by program span (``bench/scopes.py``)
and the layer probe that reads them (``bench/trace_layers.py``)."""

from __future__ import annotations

import gzip
import os

import pytest

from bench_tiny import bench_at, tiny_cell

import jax

import scopes as S
import trace_layers as L
import trace_reduce as T
from repro.telemetry import MetricsRegistry, get_span_sink, set_span_sink, span

STACK = "jit(round_body)/vmap()/while/body/closed_call"


@pytest.mark.parametrize("stack,expect", [
    (f"{STACK}/jvp(slstm)/dot_general", ("slstm", False)),
    (f"{STACK}/transpose(jvp(slstm))/while/body/mul", ("slstm", True)),
    (f"{STACK}/transpose(jvp(mlstm))/reshape;transpose(jvp(mlp))/t",
     ("mlstm", True)),
    # the outermost scope wins: an MLP inside an aggregation is aggregation
    ("jit(round_body)/edge_agg/jvp(mlp)/mul", ("edge_agg", False)),
    (f"{STACK}/optimizer/sub", ("optimizer", False)),
    # a scope's name inside a longer component is no scope
    (f"{STACK}/jvp(slstm_extra)/my_mlp/add", (None, False)),
    (f"{STACK}/transpose(jvp())/mul", (None, True)),
    ("", (None, False)),
])
def test_scope_rule(stack, expect):
    assert S.scope_of(stack) == expect


HLO = """HloModule jit_round_body, entry_computation_layout={...}

%fused_computation.7 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %mul.3 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(round_body)/transpose(jvp(slstm))/mul"}
  ROOT %add.2 = f32[4]{0} add(%mul.3, %param_0), metadata={op_name="jit(round_body)/transpose(jvp(slstm))/add"}
}

%fused_computation.8 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%param_0), metadata={op_name="jit(round_body)/optimizer/neg"}
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.7
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(round_body)/edge_agg/mul"}
  ROOT %copy.4 = f32[4]{0} copy(%fusion.2)
}
"""


def test_op_stacks_from_hlo_text():
    st = S.op_stacks(HLO)
    # a fusion without metadata takes its fused computation's root's
    assert st["fusion.1"] == "jit(round_body)/transpose(jvp(slstm))/add"
    assert st["fusion.2"] == "jit(round_body)/edge_agg/mul"   # its own
    assert "copy.4" not in st and "p" not in st
    assert S.label("fusion.1", st) == "slstm/fusion.1"
    assert S.label("copy.4", st) == "copy.4"


# a recorded window 0..100 of one device: a loop of the sLSTM whose body
# runs a forward and a backward fusion, the optimizer, and an unscoped op
OPS = {0: [("while.3", 10, 40), ("fusion.f", 12, 30), ("fusion.b", 30, 38),
           ("fusion.o", 60, 70), ("copy.9", 70, 75)]}
STACKS = {"while.3": f"{STACK}/jvp(slstm)/while",
          "fusion.f": f"{STACK}/jvp(slstm)/while/body/mul",
          "fusion.b": f"{STACK}/transpose(jvp(slstm))/while/body/mul",
          "fusion.o": f"{STACK}/optimizer/sub"}
SPANS = [("window", 0, 100), ("sched_step", 40, 60),
         ("sched.price", 42, 55), ("sched.cohort", 41, 42),
         ("loss_wait", 75, 100)]


def test_forward_backward_and_unscoped_split():
    sc = S.summarize(OPS, SPANS, 0, 100, STACKS)
    # the loop's own 4 is forward sLSTM time, as its body's first fusion
    assert sc.seconds == {"slstm": [4 + 18, 8], "optimizer": [10, 0],
                          None: [5, 0]}
    assert sc.busy_s == 30 + 15
    assert sum(map(sum, sc.seconds.values())) == sc.busy_s
    assert sc.scope_s("slstm") == 30
    assert sc.scope_s("edge_agg", "global_agg") is None
    assert sc.top_ops[0] == ["slstm/fusion.f", 18]
    assert ["copy.9", 5] in sc.top_ops
    assert sc.unscoped_ops == [["copy.9", 5]]


def test_gaps_named_by_the_innermost_program_span():
    sc = S.summarize(OPS, SPANS, 0, 100, STACKS)
    # 40..60 (midpoint 50: inside sched.price, inside sched_step);
    # 0..10 (none), 75..100 (loss_wait)
    assert sc.gaps == [["loss_wait", 25], ["sched.price", 20],
                       ["none", 10]]
    assert T.span_at(SPANS, 41.5) == "sched.cohort"
    assert T.span_at(SPANS, 58) == "sched_step"


def test_no_device_ops_is_no_summary():
    assert S.summarize({}, SPANS, 0, 100, STACKS) is None
    assert S.summarize({0: []}, SPANS, 0, 100, STACKS) is None


def test_profiler_round_trip_on_the_host(tmp_path):
    """Program spans land on /host:CPU, nested in the harness span."""
    reg = MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("sched_step"):
                set_span_sink(reg)
                for name in ("sched.cohort", "sched.channel",
                             "sched.price", "sched.report"):
                    with span(name):
                        jax.numpy.ones(8).sum().block_until_ready()
                set_span_sink(None)
    finally:
        set_span_sink(None)
        jax.profiler.stop_trace()
    ops, spans, (lo, hi) = S.read_profile(str(tmp_path), 1)
    assert ops == {}                        # no TPU plane on the host
    by = {n: (s, e) for n, s, e in spans}
    outer = by["sched_step"]
    for name in ("sched.cohort", "sched.channel", "sched.price",
                 "sched.report"):
        s, e = by[name]
        assert outer[0] <= s < e <= outer[1], name
        assert T.span_at(spans, (s + e) / 2) == name
        assert reg[f"span.{name}"].count == 1
    assert lo <= outer[0] and outer[1] <= hi


def test_layer_numbers():
    sc = S.summarize(OPS, SPANS, 0, 100, STACKS)
    out = L.layer_numbers(sc, 2, {"sched.price": 0.004, "sched.cohort":
                                  0.001, "sched.channel": 0.002}, 0.01)
    assert out["slstm_device_ms"] == 1e3 * 30 / 2
    assert out["optimizer_device_ms"] == 1e3 * 10 / 2
    assert out["mlstm_device_ms"] is None
    assert out["edge_agg_device_ms"] is None
    assert out["round_device_ms"] == 1e3 * 45 / 2
    assert out["unscoped_share"] == pytest.approx(100 * 5 / 45)
    assert out["sched_price_ms"] == pytest.approx(2.0)
    assert out["sched_draw_ms"] == pytest.approx(1.5)
    assert out["sched_report_ms"] is None
    assert out["sched_step_ms"] == pytest.approx(5.0)
    bare = L.layer_numbers(None, 2, {}, 0.01)
    assert bare["sched_price_ms"] is None and "slstm_device_ms" not in bare


def test_probe_on_a_tiny_cell(tmp_path):
    """Set-up, a traced window with the sink on, the on/off cost rounds;
    on the host there is no device plane, so only host numbers."""
    bench = bench_at()
    cell = tiny_cell(bench, "xlstm350m-round")
    res = L.measure(bench, cell, 3000000001, cost_rounds=1,
                    save=str(tmp_path), log=lambda *a, **k: None)
    assert get_span_sink() is None
    n = res["numbers"]
    assert n["sched_price_ms"] > 0 and n["sched_draw_ms"] > 0
    assert n["sched_price_ms"] + n["sched_draw_ms"] <= n["sched_step_ms"]
    cost = res["span_cost"]
    assert len(cost["sched_step_ms_on"]) == 1
    assert cost["span_us_on"] > cost["span_us_off"] > 0
    assert get_span_sink() is None
    assert os.path.getsize(tmp_path / "window.xplane.pb.gz") > 0
    assert "op_name=" in gzip.decompress(
        (tmp_path / "round.hlo.txt.gz").read_bytes()).decode()
