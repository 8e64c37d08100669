"""The edge-round driver that reads the round by layer
(``bench/kinds/edge_round_layers.py``), its two metrics and the flash
kernel's counts (``bench/rooflines/flash_attention.py``)."""

from __future__ import annotations

import math

import pytest

from bench_tiny import bench_at, run_tiny, tiny_cell

import scopes as S
import trace_reduce as T

CELL = "m4t-medium-round"
NEW = ("attention_device_ms", "flash_attention_roofline")


@pytest.fixture(scope="module")
def bench():
    return bench_at()


@pytest.fixture(scope="module")
def driver(bench):
    return bench.module("kinds", "edge_round_layers")


@pytest.fixture(scope="module")
def roof(bench):
    return bench.module("rooflines", "flash_attention")


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_runs_correct(bench, trace):
    """The cell at a tiny size on the CPU, untraced and traced.  The CPU
    trace has no device plane, so the device metrics are absent there
    (not zero), and reading them raises nothing."""
    cell = tiny_cell(bench, CELL)
    assert cell.traffic["kind"] == "edge_round_layers"
    assert {m["name"] for m in cell.per_layer} >= set(NEW)
    res = run_tiny(bench, cell, trace=trace)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res["checks"]) == list(cell.limits)
    if trace:
        assert not set(NEW) & set(res["metrics"])
        assert "sched_step_ms" in res["metrics"]
    else:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_new_metrics_only_in_their_cell(bench):
    names = {m["name"] for m in bench.cell("xlstm350m-round").per_layer}
    assert not names & set(NEW)


# a recorded window of one device, 0..100 s, two rounds: the compiled
# round's kernel calls (a causal self-attention whose operands are named,
# a cross-attention with f32 keys and values whose operand shapes are
# written inline), a backward fusion under the cross-attention scope, and
# an operation outside attention
Q = "bf16[2,1,16,512,64]{4,3,2,1,0}"
KV32 = "f32[2,1,16,1024,64]{4,3,2,1,0}"
STACK = "jit(round_body)/vmap()/while/body/closed_call"
HLO = f"""HloModule jit_round_body

%fused_computation.1 (param_0: f32[4]) -> f32[4] {{
  %param_0 = f32[4]{{0}} parameter(0)
  ROOT %mul.1 = f32[4]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{STACK}/transpose(jvp())/while/body/closed_call/cross_attention/attention/while/body/mul"}}
}}

ENTRY %main (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  %q.1 = {Q} parameter(1)
  %kv.2 = {KV32} parameter(2)
  %flash_attention_fwd_causal.3 = ({Q}, f32[2,1,16,512,128]{{4,3,2,1,0}}) custom-call(%q.1, %q.1, %q.1), custom_call_target="tpu_custom_call", operand_layout_constraints={{{Q}, {Q}, {Q}}}, metadata={{op_name="{STACK}/jvp()/while/body/closed_call/attention/jit(flash_attention_hmajor)/flash_attention_fwd_causal/pallas_call"}}
  %flash_attention_fwd.4 = ({Q}, f32[2,1,16,512,128]{{4,3,2,1,0}}) custom-call({Q} %q.1, {KV32} %kv.2, {KV32} %kv.2), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}/jvp()/while/body/closed_call/cross_attention/attention/jit(flash_attention_hmajor)/flash_attention_fwd/pallas_call"}}
  %fusion.5 = f32[4]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %fusion.6 = f32[4]{{0}} add(%p, %p), metadata={{op_name="{STACK}/jvp()/mlp/add"}}
  ROOT %copy.7 = f32[4]{{0}} copy(%fusion.6)
}}
"""
OPS = {0: [("flash_attention_fwd_causal.3", 0, 2), ("flash_attention_fwd.4", 2, 5),
           ("fusion.5", 5, 15), ("fusion.6", 15, 45),
           ("flash_attention_fwd_causal.3", 50, 52),
           ("flash_attention_fwd.4", 52, 55), ("fusion.5", 55, 65),
           ("fusion.6", 65, 95)]}
SPANS = [("window", 0, 100)]


def test_kernel_calls_from_the_compiled_text(driver, roof):
    calls = driver.kernel_calls(HLO, roof)
    assert set(calls) == {"flash_attention_fwd_causal.3",
                          "flash_attention_fwd.4"}
    causal = dict(B=2, H=16, KVH=16, Sq=512, Skv=512, d=64, causal=True)
    assert calls["flash_attention_fwd_causal.3"] == (
        roof.forward_flops(**causal), roof.forward_bytes(**causal))
    cross = dict(B=2, H=16, KVH=16, Sq=512, Skv=1024, d=64, causal=False)
    assert calls["flash_attention_fwd.4"] == (
        roof.forward_flops(**cross),
        roof.forward_bytes(**cross, itemsize=2, kv_itemsize=4))
    # a window in the name narrows the pairs
    win = HLO.replace("fwd_causal.3", "fwd_causal_window128.3")
    flops = driver.kernel_calls(win, roof)[
        "flash_attention_fwd_causal_window128.3"][0]
    assert flops == roof.forward_flops(**causal, window=128) < \
        roof.forward_flops(**causal)


def test_metrics_from_a_recorded_window(bench, driver, roof):
    """Both metrics present, finite and as counted by hand on the recorded
    window: attention is the kernel calls and the scoped fusion, the
    kernel's time its own operations'."""
    calls = driver.kernel_calls(HLO, roof)
    secs, n, flops, nbytes = driver.kernel_numbers(OPS[0], 0, 100, calls)
    assert n == 4 and secs == pytest.approx(10.0)
    assert flops == 2 * sum(f for f, _ in calls.values())
    scoped = S.summarize(OPS, SPANS, 0, 100, S.op_stacks(HLO))
    out = driver.LayersOutcome(
        end_to_end={}, attempted=2, failed=0, checks={},
        memory_peak_bytes=None, rounds_traced=2,
        trace=T.summarize(OPS, SPANS, 0, 100),
        attention_s=scoped.scope_s(*driver.ATTENTION_SCOPES),
        kernel_s=secs, kernel_flops=flops, kernel_bytes=nbytes,
        peaks=bench.peaks("TPU v5 lite"))
    got = {m: bench.module("metrics", m).read(out) for m in NEW}
    assert all(v is not None and math.isfinite(v) for v in got.values())
    # kernels 2 + 3 s and fusion.5 10 s a round, of 100 s over 2 rounds
    assert got["attention_device_ms"] == pytest.approx(1e3 * 30 / 2)
    peaks = out.peaks
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    assert got["flash_attention_roofline"] == pytest.approx(
        100 * least / 10.0)


def test_no_kernel_no_scopes_reads_nothing(bench, driver, roof):
    """A program without the kernel or the scopes: both metrics absent."""
    plain = HLO.replace("flash_attention_fwd", "fusion_x").replace(
        "attention", "other")
    calls = driver.kernel_calls(plain, roof)
    assert calls == {}
    secs, n, _, _ = driver.kernel_numbers(OPS[0], 0, 100, calls)
    assert secs is None and n == 0
    scoped = S.summarize(OPS, SPANS, 0, 100, S.op_stacks(plain))
    out = driver.LayersOutcome(
        end_to_end={}, attempted=2, failed=0, checks={},
        memory_peak_bytes=None, rounds_traced=2,
        trace=T.summarize(OPS, SPANS, 0, 100),
        attention_s=scoped.scope_s(*driver.ATTENTION_SCOPES),
        kernel_s=secs, peaks=bench.peaks("TPU v5 lite"))
    assert [bench.module("metrics", m).read(out) for m in NEW] == \
        [None, None]


def test_roofline_counts_by_hand_causal(roof):
    # one batch, 2 query heads on 1 kv head, 4 rows, d 8: 4+3+2+1 = 10
    # pairs per head
    a = dict(B=1, H=2, KVH=1, Sq=4, Skv=4, d=8, causal=True)
    assert roof.pairs(4, 4, True) == 10
    assert roof.forward_flops(**a) == 2 * 10 * 2 * (8 + 8)          # 640
    # q 64, out 64, k+v 64 elements of 2 bytes, 8 rows of f32 lse
    assert roof.forward_bytes(**a) == (64 + 64 + 64) * 2 + 8 * 4    # 416
    assert roof.backward_flops(**a) == 2 * 10 * 2 * (3 * 8 + 2 * 8)
    assert roof.backward_bytes(**a) == (2 * 64 + 2 * 64 + 2 * 64) * 2 + 32
    assert roof.pairs(4, 4, True, window=2) == 1 + 2 + 2 + 2


def test_roofline_counts_by_hand_cross(roof):
    # 2 batches, 4 query heads on 2 kv heads, 2 queries onto 6 keys, d 4
    a = dict(B=2, H=4, KVH=2, Sq=2, Skv=6, d=4, causal=False)
    assert roof.pairs(2, 6, False) == 12
    assert roof.forward_flops(**a) == 2 * 4 * 12 * 2 * 8            # 1536
    # q 64, out 64 elements of 2 bytes; k+v 192 of 4; lse 16 rows of 4
    assert roof.forward_bytes(**a, kv_itemsize=4) == \
        (64 + 64) * 2 + 192 * 4 + 16 * 4
    assert roof.backward_flops(**a) == 2 * 4 * 12 * 2 * (12 + 8)
