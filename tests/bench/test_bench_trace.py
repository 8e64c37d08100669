"""The reduction from a trace to busy time, idle share and idle gaps."""

from __future__ import annotations

import time

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts bench/ on sys.path)

import trace_reduce as T

# a small recorded window, 0..100: two devices' operations (on device 0
# a loop whose body runs two fusions) and the host spans open meanwhile
OPS = {
    0: [("while.3", 10, 40), ("fusion.1", 12, 30), ("fusion.2", 30, 38),
        ("fusion.1", 60, 90)],
    1: [("fusion.1", 0, 50), ("all-reduce.4", 50, 100)],
}
SPANS = [("window", 0, 100), ("sched_step", 40, 60),
         ("round_dispatch", 60, 62), ("loss_wait", 62, 100),
         ("batch_put", 0, 5)]


def test_busy_union_and_idle():
    s = T.summarize(OPS, SPANS, 0, 100)
    assert s.busy_by_device == [60, 100]      # 10..40 and 60..90; all
    assert s.busy_s == 80
    assert s.window_s == 100
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.2)


def test_window_clips_operations():
    s = T.summarize(OPS, SPANS, 20, 70)
    assert s.busy_by_device == [30, 50]       # 20..40, 60..70; 20..70


def test_top_ops_by_self_time_summed_over_devices():
    s = T.summarize(OPS, SPANS, 0, 100)
    top = dict(map(tuple, s.top_ops))
    # the loop's own time is what its body leaves: 30 - 18 - 8
    assert top == {"fusion.1": 18 + 30 + 50, "all-reduce.4": 50,
                   "fusion.2": 8, "while.3": 4}
    assert s.top_ops[0] == ["fusion.1", 98]


def test_short_names():
    assert T.short_name("%while.2540 = (s32[], bf16[2,4]) while(%t)") == \
        "while.2540"
    assert T.short_name("fusion.7") == "fusion.7"


def test_gaps_named_by_the_host_span():
    s = T.summarize(OPS, SPANS, 0, 100)
    # device 0 idles 0..10 (batch_put ends at 5: midpoint 5 is in no
    # inner span), 40..60 (sched_step), 90..100 (loss_wait)
    assert s.gaps == [["sched_step", 20], ["none", 10], ["loss_wait", 10]]
    assert s.breakdown() == {"device_ops": s.top_ops, "idle_gaps": s.gaps}


def test_no_device_operations_reads_nothing():
    assert T.summarize({}, SPANS, 0, 100) is None
    assert T.summarize({0: []}, SPANS, 0, 100) is None


def test_recorded_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("sched_step"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("round_dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, spans, (lo, hi) = T.read_profile(str(tmp_path), devices=1)
    names = [n for n, _, _ in spans]
    assert sorted(names) == ["round_dispatch", "sched_step", "window"]
    sched = next((s, e) for n, s, e in spans if n == "sched_step")
    assert lo <= sched[0] and sched[1] <= hi
    assert sched[1] - sched[0] >= 0.02
    assert ops == {}                          # no TPU plane on the CPU
