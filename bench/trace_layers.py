"""A cell's traced rounds by the program's own layer names, on the chip.

    python3 bench/trace_layers.py --workload <name> --seed <n>
                                  [--cost-rounds K] [--save DIR]

Sets the cell up as ``bench/run.py`` does (``bench/kinds/<kind>.py``),
then traces the traffic's ``trace_rounds`` rounds under the profiler
with a span sink installed (``repro.telemetry.set_span_sink``) and
reduces the trace by device scope and host span (``scopes.py``).
Prints per round: the device self time of each scope, forward and
backward, the unscoped share of the device's busy time, the scheduler's
span seconds, and the breakdown with scoped operation labels and gaps
named by the innermost span; the last line of stdout is one JSON object.

``--cost-rounds K`` then traces 2K more rounds, the sink off and on in
turn, and reports the harness's ``sched_step`` per round of each, and
the host microseconds of one span, on and off: what the spans cost while
the profiler records.  ``--save DIR`` keeps the
trace and the round's HLO text there.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def _trace(sess, rd, n, trace_dir, sink=None, on=lambda i: True):
    """Rounds rd..rd+n-1 under the profiler; the sink is installed for
    the rounds ``on(i)`` picks.  Returns the harness's sched_step seconds
    per round."""
    import jax

    import trace_reduce as T
    from repro.telemetry import set_span_sink

    steps = []
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            for i in range(n):
                set_span_sink(sink if on(i) else None)
                before = sess.spans.total["sched_step"]
                sess.round(rd + i)
                steps.append(sess.spans.total["sched_step"] - before)
    finally:
        set_span_sink(None)
        jax.profiler.stop_trace()
    return steps


def _span_us(on=False, n=2000) -> float:
    """Host microseconds of one span opened and closed, the sink
    installed or not, while the profiler records."""
    import jax

    from repro.telemetry import (SPAN_NAMES, MetricsRegistry, set_span_sink,
                                 span)

    tmp = tempfile.mkdtemp(prefix="bench-layers-span-")
    jax.profiler.start_trace(tmp)
    set_span_sink(MetricsRegistry() if on else None)
    try:
        t0 = time.perf_counter()
        for _ in range(n):
            for name in SPAN_NAMES:
                with span(name):
                    pass
        dt = time.perf_counter() - t0
    finally:
        set_span_sink(None)
        jax.profiler.stop_trace()
        shutil.rmtree(tmp, ignore_errors=True)
    return 1e6 * dt / (n * len(SPAN_NAMES))


def _hlo_text(prog, sess) -> str:
    """The compiled round's text (from the persistent cache)."""
    import jax
    import jax.numpy as jnp

    batch = jax.device_put(sess.pool[0])
    mask = jnp.ones((prog.C,), jnp.float32)
    return prog.fn.lower(sess.params, sess.opt_state, batch, prog.au,
                         prog.ab, mask).compile().as_text()


def _save(trace_dir, save, hlo_text, log, limit=40 << 20):
    """The trace and the HLO text the stacks came from, gzipped; the
    trace only where it is under ``limit`` bytes compressed."""
    import glob
    import gzip

    out = Path(save)
    out.mkdir(parents=True, exist_ok=True)
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    packed = gzip.compress(Path(path).read_bytes())
    if len(packed) < limit:
        (out / "window.xplane.pb.gz").write_bytes(packed)
    else:
        log(f"[layers] trace not saved: {len(packed)} B gzipped",
            file=sys.stderr)
    (out / "round.hlo.txt.gz").write_bytes(gzip.compress(hlo_text.encode()))


def layer_numbers(sc, rounds: int, span_s: dict, sched_step_s: float):
    """The per-layer numbers of one traced window, in ms per round (and
    percent for the unscoped share); a number whose scope ran no
    operation or whose span never opened is None."""
    def ms(v):
        return None if v is None else 1e3 * v / rounds

    def spans(*names):
        hit = [span_s[n] for n in names if n in span_s]
        return sum(hit) if hit else None

    out = {"sched_price_ms": ms(spans("sched.price")),
           "sched_draw_ms": ms(spans("sched.cohort", "sched.channel")),
           "sched_report_ms": ms(spans("sched.report")),
           "sched_step_ms": ms(sched_step_s)}
    if sc is None:
        return out
    out.update(
        slstm_device_ms=ms(sc.scope_s("slstm")),
        mlstm_device_ms=ms(sc.scope_s("mlstm")),
        lm_loss_device_ms=ms(sc.scope_s("lm_loss")),
        optimizer_device_ms=ms(sc.scope_s("optimizer")),
        edge_agg_device_ms=ms(sc.scope_s("edge_agg", "global_agg")),
        round_device_ms=ms(sc.busy_s))
    unscoped = sc.scope_s(None)
    out["unscoped_share"] = (100.0 * (unscoped or 0.0) / sc.busy_s
                             if sc.busy_s else None)
    return out


def measure(bench, cell, seed: int, *, cost_rounds=0, save=None,
            log=print) -> dict:
    import scopes as S
    from repro.telemetry import MetricsRegistry

    er = bench.module("kinds", cell.traffic["kind"])
    t = cell.traffic
    rounds = t["trace_rounds"]
    prog = er.Program(cell, bench)
    sess = er.Session(prog, seed, er.Spans(annotate=True))
    sess.first_rounds(t["check_rounds"])       # compiles, as set-up does
    rd = t["check_rounds"]
    sink = MetricsRegistry()
    tmp = tempfile.mkdtemp(prefix="bench-layers-")
    try:
        steps = _trace(sess, rd, rounds, tmp, sink)
        rd += rounds
        ops, host, (lo, hi) = S.read_profile(tmp, cell.chips)
        text = _hlo_text(prog, sess)
        if save:
            _save(tmp, save, text, log)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    stacks = S.op_stacks(text)
    sc = S.summarize(ops, host, lo, hi, stacks)
    span_s = {k[len("span."):]: sink[k].sum for k in sink.names()
              if k.startswith("span.")}
    result = {"workload": cell.name, "seed": seed, "rounds": rounds,
              "numbers": layer_numbers(sc, rounds, span_s, sum(steps))}
    if sc is not None:
        result["scopes_ms"] = {
            str(k): [1e3 * f / rounds, 1e3 * b / rounds]
            for k, (f, b) in sorted(sc.seconds.items(), key=str)}
        result["breakdown"] = sc.breakdown()
        result["window_s"], result["busy_s"] = sc.window_s, sc.busy_s
        for k, (f, b) in result["scopes_ms"].items():
            log(f"[layers] {k}: forward {f:.3f} ms, backward {b:.3f} ms "
                f"per round", file=sys.stderr)
        log(f"[layers] unscoped share of round_device_ms: "
            f"{result['numbers']['unscoped_share']:.2f}%", file=sys.stderr)
        for name, v in sc.unscoped_ops:
            log(f"[layers]   unscoped {name}: {1e3 * v / rounds:.3f} ms "
                f"per round, {stacks.get(name, 'no op_name')}",
                file=sys.stderr)
    if cost_rounds:
        tmp = tempfile.mkdtemp(prefix="bench-layers-cost-")
        try:
            steps = _trace(sess, rd, 2 * cost_rounds, tmp, MetricsRegistry(),
                           on=lambda i: i % 2 == 1)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        off, on = steps[0::2], steps[1::2]
        result["span_cost"] = {
            "sched_step_ms_off": [1e3 * v for v in off],
            "sched_step_ms_on": [1e3 * v for v in on],
            "median_diff_ms": 1e3 * (statistics.median(on)
                                     - statistics.median(off)),
            "span_us_on": _span_us(on=True), "span_us_off": _span_us()}
    sess.free()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cost-rounds", type=int, default=0)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    t_start = time.time()
    sys.path.insert(0, str(BENCH))
    import run

    run._prepare_env()
    import harness

    bench = harness.Bench(run.ROOT)
    cell = bench.cell(args.workload)
    harness.start_jax()
    device = harness.require_chips(cell.chips)
    result = measure(bench, cell, args.seed, cost_rounds=args.cost_rounds,
                     save=args.save)
    result["device"] = device
    result["total_s"] = time.time() - t_start
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
