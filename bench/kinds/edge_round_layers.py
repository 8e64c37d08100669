"""The PHSFL edge round of ``edge_round.py``, whose traced run also reads
the round by the program's own layers and the flash kernel's time.

The untraced run is ``edge_round.run``, unchanged.  The traced run sets up
and traces the same ``trace_rounds`` rounds with the same ``Program``,
``Session`` and reference as ``edge_round.run``, reduces the trace as it
does (``trace_reduce.summarize``), and besides:

- charges each device operation's self time to the program's device
  scopes (``scopes.py``), with the name stacks taken from the compiled
  round's HLO ``op_name``s: ``attention_s`` is the self time of
  ``attention`` and ``cross_attention``, forward and backward;
- sums the self time of the flash kernel's own operations (instructions
  named ``flash_attention_fwd[_causal][_window<w>]``, one per call) and
  the work each call needs (``bench/rooflines/flash_attention.py``, from
  the call's operand shapes in the HLO).

Against a program without these names (no scopes, no kernel) the numbers
are None and the metrics that read them are absent.
"""

from __future__ import annotations

import importlib.util
import math
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import reference as R
import scopes as S
import trace_reduce
from harness import Outcome, Run


def _edge_round():
    """``edge_round.py`` beside this file, loaded once, under the key the
    harness gives it (``Bench.module``)."""
    key = "bench_kinds_edge_round"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, Path(__file__).with_name("edge_round.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


ER = _edge_round()
# the round as edge_round.py builds, feeds and checks it (trace_layers.py
# drives a cell's kind through these names)
Program, Session, Spans = ER.Program, ER.Session, ER.Spans
reference_readings = ER.reference_readings

ATTENTION_SCOPES = ("attention", "cross_attention")
KERNEL = re.compile(r"flash_attention_fwd(_causal)?(?:_window(\d+))?"
                    r"(?:\.\d+)?")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR = re.compile(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f8e4m3fn": 1, "f8e5m2": 1}


@dataclass
class LayersOutcome(Outcome):
    """``Outcome`` with the traced window's attention and kernel numbers."""
    attention_s: float | None = None      # self s, attention scopes
    kernel_s: float | None = None         # self s, the flash kernel's calls
    kernel_flops: float = 0.0             # work the calls needed
    kernel_bytes: float = 0.0


def _operand_shapes(line: str, shape_of: dict) -> list:
    """[(dtype, dims)] of a custom call's operands: inline where the text
    gives them, else the shape of the instruction each names."""
    args = line.split("custom-call(", 1)[1]
    depth, end = 0, 0
    for end, ch in enumerate(args):
        depth += ch in "([{"
        if ch in ")]}":
            if depth == 0:
                break
            depth -= 1
    out = []
    for arg in re.split(r",\s*(?![^\[{]*[\]}])", args[:end]):
        m = _SHAPE.search(arg)
        name = arg.strip().split()[-1].lstrip("%") if arg.strip() else ""
        shape = m.groups() if m else shape_of.get(name)
        if shape is None:
            return []
        out.append((shape[0], [int(x) for x in shape[1].split(",") if x]))
    return out


def kernel_calls(hlo_text: str, roof) -> dict:
    """Instruction name -> (flops, bytes) of one call of the flash kernel,
    from the shapes of its operands q, k, v and the mask its name states;
    ``roof`` is ``bench/rooflines/flash_attention.py``."""
    shape_of, lines = {}, []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        if sh := _SHAPE.search(m.group(2)):
            shape_of[m.group(1)] = sh.groups()
        if KERNEL.fullmatch(m.group(1)) and "custom-call(" in line:
            lines.append((m.group(1), line))
    out = {}
    for name, line in lines:
        ops = _operand_shapes(line, shape_of)
        if len(ops) < 3:
            print(f"[bench] {name}: no operand shapes in {line[:300]!r}",
                  file=sys.stderr)
            continue
        (dt, q), (kdt, kk), (_, v) = ops[:3]
        k = KERNEL.fullmatch(name)
        args = dict(B=math.prod(q[:-3]), H=q[-3], KVH=kk[-3], Sq=q[-2],
                    Skv=kk[-2], d=q[-1], causal=bool(k.group(1)), dv=v[-1])
        out[name] = (
            roof.forward_flops(**args, window=int(k.group(2) or 0)),
            roof.forward_bytes(**args, itemsize=_ITEMSIZE.get(dt, 4),
                               kv_itemsize=_ITEMSIZE.get(kdt, 4)))
    return out


def kernel_numbers(ops, lo, hi, calls: dict):
    """(seconds, calls, flops, bytes) of the kernel's operations among one
    device's ``ops`` [(name, start, end)] inside [lo, hi]; seconds None
    where none ran."""
    own = trace_reduce.self_times(ops, lo, hi)
    n, flops, nbytes, secs = 0, 0.0, 0.0, None
    for name, s, e in ops:
        if name in calls and min(e, hi) > max(s, lo):
            n += 1
            flops += calls[name][0]
            nbytes += calls[name][1]
    for name in calls:
        if name in own:
            secs = (secs or 0.0) + own[name]
    return secs, n, flops, nbytes


def _hlo_text(prog, sess) -> str:
    """The compiled round's text (its executable comes from the compile
    cache)."""
    import jax
    import jax.numpy as jnp

    batch = jax.device_put(sess.pool[0])
    mask = jnp.ones((prog.C,), jnp.float32)
    return prog.fn.lower(sess.params, sess.opt_state, batch, prog.au,
                         prog.ab, mask).compile().as_text()


def run(r: Run) -> Outcome:
    if not r.trace:
        return ER.run(r)

    import jax

    cell = r.cell
    t = cell.traffic
    n_check = t["check_rounds"]
    spans = Spans(annotate=True)
    compiles = ER.CompileCount()
    prog = Program(cell, r.bench, r.wrap_round)
    sess = Session(prog, r.seed, spans)
    prog_read = sess.first_rounds(n_check)
    spans.total.clear()

    rd, attempted, failed = n_check, 0, 0
    compiled_in_setup = compiles.n
    setup_s = time.time() - r.t_start
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(t["trace_rounds"]):
                failed += not math.isfinite(sess.round(rd))
                rd += 1
                attempted += 1
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        ops, host, (lo, hi) = S.read_profile(tmp, cell.chips)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"[bench] setup_s={setup_s:.3f} compiles: {compiled_in_setup} in "
          f"set-up, {compiles.n - compiled_in_setup} in the window",
          file=sys.stderr)
    summary = trace_reduce.summarize(ops, host, lo, hi)
    text = _hlo_text(prog, sess)
    scoped = S.summarize(ops, host, lo, hi, S.op_stacks(text))
    busiest = max(ops, key=lambda d: trace_reduce.union_length(
        [(s, e) for _, s, e in ops[d]], lo, hi)) if ops else None
    roof = r.bench.module("rooflines", "flash_attention")
    kern = (kernel_numbers(ops[busiest], lo, hi, kernel_calls(text, roof))
            if busiest is not None else (None, 0, 0.0, 0.0))
    peak = ER._peak_bytes(cell.chips)
    sess.free()

    ref_read = reference_readings(prog, sess, n_check)
    numbers = R.compare(prog_read, ref_read)
    print(f"[bench] readings: {numbers}", file=sys.stderr)
    checks = {k: (numbers[k], float(lim)) for k, lim in cell.limits.items()}

    out = LayersOutcome(
        end_to_end={"train_tokens_per_s":
                    attempted * prog.tokens_per_round() / window_s,
                    "setup_s": setup_s},
        attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=peak, rounds_traced=attempted,
        trace=summary, span_s=dict(spans.total), chips=cell.chips,
        attention_s=(scoped.scope_s(*ATTENTION_SCOPES)
                     if scoped is not None else None),
        kernel_s=kern[0], kernel_flops=kern[2], kernel_bytes=kern[3])
    print(f"[bench] attention_s={out.attention_s} kernel_s={out.kernel_s} "
          f"kernel_calls={kern[1]}", file=sys.stderr)
    if summary is not None:
        dev = jax.devices()[0]
        out.peaks = r.bench.peaks(dev.device_kind)
        out.flops_per_round = r.bench.module(
            "flops", cell.config_name).round_flops(cell.config, t)
    return out
