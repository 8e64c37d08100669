"""Device time by the program's layer names, and idle gaps by its spans.

The program names its layers (``repro.telemetry.spans``): every operation
of the jitted round carries a device scope in its HLO ``op_name``
metadata, backward operations under ``transpose(jvp(<scope>))``, and the
cohort scheduler opens host spans (``sched.*``) on the profiler's clock
while a span sink is installed.

An operation belongs to the outermost name of ``SCOPES`` that is a whole
component of its name stack once transform wrappers (``jvp(...)``,
``transpose(...)``) are unwrapped, and is backward where a
``transpose(...)`` wraps a component; an operation of no scope is
unscoped.  Its self time (``trace_reduce.self_times``) goes to its scope,
forward or backward.  An idle gap is named by the innermost span open at
its midpoint, the harness's or the program's.

A TPU trace's operation events carry no name stack (their stats are
the device offset, the duration and a time scale), so the stacks come
from the compiled round's HLO text (``op_stacks``), by instruction
name.  Against a program without named layers every operation is
unscoped and no program span shows: the numbers that read them are then
absent, not zero.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

import trace_reduce as T

try:
    from repro.telemetry.spans import SCOPES, SPAN_NAMES
except ImportError:           # a program without named layers
    SCOPES, SPAN_NAMES = (), ()

HOST_SPANS = T.SPAN_NAMES | frozenset(SPAN_NAMES)

_WRAPPED = re.compile(r"([\w.\-]*)\((.*)\)")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_of(stack: str):
    """(scope or None, backward) of one operation's name stack."""
    found, backward = None, False
    for comp in stack.split(";", 1)[0].split("/"):
        while m := _WRAPPED.fullmatch(comp):
            backward |= m.group(1) == "transpose"
            comp = m.group(2)
        if found is None and comp in SCOPES:
            found = comp
    return found, backward


def op_stacks(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` metadata of a compiled module's
    text.  A fusion without its own takes its fused computation's (the
    root's, else the first instruction's that has one)."""
    own, calls, first, root = {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMP.match(line)
            comp = m.group(1) if m else comp
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        is_root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        if op:
            own[name] = op.group(1)
            first.setdefault(comp, op.group(1))
            if is_root:
                root[comp] = op.group(1)
        elif c := _CALLS.search(rest):
            calls[name] = c.group(1)
    for name, called in calls.items():
        stack = root.get(called, first.get(called))
        if stack is not None:
            own.setdefault(name, stack)
    return own


def scope_seconds(ops, stacks: dict, lo, hi) -> dict:
    """{scope or None: [forward, backward]} self time of one device's
    ``ops`` [(name, start, end)] inside [lo, hi]."""
    out = {}
    for name, t in T.self_times(ops, lo, hi).items():
        sc, bwd = scope_of(stacks.get(name, ""))
        out.setdefault(sc, [0.0, 0.0])[bwd] += t
    return out


def label(name: str, stacks: dict) -> str:
    """'slstm/fusion.12' for a scoped operation, the name otherwise."""
    sc, _ = scope_of(stacks.get(name, ""))
    return f"{sc}/{name}" if sc else name


@dataclass
class Scoped:
    """One traced window of the busiest device, by layer."""
    window_s: float
    busy_s: float
    seconds: dict                 # scope or None -> [forward, backward]
    top_ops: list                 # [label, seconds], the TOP largest
    gaps: list                    # [span, seconds], the TOP longest
    unscoped_ops: list            # [name, seconds], the TOP largest

    def scope_s(self, *names) -> float | None:
        """Self seconds of the named scopes, or None where none ran."""
        hit = [sum(self.seconds[n]) for n in names if n in self.seconds]
        return sum(hit) if hit else None

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops, "idle_gaps": self.gaps}


def summarize(device_ops, spans, lo, hi, stacks: dict) -> Scoped | None:
    """The busiest device's window by layer; None where no device ran an
    operation."""
    busy = {d: T.union_length([(s, e) for _, s, e in ops], lo, hi)
            for d, ops in device_ops.items() if ops}
    if not busy:
        return None
    dev = max(busy, key=busy.get)
    ops = device_ops[dev]
    own = sorted(T.self_times(ops, lo, hi).items(), key=lambda kv: -kv[1])
    gaps = T.idle_gaps([(s, e) for _, s, e in ops], lo, hi)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:T.TOP]
    return Scoped(
        window_s=hi - lo, busy_s=busy[dev],
        seconds=scope_seconds(ops, stacks, lo, hi),
        top_ops=[[label(n, stacks), v] for n, v in own[:T.TOP]],
        gaps=[[T.span_at(spans, (s + e) / 2), e - s] for s, e in longest],
        unscoped_ops=[[n, v] for n, v in own
                      if scope_of(stacks.get(n, ""))[0] is None][:T.TOP])


def read_profile(trace_dir, devices: int):
    """As ``trace_reduce.read_profile``, with the program's spans among
    the host spans: ({device: [(name, start, end)]}, [(name, start,
    end)], window (lo, hi)), in seconds."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device_ops, spans, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            idx = int(plane.name[len(T.DEVICE_PREFIX):])
            if idx >= devices:
                continue
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    device_ops[idx] = [
                        (T.short_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        t = (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                        spans.append(t)
                        if e.name == T.WINDOW_SPAN:
                            window = t[1:]
    if window is None:
        raise RuntimeError("the trace holds no 'window' span")
    return device_ops, spans, window
