"""From a profiler trace to the numbers the per-layer metrics read.

A traced window holds device operations (one line of each device plane)
and the harness's host spans (``jax.profiler.TraceAnnotation``), on one
clock.  Busy time is the union of a device's operation intervals inside
the window; an idle gap is a stretch of the window that no operation
covers, named by the host span that was open at its midpoint.
"""

from __future__ import annotations

import glob
import os
import sys
from dataclasses import dataclass

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
TOP = 10


def merged(intervals, lo, hi):
    """Sorted, disjoint intervals covering ``intervals`` clipped to
    [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals, lo, hi) -> float:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def idle_gaps(intervals, lo, hi):
    """The stretches of [lo, hi] that no interval covers."""
    gaps, t = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_at(spans, t) -> str:
    """Name of the innermost host span open at ``t`` ("none" if none)."""
    best = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t < e and (
                best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


@dataclass
class Summary:
    window_s: float
    busy_by_device: list          # seconds per device
    top_ops: list                 # [name, seconds] summed over devices,
    #                               the TOP largest
    gaps: list                    # [host span, seconds], the TOP longest

    @property
    def busy_s(self) -> float:
        return sum(self.busy_by_device) / len(self.busy_by_device)

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops, "idle_gaps": self.gaps}


def self_times(ops, lo, hi) -> dict:
    """Per operation name, the time inside [lo, hi] in which it ran with
    none of the operations nested in it running (a loop's body runs
    inside the loop's own interval)."""
    evs = sorted(((max(s, lo), min(e, hi), n) for n, s, e in ops
                  if min(e, hi) > max(s, lo)), key=lambda x: (x[0], -x[1]))
    totals, stack = {}, []                    # stack: [end, name, self]

    def close(item):
        totals[item[1]] = totals.get(item[1], 0.0) + item[2]

    for s, e, name in evs:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for item in stack:
        close(item)
    return totals


def summarize(device_ops, spans, lo, hi) -> Summary | None:
    """``device_ops``: {device: [(op name, start, end)]}; ``spans``:
    [(name, start, end)]; one time unit in and out.  None where no device
    ran an operation in the window."""
    if not any(device_ops.values()):
        return None
    busy, totals, gaps = [], {}, []
    for ops in device_ops.values():
        iv = [(s, e) for _, s, e in ops]
        busy.append(union_length(iv, lo, hi))
        for name, t in self_times(ops, lo, hi).items():
            totals[name] = totals.get(name, 0.0) + t
        gaps += idle_gaps(iv, lo, hi)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Summary(window_s=hi - lo, busy_by_device=busy,
                   top_ops=[[n, v] for n, v in top],
                   gaps=[[span_at(spans, (s + e) / 2), e - s]
                         for s, e in longest])


def read_profile(trace_dir, devices: int):
    """Device operations and host spans from the ``.xplane.pb`` under
    ``trace_dir``, in seconds: ({device: [(name, start, end)]},
    [(name, start, end)], window (lo, hi))."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device_ops, spans, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            if idx >= devices:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[idx] = [
                        (short_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPAN_NAMES:
                        t = (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                        spans.append(t)
                        if e.name == WINDOW_SPAN:
                            window = t[1:]
    if window is None:
        raise RuntimeError("the trace holds no 'window' span")
    print(f"[trace] operations per device: "
          f"{ {d: len(v) for d, v in device_ops.items()} }", file=sys.stderr)
    return device_ops, spans, window


def short_name(hlo: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    return hlo.split(" = ", 1)[0].lstrip("%")


# the harness's host spans (see bench/kinds/edge_round.py)
SPAN_NAMES = frozenset({WINDOW_SPAN, "batch_put", "sched_step",
                        "round_dispatch", "loss_wait"})
