"""What every cell shares: finding its files by name, the device guard, and
the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness finds, by those names alone:

- ``bench/configs/<config>.json``: the model's sizes as run;
- ``bench/refs/<config>.py``: its plain float32 reference (``loss``);
- ``bench/flops/<config>.py``: its model FLOPs (``round_flops``);
- ``bench/traffic/<traffic>.json``: the mix, whose ``kind`` names the
  driver ``bench/kinds/<kind>.py`` (``run``);
- ``bench/limits/<cell>.json``: the limit of each number that decides
  ``correct``;
- ``bench/metrics/<metric>.py``: one reader (``read``) per per-layer metric.

So a later change adds a configuration, a mix, a cell or a metric by
adding files and entries, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list        # manifest entries that this cell reports
    per_layer: list


@dataclass
class Run:
    """What a driver (``bench/kinds/<kind>.py``) is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float          # process start, on time.time()
    bench: "Bench"
    # test hook: wraps the jitted round (fn, num_clients) -> fn, to plant a
    # fault under the timed path; None in every benchmark run
    wrap_round: Callable | None = None


@dataclass
class Outcome:
    """What a driver returns; the metric readers read it."""
    end_to_end: dict                 # name -> value
    attempted: int
    failed: int
    checks: dict                     # name -> (value, limit)
    memory_peak_bytes: int | None
    # the traced run only
    rounds_traced: int = 0
    trace: Any = None                # trace_reduce.Summary
    span_s: dict = field(default_factory=dict)   # span name -> total seconds
    flops_per_round: float | None = None
    chips: int = 1
    peaks: dict | None = None

    @property
    def correct(self) -> bool:
        return all(math.isfinite(v) and v <= lim
                   for v, lim in self.checks.values()) and self.failed == 0


class Bench:
    def __init__(self, root):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.manifest = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, *parts):
        return json.loads(self.dir.joinpath(*parts).read_text())

    def module(self, group: str, name: str):
        """Load ``bench/<group>/<name>.py`` (names may hold '.' and '-')."""
        path = self.dir / group / f"{name}.py"
        key = f"bench_{group}_{name}"
        if key in sys.modules:
            return sys.modules[key]
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; one "
                             f"of {sorted(cells)}")
        w = cells[name]

        def applies(m):
            return "workloads" not in m or name in m["workloads"]

        return Cell(
            name=name, chips=int(w["chips"]), config_name=w["config"],
            config=self.config(w["config"]),
            traffic=self._json("traffic", f"{w['traffic']}.json"),
            limits=self._json("limits", f"{name}.json"),
            end_to_end=[m for m in self.manifest["end_to_end"] if applies(m)],
            per_layer=[m for m in self.manifest["per_layer"] if applies(m)])

    def config(self, name: str) -> dict:
        return self._json("configs", f"{name}.json")

    def peaks(self, device_kind: str) -> dict:
        table = self._json("peaks.json")["devices"]
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"bench/peaks.json")
        return table[device_kind]

    def run_cell(self, cell: Cell, *, seed: int, seconds: float, trace: bool,
                 device: dict, t_start: float, wrap_round=None) -> dict:
        driver = self.module("kinds", cell.traffic["kind"])
        out = driver.run(Run(cell=cell, seed=seed, seconds=seconds,
                             trace=trace, t_start=t_start, bench=self,
                             wrap_round=wrap_round))
        if trace:
            wanted = cell.per_layer
            values = {m["name"]: self.module("metrics", m["name"]).read(out)
                      for m in wanted}
        else:
            wanted = cell.end_to_end
            values = {m["name"]: out.end_to_end.get(m["name"])
                      for m in wanted}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in wanted if values[m["name"]] is not None}
        dev = dict(device, memory_peak_bytes=out.memory_peak_bytes)
        result = {"correct": out.correct, "attempted": out.attempted,
                  "failed": out.failed, "metrics": metrics, "device": dev}
        if trace and out.trace is not None:
            dev["busy_s"] = out.trace.busy_s
            dev["window_s"] = out.trace.window_s
            result["breakdown"] = out.trace.breakdown()
        result["checks"] = {k: {"value": v, "limit": lim}
                            for k, (v, lim) in out.checks.items()}
        return result


def start_jax() -> None:
    """The program's persistent compile cache (at the directory that
    ``run._prepare_env`` set), keeping every program, so that only a
    checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(chips: int) -> dict:
    """The device guard: a TPU with at least ``chips`` devices, or exit
    nonzero.  Returns the ``device`` entry of the result line."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[bench] platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{dev.platform!r}); no result")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}; no result")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def emit(result: dict) -> None:
    """The numbers compared, as the last lines of stderr; then the result
    as the last line of stdout."""
    for name, c in result["checks"].items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"[check] correct = {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
