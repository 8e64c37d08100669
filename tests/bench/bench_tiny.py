"""Shared helpers of the benchmark's CPU tests: the bench directory on
``sys.path``, and a cell cut to a size a CPU test run can hold."""

from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny_cell(bench, name, config=None):
    """Cell ``name`` (with configuration ``config`` in place of its own,
    where given) cut to a few layers of width 64, a vocabulary of 512 and
    sequences of 64 over 1000 clients; dtype, round and limits as the
    cell has them."""
    cell = bench.cell(name)
    if config is not None:
        cell = dataclasses.replace(cell, config_name=config,
                                   config=bench.config(config))
    cfg = dict(cell.config, num_layers=4, d_model=64, vocab_size=512,
               n_client_layers=1)
    if "xlstm" in cfg:
        cfg.update(num_heads=2, num_kv_heads=2, head_dim=32)
        cfg["xlstm"] = dict(cfg["xlstm"], num_heads=2)
    else:
        cfg.update(num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
                   d_ff=128)
        cfg["encdec"] = dict(num_encoder_layers=2, max_source_len=32)
    traffic = dict(cell.traffic, seq=64, population=1000, trace_rounds=2)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run_tiny(bench, cell, *, seed=3000000001, trace=False, wrap_round=None):
    return bench.run_cell(cell, seed=seed, seconds=1.0, trace=trace,
                          device=CPU, t_start=time.time(),
                          wrap_round=wrap_round)


def bench_at(root=ROOT):
    return harness.Bench(root)
