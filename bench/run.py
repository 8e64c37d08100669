"""Benchmark entry point.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s>
                         --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it starts
on and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``), then ``checks``: every number compared for
``correct`` beside its limit.  The same numbers close standard error.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of a short
window.  Anything but a TPU with the cell's number of chips exits nonzero
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _prepare_env():
    """Set what must be set before JAX is imported."""
    # the cohort scheduler's float64 core runs on JAX's CPU backend
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # libtpu would otherwise log under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _prepare_env()

    import harness

    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)

    harness.start_jax()
    device = harness.require_chips(cell.chips)
    result = bench.run_cell(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), device=device,
                            t_start=T_START)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
