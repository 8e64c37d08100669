"""Readings that the limits of ``correct`` are set from, at a cell's own
size, on the chip.

    python3 bench/control.py --workload <name> --seeds 1 2 3 ... [--out F]

For each seed, in one process (the round compiles once): the program's
first rounds, then, from the same weights, batches and masks, the plain
reference, its control (the reference computed in float8, the precision
below the configuration's bfloat16: matrix products' operands and stored
parameters), and the reference put in the program's place with half of
each batch left out.
Each is compared with the reference by ``reference.compare``; one JSON
line per seed goes to stdout and to ``--out``.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def calibrate(bench, cell, seeds, log=print):
    """One dict of readings per seed."""
    import reference as R
    from faults import halve

    er = bench.module("kinds", "edge_round")
    n = cell.traffic["check_rounds"]
    prog = er.Program(cell, bench)
    rows = []
    for seed in seeds:
        t0 = time.time()
        sess = er.Session(prog, seed, er.Spans(annotate=False))
        prog_read = sess.first_rounds(n)
        t1 = time.time()
        sess.free()
        ref = er.reference_readings(prog, sess, n)
        t2 = time.time()
        ctrl = er.reference_readings(prog, sess, n, cast=R.fp8)
        half = er.reference_readings(prog, sess, n, batch_fault=halve)
        row = {"seed": seed, "program": R.compare(prog_read, ref),
               "control": R.compare(ctrl, ref),
               "half_batch": R.compare(half, ref),
               "program_s": t1 - t0, "reference_s": t2 - t1}
        log(json.dumps(row))
        rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import run

    run._prepare_env()
    import harness

    bench = harness.Bench(run.ROOT)
    cell = bench.cell(args.workload)
    harness.start_jax()
    harness.require_chips(cell.chips)
    out = open(args.out, "a") if args.out else None

    def log(line):
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        calibrate(bench, cell, args.seeds, log)
    finally:
        if out:
            out.close()


if __name__ == "__main__":
    main()
