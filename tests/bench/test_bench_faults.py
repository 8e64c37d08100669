"""``correct`` at a tiny size on the CPU: false under each fault planted
under the timed path, and false for the control."""

from __future__ import annotations

import pytest

from bench_tiny import bench_at, run_tiny, tiny_cell

import faults

# the cells' configuration and the one whose cell is left for later
# (PERF.md, Open questions): both run through the harness at a tiny size
CONFIGS = ["xlstm-350m", "seamless-m4t-medium"]


@pytest.fixture(scope="module")
def bench():
    return bench_at()


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("config", CONFIGS)
def test_planted_fault_is_not_correct(bench, config, fault):
    res = run_tiny(bench, tiny_cell(bench, "xlstm350m-round", config),
                   wrap_round=faults.FAULTS[fault])
    assert res["correct"] is False, res["checks"]


def test_frozen_head_moved_is_not_correct(bench):
    def move_head(fn, c):
        def broken(params, opt_state, batch, au, ab, mask):
            p, s, m = fn(params, opt_state, batch, au, ab, mask)
            p = dict(p, lm_head={"w": p["lm_head"]["w"] * 1.01})
            return p, s, m
        return broken

    res = run_tiny(bench, tiny_cell(bench, "xlstm350m-round"),
                   wrap_round=move_head)
    assert res["checks"]["frozen_change"]["value"] > 0
    assert res["correct"] is False


@pytest.mark.parametrize("config", CONFIGS)
def test_control_reads_not_correct(bench, config):
    """``control.calibrate`` at a tiny size: the program reads within the
    cell's limits, while the control (the reference computed in float8:
    matrix products' operands and stored parameters) and the reference
    with half of each batch left out each fail at least one."""
    import control

    c = tiny_cell(bench, "xlstm350m-round", config)
    [row] = control.calibrate(bench, c, [7], log=lambda line: None)

    def fails(numbers):
        return any(numbers[k] > lim for k, lim in c.limits.items())

    assert not fails(row["program"]), row["program"]
    assert fails(row["control"]), row["control"]
    assert fails(row["half_batch"]), row["half_batch"]
