"""Faults planted under the timed path, to show that ``correct`` catches
them (tests/bench) and to read them at a cell's own size (control.py).

Each wrapper takes the jitted round ``fn(params, opt_state, batch,
alpha_u, alpha_b, mask)`` and the number of clients, and returns a broken
one with the same signature.
"""

from __future__ import annotations

import numpy as np


def half_rows(c: int) -> np.ndarray:
    """Client rows when the second half of the batch is left out: the first
    half twice over, so the mean is taken over the rest."""
    return np.concatenate([np.arange(c // 2)] * 2)


def halve(batch: dict, c: int) -> dict:
    rows = half_rows(c)
    return {k: v[rows] for k, v in batch.items()}


def state_unchanged(fn, c):
    def broken(params, opt_state, batch, au, ab, mask):
        _, _, metrics = fn(params, opt_state, batch, au, ab, mask)
        return params, opt_state, metrics
    return broken


def half_batch(fn, c):
    def broken(params, opt_state, batch, au, ab, mask):
        return fn(params, opt_state, halve(batch, c), au, ab, mask)
    return broken


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}
