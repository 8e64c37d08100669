"""Pieces of the comparison that decides ``correct``."""

from __future__ import annotations

import numpy as np
import pytest

from bench_tiny import ROOT  # noqa: F401  (puts bench/ on sys.path)

import jax
import jax.numpy as jnp

import reference as R


@pytest.mark.parametrize("scale", [1e-4, 1e-2, 1.0, 30.0, 200.0])
def test_e4m3_rounds_as_the_float8_cast(scale):
    x = np.random.default_rng(0).standard_normal(20000) * scale
    x = np.clip(x, -448, 448).astype(np.float32)
    want = x.astype(jnp.float8_e4m3fn).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(jax.jit(R.e4m3)(x)), want)


def test_fp8_is_straight_through_and_scaled():
    x = jnp.linspace(-3.0, 5.0, 101)
    y, g = jax.value_and_grad(lambda v: R.fp8(v).sum())(x)
    np.testing.assert_array_equal(g, np.ones(101))
    assert float(jnp.abs(R.fp8(x)).max()) == 5.0   # the max maps to 448
    assert 0 < float(jnp.abs(R.fp8(x) - x).max()) <= 5.0 / 16


def test_leaf_gap_worst_leaf_over_reference_or_median():
    ref = np.array([1.0, 2.0, 4.0, 1e-5, 0.0])
    prog = np.array([1.1, 2.0, 4.4, 5.0, 0.0])
    # median of moved leaves = 2.0; leaf 3 (1e-5 < 1e-3 * 2) is left out;
    # gaps 0.1/2, 0, 0.4/4
    assert R.leaf_gap(prog, ref) == pytest.approx(0.1)


def test_compare_names():
    read = {"loss": [10.0, 9.0], "grad1": np.ones(3), "delta": np.ones(3),
            "frozen_change": 0.0}
    other = dict(read, loss=[10.0, 9.009], grad1=np.array([1, 1, 1.5]))
    got = R.compare(other, read)
    assert got == pytest.approx({"loss_gap": 0.001, "grad1_gap": 0.5,
                                 "delta_gap": 0.0, "frozen_change": 0.0})
