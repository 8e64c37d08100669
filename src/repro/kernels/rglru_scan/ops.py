"""Jitted wrapper for the RG-LRU kernel with ref-based VJP."""

from __future__ import annotations

import jax

from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
from repro.kernels.rglru_scan.ref import rglru_scan_ref


@jax.custom_vjp
def _rglru_scan_core(log_a, b, h0):
    return rglru_scan_pallas(log_a, b, h0)


def _fwd(log_a, b, h0):
    return _rglru_scan_core(log_a, b, h0), (log_a, b, h0)


def _bwd(res, g):
    log_a, b, h0 = res
    _, vjp = jax.vjp(rglru_scan_ref, log_a, b, h0)
    return vjp(g)


_rglru_scan_core.defvjp(_fwd, _bwd)


def rglru_scan(log_a, b, h0):
    return _rglru_scan_core(log_a, b, h0)
