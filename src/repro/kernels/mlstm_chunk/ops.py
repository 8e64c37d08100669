"""Jitted wrapper for the mLSTM chunk kernel with ref-based VJP."""

from __future__ import annotations

import jax

from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_pallas
from repro.kernels.mlstm_chunk.ref import mlstm_ref


@jax.custom_vjp
def _mlstm_chunk_core(q, k, v, li, lf):
    return mlstm_chunk_pallas(q, k, v, li, lf)


def _fwd(q, k, v, li, lf):
    return _mlstm_chunk_core(q, k, v, li, lf), (q, k, v, li, lf)


def _bwd(res, g):
    _, vjp = jax.vjp(mlstm_ref, *res)
    return vjp(g)


_mlstm_chunk_core.defvjp(_fwd, _bwd)


def mlstm_chunk(q, k, v, li, lf):
    return _mlstm_chunk_core(q, k, v, li, lf)
