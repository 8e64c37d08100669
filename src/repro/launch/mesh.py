"""Production mesh construction.

Axis roles (DESIGN.md §2):
    pod    — PHSFL edge servers (CS-level aggregation domain), multi-pod only
    data   — clients within an edge server (edge-level aggregation domain)
    model  — tensor parallelism inside one client's model replica

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_alt_mesh():
    """Same 256 chips, reshaped (32, 16->8 TP): the §Perf mesh-reshape
    iteration for TP-all-reduce-bound steps (halves per-chip TP activation
    traffic at the cost of more clients / FSDP shards)."""
    return jax.make_mesh((32, 8), ("data", "model"))


def make_debug_mesh(*, multi_pod: bool = False):
    """Small mesh for CPU integration tests (8 fake devices)."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def num_chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n


def num_clients(mesh) -> int:
    """Total client slots = product of the client-role axes."""
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
