"""The benchmark's weights: made on the device from the seed in one jitted
call, in the program's parameter layout and storage types.

Every matrix is drawn from a normal of standard deviation 0.02 cut at two
deviations (the GPT-2 initialization), norm scales are ones and biases
zeros.  The layout comes from the shapes of the program's parameters; the
values do not come from the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

STD = 0.02


def _leaf(key, name, shape, dtype):
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name in ("b", "bias"):
        return jnp.zeros(shape, dtype)
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x * STD).astype(dtype)


def initializer(shapes):
    """``init(key, clients)``: the weights for ``shapes`` (a tree of
    ShapeDtypeStruct), broadcast to a leading client axis when
    ``clients`` is not None."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @partial(jax.jit, static_argnums=1)
    def init(key, clients=None):
        leaves = []
        for i, (path, s) in enumerate(flat):
            x = _leaf(jax.random.fold_in(key, i),
                      getattr(path[-1], "key", None), s.shape, s.dtype)
            if clients is not None:
                x = jnp.broadcast_to(x[None], (clients,) + x.shape)
            leaves.append(x)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return init


def seed_key(seed: int, stream: int):
    """An independent key per use of the seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), stream)
