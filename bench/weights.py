"""Random weights from ``--seed``, made by the benchmark on the device.

The program is handed these weights; the plain reference reads the same
arrays.  Only the program's parameter *layout* (the tree of shapes and
dtypes, from ``jax.eval_shape`` of its initialiser) is taken from it; every
value is drawn here, in one jitted call, directly in the served dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: 1-D leaves are RMSNorm gains stored as an offset from 1 (the program's
#: convention: weight = 1 + g); they get 1 + GAIN_SD * N(0, 1)
GAIN_SD = 0.1


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds go past 32 bits)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def _scale(name: str, shape) -> float:
    if name == "embed":                 # [vocab, d]
        return shape[-1] ** -0.5
    return shape[-2] ** -0.5            # [..., fan_in, fan_out]


def layout(model):
    """The program's parameter tree of ``ShapeDtypeStruct``s."""
    return jax.eval_shape(model.init, jax.random.key(0))


def make(model, seed: int):
    """Every parameter of ``model``'s layout, drawn from ``seed``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout(model))

    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            name = getattr(path[-1], "key", str(path[-1]))
            if len(s.shape) == 1:
                v = GAIN_SD * jax.random.normal(k, s.shape, jnp.float32)
                out.append(v.astype(s.dtype))
            else:
                v = jax.random.normal(k, s.shape, s.dtype)
                out.append(v * jnp.asarray(_scale(name, s.shape), s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
