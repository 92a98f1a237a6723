"""Seeded weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights, so the plain reference
can use them without taking anything the program made.  The program's
parameter layout (the tree of names and shapes its ``init`` declares, read
with ``jax.eval_shape``, which runs nothing) is the checkpoint format the
weights are written in.  Every leaf is filled by its name:

* ``scale`` (norm gains): ones; ``b``, ``bias``, ``b1``, ``b2``: zeros;
* ``embed``: normal, standard deviation 0.02;
* a convolution kernel ``w`` (4 or more axes, ``(..., kh, kw, cin,
  cout)``): normal / sqrt(kh kw cin);
* every other matrix ``(..., fan_in, fan_out)``: normal / sqrt(fan_in).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ZEROS = {"b", "bias", "b1", "b2"}


def leaf_name(path) -> str:
    k = path[-1]
    return str(getattr(k, "key", getattr(k, "idx", k)))


def _std(name: str, shape) -> float | None:
    if name == "scale" or name in ZEROS:
        return None
    if name == "embed":
        return 0.02
    if name == "w" and len(shape) >= 4:
        return 1.0 / math.sqrt(shape[-4] * shape[-3] * shape[-2])
    return 1.0 / math.sqrt(shape[-2])


def fill(key, shapes):
    """A tree like ``shapes`` (of ``jax.ShapeDtypeStruct``) filled from
    ``key``; call inside ``jax.jit``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = leaf_name(path)
        std = _std(name, s.shape)
        if std is None:
            a = (jnp.ones if name == "scale" else jnp.zeros)(s.shape, s.dtype)
        else:
            a = (jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                   jnp.float32) * std).astype(s.dtype)
        leaves.append(a)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make(key, shapes):
    """Weights for ``shapes`` from ``key`` in one jitted call."""
    return jax.jit(lambda k: fill(k, shapes))(key)
