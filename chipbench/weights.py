"""Forecaster weights from the seed, made on the device in one jitted call.

Every leaf of every target is drawn as ``normal * hidden**-0.5`` in f32
(the scale of the program's own initialisers), biases included, so that
every weight the forward reads is non-zero.  The shapes come from the
architecture's ``models/<arch>.py``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shapes: tuple, scale: float):
    keys = jax.random.split(key, len(shapes))
    return [jax.random.normal(k, s, jnp.float32) * scale
            for k, s in zip(keys, shapes)]


def make(leaf_shapes: dict, Z: int, hidden: int, seed: int) -> dict:
    """``{leaf: (Z, ...) float32 host array}`` for ``Z`` targets."""
    names = list(leaf_shapes)
    shapes = tuple((Z,) + tuple(leaf_shapes[n]) for n in names)
    # a 32-bit key from any whole-number seed (seeds may exceed 2**32)
    k = int(np.random.default_rng([int(seed), 2]).integers(0, 2**31 - 1))
    out = _draw(jax.random.PRNGKey(k), shapes, float(hidden) ** -0.5)
    return dict(zip(names, jax.device_get(out)))
