"""Forecaster weights from the seed, made on the device one leaf at a time.

Every leaf of every target is drawn as ``normal * hidden**-0.5`` in f32
(the scale of the program's own initialisers), biases included, so that
every weight the forward reads is non-zero.  The shapes come from the
architecture's ``models/<arch>.py``.

The seed's key is split into one subkey per leaf; each leaf is drawn by a
jitted call of its own, copied to the host and its device buffer freed
before the next is drawn, so a device never holds more than one leaf and
its draw.  At 131072 attention targets the leaves take 17.9 GB, more than
one chip holds; the largest alone takes 5.24 GB."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape: tuple, scale: float):
    return jax.random.normal(key, shape, jnp.float32) * scale


def make(leaf_shapes: dict, Z: int, hidden: int, seed: int) -> dict:
    """``{leaf: (Z, ...) float32 host array}`` for ``Z`` targets."""
    names = list(leaf_shapes)
    # a 32-bit key from any whole-number seed (seeds may exceed 2**32)
    k = int(np.random.default_rng([int(seed), 2]).integers(0, 2**31 - 1))
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(k), len(names)))
    scale = float(hidden) ** -0.5
    out = {}
    for name, key in zip(names, keys):
        leaf = _draw(key, (Z,) + tuple(leaf_shapes[name]), scale)
        out[name] = np.asarray(jax.device_get(leaf))
        leaf.delete()
    return out
