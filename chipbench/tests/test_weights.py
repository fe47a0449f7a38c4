"""The weights drawn leaf by leaf are bitwise the weights of one jitted
draw of every leaf, the form they had before a leaf at a time was needed
(the leaves of 131072 attention targets outgrow one chip)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.layout import Layout


@functools.partial(jax.jit, static_argnums=(1, 2))
def one_draw(key, shapes: tuple, scale: float):
    """The oracle: every leaf in one jitted call, from the same subkeys."""
    keys = jax.random.split(key, len(shapes))
    return [jax.random.normal(k, s, jnp.float32) * scale
            for k, s in zip(keys, shapes)]


@pytest.mark.parametrize("Z", [16, 512])
@pytest.mark.parametrize("arch", ["lstm", "attn"])
def test_leaf_by_leaf_is_the_single_draw(arch, Z):
    shapes = Layout().model(arch).leaf_shapes(50, 5)
    seed = 2**33 + 19
    got = weights.make(shapes, Z, 50, seed)
    k = int(np.random.default_rng([seed, 2]).integers(0, 2**31 - 1))
    want = one_draw(jax.random.PRNGKey(k),
                    tuple((Z,) + tuple(s) for s in shapes.values()),
                    50 ** -0.5)
    assert list(got) == list(shapes)
    for name, w in zip(shapes, want):
        assert got[name].dtype == np.float32
        assert got[name].shape == (Z,) + tuple(shapes[name])
        np.testing.assert_array_equal(got[name], np.asarray(w))
