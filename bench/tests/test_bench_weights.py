"""Weights from the seed: the same in any process and at any batching,
different for another seed, and drawn at their stated scales."""
import tiny  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import spec, weights
from repro.models.model import Model


@pytest.fixture(scope="module")
def abstract():
    return Model(spec.program_config(tiny.CONFIG)).abstract_params()


def test_same_seed_same_weights_other_seed_other(abstract):
    big = 2 ** 31 + 12345
    a, b = weights.make_params(abstract, big), weights.make_params(abstract, big)
    c = weights.make_params(abstract, big + 1)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(bool((x == y).all()) for x, y in zip(la, lb))
    assert all(bool((x != y).any()) for x, y in zip(la, lc))
    assert all(x.dtype == jnp.bfloat16 for x in la)


def test_each_group_is_drawn_from_its_own_key(abstract):
    """A layer of a stacked leaf is the same drawn alone, from its key."""
    seed, name = 7, "blocks/mlp/up"
    p = weights.make_params(abstract, seed)
    full = p["blocks"]["mlp"]["up"]
    shape = tuple(full.shape)
    assert weights.groups(name, shape) == shape[0]
    one = jax.jit(lambda k: weights.leaf(k, name, (1,) + shape[1:],
                                         full.dtype))
    import zlib
    key = weights.base_key(seed)
    lkey = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(jax.random.fold_in(lkey, 1), (shape[1] * shape[2],),
                          jnp.float32)
    want = (z * shape[1] ** -0.5).astype(full.dtype).reshape(shape[1:])
    assert bool((full[1] == want).all())
    assert weights.groups("embed", (151936, 2048)) == 128
    assert weights.groups("embed", (92544, 2048)) == 128
    assert weights.groups("final_norm", (2048,)) == 1
    del one


def test_scales(abstract):
    p = weights.make_params(abstract, 3)
    wq = np.asarray(p["blocks"]["attn"]["wq"], np.float32)
    assert wq.std() == pytest.approx(128 ** -0.5, rel=0.05)
    assert np.asarray(p["embed"], np.float32).std() == pytest.approx(0.02, rel=0.05)
    assert np.asarray(p["final_norm"], np.float32).mean() == pytest.approx(1, abs=0.05)


def test_unknown_parameter_is_an_error():
    with pytest.raises(ValueError):
        weights.leaf_rule("blocks/attn/mystery", (4, 4))
