"""Random weights from a seed, made on the device in one jitted call.

The program's own abstract parameter tree (``jax.eval_shape`` of its
init, no allocation) gives the names, shapes and dtype; the values are
the benchmark's.  Each leaf is drawn in groups (a layer of a stacked
leaf, a block of rows of a table), each group from a key of its own
derived from the seed, the leaf's path and the group index, in f32 and
cast to the served dtype one group at a time, so no f32 copy of a whole
leaf is ever held.  The same seed gives the same weights in any process.

Scales: the embedding and output head N(0, 0.02); matrices N(0, 1/fan_in);
attention biases N(0, 0.1); norm gains 1 + N(0, 0.1).
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

TABLE_GROUPS = 128               # a table's rows are drawn in up to this many groups


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def leaf_rule(name: str, shape: Tuple[int, ...]) -> Tuple[float, float]:
    """(scale, offset) of a leaf's normal draw, by its name."""
    last = name.rsplit("/", 1)[-1]
    if last in ("embed", "lm_head"):
        return 0.02, 0.0
    if last.startswith("norm") or last == "final_norm":
        return 0.1, 1.0
    if last in ("bq", "bk", "bv"):
        return 0.1, 0.0
    if last.startswith("w") or last in ("gate", "up", "down"):
        return 1.0 / math.sqrt(shape[-2]), 0.0
    raise ValueError(f"no weight rule for parameter {name!r} {shape}")


def groups(name: str, shape: Tuple[int, ...]) -> int:
    """How many groups a leaf is drawn in: its layers when stacked, else
    a power of two that divides its rows (at most TABLE_GROUPS)."""
    if name.startswith("blocks/"):
        return shape[0]
    if len(shape) < 2:
        return 1
    return math.gcd(shape[0], TABLE_GROUPS)


def leaf(key, name: str, shape, dtype):
    """One leaf: group g is N(0,1) from fold_in(leaf key, g), scaled."""
    scale, offset = leaf_rule(name, shape)
    lkey = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    g = groups(name, shape)
    part = (math.prod(shape) // g,)

    def draw(i):
        z = jax.random.normal(jax.random.fold_in(lkey, i), part, jnp.float32)
        return (z * scale + offset).astype(dtype)
    return jax.lax.map(draw, jnp.arange(g, dtype=jnp.int32)).reshape(shape)


def make_params(abstract: Dict, seed: int) -> Dict:
    """The program's parameter tree filled from ``seed``, on the device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = [(path_name(p), tuple(a.shape), a.dtype) for p, a in leaves]

    @jax.jit
    def build(key):
        return [leaf(key, n, s, d) for n, s, d in specs]

    return jax.tree_util.tree_unflatten(treedef, build(base_key(seed)))

