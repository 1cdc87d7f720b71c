"""The benchmark's own weights: made on the device from ``--seed`` in
one jitted call, in the layout of the program's parameter tree (its
shapes and names only), so that the program and the plain reference
start from the same numbers and neither has made them.

Rule per leaf, by its name: a norm ``scale`` is ones, the embedding
``table`` is N(0, 0.02^2), every other matrix is N(0, 1/fan_in) with
fan_in the second-to-last axis.  Each client draws its own weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _one_client(key, shapes):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, sds) in enumerate(flat):
        name = _leaf_name(path)
        k = jax.random.fold_in(key, i)
        if name.endswith("scale"):
            w = jnp.ones(sds.shape, jnp.float32)
        elif name.endswith("table"):
            w = 0.02 * jax.random.normal(k, sds.shape, jnp.float32)
        else:
            w = jax.random.normal(k, sds.shape, jnp.float32) \
                * sds.shape[-2] ** -0.5
        out.append(w.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _maker(treedef, leaves, n_clients: int):
    """One jitted function per tree of shapes and client count."""
    shapes = jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in leaves])

    @jax.jit
    def make(key):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(n_clients))
        return jax.vmap(lambda k: _one_client(k, shapes))(keys)
    return make


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds above 2**32
    fold their high word in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(seed: int, shapes, n_clients: int):
    """Stacked (n_clients, ...) weights for the tree ``shapes`` (a pytree
    of ShapeDtypeStructs), on the default device, from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten(shapes)
    make = _maker(treedef, tuple((s.shape, str(s.dtype)) for s in flat),
                  int(n_clients))
    return make(jax.random.fold_in(seed_key(seed), 0x5EED))
