"""The benchmark's own weights: made on the device from ``--seed`` in
one jitted call, in the layout of the program's parameter tree (its
shapes and names only), so that the program and the plain reference
start from the same numbers and neither has made them.

Rule per leaf, by its name: a norm ``scale`` is ones, the embedding
``table`` is N(0, 0.02^2), every other matrix is N(0, 1/fan_in) with
fan_in the second-to-last axis.  Each client draws its own weights,
from the seed and its index alone, so that they are the same numbers
whether the clients are made stacked on one device, sharded over a
client mesh (no device then holds every client) or one at a time.
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


def _clients(key, index, shapes):
    """The stacked weights of the clients numbered ``index``."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(index)
    return jax.vmap(lambda k: _one_client(k, shapes))(keys)


def _shapes(treedef, leaves):
    return jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct(s, d) for s, d in leaves])


@functools.lru_cache(maxsize=None)
def _maker(treedef, leaves, n_clients: int, mesh=None):
    """One jitted function per tree of shapes, client count and mesh.
    On a client mesh each device makes its own clients."""
    shapes = _shapes(treedef, leaves)
    if mesh is None:
        @jax.jit
        def make(key):
            return _clients(key, jnp.arange(n_clients), shapes)
        return make
    from jax.sharding import PartitionSpec as P
    local = n_clients // mesh.shape["clients"]

    def shard(key):
        first = jax.lax.axis_index("clients") * local
        return _clients(key, first + jnp.arange(local), shapes)
    return jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P(),
                                 out_specs=P("clients")))


@functools.lru_cache(maxsize=None)
def _client_maker(treedef, leaves):
    shapes = _shapes(treedef, leaves)
    return jax.jit(lambda key, i: _one_client(jax.random.fold_in(key, i),
                                              shapes))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds above 2**32
    fold their high word in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _tree_key(shapes):
    flat, treedef = jax.tree_util.tree_flatten(shapes)
    return treedef, tuple((s.shape, str(s.dtype)) for s in flat)


def _weights_key(seed: int):
    return jax.random.fold_in(seed_key(seed), 0x5EED)


def make_weights(seed: int, shapes, n_clients: int, mesh=None):
    """Stacked (n_clients, ...) weights for the tree ``shapes`` (a pytree
    of ShapeDtypeStructs) from ``seed``: on the default device, or with
    a client ``mesh`` sharded over its ``clients`` axis, each device
    making only the clients it holds."""
    make = _maker(*_tree_key(shapes), int(n_clients), mesh)
    return make(_weights_key(seed))


def make_client_weights(seed: int, shapes, client: int, device):
    """Client ``client``'s weights alone (no client axis), made on
    ``device``: the same numbers as its row of :func:`make_weights`."""
    make = _client_maker(*_tree_key(shapes))
    return make(jax.device_put(_weights_key(seed), device), int(client))
