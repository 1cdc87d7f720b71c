"""Plain reference of the compressed-L2GD protocol steps the benchmark
checks (Algorithm 1 of arXiv:2209.05148), written from the equations:

* local step:  x_i <- x_i - eta / (n (1 - p)) * grad f_i(x_i);
* aggregation: t = C_M(mean_i C_i(x_i)), then
  x_i <- x_i - eta lam / (n p) * (x_i - t);
* natural compression: each nonzero x = s m 2^e (m in [1, 2)) becomes
  s 2^e or s 2^(e+1), the latter with probability m - 1 (unbiased);
* QSGD with s levels over buckets of b consecutive elements of the
  flattened model: x -> ||v|| sign(x) l / s, with l = floor(s |x| /
  ||v||) raised by one with probability equal to the remainder.

It draws its own random numbers, so its compressed target differs from
the program's element by element; what it gives is the statistics of a
correct compression of the same input.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def natural(key, x):
    m, e = jnp.frexp(jnp.abs(x))          # |x| = m 2^e, m in [0.5, 1)
    low = jnp.ldexp(jnp.ones_like(x), e - 1)
    up = jax.random.uniform(key, x.shape) < (2.0 * m - 1.0)
    return jnp.where(x == 0, 0.0, jnp.sign(x) * low * (1.0 + up))


def qsgd(key, flat, levels: int, bucket: int):
    d = flat.shape[0]
    pad = (-d) % bucket
    v = jnp.pad(flat, (0, pad)).reshape(-1, bucket)
    norm = jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))
    level = jnp.where(norm > 0, jnp.abs(v) / jnp.where(norm > 0, norm, 1.0),
                      0.0) * levels
    lo = jnp.floor(level)
    l = lo + (jax.random.uniform(key, v.shape) < (level - lo))
    return (norm * jnp.sign(v) * l / levels).reshape(-1)[:d]


def compress(codec: dict, key, flat):
    if codec["name"] == "natural":
        return natural(key, flat)
    if codec["name"] == "qsgd":
        return qsgd(key, flat, codec["levels"], codec["bucket"])
    raise ValueError(f"no reference for codec {codec['name']!r}")


def mean(flats):
    """mean_i x_i of the clients' flat models (a sequence of (d,)
    arrays, each on any device), formed on the first one's device."""
    home = flats[0].sharding
    return sum(jax.device_put(f, home) for f in flats) / len(flats)


def target(key, flats, uplink: dict, downlink: dict):
    """t = C_M(mean_i C_i(x_i)) over the clients' flat models (as in
    :func:`mean`): each C_i on its client's device, the mean and C_M on
    the first one's."""
    n, home = len(flats), flats[0].sharding
    keys = jax.random.split(key, n + 1)
    up = sum(jax.device_put(
        compress(uplink, jax.device_put(keys[i], f.sharding), f), home)
        for i, f in enumerate(flats)) / n
    return compress(downlink, jax.device_put(keys[n], home), up)


def target_stats(t, m):
    """(relative error ||t - m|| / ||m||, correlation of the error with
    m) of a compressed target t of the exact mean m.  A correct unbiased
    compression leaves the correlation near 1/sqrt(d)."""
    err = t - m
    en, mn = jnp.linalg.norm(err), jnp.linalg.norm(m)
    return en / mn, jnp.vdot(err, m) / (en * mn)
