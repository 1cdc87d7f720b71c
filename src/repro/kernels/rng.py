"""Counter-based RNG for in-kernel dither noise (see DESIGN.md §6).

The legacy kernels took an explicit uniform-noise operand generated with
``jax.random`` outside the kernel — an HBM-materialized array as large as
the parameters themselves, doubling the read traffic of a bandwidth-bound
elementwise op.  Instead we derive the noise from a per-element counter:

    bits(i)    = fmix32((i * GOLDEN + s0) ^ s1)        (murmur3 finalizer)
    uniform(i) = (bits(i) >> 8) * 2^-24                in [0, 1)

where ``i`` is the element's flat index in the (n_buckets, bucket) view
and (s0, s1) are two uint32 seed words folded out of a JAX PRNG key.  The
value at index ``i`` depends only on (i, s0, s1), so the same stream is
reproduced bit-exactly by three independent evaluations: tile-local
indices + grid offset inside a Pallas kernel, a whole-buffer jnp
evaluation (the CPU fallback and the ref.py oracles), and any rows
tiling in between.  Compiled TPU kernels may instead use the hardware
PRNG (``pltpu.prng_seed``/``prng_random_bits``) which is faster but not
reproducible off-device; tests always pin the counter path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["GOLDEN", "fmix32", "counter_bits", "bits_to_uniform",
           "counter_uniform_2d", "tile_uniform"]

GOLDEN = 0x9E3779B9          # 2^32 / golden ratio; odd -> bijective mul
_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35  # murmur3 fmix32 constants


def fmix32(x: jax.Array) -> jax.Array:
    """murmur3 32-bit finalizer: full avalanche on uint32."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(_M2)
    x = x ^ (x >> jnp.uint32(16))
    return x


def counter_bits(idx: jax.Array, s0, s1) -> jax.Array:
    """uint32 hash of (flat element index, seed pair)."""
    s0 = jnp.asarray(s0, jnp.uint32)
    s1 = jnp.asarray(s1, jnp.uint32)
    return fmix32((idx.astype(jnp.uint32) * jnp.uint32(GOLDEN) + s0) ^ s1)


def bits_to_uniform(bits: jax.Array) -> jax.Array:
    """Top 24 bits -> float32 uniform in [0, 1) (exact, fp32-representable).

    The shifted value is below 2^24, so the detour through int32 keeps it
    exactly; Mosaic has no direct uint32 -> float32 cast."""
    return (bits >> jnp.uint32(8)).astype(jnp.int32).astype(
        jnp.float32) * jnp.float32(1.0 / (1 << 24))


def counter_uniform_2d(seeds: jax.Array, shape, *, row_offset=0) -> jax.Array:
    """[0, 1) uniforms for a (rows, cols) tile of the bucketed buffer.

    ``seeds`` is a (2,) uint32 array; ``row_offset`` is the tile's first
    global row.  Element (r, c) uses flat index (row_offset + r) * cols + c,
    so any tiling of the same buffer yields the same stream.
    """
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    idx = (jnp.asarray(row_offset, jnp.uint32) + r) * jnp.uint32(shape[1]) + c
    return bits_to_uniform(counter_bits(idx, seeds[0], seeds[1]))


def tile_uniform(seeds_ref, shape, hw_rng: bool) -> jax.Array:
    """[0, 1) uniforms for the current grid step's (rows, cols) tile,
    inside a Pallas kernel whose grid runs over row tiles; ``seeds_ref``
    holds the seed pair as a (1, 2) block (see
    :func:`repro.kernels.dispatch.scalar_spec`).

    ``hw_rng`` draws from the TPU hardware PRNG, seeded with the seed
    pair and the tile index folded into the first word (Mosaic takes at
    most two seed words).  Otherwise the counter RNG runs on the tile's
    global flat indices, bit-compatible with the jnp fallback and the
    ref oracles."""
    if hw_rng:
        tile = pl.program_id(0).astype(jnp.uint32) * jnp.uint32(GOLDEN)
        pltpu.prng_seed(seeds_ref[0, 0] ^ tile, seeds_ref[0, 1])
        bits = pltpu.prng_random_bits(shape)
        if bits.dtype != jnp.uint32:
            bits = jax.lax.bitcast_convert_type(bits, jnp.uint32)
        return bits_to_uniform(bits)
    row0 = (pl.program_id(0) * shape[0]).astype(jnp.uint32)
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    idx = (row0 + r) * jnp.uint32(shape[1]) + c
    return bits_to_uniform(counter_bits(idx, seeds_ref[0, 0],
                                        seeds_ref[0, 1]))
