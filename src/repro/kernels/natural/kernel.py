"""Natural compression — Pallas TPU kernels.

Stochastic rounding of the float32 magnitude to a power of two via uint32
bit manipulation (probability of bumping the exponent = mantissa / 2^23,
which is exactly unbiased).  Elementwise -> trivially tileable; the win on
TPU is fusing bitcast + mask + select in VMEM on the communication path
instead of five separate HBM-bound elementwise HLO ops.

Tiles are (rows, 128): lane-aligned for the VPU, ``rows`` autotuned to a
VMEM budget.  As with the QSGD kernels, dither noise is generated inside
the kernel (hardware PRNG when compiled on TPU, the counter RNG from
:mod:`repro.kernels.rng` in interpret mode / the jnp CPU fallback), so no
full-size noise operand is read from HBM.  The legacy explicit-noise
entry point (:func:`natural_compress_2d`) remains the oracle surface.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import (autotune_rows, default_interpret, on_tpu,
                                   scalar_spec)
from repro.kernels.natural.ref import (natural_compress_ref,
                                       natural_fused_ref, natural_pack_ref)
from repro.kernels.rng import tile_uniform

__all__ = ["natural_compress_2d", "natural_fused", "natural_fused_pallas",
           "natural_pack"]


def _round_to_pow2(x, u):
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    mantissa = bits & jnp.uint32(0x7FFFFF)
    # mantissa < 2^23: exact through int32 (Mosaic has no u32 -> f32 cast)
    prob = mantissa.astype(jnp.int32).astype(jnp.float32) \
        * (1.0 / float(1 << 23))
    up = (u < prob).astype(jnp.uint32)
    rounded = (bits & jnp.uint32(0xFF800000)) + (up << 23)
    out = jax.lax.bitcast_convert_type(rounded, jnp.float32)
    passthrough = (x == 0.0) | ~jnp.isfinite(x)
    return jnp.where(passthrough, x, out)


def _natural_kernel(x_ref, u_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = _round_to_pow2(x, u_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def natural_compress_2d(x2d: jax.Array, noise: jax.Array, *, rows: int = None,
                        interpret: bool = None) -> jax.Array:
    n, b = x2d.shape
    if interpret is None:
        interpret = default_interpret()
    if rows is None:
        rows = autotune_rows(n, 3 * b * 4)
    rows = min(rows, n)
    return pl.pallas_call(
        _natural_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, b), lambda i: (i, 0)),
                  pl.BlockSpec((rows, b), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), x2d.dtype),
        name="natural_compress_2d",
        interpret=interpret,
    )(x2d, noise)


def _natural_fused_kernel(seeds_ref, x_ref, o_ref, *, hw_rng: bool):
    x = x_ref[...].astype(jnp.float32)
    u = tile_uniform(seeds_ref, x.shape, hw_rng)
    o_ref[...] = _round_to_pow2(x, u).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "interpret", "hw_rng"))
def natural_fused_pallas(x2d: jax.Array, seeds: jax.Array, *,
                         rows: int = None, interpret: bool = None,
                         hw_rng: bool = None) -> jax.Array:
    """One-launch natural compression with in-kernel noise; ``seeds`` is a
    (2,) uint32 array (see :func:`repro.core.flatbuf.seeds_of`)."""
    n, b = x2d.shape
    if interpret is None:
        interpret = default_interpret()
    if hw_rng is None:
        hw_rng = not interpret
    if rows is None:
        rows = autotune_rows(n, 2 * b * 4)
    rows = min(rows, n)
    return pl.pallas_call(
        functools.partial(_natural_fused_kernel, hw_rng=hw_rng),
        grid=(pl.cdiv(n, rows),),
        in_specs=[scalar_spec((1, 2), interpret),
                  pl.BlockSpec((rows, b), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), x2d.dtype),
        name="natural_fused_pallas",
        interpret=interpret,
    )(seeds.reshape(1, 2), x2d)


_natural_fused_jnp = jax.jit(natural_fused_ref)


def natural_fused(x2d: jax.Array, seeds: jax.Array, *,
                  rows: int = None) -> jax.Array:
    """Backend-dispatched fused natural compression: compiled Pallas +
    hardware PRNG on TPU, single fused jnp pass elsewhere."""
    if on_tpu():
        return natural_fused_pallas(x2d, seeds, rows=rows, interpret=False,
                                    hw_rng=True)
    return _natural_fused_jnp(x2d, seeds)


_natural_pack_jnp = jax.jit(natural_pack_ref)


def natural_pack(x2d: jax.Array, seeds: jax.Array, *, rows: int = None):
    """Backend-dispatched wire encode: (uint8 exponent codes, packed sign
    bitmap).  On TPU the compiled fused kernel produces the rounded f32
    buffer and the bit-split runs as a fused XLA epilogue; elsewhere the
    one-pass bits-domain jnp encode (:func:`natural_pack_ref`) never
    materializes the f32 output at all — the pack-bandwidth hot path.
    Bit-exact with ``natural_split(natural_fused(...))`` on both routes."""
    if on_tpu():
        from repro.core.codec import natural_split, pack_bits
        exps, signs = natural_split(
            natural_fused_pallas(x2d, seeds, rows=rows, interpret=False,
                                 hw_rng=True))
        return exps, pack_bits(signs, 1)
    return _natural_pack_jnp(x2d, seeds)
