"""QSGD (random dithering) quantize-dequantize — Pallas TPU kernels.

This is the hot-spot on the communication path: every aggregation round
each client quantizes its full model shard (O(params/chips) elements), and
at production scale (123B params / 16-way model parallel) that is ~7.7e9
elements per client per round.  Fusing scale computation + dithering +
(de)quantization in one VMEM pass avoids three HBM round-trips of the
jnp composition (abs -> norm -> scale -> floor -> select).

Layout: the flat parameter vector is bucketed as (n_buckets, bucket); the
kernel tiles ``rows`` buckets per grid step (autotuned to a VMEM budget)
so the working set fits on-core.  ``bucket`` is expected to be a multiple
of 128 (lane dimension); rows x bucket tiles are MXU/VPU aligned.

Dither noise is generated INSIDE the kernel: compiled TPU kernels use the
hardware PRNG (``pltpu.prng_seed``/``prng_random_bits``); interpret mode
and the pure-jnp CPU fallback use the bit-compatible counter RNG from
:mod:`repro.kernels.rng`, eliminating the full-size HBM noise operand of
the legacy kernel and roughly halving read traffic.  The legacy
explicit-noise entry point (:func:`qsgd_dequantized`) is kept as the
oracle-comparison surface for tests and benchmarks.

Three public families, all dispatching compiled-vs-fallback from
``jax.default_backend()`` (DESIGN.md §5):

  qsgd_fused   — quantize-dequantize in one launch (compressor semantics)
  qsgd_pack    — quantize to the int8 wire payload (codes + bucket norms)
  qsgd_unpack  — dequantize a payload; bit-exact vs qsgd_fused
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import (autotune_rows, default_interpret, on_tpu,
                                   scalar_spec)
from repro.kernels.qsgd.ref import (qsgd_dequantized_ref, qsgd_fused_ref,
                                    qsgd_pack_ref, qsgd_unpack_ref)
from repro.kernels.rng import tile_uniform

__all__ = ["qsgd_dequantized", "qsgd_fused", "qsgd_fused_pallas",
           "qsgd_pack", "qsgd_pack_pallas", "qsgd_unpack",
           "qsgd_unpack_pallas"]


def _quantize(x, u, levels: int):
    """Shared bucket quantizer: returns (codes f32 in [-s, s], norm)."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    safe = jnp.where(norm == 0.0, 1.0, norm)
    s = float(levels)
    scaled = jnp.abs(x) / safe * s
    lo = jnp.floor(scaled)
    q = lo + (u < (scaled - lo)).astype(jnp.float32)
    return jnp.sign(x) * q, norm


# --------------------------------------------------------------------------
# legacy explicit-noise kernel (oracle surface; bit-exact vs ref.py)
# --------------------------------------------------------------------------

def _qsgd_kernel(x_ref, u_ref, o_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)                  # (rows, bucket)
    codes, norm = _quantize(x, u_ref[...], levels)
    out = codes * (norm / float(levels))
    o_ref[...] = jnp.where(norm == 0.0, 0.0, out).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("levels", "rows", "interpret"))
def qsgd_dequantized(x2d: jax.Array, noise: jax.Array, *, levels: int = 127,
                     rows: int = None, interpret: bool = None) -> jax.Array:
    """x2d: (n_buckets, bucket) float32; noise: same shape uniform [0,1).
    Returns the dequantized compressed value, same shape."""
    n, b = x2d.shape
    if interpret is None:
        interpret = default_interpret()
    if rows is None:
        rows = autotune_rows(n, 3 * b * 4)
    rows = min(rows, n)
    grid = (pl.cdiv(n, rows),)
    return pl.pallas_call(
        functools.partial(_qsgd_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, b), lambda i: (i, 0)),
            pl.BlockSpec((rows, b), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), x2d.dtype),
        name="qsgd_dequantized",
        interpret=interpret,
    )(x2d, noise)


# --------------------------------------------------------------------------
# fused in-kernel-RNG quantize-dequantize
# --------------------------------------------------------------------------

def _qsgd_fused_kernel(seeds_ref, x_ref, o_ref, *, levels: int, hw_rng: bool):
    x = x_ref[...].astype(jnp.float32)
    u = tile_uniform(seeds_ref, x.shape, hw_rng)
    codes, norm = _quantize(x, u, levels)
    out = codes * (norm / float(levels))
    o_ref[...] = jnp.where(norm == 0.0, 0.0, out).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("levels", "rows", "interpret", "hw_rng"))
def qsgd_fused_pallas(x2d: jax.Array, seeds: jax.Array, *, levels: int = 127,
                      rows: int = None, interpret: bool = None,
                      hw_rng: bool = None) -> jax.Array:
    """One-launch quantize-dequantize with in-kernel noise; ``seeds`` is a
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
        functools.partial(_qsgd_fused_kernel, levels=levels, hw_rng=hw_rng),
        grid=(pl.cdiv(n, rows),),
        in_specs=[
            scalar_spec((1, 2), interpret),
            pl.BlockSpec((rows, b), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), x2d.dtype),
        name="qsgd_fused_pallas",
        interpret=interpret,
    )(seeds.reshape(1, 2), x2d)


_qsgd_fused_jnp = jax.jit(qsgd_fused_ref, static_argnames=("levels",))


def qsgd_fused(x2d: jax.Array, seeds: jax.Array, *,
               levels: int = 127, rows: int = None) -> jax.Array:
    """Backend-dispatched fused compress: compiled Pallas + hardware PRNG
    on TPU, single fused jnp pass (counter RNG, bit-compatible with the
    interpret-mode kernel) elsewhere."""
    if on_tpu():
        return qsgd_fused_pallas(x2d, seeds, levels=levels, rows=rows,
                                 interpret=False, hw_rng=True)
    return _qsgd_fused_jnp(x2d, seeds, levels=levels)


# --------------------------------------------------------------------------
# packed int8 wire payload
# --------------------------------------------------------------------------

def _qsgd_pack_kernel(seeds_ref, x_ref, c_ref, n_ref, *, levels: int,
                      hw_rng: bool):
    x = x_ref[...].astype(jnp.float32)
    u = tile_uniform(seeds_ref, x.shape, hw_rng)
    codes, norm = _quantize(x, u, levels)
    c_ref[...] = codes.astype(jnp.int8)     # |codes| <= levels <= 127
    n_ref[...] = norm


@functools.partial(jax.jit,
                   static_argnames=("levels", "rows", "interpret", "hw_rng"))
def qsgd_pack_pallas(x2d: jax.Array, seeds: jax.Array, *, levels: int = 127,
                     rows: int = None, interpret: bool = None,
                     hw_rng: bool = None):
    """Quantize to the wire payload: (codes int8 (n, b), norms f32 (n, 1)).
    Requires ``levels <= 127`` so sign*magnitude fits int8."""
    if levels > 127:
        raise ValueError(f"levels={levels} does not fit the int8 payload")
    n, b = x2d.shape
    if interpret is None:
        interpret = default_interpret()
    if hw_rng is None:
        hw_rng = not interpret
    if rows is None:
        rows = autotune_rows(n, 2 * b * 4, min_itemsize=1)
    rows = min(rows, n)
    return pl.pallas_call(
        functools.partial(_qsgd_pack_kernel, levels=levels, hw_rng=hw_rng),
        grid=(pl.cdiv(n, rows),),
        in_specs=[
            scalar_spec((1, 2), interpret),
            pl.BlockSpec((rows, b), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, b), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, b), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        name="qsgd_pack_pallas",
        interpret=interpret,
    )(seeds.reshape(1, 2), x2d)


_qsgd_pack_jnp = jax.jit(qsgd_pack_ref, static_argnames=("levels",))


def qsgd_pack(x2d: jax.Array, seeds: jax.Array, *, levels: int = 127,
              rows: int = None):
    """Backend-dispatched pack: (codes int8, per-bucket norms f32)."""
    if on_tpu():
        return qsgd_pack_pallas(x2d, seeds, levels=levels, rows=rows,
                                interpret=False, hw_rng=True)
    return _qsgd_pack_jnp(x2d, seeds, levels=levels)


def _qsgd_unpack_kernel(c_ref, n_ref, o_ref, *, levels: int):
    o_ref[...] = c_ref[...].astype(jnp.float32) * (n_ref[...] / float(levels))


@functools.partial(jax.jit, static_argnames=("levels", "rows", "interpret"))
def qsgd_unpack_pallas(codes: jax.Array, norms: jax.Array, *,
                       levels: int = 127, rows: int = None,
                       interpret: bool = None) -> jax.Array:
    n, b = codes.shape
    if interpret is None:
        interpret = default_interpret()
    if rows is None:
        rows = autotune_rows(n, 2 * b * 4, min_itemsize=1)
    rows = min(rows, n)
    return pl.pallas_call(
        functools.partial(_qsgd_unpack_kernel, levels=levels),
        grid=(pl.cdiv(n, rows),),
        in_specs=[
            pl.BlockSpec((rows, b), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        name="qsgd_unpack_pallas",
        interpret=interpret,
    )(codes, norms)


_qsgd_unpack_jnp = jax.jit(qsgd_unpack_ref, static_argnames=("levels",))


def qsgd_unpack(codes: jax.Array, norms: jax.Array, *,
                levels: int = 127) -> jax.Array:
    """Dequantize a packed payload; bit-exact vs :func:`qsgd_fused` run
    with the same seeds (same codes, same norms, same float ops)."""
    if on_tpu():
        return qsgd_unpack_pallas(codes, norms, levels=levels,
                                  interpret=False)
    return _qsgd_unpack_jnp(codes, norms, levels=levels)
