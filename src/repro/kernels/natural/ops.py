"""Public wrappers: single-array natural compression + the fused
decode->reduce aggregation kernel.

``natural_compress`` routes lane-padding through the flat-buffer
engine's bucketizer and generates noise in-kernel; backend dispatch is
automatic (compiled Pallas on TPU, fused jnp elsewhere).  Pass
``interpret`` explicitly to pin the interpret-mode Pallas kernel
(tests).

``natural_reduce`` is the server half of the one-pass aggregation
engine (DESIGN.md §10): it consumes a STACKED natural wire batch —
exponent codes (n, n_buckets, bucket) uint8 plus packed sign bitmaps
(n, n_buckets, bucket//8) uint8 — and accumulates the weighted sum of
the reconstructed buffers (the ``natural_merge`` bit composition
``(sign << 31) | (exp << 23)``) into a single (n_buckets, bucket)
float32 accumulator: server memory is O(d), not O(n*d).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import (VMEM_BUDGET_BYTES, autotune_rows, on_tpu,
                                   scalar_spec)
from repro.kernels.natural.kernel import natural_fused, natural_fused_pallas
from repro.kernels.natural.ref import natural_reduce_ref

__all__ = ["natural_compress", "natural_reduce", "natural_reduce_pallas"]

_LANE = 128


def natural_compress(key, x, *, interpret: bool = None):
    from repro.core.flatbuf import bucketize, seeds_of, unbucketize
    flat = x.reshape(-1)
    d = flat.shape[0]
    x2d = bucketize(flat.astype("float32"), _LANE)
    seeds = seeds_of(key)
    if interpret is None:
        out = natural_fused(x2d, seeds)
    else:
        out = natural_fused_pallas(x2d, seeds, interpret=interpret)
    return unbucketize(out, d).reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# fused decode->reduce (the one-pass server aggregation, DESIGN.md §10)
# --------------------------------------------------------------------------

def _spread_signs(packed):
    """Lane-dense sign decode: (rows, b // 8) packed bytes -> (rows, b)
    int32 0/1 sign fields, bit k of byte j at lane 8j + k.

    One matmul against the 0/1 matrix ``spread[j, l] = (l // 8 == j)``
    repeats every byte over its 8 lanes on the MXU (byte values 0-255
    and one-term sums are exact in bf16 -> f32), then each lane keeps
    bit ``lane & 7``.  Every value after the matmul is (rows, b) wide:
    a (rows, b // 8, 8) shift form would pad its minor 8 to 128 lanes
    and move 8-lane groups across sublanes to flatten."""
    rows, bs = packed.shape
    b = 8 * bs
    j = jax.lax.broadcasted_iota(jnp.int32, (bs, b), 0)
    lane_byte = jax.lax.broadcasted_iota(jnp.int32, (bs, b), 1) >> 3
    spread = jnp.where(lane_byte == j, 1.0, 0.0).astype(jnp.bfloat16)
    # Mosaic casts uint8 to a float only through int32
    byte = jnp.dot(packed.astype(jnp.int32).astype(jnp.float32)
                   .astype(jnp.bfloat16), spread,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, b), 1)
    return (byte >> (lane & 7)) & 1


def _merge_tile(e_ref, s_ref):
    """Reconstruct one client's (rows, b) f32 tile from its exponent
    codes and packed sign bitmap — the in-kernel ``natural_merge``."""
    exps = e_ref[0].astype(jnp.int32)                   # (rows, b)
    sign = _spread_signs(s_ref[0])                      # (rows, b)
    bits = (sign << 31) | (exps << 23)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _natural_reduce_kernel(*refs, has_w: bool):
    e_ref, s_ref = refs[0], refs[1]
    w_ref = refs[2] if has_w else None
    o_ref = refs[-1]
    i = pl.program_id(1)                     # client axis, innermost

    # the output block stays in VMEM while i runs: it is the accumulator
    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    y = _merge_tile(e_ref, s_ref)
    if has_w:
        y = y * w_ref[i]
    o_ref[...] += y


@functools.partial(jax.jit, static_argnames=("rows", "interpret", "has_w"))
def _natural_reduce_pallas(exps, signs, weights, *, rows: int,
                           interpret: bool, has_w: bool):
    n, nb, b = exps.shape
    bs = signs.shape[-1]                     # b // 8 packed bytes
    rows = min(rows, nb)
    grid = (pl.cdiv(nb, rows), n)            # client axis innermost
    in_specs = [
        pl.BlockSpec((1, rows, b), lambda t, i: (i, t, 0)),
        pl.BlockSpec((1, rows, bs), lambda t, i: (i, t, 0)),
    ]
    args = (exps, signs)
    kernel = functools.partial(_natural_reduce_kernel, has_w=has_w)
    if has_w:
        in_specs.append(scalar_spec((n,), interpret))
        args = args + (weights.reshape(n).astype(jnp.float32),)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, b), lambda t, i: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, b), jnp.float32),
        name="_natural_reduce_pallas",
        interpret=interpret,
    )(*args)


def _reduce_row_bytes(b: int) -> int:
    """VMEM bytes one bucket row of the natural reduce keeps live: the
    double-buffered exps, signs and f32 output blocks (the output block
    is the accumulator) and the decode's temporaries.  A VMEM tile pads
    its minor dim to 128 lanes, so the (rows, b // 8) sign block and its
    bf16 copy each take a full 128-lane row."""
    lanes = lambda m: -(-m // _LANE) * _LANE
    io = 2 * b + 2 * lanes(b // 8) + 2 * 4 * b   # double-buffered blocks
    spread = 2 * lanes(b // 8) + 4 * b       # bf16 signs, f32 byte spread
    merge = 4 * 4 * b                # exps i32, sign i32, bits, y
    return io + spread + merge


def _reduce_rows(nb: int, b: int) -> int:
    """Rows per tile of the natural reduce.  ``_reduce_row_bytes`` counts
    both pipeline buffers, so the tile takes both stages' budget."""
    return autotune_rows(nb, _reduce_row_bytes(b), min_itemsize=1,
                         vmem_budget=2 * VMEM_BUDGET_BYTES)


def natural_reduce_pallas(exps, signs, weights=None, *, rows: int = None,
                          interpret: bool = None):
    """Pallas path of :func:`natural_reduce`: grid (bucket_tiles, n) with
    the client axis innermost/sequential, accumulating in the f32 output
    block that stays in VMEM across it (the flash-attention streaming
    pattern); signs are decoded in-tile, lane-dense
    (:func:`_spread_signs`).  Two stablelm-1.6b payloads reduce in
    4.5 ms on a v5e at the 1,920 rows this picks, 5.6 ms at 832 (PERF.md
    section 6)."""
    n, nb, b = exps.shape
    if interpret is None:
        interpret = not on_tpu()
    if rows is None:
        rows = _reduce_rows(nb, b)
    return _natural_reduce_pallas(exps, signs, weights, rows=rows,
                                  interpret=interpret,
                                  has_w=weights is not None)


_natural_reduce_jnp = jax.jit(natural_reduce_ref,
                              static_argnames=("unroll",))


def natural_reduce(exps, signs, weights=None, *, rows: int = None
                   ) -> jax.Array:
    """Backend-dispatched fused decode->reduce over the leading client
    axis in ONE pass with an O(d) accumulator (compiled Pallas on TPU, a
    jnp ``lax.scan`` accumulation elsewhere; both add clients in index
    order 0..n-1)."""
    if on_tpu():
        return natural_reduce_pallas(exps, signs, weights, rows=rows,
                                     interpret=False)
    return _natural_reduce_jnp(exps, signs, weights)
