"""Public wrappers: single-array QSGD compression + the fused
decode->reduce aggregation kernel.

``qsgd_compress`` routes padding/bucketing through the flat-buffer
engine's bucketizer (:func:`repro.core.flatbuf.bucketize`) — the one
implementation shared with ``compressors.QSGD`` — and generates noise
in-kernel, so there is no full-size noise operand.

``qsgd_reduce`` is the server half of the one-pass aggregation engine
(DESIGN.md §10): it consumes a STACKED packed payload batch — codes
(n, n_buckets, bucket) int8 plus per-bucket norms (n, n_buckets, 1) —
and accumulates ``sum_i w_i * codes_i * (norms_i / s)`` directly into a
single (n_buckets, bucket) float32 accumulator, never materializing any
per-client dequantized buffer: server memory is O(d), not O(n*d).

Backend dispatch (compiled Pallas on TPU, fused jnp elsewhere) is
automatic; pass ``interpret`` explicitly to pin the interpret-mode
Pallas kernel (tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import autotune_rows, on_tpu, scalar_spec
from repro.kernels.qsgd.kernel import qsgd_fused, qsgd_fused_pallas
from repro.kernels.qsgd.ref import qsgd_reduce_ref

__all__ = ["qsgd_compress", "qsgd_reduce", "qsgd_reduce_pallas"]


def qsgd_compress(key, x, *, levels: int = 127, bucket: int = 2048,
                  interpret: bool = None):
    """Quantize-dequantize an arbitrary-shape array (compressor semantics)."""
    from repro.core.flatbuf import bucketize, seeds_of, unbucketize
    flat = x.reshape(-1)
    d = flat.shape[0]
    x2d = bucketize(flat.astype("float32"), bucket)
    seeds = seeds_of(key)
    if interpret is None:
        out = qsgd_fused(x2d, seeds, levels=levels)
    else:
        out = qsgd_fused_pallas(x2d, seeds, levels=levels,
                                interpret=interpret)
    return unbucketize(out, d).reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# fused decode->reduce (the one-pass server aggregation, DESIGN.md §10)
# --------------------------------------------------------------------------

def _qsgd_reduce_kernel(*refs, levels: int, has_w: bool):
    c_ref, n_ref = refs[0], refs[1]
    w_ref = refs[2] if has_w else None
    o_ref, acc_ref = refs[-2], refs[-1]
    i = pl.program_id(1)                     # client axis, innermost

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    y = c_ref[0].astype(jnp.float32) * (n_ref[0] / float(levels))
    if has_w:
        y = y * w_ref[i]
    acc_ref[...] += y

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("levels", "rows", "interpret", "has_w"))
def _qsgd_reduce_pallas(codes, norms, weights, *, levels: int, rows: int,
                        interpret: bool, has_w: bool):
    n, nb, b = codes.shape
    rows = min(rows, nb)
    grid = (pl.cdiv(nb, rows), n)            # client axis innermost
    in_specs = [
        pl.BlockSpec((1, rows, b), lambda t, i: (i, t, 0)),
        pl.BlockSpec((1, rows, 1), lambda t, i: (i, t, 0)),
    ]
    args = (codes, norms)
    kernel = functools.partial(_qsgd_reduce_kernel, levels=levels,
                               has_w=has_w)
    if has_w:
        in_specs.append(scalar_spec((n,), interpret))
        args = args + (weights.reshape(n).astype(jnp.float32),)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, b), lambda t, i: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, b), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, b), jnp.float32)],
        name="_qsgd_reduce_pallas",
        interpret=interpret,
    )(*args)


def qsgd_reduce_pallas(codes, norms, weights=None, *, levels: int = 127,
                       rows: int = None, interpret: bool = None):
    """Pallas path of :func:`qsgd_reduce`: grid (bucket_tiles, n) with the
    client axis innermost/sequential; the f32 accumulator lives in VMEM
    scratch across client steps and the output tile is written once on
    the last client — the flash-attention streaming pattern."""
    n, nb, b = codes.shape
    if interpret is None:
        interpret = not on_tpu()
    if rows is None:
        rows = autotune_rows(nb, 3 * b * 4, min_itemsize=1)
    return _qsgd_reduce_pallas(codes, norms, weights, levels=levels,
                               rows=rows, interpret=interpret,
                               has_w=weights is not None)


_qsgd_reduce_jnp = jax.jit(qsgd_reduce_ref,
                           static_argnames=("levels", "unroll"))


def qsgd_reduce(codes, norms, weights=None, *, levels: int = 127,
                rows: int = None) -> jax.Array:
    """Backend-dispatched fused decode->reduce: ``sum_i w_i * codes_i *
    (norms_i / s)`` over the leading client axis in ONE pass, O(d)
    accumulator state (compiled Pallas on TPU, a jnp ``lax.scan``
    accumulation elsewhere; both add clients in index order 0..n-1)."""
    if on_tpu():
        return qsgd_reduce_pallas(codes, norms, weights, levels=levels,
                                  rows=rows, interpret=False)
    return _qsgd_reduce_jnp(codes, norms, weights, levels=levels)
