"""Mamba selective scan (S6) — Pallas TPU kernel.

TPU adaptation of the hardware-aware scan: instead of CUDA shared-memory
chunking, the (d_inner, N) state lives in a VMEM scratch that persists
across the sequential chunk axis of the grid.  Grid = (B, E_blocks,
n_chunks) with the chunk axis innermost/sequential ("arbitrary"
dimension semantics): each step loads one (chunk, E_blk) tile of
dt/x and one (chunk, N) tile of B/C, runs the recurrence with a
fori_loop over the chunk, and writes the (chunk, E_blk) output tile.
The full (B, L, E, N) tensor never exists — the same insight that makes
the CUDA kernel memory-bound-optimal, expressed TPU-natively.

E_blk is a multiple of 128 (lane dim) when d_inner allows; N = 16 rides in
the sublane dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import default_interpret

__all__ = ["selective_scan"]


def _scan_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, o_ref, h_ref, *,
                 chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...].astype(jnp.float32)                 # (E_blk, N)
    dt = dt_ref[0].astype(jnp.float32)                 # (chunk, E_blk)
    Bm = b_ref[0].astype(jnp.float32)                  # (chunk, N)
    Cm = c_ref[0].astype(jnp.float32)                  # (chunk, N)
    x = x_ref[0].astype(jnp.float32)                   # (chunk, E_blk)

    def body(t, carry):
        h, ys = carry
        decay = jnp.exp(dt[t][:, None] * A)            # (E_blk, N)
        drive = (dt[t] * x[t])[:, None] * Bm[t][None, :]
        h = decay * h + drive
        y_t = jnp.sum(h * Cm[t][None, :], axis=1)      # (E_blk,)
        ys = jax.lax.dynamic_update_slice(ys, y_t[None, :], (t, 0))
        return h, ys

    h0 = h_ref[...]
    ys0 = jnp.zeros((chunk, x.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, chunk, body, (h0, ys0))
    h_ref[...] = h
    o_ref[0] = ys.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "e_blk", "interpret"))
def selective_scan(dt: jax.Array, Bm: jax.Array, Cm: jax.Array, x: jax.Array,
                   A: jax.Array, *, chunk: int = 64, e_blk: int = 128,
                   interpret: bool = None) -> jax.Array:
    """dt/x: (B, L, E); Bm/Cm: (B, L, N); A: (E, N).  Returns y (B, L, E).
    L must be a multiple of ``chunk`` (callers pad); E a multiple of e_blk
    or smaller.  ``interpret=None`` follows the backend (compiled on
    TPU, interpreter elsewhere)."""
    if interpret is None:
        interpret = default_interpret()
    B, L, E = x.shape
    N = A.shape[1]
    e_blk = min(e_blk, E)
    assert L % chunk == 0 and E % e_blk == 0
    grid = (B, E // e_blk, L // chunk)
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, e_blk), lambda b, e, c: (b, c, e)),  # dt
            pl.BlockSpec((1, chunk, N), lambda b, e, c: (b, c, 0)),      # B
            pl.BlockSpec((1, chunk, N), lambda b, e, c: (b, c, 0)),      # C
            pl.BlockSpec((1, chunk, e_blk), lambda b, e, c: (b, c, e)),  # x
            pl.BlockSpec((e_blk, N), lambda b, e, c: (e, 0)),            # A
        ],
        out_specs=pl.BlockSpec((1, chunk, e_blk), lambda b, e, c: (b, c, e)),
        out_shape=jax.ShapeDtypeStruct((B, L, E), x.dtype),
        scratch_shapes=[pltpu.VMEM((e_blk, N), jnp.float32)],
        name="selective_scan",
        interpret=interpret,
    )(dt, Bm, Cm, x, A)
