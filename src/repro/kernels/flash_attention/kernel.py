"""Flash attention (streaming softmax) — Pallas TPU kernel.

Causal + optional sliding-window masking.  Grid = (B, H, n_q_blocks,
n_kv_blocks) with the kv axis innermost/sequential; running max / sum /
accumulator live in VMEM scratch persisting across kv steps and the output
tile is written on the last kv step.  Block shapes (bq, D) x (bk, D) are
MXU-aligned for D in {64, 128, 256}.

Used by the serving/prefill path as the memory-optimal attention (the
(S, T) score matrix never exists); validated in interpret mode against
ref.py on CPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, scale: float, causal: bool,
                  window: int | None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T) * scale                          # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)                               # (bq, bk)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
    m_ref[...] = m_cur

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = None) -> jax.Array:
    """q: (B,H,S,D), k/v: (B,H,T,D) (GQA repeat done by caller).  S and T
    must be multiples of bq/bk (caller pads).  ``interpret=None`` follows
    the backend rule of DESIGN.md §5 (compiled on TPU, interpreter
    elsewhere); the dispatched entry point that picks the WINNING impl
    per backend is :func:`repro.kernels.flash_attention.ops.
    flash_attention_op`."""
    from repro.kernels.dispatch import default_interpret
    if interpret is None:
        interpret = default_interpret()
    B, H, S, D = q.shape
    T = k.shape[2]
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0
    scale = 1.0 / math.sqrt(D)
    grid = (B, H, S // bq, T // bk)
    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, D), jnp.float32),   # accumulator
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
