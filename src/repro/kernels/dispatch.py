"""Backend dispatch for the compression kernels (see DESIGN.md §5).

The kernels serve three execution modes:

  * compiled Pallas on TPU      — the deployment target; hardware PRNG.
  * interpret-mode Pallas       — kernel validation on CPU (tests only;
                                  the interpreter is far too slow for the
                                  hot path).
  * pure-jnp fallback           — the CPU hot path: identical math to the
                                  kernels, one fused XLA elementwise pass,
                                  bit-compatible with interpret mode.

``default_interpret()`` retires the old hardcoded ``interpret=True``
defaults: kernels compile whenever the backend is TPU and fall back to
the interpreter elsewhere.  The flat-buffer engine goes one step further
and routes CPU traffic to the jnp fallback (``on_tpu()``).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["VMEM_BUDGET_BYTES", "on_tpu", "default_interpret",
           "autotune_rows", "autotune_attn_blocks", "row_align",
           "scalar_spec"]

# Working VMEM budget for one pipeline stage.  Cores have ~16 MiB of VMEM;
# we target a quarter of it so double buffering (x2) plus compiler scratch
# still fit comfortably.
VMEM_BUDGET_BYTES = 4 * 1024 * 1024
_ROW_ALIGN = 8  # float32 sublane count


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas mode for the current backend: compiled on TPU, interpret
    elsewhere (CPU/GPU run the kernels through the interpreter)."""
    return not on_tpu()


def row_align(itemsize: int) -> int:
    """Rows of one native (sublane, 128) TPU tile for ``itemsize``-byte
    data: 8 for 32-bit, 16 for 16-bit, 32 for 8-bit (narrow dtypes pack
    several rows per 32-bit sublane)."""
    return _ROW_ALIGN * max(4 // int(itemsize), 1)


def autotune_rows(n_buckets: int, row_bytes: int, *, min_itemsize: int = 4,
                  vmem_budget: int = VMEM_BUDGET_BYTES) -> int:
    """Rows (buckets) per grid step so the kernel's live VMEM bytes,
    ``row_bytes`` per bucket row, fit the budget, clamped to the grid.
    Rows are aligned to the native tile of the narrowest dtype the
    kernel blocks (``min_itemsize`` bytes): 32 rows for int8/uint8."""
    align = row_align(min_itemsize)
    rows = vmem_budget // max(int(row_bytes), 1)
    rows = max((rows // align) * align, align)
    return int(min(rows, max(n_buckets, 1)))


def scalar_spec(shape, interpret: bool):
    """Block spec for a small whole-array operand read as scalars (the
    RNG seed pair, the (n,) per-client weights): SMEM when compiled —
    a VMEM block of it would break the (8, 128) tiling rule — and one
    whole-array block under the interpreter.  Pass the seed pair as a
    (1, 2) array: a vmapped kernel squeezes the new leading axis out of
    the block, and the tiling rule, which SMEM blocks obey too, then
    still sees the full last two dims."""
    if interpret:
        zeros = (0,) * len(shape)
        return pl.BlockSpec(tuple(shape), lambda *_: zeros)
    return pl.BlockSpec(memory_space=pltpu.SMEM)


_ATTN_BLOCK_ALIGN = 128  # MXU tile edge; q/k blocks stay lane-aligned


def autotune_attn_blocks(S: int, T: int, D: int, *, itemsize: int = 4,
                         vmem_budget: int = VMEM_BUDGET_BYTES):
    """(bq, bk) block sizes for the flash-attention kernel so the live
    tiles — q (bq, D), k/v (bk, D), scores (bq, bk), accumulator (bq, D)
    — fit the VMEM budget, MXU-aligned (multiples of 128) and clamped to
    the sequence lengths.  Square blocks: the streaming-softmax kernel is
    balanced when the q and kv tiles match."""
    def fits(b):
        # q + accumulator (2 b D) + k + v (2 b D) + scores (b^2) live
        # tiles, double-buffered
        return 2 * itemsize * b * (4 * D + b) <= vmem_budget

    b = _ATTN_BLOCK_ALIGN
    while b * 2 <= min(S, T) and fits(b * 2):
        b *= 2

    def fit_axis(block, length):
        # the kernel requires block | length: shrink to a divisor
        # (powers of two stay MXU-aligned); sequences shorter than one
        # block clamp to the length, exactly like the old fixed default
        while block > _ATTN_BLOCK_ALIGN and length % block:
            block //= 2
        return min(block, max(length, 1))

    return fit_axis(b, S), fit_axis(b, T)
