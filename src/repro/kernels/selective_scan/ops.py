"""jit'd wrapper: pads L to the chunk multiple and dispatches the kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.selective_scan.kernel import selective_scan

__all__ = ["selective_scan_op"]


def selective_scan_op(dt, Bm, Cm, x, A, *, chunk: int = 64, e_blk: int = 128,
                      interpret: bool = None):
    B, L, E = x.shape
    pad = (-L) % chunk
    if pad:
        padt = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        dt, Bm, Cm, x = padt(dt), padt(Bm), padt(Cm), padt(x)
    e_blk = min(e_blk, E)
    while E % e_blk:
        e_blk //= 2
    y = selective_scan(dt, Bm, Cm, x, A, chunk=chunk, e_blk=e_blk,
                       interpret=interpret)
    return y[:, :L]
