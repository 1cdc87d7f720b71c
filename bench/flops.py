"""Operations and bytes the benchmark credits to the compression
kernels of one communicated round, per kernel family, computed from
shapes alone: the bytes the kernel must read and write (HBM traffic at
its roofline) and its elementwise operations.  All of them are
bandwidth-bound.  A model's FLOPs per trained token are its own
(``models/<model>.py``).
"""
from __future__ import annotations

import math


def padded(n: int, bucket: int) -> int:
    return int(math.ceil(n / bucket) * bucket)


def round_kernel_cost(codec: str, d: int, n_clients: int, bucket: int):
    """{family: (bytes, ops)} of one communicated round over a flat model
    of d elements: the n uplink encodes, the server's decode-and-reduce
    of the n payloads, and the downlink encode of the mean.

    natural: each client's encode kernel (``natural_fused_pallas``) reads
    and writes 4 B per element (the split into a 1 B exponent and a sign
    bit is XLA's, outside the kernel); the reduce reads n payloads of
    1 + 1/8 B per element and writes the 4 B mean; the downlink encode
    reads and writes 4 B per element.
    qsgd: the encode reads 4 B and writes a 1 B code per element and a
    4 B norm per bucket; the reduce reads n payloads and writes 4 B; the
    downlink encode reads and writes 4 B per element."""
    if codec == "natural":
        d = padded(d, 128)
        payload = d * (1 + 1 / 8)
        return {"encode": (n_clients * 8 * d, n_clients * 8 * d),
                "reduce": (n_clients * payload + 4 * d, n_clients * 4 * d),
                "downlink": (8 * d, 8 * d)}
    if codec == "qsgd":
        d = padded(d, bucket)
        payload = d + 4 * d / bucket
        return {"encode": (n_clients * (4 * d + payload), n_clients * 8 * d),
                "reduce": (n_clients * payload + 4 * d, n_clients * 4 * d),
                "downlink": (8 * d, 10 * d)}
    raise ValueError(f"no kernel costs for codec {codec!r}")
