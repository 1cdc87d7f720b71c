"""Operations and bytes the benchmark credits to the work, computed from
shapes alone.

* Model FLOPs per trained token: 6 x the matmul parameters a token goes
  through (forward 2, backward 4) plus causal-free attention scores and
  values (12 S H D per layer, forward and backward: the program computes
  the full masked S x S matrix).  Recomputation under ``remat`` is not
  counted.  The embedding lookup is not a matmul; the tied unembedding
  is.
* Compression kernels of one communicated round, per kernel family:
  the bytes the kernel must read and write (HBM traffic at its roofline)
  and its elementwise operations.  All of them are bandwidth-bound.
"""
from __future__ import annotations

import math


def matmul_params_per_token(spec: dict) -> int:
    d, H, K, D = spec["d_model"], spec["heads"], spec["kv_heads"], \
        spec["head_dim"]
    attn = d * (H + 2 * K) * D + H * D * d
    if spec.get("experts"):
        ffn = d * spec["experts"] + spec["experts_per_token"] * 3 * d \
            * spec["expert_width"]
    else:
        ffn = 3 * d * spec["d_ff"]
    return spec["layers"] * (attn + ffn) + spec["vocab"] * d


def train_flops_per_token(spec: dict, seq: int) -> float:
    attn_scores = 12 * seq * spec["heads"] * spec["head_dim"] \
        * spec["layers"]
    return 6.0 * matmul_params_per_token(spec) + attn_scores


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
