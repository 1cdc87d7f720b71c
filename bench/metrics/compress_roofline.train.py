"""Roofline share of the compression kernels in the traced window: the
least time the chip could take for the rounds' kernel work, the larger
of bytes over peak HBM bandwidth and operations over peak FLOP/s for
each kernel family (bench/flops.round_kernel_cost, from the flat model
size), over the kernels' summed device time.  All of them are bound by
bytes.  A window with no kernel event reads nothing."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import flops  # noqa: E402
from bench import trace as tl  # noqa: E402

#: trace event names of each codec's Pallas kernels in a round (the
#: natural ones as read from a v5e trace; the qsgd ones by the same rule,
#: the name of the function that makes the pallas_call)
KERNELS = {
    "natural": ["natural_fused_pallas", "_natural_reduce_pallas"],
    "qsgd": ["qsgd_fused_pallas", "qsgd_pack_pallas", "_qsgd_reduce_pallas",
             "qsgd_unpack_pallas"],
}


def kernel_seconds(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    names = KERNELS.get(rec["params"]["uplink"]["name"])
    evs = tl.matching(tr["events"], names or [])
    if not evs:
        return None
    return sum(d for _, _, d in evs) * 1e-9


def read(rec):
    t = kernel_seconds(rec)
    if t is None or not rec.get("comm_rounds"):
        return None
    P, pk = rec["params"], rec["peaks"]
    cost = flops.round_kernel_cost(P["uplink"]["name"], rec["flat_size"],
                                   P["clients"],
                                   P["uplink"].get("bucket", 128))
    least = sum(max(b / pk["hbm_bytes_per_s"], o / pk["flops"])
                for b, o in cost.values()) * rec["comm_rounds"]
    return 100.0 * least / t
