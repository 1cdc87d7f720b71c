"""Device time of the compression kernels (uplink encode, server
decode-and-reduce, downlink encode) per communicated round in the
traced window: the summed durations of the trace events whose names
are the Pallas kernels of the cell's codec, over the rounds."""
import os
from importlib import util

_spec = util.spec_from_file_location(
    "bench_compress_kernels",
    os.path.join(os.path.dirname(__file__), "compress_roofline.train.py"))
_roof = util.module_from_spec(_spec)
_spec.loader.exec_module(_roof)


def read(rec):
    t = _roof.kernel_seconds(rec)
    if t is None or not rec.get("comm_rounds"):
        return None
    return 1e3 * t / rec["comm_rounds"]
