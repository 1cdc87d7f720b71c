#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: check the devices (TPU chips, as many as the cell asks
for, or exit non-zero with no result), set up (weights from the seed,
compile or load from ``<checkout>/.jax_cache``, warm up), measure for
``--seconds``, check the timed path's output against the plain
reference, and print one JSON line as the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window with the JAX profiler and reports its per-layer
metrics, with ``device.busy_s``/``window_s`` and a ``breakdown``.
The compared numbers and their limits are the last lines of standard
error and the last key of the JSON line.
"""
from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.require_chips(cell["chips"])
    kind = importlib.import_module(f"bench.kinds.{cell['kind']}")
    result, checks = kind.run(cell, args, T0, device)
    harness.emit(result, checks)


if __name__ == "__main__":
    main()
