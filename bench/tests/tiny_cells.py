#!/usr/bin/env python3
"""A cell at a CPU size, run through its kind; and, for a cell on more
than one chip, every run the tests make of it in one process of its own,
on as many forced CPU devices as the cell asks for:

    XLA_FLAGS=--xla_force_host_platform_device_count=<chips> \\
    JAX_PLATFORMS=cpu python3 bench/tests/tiny_cells.py \\
        --workload <cell> [--bench-dir DIR]

That process runs the sound cell, each fault its chips can have, and
the bfloat16 control, reads where the state lies and what the chunk
compiles to, and prints one JSON line (see :func:`on_devices`).
"""
from __future__ import annotations

import argparse
import copy
import importlib
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

#: protocol keys whose first 4-step chunk is 0, 0, 1, 1 at p = 0.5 / 0.1
TINY_KEYS = {0.5: 2, 0.1: 408}
SEED = 2 ** 31 + 7
CONTROL_SEED = 3


def tiny(name: str, bench_dir: str = None) -> dict:
    """The cell at a CPU size: widths (its model's ``tiny``), batch,
    sequence and chunk cut, everything else (clients, chips, codecs, p,
    limits) as the cell states it."""
    cell = copy.deepcopy(harness.load_cell(name, bench_dir))
    spec = cell["config_spec"]
    spec.update(harness.model_of(spec, bench_dir).tiny(spec))
    P = cell["params"]
    P.update(batch=2, seq=16, chunk=4,
             protocol_key=TINY_KEYS[P["p"]])
    return cell


def device(cell: dict) -> dict:
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": cell["chips"]}


def run_cell(cell, seconds=0.3, seed=SEED):
    """(result, checks, wall seconds) of one run of the cell through its
    kind."""
    kind = importlib.import_module(f"bench.kinds.{cell['kind']}")
    t0 = time.time()
    result, checks = kind.run(cell, types.SimpleNamespace(
        seed=seed, seconds=seconds, trace=0), t0, device(cell),
        harness.benchmark_spec(ROOT))
    return result, checks, time.time() - t0


def correct(result, checks) -> bool:
    return result["correct"] and all(c["ok"] for c in checks)


def placement(cell) -> dict:
    """Where the first chunk leaves the clients, and what the chunk
    compiles to: the devices each params leaf spans with the clients
    each holds, whether the compiled chunk holds an all-gather, how many
    of its inputs it aliases to outputs, and whether the state it was
    given is gone after a call (donated)."""
    import jax
    from bench.kinds import train
    job = train.Job(cell, SEED)
    state, _ = job.first_chunk()
    leaves = jax.tree_util.tree_leaves(state.params)
    spans = [sorted((s.device.id, s.data.shape[0])
                    for s in leaf.addressable_shards) for leaf in leaves]
    text = job.roll.lower(job.key, state, job.hp, job.stack(job.done),
                          None).compile().as_text()
    state, *_ = job.chunk(state)
    return {"param_leaves": len(leaves), "spans": spans,
            "all_gather": "all-gather" in text,
            "aliased": text.count("may-alias") + text.count("must-alias"),
            "donated": all(leaf.is_deleted() for leaf in leaves)}


def weights_agree(cell) -> dict:
    """The seed's clients made sharded, stacked, and one at a time on
    the device that holds each: whether each pair is bit for bit equal."""
    import jax
    import numpy as np
    from bench import weights
    from bench.kinds import train
    job = train.Job(cell, SEED)
    stacked = weights.make_weights(SEED, job.shapes, job.n)
    sharded = job.weights()
    one = [weights.make_client_weights(SEED, job.shapes, i, job.device_of(i))
           for i in range(job.n)]
    equal = lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b)))
    leaves = jax.tree_util.tree_leaves
    return {"sharded": all(equal(a, b) for a, b in
                           zip(leaves(stacked), leaves(sharded))),
            "one_at_a_time": all(
                equal(a[i], b) for i in range(job.n)
                for a, b in zip(leaves(stacked), leaves(one[i]))),
            "on_its_device": all(
                leaf.devices() == {job.device_of(i)}
                for i in range(job.n) for leaf in leaves(one[i]))}


def on_devices(name: str, bench_dir: str = None) -> dict:
    """Every run the tests make of a multi-chip cell's tiny copy:
    ``{"runs": {variant: [result, checks, wall seconds]}, "control": row,
    "placement": ..., "weights": ...}``, variants being "sound" and
    each fault of the cell's chips."""
    from bench import readings
    from bench.kinds import train
    cell = tiny(name, bench_dir)
    runs = {"sound": run_cell(cell, seconds=0.3)}
    for fault in readings.faults_of(cell):
        with readings.planting(fault, train):
            runs[fault] = run_cell(cell, seconds=0.1)
    control = readings.readings(cell, [CONTROL_SEED], ["control"])[0]
    return {"runs": runs, "control": control,
            "placement": placement(cell), "weights": weights_agree(cell)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--bench-dir", default=None)
    args = ap.parse_args(argv)
    print(json.dumps(on_devices(args.workload, args.bench_dir)), flush=True)


if __name__ == "__main__":
    main()
