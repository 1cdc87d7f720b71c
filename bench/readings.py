#!/usr/bin/env python3
"""Readings that set a train cell's limits: the compared numbers of the
first chunk (see ``bench/kinds/train.py``) for sound runs of the
program, for the lower-precision control and for planted faults, over
several seeds, at the cell's own size.  No measured window.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        [--variants sound,control,...] [--protocol-key K] [--out FILE]

Variants:
  sound      the program as the configuration states it;
  control    the program's own bfloat16 path (weights and compute), the
             precision below the configuration's float32;
  unchanged  each chunk returns the state it was given;
  halfbatch  the local gradient taken over half of each client's batch;
  altered    the aggregation target altered where it is produced
             (scaled by 1 + 1/16);
  cached     a round on the cached target leaves the state as it was;
  nogather   the uplink all_gather left out: each chip averages only
             the clients it holds (cells on more than one chip).
``--protocol-key`` reads under another key whose first chunk has the
same branches (a witness for a reading that a fixed key may shift).
One JSON line per (variant, seed) goes to ``--out``.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import functools
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402

VARIANTS = ("sound", "control", "unchanged", "halfbatch", "altered",
            "cached", "nogather")
#: the faults a cell can have: on one chip there is no exchange to leave
#: out
FAULTS = VARIANTS[2:]
ONE_CHIP_FAULTS = FAULTS[:-1]


def faults_of(cell: dict) -> tuple:
    return FAULTS if cell["chips"] > 1 else ONE_CHIP_FAULTS


def planted(fault: str, train) -> list:
    """[(owner, attribute, replacement)] that plant ``fault`` under a
    whole run of a train cell, on one chip or on a client mesh (the CPU
    tests plant them the same way)."""
    import jax
    import jax.numpy as jnp
    from repro.core import aggregation, rollout
    from repro.core.aggregation import compressed_average

    if fault == "unchanged":
        def unchanged(real, donated: bool):
            def chunk(*a, **kw):
                roll = real(*a, **kw)

                def broken(key, state, hp, batches, forced):
                    # a donated chunk consumes the state it is given
                    kept = jax.tree.map(jnp.copy, state) if donated \
                        else state
                    _, tr = roll(key, state, hp, batches, forced)
                    return kept, tr
                return broken
            return chunk
        return [(train, "program_chunk",
                 unchanged(train.program_chunk, False)),
                (train, "program_sharded_chunk",
                 unchanged(train.program_sharded_chunk, True))]
    if fault == "halfbatch":
        real_grad = train.program_grad_fn

        def grad_fn_of(cfg):
            full = real_grad(cfg)

            def half(p, b):
                return full(p, {"tokens": b["tokens"][:b["tokens"].shape[0]
                                                      // 2]})
            return half
        return [(train, "program_grad_fn", grad_fn_of)]
    if fault == "altered":
        def alter(t):
            return jax.tree.map(lambda a: a * (1.0 + 1.0 / 16), t)

        def chunk(grad_fn, up, down, length, local_steps):
            def avg(k, params):
                return alter(compressed_average(k, params, up, down))
            return jax.jit(functools.partial(
                rollout.rollout_l2gd, grad_fn=grad_fn, steps=length,
                client_comp=up, master_comp=down, batch_axis=0,
                average_fn=avg, local_steps=local_steps))
        real_sharded = rollout.make_client_sharded_average

        def sharded_average(*a, **kw):
            avg = real_sharded(*a, **kw)
            return lambda *b, **c: alter(avg(*b, **c))
        return [(train, "program_chunk", chunk),
                (rollout, "make_client_sharded_average", sharded_average)]
    if fault == "cached":
        real_step = rollout.l2gd_step

        def step(state, *a, **kw):
            new, m = real_step(state, *a, **kw)
            skip = m["branch"] == 2
            kept = state._replace(step=new.step)
            return jax.tree.map(lambda o, x: jnp.where(skip, o, x),
                                kept, new), m
        return [(rollout, "l2gd_step", step)]
    if fault == "nogather":
        # the client-sharded average gathers one payload per local client
        # (batched): without the gather they are this chip's alone
        return [(aggregation, "_gather_payloads",
                 lambda payload, axes, *, batched: payload)]
    raise ValueError(f"unknown fault {fault!r}")


@contextlib.contextmanager
def planting(fault, train):
    """``fault`` planted for the block (none for sound and control)."""
    plants = [] if fault in ("sound", "control") else planted(fault, train)
    saved = [(o, a, getattr(o, a)) for o, a, _ in plants]
    try:
        for o, a, fn in plants:
            setattr(o, a, fn)
        yield
    finally:
        for o, a, fn in saved:
            setattr(o, a, fn)


def readings(cell: dict, seeds, variants, out=None, protocol_key=None):
    """[{variant, seed, numbers}] for each variant and seed; each
    variant's jitted chunk is built once and reused across seeds."""
    from bench.kinds import train
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = copy.deepcopy(cell)
    if protocol_key is not None:
        cell["params"]["protocol_key"] = protocol_key
    rows = []
    for variant in variants:
        vcell = copy.deepcopy(cell)
        if variant == "control":
            vcell["config_spec"]["dtype"] = "bfloat16"
        roll = None
        with planting(variant, train):
            for seed in seeds:
                job = train.Job(vcell, seed, roll=roll)
                roll = job.roll
                state, prog = job.first_chunk()
                del state
                ref = train.reference_readings(job)
                nums = train.compare(prog, ref, job.d)
                row = {"cell": cell["name"], "variant": variant,
                       "seed": seed,
                       "protocol_key": cell["params"]["protocol_key"],
                       **nums, "protocol_mismatches": job.mismatches,
                       "agg_err": prog["agg_err"],
                       "ref_agg_err": ref["agg_err"],
                       "agg_corr": prog["agg_corr"],
                       "ref_agg_corr": ref["agg_corr"]}
                rows.append(row)
                print(json.dumps(row), flush=True)
                if out:
                    with open(out, "a") as f:
                        f.write(json.dumps(row) + "\n")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--protocol-key", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell["chips"])
    readings(cell, [int(s) for s in args.seeds.split(",")],
             args.variants.split(","), args.out, args.protocol_key)


if __name__ == "__main__":
    main()
