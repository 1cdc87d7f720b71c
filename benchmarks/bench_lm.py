"""Federated LM training benchmark (DESIGN.md §15): tokens/sec of the
2-D (clients x model) mesh engine vs the 1-D f32 lockstep baseline.

Device count is a process-level property (``XLA_FLAGS=
--xla_force_host_platform_device_count=N`` before jax init), so the
harness spawns one WORKER SUBPROCESS per cell; each worker runs the
K-step rollout of a reduced stablelm on its mesh and reports tokens/sec
(gradient-pass tokens per wall-second of one whole-rollout dispatch,
``repro.launch.train.tokens_processed``) as a JSON line.  Rows merge
into ``BENCH_kernels.json`` as ``lm_tokens_per_s_{cell}``.

Cells:
  1d_f32_lockstep  -- (1,1) mesh, f32, local_steps=1: the baseline.  This
                      worker ALSO asserts the §15 keystone end-to-end —
                      the 2-D engine's (1,1)-mesh graph is bit-exact with
                      the existing stacked engine (build_rollout_fn) —
                      and it runs FIRST, so no row is emitted unless the
                      keystone holds.
  1d_bf16_h4       -- (1,1) mesh, bf16 params+compute, local_steps=4
  2d_bf16_h4       -- (1,2) mesh (2 model shards), bf16, local_steps=4:
                      the headline config; run() asserts it beats the
                      baseline on tokens/sec.  H=4 amortizes the
                      per-protocol-step overhead over 4 gradient passes
                      (the LoCoDL effect the bench exists to show).

The xi stream is keyed by global step (module contract, core/rollout.py)
so every cell realizes the SAME protocol trace — tokens/sec differences
are engine differences, not luck of the draw.  Timing is best-of-ITERS
whole-rollout dispatches (CI boxes are noisy; the minimum is the stable
statistic).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_JSON = os.path.join(_ROOT, "BENCH_kernels.json")

#: (cell, n_devices, model_shards, dtype, local_steps, keystone)
CELLS = (
    ("1d_f32_lockstep", 1, 1, "float32", 1, True),
    ("1d_bf16_h4", 1, 1, "bfloat16", 4, False),
    ("2d_bf16_h4", 2, 2, "bfloat16", 4, False),
)
N_CLIENTS, BATCH, SEQ, STEPS, ITERS = 2, 2, 64, 16, 3
BASELINE, HEADLINE = "1d_f32_lockstep", "2d_bf16_h4"


def _arch(dtype: str):
    import dataclasses

    from repro.configs.base import get_config
    return dataclasses.replace(
        get_config("stablelm-1.6b").reduced(),
        n_layers=2, d_model=128, d_ff=512, n_heads=4, n_kv_heads=4,
        vocab_size=1024, head_dim=None, param_dtype=dtype,
        compute_dtype=dtype)


def _worker(cell: str, n_devices: int, model_shards: int, dtype: str,
            local_steps: int, keystone: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import device_info
    from repro.core import init_state, make_compressor, make_hyper
    from repro.data import TokenStream
    from repro.launch.mesh import make_train_mesh
    from repro.launch.steps import build_rollout_fn, build_sharded_rollout_fn
    from repro.launch.train import tokens_processed
    from repro.models import init_params

    assert len(jax.devices()) >= n_devices, \
        (len(jax.devices()), "XLA_FLAGS not applied before jax init?")
    cfg = _arch(dtype)
    hp = make_hyper(eta=0.1, lam=0.5, p=0.25, n=N_CLIENTS)
    comp = make_compressor("natural")
    ts = TokenStream(n_clients=N_CLIENTS, vocab=cfg.vocab_size, batch=BATCH,
                     seq=SEQ, seed=0)
    batches = {"tokens": jnp.stack(
        [jnp.asarray(ts.batch_at(k)) for k in range(STEPS)])}
    keys = jax.random.split(jax.random.PRNGKey(0), N_CLIENTS)
    params = jax.vmap(lambda k: init_params(k, cfg))(keys)
    key_data = jax.random.key_data(jax.random.PRNGKey(42))

    mesh = make_train_mesh(model_shards=model_shards)
    roll = build_sharded_rollout_fn(
        cfg, hp, mesh=mesh, client_comp=comp, master_comp=comp,
        length=STEPS, local_steps=local_steps, donate=False)
    st0 = init_state(params)
    out = jax.block_until_ready(roll(st0, batches, key_data))   # compile
    dt = float("inf")
    for _ in range(ITERS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(roll(st0, batches, key_data))
        dt = min(dt, time.perf_counter() - t0)
    final, trace = out

    if keystone:
        # §15 keystone: the 2-D engine on a (1,1) mesh IS the stacked
        # engine — bit-exact final params and identical xi trace
        ref_roll = build_rollout_fn(cfg, hp, client_comp=comp,
                                    master_comp=comp, length=STEPS,
                                    local_steps=local_steps, donate=False)
        ref, rtr = jax.block_until_ready(
            ref_roll(init_state(params), batches, key_data))
        for a, b in zip(jax.tree.leaves(ref.params),
                        jax.tree.leaves(final.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                "2-D engine on (1,1) mesh is not bit-exact with the " \
                "stacked engine"
        assert np.array_equal(np.asarray(rtr.xis), np.asarray(trace.xis))

    n_local = int(trace.n_local)
    n_agg = int(trace.n_agg_comm) + int(trace.n_agg_cached)
    toks = tokens_processed(n_local, n_agg, local_steps, N_CLIENTS, BATCH,
                            SEQ)
    print(json.dumps({
        "tokens_per_sec": round(toks / dt, 1),
        "steps_per_sec": round(STEPS / dt, 2),
        # us of ONE whole-rollout dispatch (shared-column semantics)
        "us_per_call": round(dt * 1e6, 1),
        "n_devices": n_devices, "model_shards": model_shards,
        "dtype": dtype, "local_steps": local_steps,
        "n_clients": N_CLIENTS, "batch": BATCH, "seq": SEQ, "steps": STEPS,
        "n_local": n_local, "n_agg": n_agg, "device": device_info(),
    }), flush=True)


def run() -> None:
    from benchmarks import common

    start = len(common.RESULTS)
    rows = {}
    for cell, ndev, shards, dtype, h, keystone in CELLS:
        env = dict(os.environ)
        # replace (not append) any inherited device-count flag
        kept = [f for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith(
                    "--xla_force_host_platform_device_count")]
        env["XLA_FLAGS"] = " ".join(
            kept + [f"--xla_force_host_platform_device_count={ndev}"])
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in [os.path.join(_ROOT, "src"), _ROOT,
                        env.get("PYTHONPATH", "")] if p)
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_lm", "--worker",
             cell, str(ndev), str(shards), dtype, str(h),
             str(int(keystone))],
            env=env, cwd=_ROOT, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(f"lm worker {cell} failed:\n{proc.stderr}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        rows[cell] = row
        common.emit(
            f"lm_tokens_per_s_{cell}", row.pop("us_per_call"),
            f"tokens/s={row['tokens_per_sec']:.0f} shards={shards} "
            f"dtype={dtype} H={h} agg={row['n_agg']}",
            device=row.pop("device"), **row)
    base = rows[BASELINE]["tokens_per_sec"]
    head = rows[HEADLINE]["tokens_per_sec"]
    if head <= base:
        raise RuntimeError(
            f"2-D mesh headline regression: {HEADLINE} "
            f"{head:.0f} tokens/s <= {BASELINE} {base:.0f} tokens/s")
    print(f"# lm headline: {HEADLINE} {head:.0f} tokens/s vs {BASELINE} "
          f"{base:.0f} tokens/s ({head / base:.2f}x)", flush=True)
    common.merge_json(_JSON, common.RESULTS[start:])


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        _worker(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
                sys.argv[5], int(sys.argv[6]), bool(int(sys.argv[7])))
    else:
        run()
