"""Client-sharded rollout benchmark (DESIGN.md §9): clients/sec of
``rollout_l2gd_sharded`` vs forced-host-device count and participation
fraction.

Device count is a process-level property (``XLA_FLAGS=
--xla_force_host_platform_device_count=N`` must be set before jax
initializes), so the harness spawns one WORKER SUBPROCESS per (devices,
participation) cell with the flag in its environment; each worker runs
the K-step sharded scan on a quadratic client problem, reports
clients/sec (client-steps per wall-second of one whole-rollout
dispatch) as a JSON line, and the parent merges every cell into
``BENCH_kernels.json`` (rows ``sharded_rollout_d{N}_p{f}``).

The d=1, participation=1.0 worker also asserts the engine's headline
invariant end-to-end: the sharded scan is bit-exact with the stacked
:func:`repro.core.rollout.rollout_l2gd` (the property
tests/test_sharded_rollout.py pins per codec).

Model size: DIM = 131072 per client (0.5 MB f32).  The original
16384-element model was dominated by the fixed per-collective overhead
of forced host devices, so adding a device could only lose; at 131072
the aggregation/gradient work the engine actually optimizes is the
bulk of a step — the regime the fused decode->reduce server
(DESIGN.md §10) targets.  Timing is best-of-``ITERS`` whole-rollout
dispatches (the 2-vCPU CI boxes are noisy; the minimum is the stable
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

DEVICE_COUNTS = (1, 2)
PARTICIPATIONS = (1.0, 0.5)
N_CLIENTS, DIM, STEPS = 8, 131072, 50


def _worker(n_devices: int, participation: float) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import device_info
    from repro.core import init_state, make_compressor, make_hyper
    from repro.core.rollout import rollout_l2gd, rollout_l2gd_sharded
    from repro.launch.mesh import make_client_mesh

    assert len(jax.devices()) >= n_devices, \
        (len(jax.devices()), "XLA_FLAGS not applied before jax init?")
    mesh = make_client_mesh(n_devices)
    comp = make_compressor("natural")
    hp = make_hyper(eta=0.3, lam=1.0, p=0.3, n=N_CLIENTS)
    batch = jax.random.normal(jax.random.PRNGKey(7), (N_CLIENTS, DIM))
    params = {"w": jnp.zeros((N_CLIENTS, DIM))}

    def grad_fn(p, b):
        g = p["w"] - b
        return 0.5 * jnp.sum(g ** 2), {"w": g}

    key = jax.random.PRNGKey(0)
    roll = jax.jit(functools.partial(
        rollout_l2gd_sharded, mesh=mesh, grad_fn=grad_fn, steps=STEPS,
        client_comp=comp, master_comp=comp, participation=participation,
        batch_axis=None))
    st0 = init_state(params)
    jax.block_until_ready(roll(key, st0, hp, batch))      # compile
    iters = 3
    dt = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jax.block_until_ready(roll(key, st0, hp, batch))
        dt = min(dt, time.perf_counter() - t0)
    final, trace = out

    if n_devices == 1 and participation == 1.0:
        ref, tr = jax.jit(functools.partial(
            rollout_l2gd, grad_fn=grad_fn, steps=STEPS, client_comp=comp,
            master_comp=comp, batch_axis=None))(key, st0, hp, batch)
        assert np.array_equal(np.asarray(ref.params["w"]),
                              np.asarray(final.params["w"])), \
            "sharded scan is not bit-exact with rollout_l2gd"
        assert np.array_equal(np.asarray(tr.xis), np.asarray(trace.xis))

    print(json.dumps({
        "clients_per_sec": round(N_CLIENTS * STEPS / dt, 1),
        "steps_per_sec": round(STEPS / dt, 1),
        # us of ONE whole-rollout dispatch — the shared results file's
        # us_per_call column keeps per-call semantics across benches
        "us_per_call": round(dt * 1e6, 1),
        "us_per_step": round(dt * 1e6 / STEPS, 1),
        "n_devices": n_devices, "participation": participation,
        "n_clients": N_CLIENTS, "dim": DIM, "steps": STEPS,
        "n_agg_comm": int(trace.n_agg_comm), "device": device_info(),
    }), flush=True)


def run() -> None:
    from benchmarks import common

    start = len(common.RESULTS)
    for ndev in DEVICE_COUNTS:
        for part in PARTICIPATIONS:
            env = dict(os.environ)
            # replace (not append) any inherited device-count flag —
            # e.g. from the CI sharded-smoke job's own XLA_FLAGS
            kept = [f for f in env.get("XLA_FLAGS", "").split()
                    if not f.startswith(
                        "--xla_force_host_platform_device_count")]
            env["XLA_FLAGS"] = " ".join(
                kept + [f"--xla_force_host_platform_device_count={ndev}"])
            env["PYTHONPATH"] = os.pathsep.join(
                p for p in [os.path.join(_ROOT, "src"), _ROOT,
                            env.get("PYTHONPATH", "")] if p)
            proc = subprocess.run(
                [sys.executable, "-m", "benchmarks.bench_sharded_rollout",
                 "--worker", str(ndev), str(part)],
                env=env, cwd=_ROOT, capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"sharded worker d{ndev} p{part} failed:\n{proc.stderr}")
            row = json.loads(proc.stdout.strip().splitlines()[-1])
            common.emit(
                f"sharded_rollout_d{ndev}_p{part}", row.pop("us_per_call"),
                f"clients/s={row['clients_per_sec']:.0f} "
                f"devices={ndev} participation={part} "
                f"agg_comm={row['n_agg_comm']}",
                device=row.pop("device"), **row)
    common.merge_json(_JSON, common.RESULTS[start:])


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        _worker(int(sys.argv[2]), float(sys.argv[3]))
    else:
        run()
