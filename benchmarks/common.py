"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import logreg_loss_and_grad, make_logreg_data

# machine-readable record of every emit() since process start; run.py
# serializes it with --json, bench_kernels.py snapshots its own slice
# into BENCH_kernels.json
RESULTS: list = []


def bench_json_path() -> str:
    """Repo-root BENCH_kernels.json — the shared perf-trajectory record
    every bench merges its rows into and ``run.py --check`` reads as the
    regression baseline."""
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_kernels.json")


def timed(fn, *args, warmup: int = 1, iters: int = 5):
    """us per call after warmup — the MINIMUM over ``iters`` calls (CPU
    wall time on small shared boxes swings +-20% call to call; the min
    is the stable statistic for relative comparisons of the jnp paths.
    TPU is the deployment target)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, out


def scenario_name(prefix: str, *parts) -> str:
    """Row name for a multi-scenario bench: ``prefix`` + one ``_``-joined
    segment per distinguishing part (cohort mix, client count, ...), e.g.
    ``scenario_name("fleet", "identity-natural-qsgd4n", "n8")`` ->
    ``fleet_identity-natural-qsgd4n_n8``.  Names key the
    BENCH_kernels.json baselines ``run.py --check`` compares against, so
    every scenario a bench emits MUST land on a distinct name — two
    scenarios sharing a name silently overwrite each other's baseline
    (and :func:`emit` warns when a run re-emits one)."""
    segs = [str(prefix)] + [str(p) for p in parts if p not in (None, "")]
    return "_".join(segs)


def device_info() -> dict:
    """The devices this process runs on, as JAX reports them — the
    ``device`` record of every row it emits."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def emit(name: str, us_per_call: float, derived, *, device: dict = None,
         **extra) -> None:
    """Print and record one row.  ``device`` is the record of the
    process that measured it — a worker subprocess passes its own, so a
    parent that only spawns workers never starts a JAX backend; by
    default it is this process's :func:`device_info`."""
    if any(r["name"] == name for r in RESULTS):
        print(f"[warn] duplicate bench row name {name!r}: this row will "
              "shadow the earlier one in the --check baseline; add the "
              "distinguishing scenario parts via scenario_name()",
              flush=True)
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
    RESULTS.append({"name": name, "us_per_call": round(us_per_call, 1),
                    "derived": str(derived),
                    "device": device or device_info(), **extra})


def write_json(path: str, results=None) -> None:
    with open(path, "w") as f:
        json.dump(RESULTS if results is None else results, f, indent=1)
    print(f"[json] wrote {len(RESULTS if results is None else results)} "
          f"rows to {path}", flush=True)


def merge_json(path: str, rows) -> None:
    """Refresh ``rows`` in a shared results file by name, preserving rows
    other benches recorded (BENCH_kernels.json carries both the kernel
    microbench and the rollout-engine rows, whichever ran last)."""
    import os
    existing = []
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
    names = {r["name"] for r in rows}
    write_json(path, [r for r in existing if r["name"] not in names]
               + list(rows))


def logreg_setup(n_clients: int = 5, heterogeneity: float = 1.0, seed: int = 0):
    data = make_logreg_data(n_clients=n_clients, heterogeneity=heterogeneity,
                            seed=seed)
    X, Y = jnp.asarray(data.features), jnp.asarray(data.labels)

    def grad_fn(p, b):
        loss, g = logreg_loss_and_grad(p["w"], b[0], b[1], 0.01)
        return loss, {"w": g}

    def mean_loss(w_stacked):
        return float(np.mean([
            logreg_loss_and_grad(jnp.asarray(w_stacked)[i], X[i], Y[i])[0]
            for i in range(n_clients)]))

    def mean_loss_global(w):
        return float(np.mean([logreg_loss_and_grad(w, X[i], Y[i])[0]
                              for i in range(n_clients)]))

    return X, Y, grad_fn, mean_loss, mean_loss_global
