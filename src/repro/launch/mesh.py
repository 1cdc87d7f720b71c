"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — smoke tests must keep seeing one
CPU device; only dryrun.py sets the 512-placeholder-device XLA flag.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256-class).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the FL client axis is
(pod, data) = 32 clients, so the aggregation collective spans the
inter-pod links — exactly the regime the paper's compression targets.

Client-sharded rollout (DESIGN.md §9): :func:`make_client_mesh` builds a
1-D mesh over a dedicated ``clients`` axis — the layout of
``repro.core.rollout.rollout_l2gd_sharded``, where each device holds
n/n_devices whole personalized models (no model parallelism) and the
aggregation branch's payload all_gather is the only cross-device
traffic.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_client_mesh",
           "make_train_mesh", "client_axes", "n_clients_of",
           "model_shards_of"]


def make_mesh(shape, axes, devices):
    """``jax.make_mesh`` over ``devices`` with every axis
    ``AxisType.Auto`` (GSPMD-propagated shardings) — the one mesh
    constructor of the repo; tests use it too."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, clients: int = None,
                         model: int = None):
    """The 2-D training mesh: a ``(clients, model)``-style axis pair.

    Default shapes keep the historic ``("data", "model")`` naming —
    (16, 16) single pod, (2, 16, 16) multi-pod, where the FL client axis
    is ``("pod", "data")``.  Passing ``clients=``/``model=`` instead
    builds an explicit ``("clients", "model")`` mesh of that shape (the
    2-D engine layout, DESIGN.md §15): each of the ``clients`` rows holds
    a client subset whose personalized models are FSDP-style sharded over
    its ``model`` columns."""
    if clients is not None or model is not None:
        c = int(clients or 1)
        m = int(model or 1)
        if multi_pod:
            raise ValueError("multi_pod composes the pod axis with the "
                             "default data x model shape; pass clients=/"
                             "model= without multi_pod")
        return make_mesh((c, m), ("clients", "model"),
                                jax.devices()[:c * m])
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return make_mesh(shape, axes, jax.devices()[:n])


def make_train_mesh(clients: int = None, model_shards: int = 1):
    """The 2-D ``(clients, model)`` mesh of the LM training engine
    (DESIGN.md §15); ``model_shards=1`` degenerates to the column-free
    layout that is bit-exact with :func:`make_client_mesh` rollouts.
    ``clients=None`` uses every visible device divided by
    ``model_shards``."""
    devices = jax.devices()
    m = int(model_shards)
    if m < 1:
        raise ValueError(f"model_shards must be >= 1, got {model_shards}")
    c = (len(devices) // m) if clients is None else int(clients)
    if c * m > len(devices):
        raise ValueError(f"mesh ({c} clients x {m} model shards) needs "
                         f"{c * m} devices, have {len(devices)}")
    return make_mesh((c, m), ("clients", "model"), devices[:c * m])


def make_client_mesh(n_shards: int = None):
    """1-D mesh over the dedicated ``clients`` axis (DESIGN.md §9) for
    the client-sharded rollout engine; defaults to every visible device.
    Force N host devices for CPU scaling runs with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    jax import; see benchmarks/bench_sharded_rollout.py)."""
    devices = jax.devices()
    n = len(devices) if n_shards is None else int(n_shards)
    return make_mesh((n,), ("clients",), devices[:n])


def client_axes(mesh) -> tuple:
    """Mesh axes that together form the FL client axis."""
    if "clients" in mesh.axis_names:
        return ("clients",)
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_clients_of(mesh) -> int:
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_shards_of(mesh) -> int:
    """Size of the ``model`` axis (1 when the mesh has none) — the 2-D
    engine's switch between the pure client-sharded path and FSDP-style
    param sharding."""
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
