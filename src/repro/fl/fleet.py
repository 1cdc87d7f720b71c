"""Heterogeneous fleets: per-cohort compression plans over one federation.

Every engine in this repo used to bind ONE :class:`~repro.core.codec.
CompressionPlan` for all n clients.  A real fleet mixes phones on LTE
with desktops on fiber, so the paper's "various compression techniques"
(§VII) must be able to coexist inside a single federation.  A
:class:`FleetPlan` is the static recipe for that: a small table of
cohort plans plus a per-client cohort assignment.  It is pure Python
configuration (like :class:`~repro.core.codec.CompressionPlan` itself)
— never a pytree, never traced.

Call-site contract (DESIGN.md §13):

  * :func:`as_fleet_plan` promotes a single plan (or plain compressor)
    to a one-cohort fleet, so every existing call site keeps working
    unchanged.
  * :func:`resolve_uplink` is the coercion every engine entry point
    applies to its ``client_comp`` argument: plain compressors/plans
    become a :class:`~repro.core.codec.CompressionPlan` via ``as_plan``;
    a UNIFORM fleet (every client in one cohort) unwraps to its single
    plan — the engines then compile the literal single-plan graph, so
    the uniform-fleet keystone (bit-exactness with the historic path) is
    structural, not numerical; only a genuinely MIXED fleet flows
    through the per-cohort code paths.
  * The ledger charges per-client wire costs from
    :meth:`FleetPlan.round_bits` — ``round_bits_vector()`` feeds
    :meth:`repro.fl.ledger.BitsLedger.replay_xi_trace` directly.

Mixed-fleet aggregation (the cohort-grouped fused reduce): clients are
grouped by cohort with STATIC index sets (the assignment is config, so
the grouping is resolved at trace time — no dynamic gather by cohort
id).  Each flat/packed cohort encodes its members with a ``vmap`` of its
own plan and folds them on the existing O(d) accumulator
(:func:`repro.core.flatbuf.reduce_payload_acc`); leafwise cohorts take
the masked weighted-sum path.  The per-cohort partial sums — each an
O(d) one-model f32 tree — are added and divided by the total
participant weight ONCE, so the mixed mean is a single renormalization
over cohort partial sums (``sum_c sum_{i in c} w_i C_i(x_i) / sum w``),
exactly the semantics of the single-plan masked mean.

This module imports only ``repro.core.codec``/``flatbuf``/``aggregation``
machinery; the core engines import IT lazily (function-local), because a
top-level ``repro.fl`` import from inside ``repro.core``'s own package
initialization would close the established core<->fl cycle (the same
rule as ``l2gd_driver``'s lazy async-engine import).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import CompressionPlan, as_plan

__all__ = ["FleetPlan", "as_fleet_plan", "fleet_from_plans",
           "resolve_uplink", "cohort_label",
           "CohortBatch", "fleet_encode", "fleet_finite_mask",
           "fleet_weighted_sum", "fleet_mean"]


def cohort_label(plan: CompressionPlan) -> str:
    """Short deterministic label of one cohort's plan (bench row names,
    ``models_per_gb`` cohort keys): codec name, qsgd levels, and an ``n``
    suffix for the narrow sub-byte wire."""
    comp = plan.codec
    name = getattr(comp, "name", type(comp).__name__.lower())
    levels = getattr(comp, "levels", None)
    if name == "qsgd" and levels is not None:
        name = f"qsgd{levels}"
    if getattr(plan, "narrow", False):
        name += "n"
    return name


@dataclasses.dataclass(frozen=True, eq=False)
class FleetPlan:
    """Cohort → :class:`CompressionPlan` table + static per-client
    assignment.

    ``cohorts`` is a tuple of plans; ``assignment[i]`` is client i's
    cohort id (so ``len(assignment)`` is the fleet size n).  The
    assignment is static configuration: engines group clients by cohort
    at trace time.  ``names`` optionally labels cohorts for reporting
    (defaults to :func:`cohort_label` of each plan).
    """

    cohorts: Tuple[CompressionPlan, ...]
    assignment: Tuple[int, ...]
    names: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        if not self.cohorts:
            raise ValueError("FleetPlan needs at least one cohort plan")
        for c, p in enumerate(self.cohorts):
            if not isinstance(p, CompressionPlan):
                raise TypeError(f"cohort {c} is not a CompressionPlan: "
                                f"{p!r} (coerce with repro.core.codec."
                                "as_plan / make_plan)")
        object.__setattr__(self, "cohorts", tuple(self.cohorts))
        assignment = tuple(int(a) for a in self.assignment)
        for i, a in enumerate(assignment):
            if not 0 <= a < len(self.cohorts):
                raise ValueError(f"client {i} assigned to cohort {a}; "
                                 f"have {len(self.cohorts)} cohorts")
        object.__setattr__(self, "assignment", assignment)
        if self.names is not None:
            names = tuple(str(s) for s in self.names)
            if len(names) != len(self.cohorts):
                raise ValueError(f"{len(names)} names for "
                                 f"{len(self.cohorts)} cohorts")
            object.__setattr__(self, "names", names)

    # -- shape ---------------------------------------------------------------
    @property
    def n_clients(self) -> int:
        return len(self.assignment)

    @property
    def n_cohorts(self) -> int:
        return len(self.cohorts)

    @property
    def used_cohorts(self) -> Tuple[int, ...]:
        """Cohort ids with at least one assigned client, ascending — the
        STATIC grouping order of every mixed-fleet fold (cohort partial
        sums are added in this order on every engine)."""
        return tuple(sorted(set(self.assignment)))

    @property
    def is_uniform(self) -> bool:
        """True when every client lives in one cohort — the keystone
        case that unwraps to the single-plan path bit-exactly."""
        return len(set(self.assignment)) <= 1

    @property
    def uniform_plan(self) -> CompressionPlan:
        """The single plan of a uniform fleet (an empty fleet reports
        cohort 0's)."""
        if not self.is_uniform:
            raise ValueError("mixed fleet has no single uniform plan; "
                             "check FleetPlan.is_uniform first")
        return self.cohorts[self.assignment[0] if self.assignment else 0]

    # -- lookups -------------------------------------------------------------
    def cohort_of(self, client: int) -> int:
        return self.assignment[client]

    def plan_for(self, client: int) -> CompressionPlan:
        return self.cohorts[self.assignment[client]]

    def clients_of(self, cohort: int) -> Tuple[int, ...]:
        """Static, ascending client indices of one cohort."""
        return tuple(i for i, a in enumerate(self.assignment) if a == cohort)

    def cohort_name(self, cohort: int) -> str:
        if self.names is not None:
            return self.names[cohort]
        return cohort_label(self.cohorts[cohort])

    @property
    def mix(self) -> str:
        """Deterministic mix label of the used cohorts (bench row names:
        ``fleet_<mix>_n<n>``), e.g. ``identity-natural-qsgd4n``."""
        return "-".join(self.cohort_name(c) for c in self.used_cohorts)

    # -- binding / accounting -------------------------------------------------
    def bind(self, params) -> "FleetPlan":
        """Bind every cohort plan to one model's shapes (enables
        ``round_bits``); accepts arrays or ShapeDtypeStructs."""
        return dataclasses.replace(
            self, cohorts=tuple(p.bind(params) for p in self.cohorts))

    def round_bits(self, client: int) -> float:
        """Exact wire bits of ONE message from ``client`` — the number
        the fleet-aware ledger charges per client (DESIGN.md §13)."""
        return self.plan_for(client).round_bits()

    def round_bits_vector(self) -> Tuple[float, ...]:
        """Per-client ``round_bits`` as a length-n tuple — the
        ``uplink_bits`` argument of :meth:`repro.fl.ledger.BitsLedger.
        replay_xi_trace`.  Cohort costs are evaluated once each."""
        per_cohort = {c: self.cohorts[c].round_bits()
                      for c in self.used_cohorts}
        return tuple(per_cohort[a] for a in self.assignment)

    def total_round_bits(self) -> float:
        """Σ_i round_bits(i): one full-participation round's uplink
        total — the conservation quantity the mixed-fleet keystone pins
        and the controller's budget constraint measures."""
        return float(sum(self.round_bits_vector()))


def as_fleet_plan(plan_or_fleet, n_clients: int, params=None) -> FleetPlan:
    """Promote a single plan/compressor to a one-cohort fleet of
    ``n_clients`` (existing call sites keep working); an existing
    :class:`FleetPlan` is size-checked and returned (bound to ``params``
    when given)."""
    if isinstance(plan_or_fleet, FleetPlan):
        if plan_or_fleet.n_clients != int(n_clients):
            raise ValueError(f"fleet covers {plan_or_fleet.n_clients} "
                             f"clients, expected {n_clients}")
        return plan_or_fleet.bind(params) if params is not None \
            else plan_or_fleet
    plan = as_plan(plan_or_fleet, params=params)
    return FleetPlan(cohorts=(plan,), assignment=(0,) * int(n_clients))


def _plan_key(plan: CompressionPlan):
    """Structural identity of a plan for cohort dedup: codec (frozen
    dataclass — field-wise equality/hash), transport, bucket, narrow.
    ``specs`` is deliberately excluded: two copies of one recipe bound to
    the same model are the same cohort."""
    return (plan.codec, plan.transport, plan.bucket, plan.narrow)


def fleet_from_plans(plans) -> FleetPlan:
    """Build a :class:`FleetPlan` from a length-n PER-CLIENT plan vector
    (ROADMAP fleet headroom: a singleton cohort per client).

    Structurally equal plans (same codec fields, transport, bucket,
    narrow — :func:`_plan_key`) dedupe into ONE cohort, so the vector
    form is bit-exact with manual cohort grouping BY CONSTRUCTION: n
    copies of one plan become the uniform one-cohort fleet (which
    :func:`resolve_uplink` unwraps to the literal single-plan path), and
    clients sharing a recipe always fold inside the same cohort partial
    sum — f32 association order never forks between the two spellings.
    Genuinely distinct plans keep one cohort each (true per-client
    compression).  Entries may be plans or plain compressors
    (``as_plan`` coercion)."""
    plans = [as_plan(p) for p in plans]
    if not plans:
        raise ValueError("fleet_from_plans needs at least one plan")
    cohorts, assignment, seen = [], [], {}
    for p in plans:
        k = _plan_key(p)
        if k not in seen:
            seen[k] = len(cohorts)
            cohorts.append(p)
        assignment.append(seen[k])
    return FleetPlan(cohorts=tuple(cohorts), assignment=tuple(assignment))


def resolve_uplink(comp, transport: Optional[str] = None):
    """The plan-or-fleet coercion every engine entry point applies to its
    uplink argument: plain compressors/plans -> ``as_plan`` (historic
    behaviour, including the deprecated-transport shim), uniform fleets
    -> their single plan (the keystone unwrap: the engine compiles the
    literal single-plan graph), mixed fleets -> the fleet itself.  A
    length-n SEQUENCE of plans is a per-client plan vector
    (:func:`fleet_from_plans`): dedupe into cohorts, then the same
    uniform/mixed rule."""
    if isinstance(comp, (list, tuple)):
        comp = fleet_from_plans(comp)
    if isinstance(comp, FleetPlan):
        if comp.is_uniform:
            return comp.uniform_plan
        return comp
    return as_plan(comp, transport)


# ---------------------------------------------------------------------------
# mixed-fleet aggregation: cohort-grouped encode + fold
# ---------------------------------------------------------------------------

class CohortBatch(NamedTuple):
    """One cohort's encoded contribution to a round, grouped at trace
    time by the static assignment.

    ``kind`` selects the fold: ``"fused"`` carries the cohort's stacked
    sanitized wire payload (flat/packed plans — folded on the O(d)
    accumulator), ``"tree"`` the cohort's stacked decoded contribution
    tree (leafwise plans — folded by the NaN-safe weighted sum).
    ``idx`` is the cohort's static client-index tuple; ``fin`` its
    (len(idx),) finite-client mask."""

    cohort: int
    idx: Tuple[int, ...]
    kind: str
    data: Any
    fin: jax.Array


def fleet_encode(fleet: FleetPlan, client_keys, params_stacked):
    """Encode a client-stacked pytree under a mixed fleet: one
    :class:`CohortBatch` per used cohort.

    ``client_keys`` is the synchronous engines' own per-client key
    schedule ``split(k_clients, n)`` — client i uses ``client_keys[i]``
    under ``fleet.plan_for(i)``, so the randomness a client sees is
    independent of which cohort the rest of the fleet landed in.
    Flat/packed cohorts are encoded with a ``vmap`` of their plan and
    sanitized mask-and-count style (:func:`repro.core.flatbuf.
    sanitize_payload`); leafwise cohorts apply per client (encode→decode
    == apply) and mask via :func:`repro.core.aggregation.
    stacked_finite_mask`."""
    from repro.core import flatbuf
    from repro.core.aggregation import stacked_finite_mask
    batches = []
    for c in fleet.used_cohorts:
        plan = fleet.cohorts[c]
        idx = fleet.clients_of(c)
        ia = jnp.asarray(idx, jnp.int32)
        keys_c = client_keys[ia]
        sub = jax.tree_util.tree_map(lambda a: a[ia], params_stacked)
        if plan.transport in ("flat", "packed"):
            payload = flatbuf.encode_clients(plan, keys_c, sub)
            fin = flatbuf.payload_finite_mask(payload)
            payload = flatbuf.sanitize_payload(payload, fin)
            batches.append(CohortBatch(c, idx, "fused", payload, fin))
        else:
            contrib = jax.vmap(lambda k, p: plan.apply(k, p))(keys_c, sub)
            fin = stacked_finite_mask(contrib)
            batches.append(CohortBatch(c, idx, "tree", contrib, fin))
    return batches


def fleet_finite_mask(batches, n: int) -> jax.Array:
    """(n,) 0/1 float32 over the whole fleet: scatter each cohort's
    finite mask back to global client indices (every client is in
    exactly one cohort, so the scatter is a partition)."""
    fin = jnp.zeros((n,), jnp.float32)
    for b in batches:
        fin = fin.at[jnp.asarray(b.idx, jnp.int32)].set(b.fin)
    return fin


def fleet_weighted_sum(batches, weights: jax.Array):
    """``sum_c sum_{i in c} w_i * decode_i`` as ONE one-model float32
    pytree: fused cohorts fold on the O(d) accumulator
    (:func:`~repro.core.flatbuf.reduce_payload_acc` — no per-client
    dequantized buffer), leafwise cohorts on the NaN-safe weighted
    client sum.  Cohort partial sums are added in ``used_cohorts``
    order (ascending cohort id) on every engine — the deterministic
    grouping rule of DESIGN.md §13.  ``weights`` is the GLOBAL (n,)
    weight vector; each cohort takes its static slice."""
    from repro.core import flatbuf
    from repro.core.aggregation import weighted_client_sum
    total = None
    for b in batches:
        w_c = weights[jnp.asarray(b.idx, jnp.int32)]
        if b.kind == "fused":
            layout = b.data.layout
            acc = flatbuf.reduce_payload_acc(b.data, w_c)
            part = flatbuf.unravel(
                layout, flatbuf.unbucketize(acc, layout.d))
        else:
            part = weighted_client_sum(b.data, w_c)
        part = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), part)
        total = part if total is None else jax.tree_util.tree_map(
            jnp.add, total, part)
    return total


def fleet_mean(fleet: FleetPlan, client_keys, params_stacked, mask=None):
    """The mixed-fleet masked mean ``sum_i m_i C_i(x_i) / sum_i m_i``
    over per-cohort plans — the uplink half of the paper's exchange with
    heterogeneous C_i (the downlink C_M is the caller's, unchanged).

    Semantics mirror the single-plan :func:`repro.core.flatbuf.
    reduce_payload_mean` exactly: non-finite clients are excluded from
    numerator AND denominator (mask-and-count), an empty support clamps
    the denominator to 1 (zeros-tree mean), and the result is cast back
    to the parameter dtypes.  The accumulation is f32 throughout with
    ONE division by the total weight (not per cohort), so cohort
    grouping changes the mean only by f32 association order."""
    n = fleet.n_clients
    with jax.named_scope("uplink_encode"):
        batches = fleet_encode(fleet, client_keys, params_stacked)
    with jax.named_scope("server_reduce"):
        fin = fleet_finite_mask(batches, n)
        if mask is None:
            w = fin
        else:
            w = mask.reshape(-1).astype(jnp.float32) * fin
        denom = jnp.sum(w)
        safe = jnp.where(denom > 0, denom, 1.0)
        total = fleet_weighted_sum(batches, w)
        return jax.tree_util.tree_map(
            lambda s, a: (s / safe).astype(a.dtype), total, params_stacked)
