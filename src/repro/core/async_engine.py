"""Arrival-ordered aggregation engine with deterministic fault injection.

Every other engine in this repo (stacked, sharded, host) steps all n
clients in lockstep: a communication round completes instantly with
every payload present.  A real fleet has stragglers, dropped uplinks and
clients that go dark mid-round.  This engine simulates that chaos ON
DEVICE, inside the same ``lax.scan`` protocol skeleton, with every fault
drawn from a fourth threefry stream of the existing determinism contract
(:mod:`repro.fl.faults`) — a faulty run is a pure function of
``(key, FaultPlan)`` and replays bit-for-bit.

Round model (DESIGN.md §11).  A communication round r opens on every
fresh-communication step (protocol branch 1).  Each alive participant
sends its compressed payload with a drawn integer latency; arrival order
is ``(latency, client index)`` — the same index order the fused reduce
folds clients in.  The server completes the round once the first
``q = FaultPlan.quorum_count(s)`` arrivals have reported:

  * the quorum cohort folds NOW, weight ``staleness_decay ** 0 = 1``;
  * stragglers (rank >= q) land at round ``r + max(latency, 1)`` with
    staleness weight ``staleness_decay ** delay``, held in a bounded
    ring buffer of ``max_delay + 1`` slots (slot = landing round mod
    slots) as ALREADY-WEIGHTED O(d) accumulator sums — the buffer never
    stores per-client payloads;
  * payloads that would land more than ``max_delay`` rounds late are
    EVICTED at send time (counted, never folded); dropped uplinks are
    lost in transit; crashed clients neither send nor receive (their
    aggregation update is masked out, and the broadcast target they
    miss is the shared cache — per-client cache divergence is not
    modeled, see §11).

The round's target is the staleness-weighted mean over everything that
landed — quorum cohort plus the slot's matured stragglers — renormalized
by the realized weight total (graceful degradation: the mean never
divides by zero; a round where nothing lands falls back to the cached
target).  Non-finite payloads are excluded mask-and-count exactly as in
:func:`repro.core.flatbuf.reduce_payload_mean`.

Keystone invariant (test-enforced, tests/test_async_engine.py): with
``FaultPlan.is_null`` — zero latency, zero drops/crashes, quorum = 1.0 —
:func:`rollout_l2gd_async` is BIT-EXACT with :func:`repro.core.rollout.
rollout_l2gd` for every codec/transport, forced xi traces and partial
participation: every fault weight degenerates to an exact 0.0/1.0
multiply, the delay buffer only ever adds exact zeros, and the key
schedule (``split(k_clients, n)`` / shared ``k_master``) is the
synchronous engine's own.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (_resolve_uplink, masked_client_mean,
                                    stacked_finite_mask, weighted_client_sum)
from repro.core.codec import (CompressionPlan, NarrowQSGDPayload,
                              QSGDPayload, as_plan)
from repro.core.compressors import Identity
from repro.core.l2gd import (L2GDHyper, L2GDState, aggregation_update,
                             draw_xi, local_update)
from repro.core.rollout import (_rollout_length, participant_count,
                                participation_masks)
from repro.fl.faults import FaultPlan, fault_draws

__all__ = ["AsyncAggState", "AsyncRolloutTrace", "EVENT_FIELDS",
           "init_async_state", "rollout_l2gd_async", "fault_totals",
           "agg_state_to_tree", "agg_state_from_tree"]

#: columns of ``AsyncRolloutTrace.events`` (K, 8) int32, per step:
#:   sent      — alive participants that transmitted this round
#:   delivered — sent payloads the server eventually folds (fresh or
#:               buffered; excludes dropped / evicted / rejected)
#:   dropped   — sent payloads lost in transit
#:   evicted   — sent payloads landing > max_delay rounds late
#:   crashed   — participants offline this round (never sent)
#:   fresh     — payloads folded THIS round at staleness 0 (quorum cohort)
#:   stale     — buffered straggler payloads folded THIS round
#:   rejected  — deliverable payloads excluded by the finite guard
#: Conservation: sent == delivered + dropped + evicted + rejected.
EVENT_FIELDS = ("sent", "delivered", "dropped", "evicted", "crashed",
                "fresh", "stale", "rejected")


class AsyncAggState(NamedTuple):
    """The server's carry across communication rounds.

    ``buf`` holds ALREADY-WEIGHTED contribution sums per future landing
    round — one (n_buckets, bucket) f32 accumulator per slot for the
    fused transports, a pytree of one-model f32 leaves per slot for the
    leafwise transport — so buffer memory is O(slots * d), independent
    of n.  Slot ``r mod n_slots`` matures when round r completes."""

    buf: Any            # (n_slots, ...) weighted pending contributions
    buf_w: jax.Array    # (n_slots,) f32  — pending staleness-weight total
    buf_cnt: jax.Array  # (n_slots,) int32 — pending payload count
    rnd: jax.Array      # () int32 — communication round counter


class AsyncRolloutTrace(NamedTuple):
    """:class:`repro.core.rollout.RolloutTrace` plus the fault record."""

    losses: jax.Array       # (K,) f32 mean client loss, pre-update params
    xis: jax.Array          # (K,) int32 xi_k realization
    branches: jax.Array     # (K,) int32 protocol branch (0/1/2)
    n_local: jax.Array      # () int32
    n_agg_comm: jax.Array   # () int32
    n_agg_cached: jax.Array  # () int32
    events: jax.Array       # (K, 8) int32 — EVENT_FIELDS columns


def fault_totals(trace: AsyncRolloutTrace) -> dict:
    """Host-side {event: total count} summary of a trace (the driver's
    ``L2GDRun.fault_stats``)."""
    ev = np.asarray(trace.events)
    return {name: int(ev[:, i].sum()) for i, name in enumerate(EVENT_FIELDS)}


def agg_state_to_tree(agg: AsyncAggState) -> dict:
    """:class:`AsyncAggState` as a plain dict pytree (checkpoint form).
    ``rnd`` is the round clock slot indices are computed modulo, so a
    restored buffer matures stragglers on exactly the original rounds."""
    return {"buf": agg.buf, "buf_w": agg.buf_w, "buf_cnt": agg.buf_cnt,
            "rnd": agg.rnd}


def agg_state_from_tree(tree: dict) -> AsyncAggState:
    return AsyncAggState(buf=tree["buf"],
                         buf_w=jnp.asarray(tree["buf_w"], jnp.float32),
                         buf_cnt=jnp.asarray(tree["buf_cnt"], jnp.int32),
                         rnd=jnp.asarray(tree["rnd"], jnp.int32))


def _is_fused(plan) -> bool:
    return getattr(plan, "transport", None) in ("flat", "packed")


def init_async_state(params_stacked, client_comp,
                     fault_plan: FaultPlan) -> AsyncAggState:
    """Empty delay buffer + round clock for a fresh async rollout.

    The buffer's shape is the uplink plan's accumulator geometry: the
    bucketized wire accumulator for flat/packed transports (via
    ``eval_shape`` of the encode — no device work), one-model f32 leaves
    for leafwise.  A MIXED :class:`repro.fl.fleet.FleetPlan` uplink also
    buffers one-model f32 leaves — each cohort folds on its own wire
    accumulator within the round, but the cross-cohort partial sums only
    compose in model space (uniform fleets unwrap first and get their
    plan's native geometry).  Chunked drivers create this ONCE and
    thread the returned state across chunks (like ``L2GDState``)."""
    up_plan = _resolve_uplink(client_comp)
    ns = fault_plan.n_slots
    if not isinstance(up_plan, CompressionPlan):
        buf = jax.tree_util.tree_map(
            lambda a: jnp.zeros((ns,) + tuple(a.shape[1:]), jnp.float32),
            params_stacked)
    elif _is_fused(up_plan):
        one = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape[1:]), a.dtype),
            params_stacked)
        pay = jax.eval_shape(
            lambda t: up_plan.encode(jax.random.PRNGKey(0), t), one)
        if isinstance(pay, QSGDPayload):
            acc = pay.codes.shape
        elif isinstance(pay, NarrowQSGDPayload):
            # the reduce widens narrow codes before folding, so the
            # accumulator is the layout's bucket grid, not the packed
            # sub-byte code shape
            acc = (pay.layout.n_buckets, pay.layout.bucket)
        else:
            acc = pay.exps.shape
        buf = jnp.zeros((ns,) + tuple(acc), jnp.float32)
    else:
        buf = jax.tree_util.tree_map(
            lambda a: jnp.zeros((ns,) + tuple(a.shape[1:]), a.dtype),
            params_stacked)
    return AsyncAggState(buf=buf, buf_w=jnp.zeros((ns,), jnp.float32),
                         buf_cnt=jnp.zeros((ns,), jnp.int32),
                         rnd=jnp.zeros((), jnp.int32))


def _isum(x) -> jax.Array:
    return jnp.sum(x).astype(jnp.int32)


def _async_agg_fresh(st, agg, k, part, lat, drp, crs, *, n, q, grad_fn, hp,
                     up_plan, down_plan, fault_plan, batch,
                     participation_mask=None):
    """The fresh-communication branch: simulate one arrival-ordered
    round.  Returns ((new_state, new_agg), loss, (8,) event counts)."""
    from repro.core import flatbuf

    D = fault_plan.max_delay
    ns = fault_plan.n_slots
    decay = fault_plan.staleness_decay
    k_clients, k_master = jax.random.split(k)
    client_keys = jax.random.split(k_clients, n)

    alive = part * (1.0 - crs)
    # arrival order = (latency, client index); non-senders rank last
    sortkey = jnp.where(alive > 0, lat, fault_plan.max_latency + 1) \
        * (n + 1) + jnp.arange(n)
    rank = jnp.argsort(jnp.argsort(sortkey))
    in_quorum = (rank < q).astype(jnp.float32)
    fresh = alive * in_quorum                     # quorum cohort
    w_fresh = fresh * (1.0 - drp)                 # ... whose uplink landed
    strag = alive * (1.0 - in_quorum) * (1.0 - drp)
    eff = jnp.maximum(lat, 1)                     # stragglers miss round r
    evict = strag * (eff > D).astype(jnp.float32)
    late = strag - evict                          # will land within D rounds

    sr = jnp.mod(agg.rnd, ns)
    stale_cnt = agg.buf_cnt[sr]
    stale_w = agg.buf_w[sr]

    # ---- encode all n clients (the synchronous key schedule), guard ----
    fleet = None if isinstance(up_plan, CompressionPlan) else up_plan
    fused = _is_fused(up_plan)
    if fleet is not None:
        # mixed fleet (DESIGN.md §13): cohort-grouped encode; each
        # cohort's quorum/straggler contributions fold on its own wire
        # accumulator and compose as one-model f32 partial sums — the
        # same structure as the leafwise tree buffer below, so the slot
        # algebra is shared verbatim
        from repro.fl.fleet import (fleet_encode, fleet_finite_mask,
                                    fleet_weighted_sum)
        cohort_batches = fleet_encode(fleet, client_keys, st.params)
        fin = fleet_finite_mask(cohort_batches, n)
    elif fused:
        payload = flatbuf.encode_clients(up_plan, client_keys, st.params)
        fin = flatbuf.payload_finite_mask(payload)
        payload = flatbuf.sanitize_payload(payload, fin)
    else:
        contrib = jax.vmap(lambda ck, p: up_plan.apply(ck, p))(
            client_keys, st.params)
        fin = stacked_finite_mask(contrib)
    rejected = _isum((w_fresh + late) * (1.0 - fin))
    w_fresh = w_fresh * fin

    # ---- fold the quorum cohort + this round's matured slot ----
    tw = jnp.sum(w_fresh) + stale_w
    tw_safe = jnp.where(tw > 0, tw, 1.0)
    if fleet is not None:
        fresh_sum = fleet_weighted_sum(cohort_batches, w_fresh)
        stale_sum = jax.tree_util.tree_map(lambda a: a[sr], agg.buf)
        ybar = jax.tree_util.tree_map(
            lambda s, b, a: ((s + b) / tw_safe).astype(a.dtype),
            fresh_sum, stale_sum, st.params)
    elif fused:
        layout = payload.layout
        acc = flatbuf.reduce_payload_acc(payload, w_fresh)
        total = acc + agg.buf[sr]
        ybar = flatbuf.unravel(
            layout, flatbuf.unbucketize(total / tw_safe, layout.d))
    else:
        fresh_sum = weighted_client_sum(contrib, w_fresh)
        stale_sum = jax.tree_util.tree_map(lambda a: a[sr], agg.buf)
        guarded = jax.tree_util.tree_map(
            lambda s, b: (s + b) / tw_safe.astype(s.dtype),
            fresh_sum, stale_sum)
        # bit-compat with compressed_average: the synchronous leafwise
        # round takes masked_client_mean (jnp.mean's bits, not sum/n)
        # whenever every payload is finite.  A round indistinguishable
        # from a synchronous one — all participants fresh and delivered,
        # nothing stale, nothing rejected — must reproduce those bits.
        sync_like = ((jnp.min(fin) > 0 if n else jnp.bool_(True))
                     & (stale_w == 0) & (stale_cnt == 0)
                     & jnp.all(w_fresh == part))
        plain = masked_client_mean(contrib, participation_mask)
        ybar = jax.tree_util.tree_map(
            lambda p, g: jnp.where(sync_like, p, g), plain, guarded)

    tgt = down_plan.apply(k_master, ybar)
    if fault_plan.is_null:
        # no fault can empty a round, so the fallback select below would
        # never fire — and merely having it in the graph perturbs how
        # XLA fuses the dequantize->update chain (different FMA
        # contraction), breaking the keystone bit-exactness.  Statically
        # drop it: the null plan compiles the synchronous target graph.
        target = tgt
    else:
        # empty round (nothing landed): keep aggregating vs the cache
        has = tw > 0
        target = jax.tree_util.tree_map(
            lambda t, c: jnp.where(has, t, c.astype(t.dtype)), tgt,
            st.cache)

    # ---- consume slot r, schedule the stragglers into future slots ----
    if fleet is None and fused:
        new_buf = agg.buf.at[sr].set(jnp.zeros_like(agg.buf[sr]))
    else:
        new_buf = jax.tree_util.tree_map(
            lambda a: a.at[sr].set(jnp.zeros_like(a[sr])), agg.buf)
    new_w = agg.buf_w.at[sr].set(0.0)
    new_cnt = agg.buf_cnt.at[sr].set(0)
    delivered_late = jnp.zeros((), jnp.int32)
    for a in range(1, D + 1):                     # static unroll, a <= D
        w_a = late * (eff == a).astype(jnp.float32) * fin
        wt_a = w_a * jnp.float32(decay ** a)      # staleness at fold time
        slot = jnp.mod(agg.rnd + a, ns)           # never == sr for a in 1..D
        if fleet is not None:
            acc_a = fleet_weighted_sum(cohort_batches, wt_a)
            new_buf = jax.tree_util.tree_map(
                lambda b, s: b.at[slot].add(s.astype(b.dtype)),
                new_buf, acc_a)
        elif fused:
            new_buf = new_buf.at[slot].add(
                flatbuf.reduce_payload_acc(payload, wt_a))
        else:
            acc_a = weighted_client_sum(contrib, wt_a)
            new_buf = jax.tree_util.tree_map(
                lambda b, s: b.at[slot].add(s.astype(b.dtype)),
                new_buf, acc_a)
        new_w = new_w.at[slot].add(jnp.sum(wt_a))
        new_cnt = new_cnt.at[slot].add(_isum(w_a))
        delivered_late = delivered_late + _isum(w_a)

    # crashed clients miss the broadcast: their update is masked out
    upd_mask = part * (1.0 - crs)
    new_params = aggregation_update(st.params, target, hp, mask=upd_mask)
    new_st = L2GDState(new_params, target, jnp.asarray(1, jnp.int32),
                       st.step + 1)
    new_agg = AsyncAggState(new_buf, new_w, new_cnt, agg.rnd + 1)

    losses, _ = jax.vmap(grad_fn)(st.params, batch)
    loss = jnp.mean(losses).astype(jnp.float32)

    fresh_ct = _isum(w_fresh)
    events = jnp.stack([
        _isum(alive),                             # sent
        fresh_ct + delivered_late,                # delivered
        _isum(alive * drp),                       # dropped
        _isum(evict),                             # evicted
        _isum(part * crs),                        # crashed
        fresh_ct,                                 # fresh
        stale_cnt,                                # stale
        rejected,                                 # rejected
    ])
    return (new_st, new_agg), loss, events


def async_l2gd_step(state: L2GDState, agg: AsyncAggState, batch,
                    xi_k: jax.Array, key: jax.Array, lat: jax.Array,
                    drp: jax.Array, crs: jax.Array, *, grad_fn: Callable,
                    hp: L2GDHyper, up_plan, down_plan,
                    fault_plan: FaultPlan, q: int, participation_mask=None):
    """One protocol step of Algorithm 1 under the fault model: the same
    3-way branch as :func:`repro.core.l2gd.l2gd_step`, with the
    fresh-communication branch replaced by the arrival-ordered round
    (:func:`_async_agg_fresh`).  Local and cached-target branches involve
    no communication, so no fault fires there — their update expressions
    are the synchronous step's own (the keystone bit-exactness leans on
    this).  ``lat``/``drp``/``crs`` are this step's pre-drawn fault
    realizations (consumed only if the step is a fresh round)."""
    n = int(hp.n)
    branch = jnp.where(xi_k == 0, 0, jnp.where(state.xi_prev == 0, 1, 2))
    part = jnp.ones((n,), jnp.float32) if participation_mask is None \
        else participation_mask.astype(jnp.float32)
    zeros8 = jnp.zeros((len(EVENT_FIELDS),), jnp.int32)

    def _mean_loss(st):
        losses, _ = jax.vmap(grad_fn)(st.params, batch)
        return jnp.mean(losses).astype(jnp.float32)

    def branch_local(op):
        st, ag, k = op
        losses, grads = jax.vmap(grad_fn)(st.params, batch)
        new_params = local_update(st.params, grads, hp)
        return ((L2GDState(new_params, st.cache, jnp.asarray(0, jnp.int32),
                           st.step + 1), ag),
                jnp.mean(losses).astype(jnp.float32), zeros8)

    def branch_agg_fresh(op):
        st, ag, k = op
        return _async_agg_fresh(st, ag, k, part, lat, drp, crs, n=n, q=q,
                                grad_fn=grad_fn, hp=hp, up_plan=up_plan,
                                down_plan=down_plan, fault_plan=fault_plan,
                                batch=batch,
                                participation_mask=participation_mask)

    def branch_agg_cached(op):
        st, ag, k = op
        new_params = aggregation_update(st.params, st.cache, hp,
                                        mask=participation_mask)
        return ((L2GDState(new_params, st.cache, jnp.asarray(1, jnp.int32),
                           st.step + 1), ag),
                _mean_loss(st), zeros8)

    (new_state, new_agg), loss, events = jax.lax.switch(
        branch, [branch_local, branch_agg_fresh, branch_agg_cached],
        (state, agg, key))
    return new_state, new_agg, {"loss": loss, "branch": branch,
                                "events": events}


def rollout_l2gd_async(key: jax.Array, state: L2GDState, hp: L2GDHyper,
                       batches, xi_trace: Optional[jax.Array] = None, *,
                       grad_fn: Callable,
                       fault_plan: Optional[FaultPlan] = None,
                       steps: Optional[int] = None,
                       client_comp: Any = Identity(),
                       master_comp: Any = Identity(),
                       batch_axis: Optional[int] = 0, unroll: int = 1,
                       participation: Optional[float] = None,
                       agg_state: Optional[AsyncAggState] = None):
    """K rounds of Algorithm 1 under the fault model, in one
    ``lax.scan``.

    Mirrors :func:`repro.core.rollout.rollout_l2gd` (same argument
    contract, same RNG pre-derivation) with two additions: a
    ``fault_plan`` (:class:`repro.fl.faults.FaultPlan`; ``None`` = the
    null plan) and the server carry ``agg_state`` (``None`` builds an
    empty delay buffer; chunked drivers thread the returned one, exactly
    like ``state`` — both carries index the SAME global step/round
    clocks, so chunking is invisible).

    Fault draws come from the fourth RNG stream
    (:func:`repro.fl.faults.fault_draws`): a function of (key, global
    step) alone, independent of codecs and chunk boundaries.  Steps that
    are not fresh rounds never consume their draws.

    Returns ``(final_state, final_agg_state, AsyncRolloutTrace)``."""
    fault_plan = fault_plan if fault_plan is not None else FaultPlan()
    length = _rollout_length(batches, batch_axis, xi_trace, steps)
    hp = jax.tree_util.tree_map(jnp.asarray, hp)
    n = int(hp.n)
    up_plan = _resolve_uplink(client_comp)   # plan, or a mixed FleetPlan
    down_plan = as_plan(master_comp)
    if not isinstance(up_plan, CompressionPlan) and up_plan.n_clients != n:
        raise ValueError(f"fleet covers {up_plan.n_clients} clients; "
                         f"hp.n = {n}")
    if agg_state is None:
        agg_state = init_async_state(state.params, up_plan, fault_plan)

    xi_key, noise_key = jax.random.split(key)
    ks = state.step + jnp.arange(length, dtype=jnp.int32)
    if xi_trace is None:
        xis_in = jax.vmap(lambda k: draw_xi(jax.random.fold_in(xi_key, k),
                                            hp.p))(ks)
    else:
        xis_in = jnp.asarray(xi_trace).astype(jnp.int32)
    subs = jax.vmap(lambda k: jax.random.fold_in(noise_key, k))(ks)
    masks = None
    s = n
    if participation is not None:
        s = participant_count(n, participation)
        if s < n:
            masks = participation_masks(xi_key, ks, n, s)
        else:
            s = n
    q = fault_plan.quorum_count(s)
    lats, drps, crss = fault_draws(xi_key, ks, n, fault_plan)

    step_fn = functools.partial(
        async_l2gd_step, grad_fn=grad_fn, hp=hp, up_plan=up_plan,
        down_plan=down_plan, fault_plan=fault_plan, q=q)

    def body(carry, xs):
        st, ag = carry
        if masks is None:
            (i, xi, sub, lat, drp, crs), mask = xs, None
        else:
            i, xi, sub, lat, drp, crs, mask = xs
        if batch_axis is None:
            batch = batches
        else:
            batch = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                batches)
        new_st, new_ag, metrics = step_fn(st, ag, batch, xi, sub, lat, drp,
                                          crs, participation_mask=mask)
        return (new_st, new_ag), (metrics["loss"], xi, metrics["branch"],
                                  metrics["events"])

    xs = (jnp.arange(length, dtype=jnp.int32), xis_in, subs, lats, drps,
          crss)
    if masks is not None:
        xs = xs + (masks,)
    (final, final_agg), (losses, xis, branches, events) = jax.lax.scan(
        body, (state, agg_state), xs, unroll=unroll)
    branches = branches.astype(jnp.int32)
    trace = AsyncRolloutTrace(
        losses=losses, xis=xis, branches=branches,
        n_local=jnp.sum(branches == 0).astype(jnp.int32),
        n_agg_comm=jnp.sum(branches == 1).astype(jnp.int32),
        n_agg_cached=jnp.sum(branches == 2).astype(jnp.int32),
        events=events)
    return final, final_agg, trace
