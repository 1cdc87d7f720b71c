"""On-device scanned rollout engine for Algorithm 1 (DESIGN.md §8).

The host driver (:mod:`repro.fl.l2gd_driver`) used to execute the
probabilistic protocol as a Python loop: one jitted dispatch AND a
blocking ``float(metrics["loss"])`` device sync per step, times a Python
double loop over (p, lambda) grids in the sweep benchmarks.  This module
puts the whole rollout on device:

  * :func:`rollout_l2gd` runs K rounds inside ONE ``lax.scan``, drawing
    xi_k ~ Bernoulli(p) via :func:`repro.core.l2gd.draw_xi` *inside* the
    scan (the step itself stays the branch-static ``lax.switch``) and
    accumulating device-side trace buffers: per-step loss, the xi
    sequence, branch ids and the protocol counters.
  * :func:`rollout_l2gd_grid` vmaps the whole rollout over array-valued
    (eta, lambda, p) axes of a traceable :class:`~repro.core.l2gd.
    L2GDHyper` — a Fig-3 meta-parameter sweep is ONE compiled dispatch
    instead of |grid| x K host round-trips.

Determinism contract (shared with the host-loop reference,
``run_l2gd(mode="host")``):

  ``xi_key, noise_key = jax.random.split(key)``; step k draws
  ``xi_k = draw_xi(fold_in(xi_key, k), p)`` and feeds
  ``fold_in(noise_key, k)`` to the step's compressor randomness, where k
  is the GLOBAL step counter ``state.step``.  The xi stream is therefore
  independent of the compressors (same key => same protocol realization
  for every codec) and chunking is invisible: resuming a rollout from a
  carried state continues the exact same streams.  Under
  :func:`rollout_l2gd_grid` every cell shares the key — common random
  numbers across the sweep (the per-cell xi draws threshold the SAME
  uniforms at their own p).

Wire-bits invariant: the scan never materializes a ledger.  It records
the xi trace and the transition counters; the host reconstructs the
:class:`~repro.fl.ledger.BitsLedger` bit-for-bit by replaying the xi
trace against the static ``plan.round_bits()``
(:meth:`~repro.fl.ledger.BitsLedger.replay_xi_trace`) — never by
re-deriving wire costs from the trace buffers (DESIGN.md §3/§8).

Partial participation (DESIGN.md §9): ``participation=f`` samples a
fixed-size subset S_k of s = round(f*n) participants for every
aggregation step from a THIRD stream derived off the xi key —
``part_key = fold_in(xi_key, -1)``, step k's mask from
``fold_in(part_key, k)`` — so the subset realization is a function of
(key, global step) alone: independent of the codecs, chunk-invariant,
and reproducible host-side (the ledger charges s/n of a round's bits
via ``replay_xi_trace(participation=...)`` without ever seeing the
masks).  ``participation=None`` (or s == n) runs the historic
full-participation path bit-exactly — no masks are materialized.

:func:`rollout_l2gd_sharded` is the same scan running INSIDE a
shard_map over a ``clients`` mesh axis (repro.launch.mesh.
make_client_mesh): params and batches are sharded on the leading client
axis, the aggregation branch's collective carries wire payloads
(repro.core.aggregation.make_client_sharded_average) and loss means are
psum reductions.  On 1 device at full participation it is bit-exact
with :func:`rollout_l2gd` — the headline equivalence test.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (_resolve_uplink, _shard_map,
                                    make_client_sharded_average)
from repro.core.codec import as_plan
from repro.core.compressors import Identity
from repro.core.l2gd import (L2GDHyper, L2GDState, draw_xi, init_state,
                             l2gd_step, make_hyper)

__all__ = ["RolloutTrace", "rollout_l2gd", "rollout_l2gd_grid",
           "rollout_l2gd_sharded", "hyper_grid", "participant_count",
           "draw_participation_mask", "participation_masks",
           "sharded_state_specs", "state_to_tree", "state_from_tree"]


class RolloutTrace(NamedTuple):
    """Device-side trace buffers of one scanned rollout.

    ``losses``/``xis``/``branches`` have a leading steps axis (plus a
    leading grid axis under :func:`rollout_l2gd_grid`); the counters are
    scalars derived from the branch trace on device.  Wire bits are NOT
    here by design: the ledger is reconstructed host-side from ``xis``
    (see module docstring)."""

    losses: jax.Array       # (K,) f32 mean client loss, pre-update params
    xis: jax.Array          # (K,) int32 xi_k realization
    branches: jax.Array     # (K,) int32 protocol branch (0/1/2)
    n_local: jax.Array      # () int32  — branch-0 steps
    n_agg_comm: jax.Array   # () int32  — branch-1 steps (fresh communication)
    n_agg_cached: jax.Array  # () int32 — branch-2 steps (cached target)


def participant_count(n: int, participation) -> int:
    """Static participant subset size |S| = round(participation * n),
    clamped to [1, n] — the ONE place the fraction becomes a count: the
    device mask sampler and the ledger's sampled-round rule
    (:meth:`repro.fl.ledger.BitsLedger.replay_xi_trace`) both read it,
    so the bits charged always match the subset actually drawn."""
    if not (0.0 < float(participation) <= 1.0):
        raise ValueError(
            f"participation must be in (0, 1], got {participation}")
    return max(1, min(int(n), int(round(float(participation) * int(n)))))


def draw_participation_mask(key: jax.Array, n: int, s: int) -> jax.Array:
    """(n,) 0/1 float32 mask with EXACTLY ``s`` participants: the s
    smallest of n iid uniforms (a uniformly random size-s subset).  The
    fixed size keeps the sampled-round ledger charge static (s/n of a
    full round) and rules out the empty-subset degenerate round."""
    if s >= n:
        return jnp.ones((n,), jnp.float32)
    u = jax.random.uniform(key, (n,))
    idx = jnp.argsort(u)
    return jnp.zeros((n,), jnp.float32).at[idx[:s]].set(1.0)


def participation_masks(xi_key: jax.Array, ks: jax.Array, n: int,
                        s: int) -> jax.Array:
    """Pre-derive the (len(ks), n) participant masks for a rollout
    window of global steps ``ks`` — the third RNG stream of the
    determinism contract: ``part_key = fold_in(xi_key, 2**32 - 1)``
    (i.e. -1 mod 2**32, disjoint from the int32-nonnegative step folds
    of the xi stream), step k's mask from ``fold_in(part_key, k)``.
    Chunk-invariant for the same reason the xi stream is: k is the
    global step counter."""
    part_key = jax.random.fold_in(xi_key, np.uint32(2 ** 32 - 1))
    return jax.vmap(lambda k: draw_participation_mask(
        jax.random.fold_in(part_key, k), n, s))(ks)


def _rollout_length(batches, batch_axis, xi_trace, steps) -> int:
    lengths = {}
    if steps is not None:
        lengths["steps="] = int(steps)
    if xi_trace is not None:
        lengths["xi_trace"] = int(xi_trace.shape[0])
    if batch_axis == 0:
        leaves = jax.tree_util.tree_leaves(batches)
        if leaves:
            lengths["batches"] = int(leaves[0].shape[0])
    if not lengths:
        raise ValueError(
            "rollout length is undetermined: pass steps=, a stacked "
            "batches pytree (batch_axis=0) or an xi_trace")
    if len(set(lengths.values())) != 1:
        raise ValueError(f"inconsistent rollout lengths: {lengths}")
    return next(iter(lengths.values()))


def rollout_l2gd(key: jax.Array, state: L2GDState, hp: L2GDHyper, batches,
                 xi_trace: Optional[jax.Array] = None, *,
                 grad_fn: Callable, steps: Optional[int] = None,
                 client_comp: Any = Identity(), master_comp: Any = Identity(),
                 batch_axis: Optional[int] = 0, average_fn=None,
                 unroll: int = 1, participation: Optional[float] = None,
                 local_steps: int = 1):
    """Run K rounds of Algorithm 1 inside one ``lax.scan``.

    Args:
      key: protocol PRNG key; split ONCE into (xi, noise) streams — see
        the module-level determinism contract.
      state: current :class:`L2GDState` (``init_state(params)`` for a
        fresh run).  ``state.step`` is the global step counter that
        indexes both RNG streams, so chunked callers just feed the
        carried state back in with the SAME key.
      hp: hypers; may carry array-valued ``eta``/``lam``/``p`` (built
        via :func:`~repro.core.l2gd.make_hyper`).
      batches: per-step batch data.  With ``batch_axis=0`` a pytree
        whose leaves carry a leading (K, ...) steps axis, indexed inside
        the scan; with ``batch_axis=None`` a single batch pytree reused
        every step (no K-fold copy for constant-batch workloads).
      xi_trace: optional (K,) int array forcing the protocol realization
        (replaces the Bernoulli draws) — the replay/property-test hook.
      grad_fn: per-client ``(params_i, batch_i) -> (loss_i, grads_i)``.
      steps: rollout length; inferable from ``batches``/``xi_trace``.
      client_comp / master_comp: uplink/downlink codecs or
        :class:`~repro.core.codec.CompressionPlan`s (as in
        :func:`~repro.core.l2gd.l2gd_step`); ``client_comp`` also takes
        a :class:`repro.fl.fleet.FleetPlan` — per-cohort uplinks with
        the static cohort assignment riding next to the participation
        mask (uniform fleets unwrap to this path bit-exactly,
        DESIGN.md §13).
      average_fn: optional aggregation override, forwarded to the step.
      unroll: ``lax.scan`` unroll factor.
      participation: optional client-sampling fraction f ∈ (0, 1]: every
        aggregation step masks the average and the update to a
        size-``round(f*n)`` participant subset drawn from the xi-derived
        stream (module docstring; DESIGN.md §9).  ``None`` (or a
        fraction giving s == n) is the historic full-participation path,
        bit-exactly.
      local_steps: LoCoDL-style burst H >= 1 forwarded to
        :func:`~repro.core.l2gd.l2gd_step` — local-branch protocol steps
        run H gradient passes on their step's batch; the wire cost of a
        round is unchanged (the ledger replays xi transitions).  H=1 is
        the historic step, bit-exactly.

    Returns: ``(final_state, RolloutTrace)`` — everything stays on
    device; a jitted rollout issues zero per-step host transfers
    (regression-tested).
    """
    length = _rollout_length(batches, batch_axis, xi_trace, steps)
    # normalize hyper leaves to device arrays (f32 step scalings on
    # device; a Python-float closure would constant-fold in f64 and
    # break stacked-vs-sharded bit-exactness — same rule as the driver)
    hp = jax.tree_util.tree_map(jnp.asarray, hp)
    # pre-derive both streams for the whole window in two vectorized
    # threefry passes (bit-identical to per-step fold_in: vmap of fold_in
    # IS fold_in per element) — the scan body then carries no RNG graphs,
    # which cuts trace/compile time and per-iteration overhead
    with jax.named_scope("rollout.streams"):
        xi_key, noise_key = jax.random.split(key)
        ks = state.step + jnp.arange(length, dtype=jnp.int32)
        if xi_trace is None:
            xis_in = jax.vmap(lambda k: draw_xi(
                jax.random.fold_in(xi_key, k), hp.p))(ks)
        else:
            xis_in = xi_trace.astype(jnp.int32)
        subs = jax.vmap(lambda k: jax.random.fold_in(noise_key, k))(ks)
        masks = None
        if participation is not None:
            s = participant_count(hp.n, participation)
            if s < hp.n:  # s == n: no masks, the base path bit-exactly
                masks = participation_masks(xi_key, ks, hp.n, s)

    def step_fn(st, batch, xi, sub, mask):
        return l2gd_step(st, batch, xi, sub, grad_fn, hp, client_comp,
                         master_comp, average_fn=average_fn,
                         participation_mask=mask, local_steps=local_steps)

    final, outs = _protocol_scan(state, length, xis_in, subs, masks,
                                 batches, batch_axis, unroll, step_fn)
    return final, _make_trace(*outs)


def _protocol_scan(state, length, xis_in, subs, masks, batches, batch_axis,
                   unroll, step_fn):
    """The ONE scan skeleton shared by the stacked and sharded engines
    (they are pinned bit-exact to each other, so the xs packing, batch
    indexing and trace outputs must not fork): ``step_fn(st, batch, xi,
    sub, mask)`` is the engine-specific step closure."""

    def body(st, xs):
        if masks is None:
            (i, xi, sub), mask = xs, None
        else:
            i, xi, sub, mask = xs
        if batch_axis is None:
            batch = batches
        else:
            batch = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                batches)
        new_st, metrics = step_fn(st, batch, xi, sub, mask)
        return new_st, (metrics["loss"], xi, metrics["branch"])

    xs = (jnp.arange(length, dtype=jnp.int32), xis_in, subs)
    if masks is not None:
        xs = xs + (masks,)
    return jax.lax.scan(body, state, xs, unroll=unroll)


def _make_trace(losses, xis, branches) -> RolloutTrace:
    branches = branches.astype(jnp.int32)
    return RolloutTrace(
        losses=losses, xis=xis, branches=branches,
        n_local=jnp.sum(branches == 0).astype(jnp.int32),
        n_agg_comm=jnp.sum(branches == 1).astype(jnp.int32),
        n_agg_cached=jnp.sum(branches == 2).astype(jnp.int32))


def sharded_state_specs(state: L2GDState, axis_name: str = "clients"
                        ) -> L2GDState:
    """PartitionSpec pytree of an :class:`L2GDState` sharded over the
    ``clients`` mesh axis (DESIGN.md §9 layout): ``params`` leading
    client axis sharded, ``cache`` (the shared aggregation target) and
    the protocol scalars replicated.  ``repro.launch.sharding.
    client_sharded_shardings`` wraps these into NamedShardings for
    placement."""
    from jax.sharding import PartitionSpec as P
    return L2GDState(
        params=jax.tree.map(lambda a: P(axis_name), state.params),
        cache=jax.tree.map(lambda a: P(), state.cache),
        xi_prev=P(), step=P())


def rollout_l2gd_sharded(key: jax.Array, state: L2GDState, hp: L2GDHyper,
                         batches, xi_trace: Optional[jax.Array] = None, *,
                         mesh, grad_fn: Callable,
                         steps: Optional[int] = None,
                         client_comp: Any = Identity(),
                         master_comp: Any = Identity(),
                         participation: Optional[float] = None,
                         batch_axis: Optional[int] = 0, unroll: int = 1,
                         axis_name: str = "clients", local_steps: int = 1):
    """:func:`rollout_l2gd` with the stacked client axis SHARDED over a
    device mesh — the whole K-step scan runs inside ONE shard_map over
    ``mesh``'s ``axis_name`` axis (repro.launch.mesh.make_client_mesh).

    Per shard the step sees its n/n_shards local clients; the
    aggregation branch's cross-shard exchange is the payload-compressed
    ``all_gather`` of :func:`repro.core.aggregation.
    make_client_sharded_average` (the collective moves each client's
    quantized wire arrays, never dequantized fp32) and loss means are
    psum reductions.  RNG streams, participation masks and the xi trace
    are pre-derived exactly as in :func:`rollout_l2gd` and enter the
    shard_map replicated, so the protocol realization is identical to
    the stacked engine's — on a 1-device mesh at full participation the
    result is bit-exact with :func:`rollout_l2gd` (the headline test,
    tests/test_sharded_rollout.py).

    Args beyond :func:`rollout_l2gd`: ``mesh`` (must carry
    ``axis_name``; n must divide by the axis size) and ``axis_name``.
    ``state``/``batches`` may be host arrays or arrays already placed
    with ``repro.launch.sharding.client_sharded_shardings``.

    Returns ``(final_state, RolloutTrace)``; the final ``params`` keep
    the client-sharded layout, everything else is replicated.
    """
    from jax.sharding import PartitionSpec as P

    length = _rollout_length(batches, batch_axis, xi_trace, steps)
    n = int(hp.n)
    n_shards = mesh.shape[axis_name]
    if n % n_shards:
        raise ValueError(f"n={n} clients do not divide the {axis_name!r} "
                         f"mesh axis of size {n_shards}")
    leaves = jax.tree_util.tree_leaves(state.params)
    if leaves and leaves[0].shape[0] != n:
        raise ValueError(f"state.params leading axis "
                         f"{leaves[0].shape[0]} != hp.n = {n}")
    hp = jax.tree_util.tree_map(jnp.asarray, hp)
    up_plan = _resolve_uplink(client_comp)   # plan, or a mixed FleetPlan
    down_plan = as_plan(master_comp)
    average_fn = make_client_sharded_average(axis_name, n, up_plan,
                                             down_plan)

    with jax.named_scope("rollout.streams"):
        xi_key, noise_key = jax.random.split(key)
        ks = state.step + jnp.arange(length, dtype=jnp.int32)
        if xi_trace is None:
            xis_in = jax.vmap(lambda k: draw_xi(
                jax.random.fold_in(xi_key, k), hp.p))(ks)
        else:
            xis_in = jnp.asarray(xi_trace).astype(jnp.int32)
        # keys cross the shard_map boundary as raw key data (uint32 rows)
        subs = jax.random.key_data(
            jax.vmap(lambda k: jax.random.fold_in(noise_key, k))(ks))
        masks = None
        if participation is not None:
            s = participant_count(n, participation)
            if s < n:
                masks = participation_masks(xi_key, ks, n, s)

    def sharded_body(xis_in, subs, masks, st, batches, hp):
        def step_fn(st, batch, xi, sub_data, mask):
            sub = jax.random.wrap_key_data(sub_data)
            return l2gd_step(st, batch, xi, sub, grad_fn, hp, up_plan,
                             down_plan, average_fn=average_fn,
                             participation_mask=mask, axis_name=axis_name,
                             local_steps=local_steps)

        return _protocol_scan(st, length, xis_in, subs, masks, batches,
                              batch_axis, unroll, step_fn)

    state_specs = sharded_state_specs(state, axis_name)
    if batch_axis is None:
        batch_specs = jax.tree_util.tree_map(lambda a: P(axis_name), batches)
    else:
        batch_specs = jax.tree_util.tree_map(lambda a: P(None, axis_name),
                                             batches)
    hp_specs = jax.tree_util.tree_map(lambda a: P(), hp)
    if masks is None:
        fn = lambda xis, subs, st, b, h: sharded_body(xis, subs, None, st,
                                                      b, h)
        in_specs = (P(), P(), state_specs, batch_specs, hp_specs)
        args = (xis_in, subs, state, batches, hp)
    else:
        fn = sharded_body
        in_specs = (P(), P(), P(), state_specs, batch_specs, hp_specs)
        args = (xis_in, subs, masks, state, batches, hp)
    out_specs = (state_specs, (P(), P(), P()))
    final, outs = _shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)(*args)
    return final, _make_trace(*outs)


def rollout_l2gd_grid(key: jax.Array, params_stacked, hp_grid: L2GDHyper,
                      batches, xi_trace: Optional[jax.Array] = None, *,
                      grad_fn: Callable, steps: Optional[int] = None,
                      client_comp: Any = Identity(),
                      master_comp: Any = Identity(),
                      batch_axis: Optional[int] = 0, unroll: int = 1,
                      jit: bool = True):
    """Vmap a whole rollout over a hyper grid — ONE compiled dispatch.

    ``hp_grid`` is an :class:`L2GDHyper` whose ``eta``/``lam``/``p`` are
    same-shaped 1-D arrays of G cells (build with :func:`hyper_grid` or
    :func:`~repro.core.l2gd.make_hyper`); every cell starts from the same
    ``init_state(params_stacked)``, shares ``key`` (common random
    numbers) and the same batches.  Returns ``(final_states, traces)``
    with a leading G axis on every array.

    Note ``vmap`` turns the protocol ``lax.switch`` into a select over
    all three branches (cells disagree on the branch), so each cell pays
    ~3 branch evaluations per step — still orders of magnitude cheaper
    than |grid| x K host dispatches (``bench_fig3_sweep``).
    """
    state = init_state(params_stacked)
    roll = functools.partial(
        rollout_l2gd, grad_fn=grad_fn, steps=steps, client_comp=client_comp,
        master_comp=master_comp, batch_axis=batch_axis, unroll=unroll)
    fn = jax.vmap(lambda hp: roll(key, state, hp, batches, xi_trace))
    if jit:
        fn = jax.jit(fn)
    return fn(hp_grid)


def state_to_tree(state: L2GDState) -> dict:
    """:class:`L2GDState` as a plain dict pytree — the checkpoint form.

    ``state.step`` is the global step counter every RNG stream is keyed
    by (xi, noise, participation, faults — module docstring), which is
    exactly why a restored state continues BIT-EXACTLY: the streams are
    functions of ``(key, step)``, never of how the run was chunked."""
    return {"params": state.params, "cache": state.cache,
            "xi_prev": state.xi_prev, "step": state.step}


def state_from_tree(tree: dict) -> L2GDState:
    """Inverse of :func:`state_to_tree` (scalars re-normalized to the
    int32 device scalars the scan carry expects)."""
    return L2GDState(params=tree["params"], cache=tree["cache"],
                     xi_prev=jnp.asarray(tree["xi_prev"], jnp.int32),
                     step=jnp.asarray(tree["step"], jnp.int32))


def hyper_grid(ps, lams, eta, n: int):
    """Flatten a cartesian (p, lambda) product into one array-valued
    :class:`L2GDHyper` for :func:`rollout_l2gd_grid`.

    ``eta`` is a scalar, an array broadcastable to the ``(|ps|, |lams|)``
    meshgrid, or a callable ``(P, L) -> eta`` evaluated on it (e.g. the
    Fig-3 stability rule ``lambda P, L: np.minimum(0.4, n * P / L)``).
    Returns ``(hp_grid, grid_shape)``; reshape per-cell outputs with
    ``out.reshape(grid_shape + out.shape[1:])``."""
    P, L = np.meshgrid(np.asarray(ps, np.float32),
                       np.asarray(lams, np.float32), indexing="ij")
    E = eta(P, L) if callable(eta) else eta
    E = np.broadcast_to(np.asarray(E, np.float32), P.shape)
    hp = make_hyper(eta=jnp.asarray(E.ravel()), lam=jnp.asarray(L.ravel()),
                    p=jnp.asarray(P.ravel()), n=n)
    return hp, P.shape
