"""The compressed aggregation layer — the paper's master/worker exchange.

Paper-faithful semantics (Algorithm 1):

  1. every client i compresses its model:      c_i = C_i(x_i)
  2. the master averages compressed models:    ybar = (1/n) sum_j c_j
  3. the master compresses the average:        t = C_M(ybar)
  4. every client aggregates against t.

On a TPU mesh there is no physical master: step 2 is an all-reduce over
the client axis and step 3 is computed *redundantly on every client with a
shared PRNG key*, which is bitwise identical to a master compressing and
broadcasting (Lemma 2 unbiasedness only needs E[C_M(ybar)] = xbar and is
unaffected).  Wire bits are charged by the ledger from the payload spec —
``CompressionPlan.round_bits()`` — see DESIGN.md §3.

Every entry point takes a :class:`repro.core.codec.CompressionPlan` (or a
plain Compressor, coerced via auto transport):

  * :func:`compressed_average` — stacked-client form (leading axis = n).
    Used by the single-host simulator AND the pjit runtime (XLA turns the
    axis-0 mean of a ("clients", ...)-sharded array into the collective).
  * :func:`compressed_average_wire` — beyond-paper TPU-native variant for
    shard_map: uplink = stochastic-round cast to a narrow dtype fused with
    ``jax.lax.pmean`` (natural compression composes with collectives as a
    dtype cast), downlink = shared-key C_M.  See EXPERIMENTS.md §Perf.
  * :func:`make_payload_sharded_average` — shard_map ``average_fn`` whose
    uplink collective carries a plan's PACKED wire payload (any codec:
    int8 QSGD codes, uint8 natural sign+exponent codes, ...) instead of
    dequantized fp32.  :func:`make_packed_sharded_average` is the kept
    QSGD-specific entry point (now a thin wrapper).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.codec import (CompressionPlan, _UNSET, _legacy_transport,
                              as_plan)
from repro.core.compressors import QSGD

__all__ = ["compressed_average", "compressed_average_wire",
           "stochastic_round_cast", "make_sharded_average",
           "make_payload_sharded_average", "make_packed_sharded_average",
           "make_client_sharded_average", "masked_client_mean",
           "stacked_finite_mask", "weighted_client_sum"]

#: TPU lane width: the row length flat wire vectors are gathered in
_LANES = 128


def _shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes type check off:
    the engines' shard bodies carry no varying-axis annotations."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _resolve_uplink(comp, transport=None):
    """Plan-or-fleet coercion for uplink arguments: single plans pass
    through, plain compressors coerce via ``as_plan``, uniform fleets
    unwrap to their one plan (the keystone: the engine then compiles the
    LITERAL single-plan graph), mixed fleets return the FleetPlan itself.
    The fl import is lazy (call time) — a top-level one would close the
    core<->fl package-init cycle (DESIGN.md §13)."""
    if isinstance(comp, CompressionPlan):
        return comp
    from repro.fl.fleet import resolve_uplink
    return resolve_uplink(comp, transport)


def masked_client_mean(tree_stacked, mask):
    """Mean over the leading client axis restricted to ``mask``'s
    participants: ``sum_i m_i x_i / sum_i m_i``.  ``mask=None`` is the
    plain ``jnp.mean`` (full participation) — the two spellings are kept
    distinct so the historic path stays bit-identical."""
    if mask is None:
        return jax.tree.map(lambda a: jnp.mean(a, axis=0), tree_stacked)
    denom = jnp.sum(mask.astype(jnp.float32))

    def one(a):
        mb = mask.reshape((a.shape[0],) + (1,) * (a.ndim - 1)).astype(a.dtype)
        return jnp.sum(a * mb, axis=0) / denom.astype(a.dtype)

    return jax.tree.map(one, tree_stacked)


def stacked_finite_mask(tree_stacked) -> jax.Array:
    """(n,) 0/1 float32 over a client-stacked pytree: 1 where client i's
    slice is finite in EVERY leaf — the leafwise-transport counterpart of
    :func:`repro.core.flatbuf.payload_finite_mask` (there the small wire
    arrays are scanned instead of decoded buffers)."""
    leaves = jax.tree_util.tree_leaves(tree_stacked)
    if not leaves:
        return jnp.ones((0,), jnp.float32)
    ok = jnp.ones((leaves[0].shape[0],), bool)
    for a in leaves:
        ok = ok & jnp.all(jnp.isfinite(a.astype(jnp.float32)),
                          axis=tuple(range(1, a.ndim)))
    return ok.astype(jnp.float32)


def weighted_client_sum(tree_stacked, weights: jax.Array):
    """NaN-safe weighted sum over the leading client axis: ``sum_i w_i *
    x_i`` with zero-weight clients EXCLUDED via ``where`` — a poisoned
    client's NaN/Inf would survive a multiply-by-zero mask (NaN * 0 is
    NaN).  ``weights`` are arbitrary non-negative floats (the async
    server's staleness weights, not just 0/1 masks).  The caller divides
    by its own weight total — the sum form is what folds into the
    arrival-ordered server's delay buffer (DESIGN.md §11)."""

    def one(a):
        wb = weights.reshape(
            (a.shape[0],) + (1,) * (a.ndim - 1)).astype(a.dtype)
        return jnp.sum(jnp.where(wb > 0, a, 0) * wb, axis=0)

    return jax.tree.map(one, tree_stacked)


def compressed_average(key: jax.Array, params_stacked,
                       client_comp, master_comp, *, mask=None, flat=_UNSET):
    """Return t = C_M( (1/n) sum_j C_j(x_j) ) for stacked client params.

    ``params_stacked`` is a pytree whose leaves carry a leading client axis
    of size n.  The returned pytree has NO client axis (it is the shared
    aggregation target, identical on all clients).

    ``client_comp`` / ``master_comp`` are :class:`CompressionPlan`s (or
    plain Compressors, coerced with auto transport: flat-buffer engine
    where supported — one fused launch per client — leafwise otherwise).
    ``mask`` (optional (n,) 0/1 array) restricts the average to a sampled
    participant subset — the partial-participation round of DESIGN.md §9:
    ``ybar = sum_i m_i C_i(x_i) / |S|`` (non-participants send nothing;
    the ledger charges only sampled uplinks).  The ``flat=`` keyword is a
    deprecated shim; in the pjit runtime pass leafwise plans instead
    (raveling model-axis-sharded leaves forces a rematerialization,
    repro.core.flatbuf's sharding note).

    ``client_comp`` may also be a :class:`repro.fl.fleet.FleetPlan`
    (heterogeneous fleet, DESIGN.md §13): clients group by cohort at
    trace time, each flat/packed cohort folds on its own O(d) fused
    accumulator, cohort partial sums add and divide ONCE by the total
    participant weight.  A uniform fleet unwraps to its single plan
    before any of this — bit-exact with the historic path.  Client i
    always uses key ``split(k_clients, n)[i]`` regardless of grouping.
    """
    transport = None
    if flat is not _UNSET:
        transport = _legacy_transport(flat, "compressed_average(..., flat=)")
    up_plan = _resolve_uplink(client_comp, transport)
    down_plan = as_plan(master_comp, transport)
    n = jax.tree_util.tree_leaves(params_stacked)[0].shape[0]
    k_clients, k_master = jax.random.split(key)
    client_keys = jax.random.split(k_clients, n)
    # named stages for a device trace: uplink_encode, server_reduce,
    # downlink (a mixed fleet names its own two stages in fleet_mean)
    if not isinstance(up_plan, CompressionPlan):
        from repro.fl.fleet import fleet_mean
        if up_plan.n_clients != n:
            raise ValueError(f"fleet covers {up_plan.n_clients} clients; "
                             f"params are stacked for {n}")
        ybar = fleet_mean(up_plan, client_keys, params_stacked, mask)
    elif up_plan.transport in ("flat", "packed"):
        # fused decode->reduce (DESIGN.md §10): encode-only vmap, then the
        # ONE-pass kernel accumulates the masked mean straight from the
        # packed codes — no per-client dequantized tree is materialized
        from repro.core import flatbuf
        with jax.named_scope("uplink_encode"):
            payload = flatbuf.encode_clients(up_plan, client_keys,
                                             params_stacked)
        with jax.named_scope("server_reduce"):
            ybar = flatbuf.reduce_payload_mean(payload, mask)
    else:
        with jax.named_scope("uplink_encode"):
            compressed = jax.vmap(lambda k, p: up_plan.apply(k, p))(
                client_keys, params_stacked)
        # fail-fast payload validation (mask-and-count, mirroring
        # reduce_payload_mean): exclude non-finite clients from numerator
        # AND denominator; select the historic expression when everything
        # is finite so that path stays bit-identical
        with jax.named_scope("server_reduce"):
            fin = stacked_finite_mask(compressed)
            all_ok = jnp.min(fin) > 0 if fin.shape[0] else jnp.bool_(True)
            w = fin if mask is None else \
                mask.reshape(-1).astype(jnp.float32) * fin
            denom = jnp.sum(w)
            guarded = jax.tree.map(
                lambda s: s / jnp.where(denom > 0, denom,
                                        1.0).astype(s.dtype),
                weighted_client_sum(compressed, w))
            plain = masked_client_mean(compressed, mask)
            ybar = jax.tree.map(lambda p, g: jnp.where(all_ok, p, g),
                                plain, guarded)
    with jax.named_scope("downlink"):
        return down_plan.apply(k_master, ybar)


def stochastic_round_cast(key: jax.Array, x: jax.Array,
                          dtype=jnp.bfloat16) -> jax.Array:
    """Unbiased stochastic rounding of float32 ``x`` to bfloat16.

    Bit-exact construction: bf16 is the top 16 bits of f32, so truncation
    drops the low 16 mantissa bits and we bump the bf16 magnitude up with
    probability low16 / 2^16 — linear interpolation between the two
    enclosing representables, hence exactly unbiased.  (A float-domain
    ``nextafter`` formulation silently degenerates to nearest-rounding
    because the next f32 value collapses back under the bf16 cast.)

    Composes with XLA collectives as a plain cast, so the wire genuinely
    carries the narrow payload.
    """
    if dtype != jnp.bfloat16:
        raise NotImplementedError("stochastic_round_cast targets bf16")
    xf = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(xf, jnp.uint32)
    low = bits & jnp.uint32(0xFFFF)
    prob = low.astype(jnp.float32) * (1.0 / 65536.0)
    u = jax.random.uniform(key, x.shape)
    up = (u < prob).astype(jnp.uint32)
    trunc = (bits & jnp.uint32(0xFFFF0000)) + (up << 16)
    out = jax.lax.bitcast_convert_type(trunc, jnp.float32)
    passthrough = ~jnp.isfinite(xf)
    return jnp.where(passthrough, xf, out).astype(dtype)


def _make_shard_map_average(mesh, client_axes: tuple, param_pspecs_stacked,
                            master_comp, uplink):
    """Shared scaffolding of the beyond-paper shard_map ``average_fn``s.

    Per shard: split keys and decorrelate the uplink key across the
    client axes (Assumption 1: independent C_i; the master key stays
    shared by design), average the shard's local clients in f32, run
    ``uplink(k_up, local_mean) -> ybar`` (whose collective IS the wire),
    cast back to param dtypes, then apply the shared-key C_M downlink.
    """
    from jax.sharding import PartitionSpec as P
    from jax.tree_util import tree_map

    axes = tuple(client_axes)
    down_plan = as_plan(master_comp)
    out_specs = tree_map(lambda s: P(*tuple(s)[1:]), param_pspecs_stacked,
                         is_leaf=lambda x: isinstance(x, P))

    def local_fn(key, params_local):
        # params_local leaves: (clients_per_shard, ...) — average locally
        # first, then let the uplink reduce over the client mesh axes.
        k_up, k_master = jax.random.split(key)
        for ax in axes:
            k_up = jax.random.fold_in(k_up, jax.lax.axis_index(ax))
        local_mean = tree_map(
            lambda a: jnp.mean(a.astype(jnp.float32), axis=0), params_local)
        ybar = uplink(k_up, local_mean, axes)
        ybar = tree_map(lambda y, a: y.astype(a.dtype), ybar, params_local)
        return down_plan.apply(k_master, ybar)

    def average_fn(key, params_stacked):
        return _shard_map(
            local_fn, mesh=mesh, in_specs=(P(), param_pspecs_stacked),
            out_specs=out_specs)(key, params_stacked)

    return average_fn


def make_sharded_average(mesh, client_axes: tuple, param_pspecs_stacked,
                         master_comp):
    """Beyond-paper: build an ``average_fn`` for :func:`repro.core.l2gd.
    l2gd_step` whose UPLINK is a genuinely narrow collective.

    Inside a shard_map over the full mesh, each client's local param shard
    is stochastically rounded to bf16 (unbiased — natural-compression-style
    narrowing) and ``pmean``-ed over the client axes: the wire carries bf16,
    halving the aggregation's collective bytes end-to-end.  The downlink
    C_M is applied shard-wise with a shared key (bitwise identical to a
    master broadcast, zero extra communication — Lemma 2 unaffected).
    """

    def uplink(k_up, local_mean, axes):
        leaves, treedef = jax.tree_util.tree_flatten(local_mean)
        up_keys = jax.random.split(k_up, len(leaves))
        meaned = []
        for k_i, leaf in zip(up_keys, leaves):
            m = stochastic_round_cast(k_i, leaf)        # bf16 wire
            for ax in axes:
                m = jax.lax.pmean(m, ax)
            meaned.append(m)
        return jax.tree_util.tree_unflatten(treedef, meaned)

    return _make_shard_map_average(mesh, client_axes, param_pspecs_stacked,
                                   master_comp, uplink)


def make_payload_sharded_average(mesh, client_axes: tuple,
                                 param_pspecs_stacked, master_comp,
                                 uplink_plan: CompressionPlan):
    """Beyond-paper: an ``average_fn`` whose UPLINK collective moves the
    plan's WIRE PAYLOAD — the same arrays ``uplink_plan.encode`` builds
    and ``round_bits()`` charges (DESIGN.md §3/§7).

    Inside a shard_map over the full mesh each client shard (1) averages
    its local clients, (2) encodes the mean to its payload (int8 QSGD
    codes + bucket norms, uint8 natural sign+exponent codes, ...),
    (3) ``all_gather``s every payload array over the client axes — the
    collective carries the quantized codes, e.g. ~3.9x fewer bytes than
    dequantized fp32 for int8 QSGD — and (4) folds the gathered payloads
    into the mean with the ONE-pass fused decode->reduce engine (O(d)
    server state, DESIGN.md §10).  Each shard's decoded payload is an
    unbiased estimate of its local mean, so the gathered average is
    unbiased for xbar (Lemma 2 unaffected).  Downlink: C_M applied
    shard-wise with a shared key, exactly as :func:`make_sharded_average`.

    The plan's layout is recomputed from the shard-LOCAL tree at trace
    time, so the same plan object serves global accounting and per-shard
    encoding.
    """

    def uplink(k_up, local_mean, axes):
        payload = uplink_plan.encode(k_up, local_mean)
        return _gather_reduce(uplink_plan, payload, axes, batched=False)

    return _make_shard_map_average(mesh, client_axes, param_pspecs_stacked,
                                   master_comp, uplink)


def _gather_payloads(payload, axes, *, batched: bool):
    """All_gather a (possibly client-batched) wire Payload over the client
    mesh axes — the collective moves the plan's packed wire arrays, never
    dequantized fp32 — and collapse the gathered mesh axes (plus any
    local client axis, ``batched=True``) into one leading axis ordered by
    global client index.

    A wire array that is one flat vector per client (the leafwise
    codecs' payloads) crosses the collective as rows of 128 lanes.  The
    flat vector gathered whole, behind the flatten of a 2-D leaf, took
    the TPU compiler minutes at stablelm-1.6b widths (its 205M-element
    embedding); in rows it compiles in about a second.  The reshape
    moves no bits."""
    lead = 1 if batched else 0

    def as_rows(a):
        if a.ndim == lead + 1 and a.shape[-1] % _LANES == 0:
            return a.reshape(a.shape[:-1] + (-1, _LANES))
        return a

    with jax.named_scope("gather"):
        gathered = jax.tree_util.tree_map(as_rows, payload)
        for ax in axes:                       # wire arrays on the wire
            gathered = jax.tree_util.tree_map(
                lambda a: jax.lax.all_gather(a, ax), gathered)
        tail = (lambda o: o.shape[1:]) if batched else (lambda o: o.shape)
        return jax.tree_util.tree_map(
            lambda orig, g: g.reshape((-1,) + tail(orig)), payload,
            gathered)


def _gather_reduce(plan, payload, axes, *, batched: bool, mask=None):
    """The shared server side of :func:`make_payload_sharded_average`
    (one payload per shard, ``batched=False``) and
    :func:`make_client_sharded_average` (one payload per local client,
    ``batched=True``): gather the wire payloads, then form the masked
    mean with the ONE-pass fused decode->reduce engine (O(d) accumulator,
    DESIGN.md §10) for flat-engine payloads, falling back to per-message
    decode + masked mean for leafwise payload trees."""
    from repro.core import flatbuf
    gathered = _gather_payloads(payload, axes, batched=batched)
    with jax.named_scope("server_reduce"):
        if flatbuf.supports_fused_reduce(gathered):
            return flatbuf.reduce_payload_mean(gathered, mask)
        deq = jax.vmap(plan.decode)(gathered)
        if mask is None and not batched:
            # make_payload_sharded_average's historic per-shard mean
            # (decoded leaves may be non-f32; keep the f32 accumulate)
            return jax.tree_util.tree_map(
                lambda a: jnp.mean(a.astype(jnp.float32), axis=0), deq)
        return masked_client_mean(deq, mask)


def make_client_sharded_average(axis_name: str, n_clients: int,
                                client_comp, master_comp):
    """Per-shard ``average_fn`` for a protocol step that is ALREADY
    running inside a shard_map whose leading client axis is sharded over
    mesh axis ``axis_name`` — the aggregation collective of the
    client-sharded rollout engine (repro.core.rollout.
    rollout_l2gd_sharded, DESIGN.md §9).

    Paper-faithful per-client semantics, distributed: every shard (1)
    derives the SAME global per-client key schedule ``split(k_clients,
    n)`` as :func:`compressed_average` and takes its own slice, (2)
    encodes each LOCAL client's model to its wire payload, (3)
    ``all_gather``s the payload arrays over ``axis_name`` — the
    collective carries the quantized codes — and (4) folds all n gathered
    messages into the (optionally masked) mean with the ONE-pass fused
    decode->reduce engine (O(d) server state, DESIGN.md §10; leafwise
    payload trees fall back to per-message decode + masked mean).  The
    downlink C_M runs shard-wise with the shared ``k_master``, bitwise
    identical to a master broadcast.

    On a 1-shard mesh with full participation this is bit-exact with
    :func:`compressed_average` (same key schedule, encode→decode ==
    apply, the SAME fused reduce over the same gathered arrays) — the
    equivalence the sharded rollout's headline test pins.

    ``client_comp`` may be a :class:`repro.fl.fleet.FleetPlan`.  A
    uniform fleet unwraps to the single-plan path above (keystone).  A
    MIXED fleet cannot group clients per shard (the shard's identity is
    a traced ``axis_index``, but cohort grouping must be static), so
    every shard encodes ALL of its local clients under EACH used cohort
    plan, gathers each cohort's payload batch over ``axis_name``, and
    weights client i by the STATIC 0/1 cohort-membership vector (× the
    participation mask × the finite guard) before the per-cohort fused
    fold — membership partitions the fleet, so each client contributes
    through exactly one cohort and the folded total divides once by the
    true participant weight.  The collective then moves every cohort's
    payload for every client (simulation-only overhead; the LEDGER still
    charges per-client ``round_bits(i)`` of the client's own plan —
    wire accounting and simulator collectives are decoupled, §13).

    ``client_comp`` may also be a length-n SEQUENCE of plans — a
    per-client plan vector (ROADMAP fleet headroom).  Structurally equal
    plans dedupe into cohorts (:func:`repro.fl.fleet.fleet_from_plans`),
    so the vector spelling is bit-exact vs manual cohort grouping by
    construction: n equal plans collapse to the uniform fleet and take
    the single-plan path; only genuinely distinct plans pay the mixed
    path (a true singleton cohort per client when all n differ).
    """
    up = _resolve_uplink(client_comp)
    down_plan = as_plan(master_comp)

    from repro.core import flatbuf
    if isinstance(up, CompressionPlan):
        up_plan = up

        def average_fn(key, params_local, mask=None):
            m = jax.tree_util.tree_leaves(params_local)[0].shape[0]
            k_clients, k_master = jax.random.split(key)
            # global key schedule, replicated; this shard's slice by index
            ckd = jax.random.key_data(jax.random.split(k_clients, n_clients))
            local_keys = jax.random.wrap_key_data(
                jax.lax.dynamic_slice_in_dim(
                    ckd, jax.lax.axis_index(axis_name) * m, m))
            # the stacked engine's stages (compressed_average), with the
            # all_gather between them named "gather"
            with jax.named_scope("uplink_encode"):
                payload = flatbuf.encode_clients(up_plan, local_keys,
                                                 params_local)
            ybar = _gather_reduce(up_plan, payload, (axis_name,),
                                  batched=True, mask=mask)
            with jax.named_scope("downlink"):
                return down_plan.apply(k_master, ybar)

        return average_fn

    fleet = up
    if fleet.n_clients != n_clients:
        raise ValueError(f"fleet covers {fleet.n_clients} clients; the "
                         f"sharded engine runs {n_clients}")

    def average_fn(key, params_local, mask=None):
        m = jax.tree_util.tree_leaves(params_local)[0].shape[0]
        k_clients, k_master = jax.random.split(key)
        ckd = jax.random.key_data(jax.random.split(k_clients, n_clients))
        local_keys = jax.random.wrap_key_data(jax.lax.dynamic_slice_in_dim(
            ckd, jax.lax.axis_index(axis_name) * m, m))
        base = jnp.ones((n_clients,), jnp.float32) if mask is None \
            else mask.reshape(-1).astype(jnp.float32)
        total, wsum = None, jnp.zeros((n_clients,), jnp.float32)
        for c in fleet.used_cohorts:
            plan_c = fleet.cohorts[c]
            member = jnp.asarray(
                [1.0 if a == c else 0.0 for a in fleet.assignment],
                jnp.float32)
            if plan_c.transport in ("flat", "packed"):
                with jax.named_scope("uplink_encode"):
                    payload = flatbuf.encode_clients(plan_c, local_keys,
                                                     params_local)
                gathered = _gather_payloads(payload, (axis_name,),
                                            batched=True)
                with jax.named_scope("server_reduce"):
                    fin = flatbuf.payload_finite_mask(gathered)
                    gathered = flatbuf.sanitize_payload(gathered, fin)
                    w = member * base * fin
                    layout = gathered.layout
                    acc = flatbuf.reduce_payload_acc(gathered, w)
                    part = flatbuf.unravel(
                        layout, flatbuf.unbucketize(acc, layout.d))
            else:
                with jax.named_scope("uplink_encode"):
                    contrib = jax.vmap(lambda k, p: plan_c.apply(k, p))(
                        local_keys, params_local)
                gathered = _gather_payloads(contrib, (axis_name,),
                                            batched=True)
                with jax.named_scope("server_reduce"):
                    fin = stacked_finite_mask(gathered)
                    w = member * base * fin
                    part = weighted_client_sum(gathered, w)
            with jax.named_scope("server_reduce"):
                part = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), part)
                total = part if total is None else jax.tree_util.tree_map(
                    jnp.add, total, part)
                wsum = wsum + w
        with jax.named_scope("server_reduce"):
            denom = jnp.sum(wsum)
            safe = jnp.where(denom > 0, denom, 1.0)
            ybar = jax.tree_util.tree_map(
                lambda s, a: (s / safe).astype(a.dtype), total, params_local)
        with jax.named_scope("downlink"):
            return down_plan.apply(k_master, ybar)

    return average_fn


def make_packed_sharded_average(mesh, client_axes: tuple,
                                param_pspecs_stacked,
                                master_comp, *,
                                levels: int = 127, bucket: int = 2048):
    """Kept QSGD-specific entry point: an ``average_fn`` whose uplink
    all_gather moves the packed int8 QSGD payload (~8.25 bits/element at
    bucket=2048).  Thin wrapper over :func:`make_payload_sharded_average`
    with a packed QSGD plan."""
    from repro.core.codec import make_plan
    plan = make_plan(QSGD(levels=levels, bucket=bucket), transport="packed")
    return make_payload_sharded_average(mesh, client_axes,
                                        param_pspecs_stacked, master_comp,
                                        plan)


def compressed_average_wire(key: jax.Array, params_local, master_comp,
                            axis_name: str, *, wire_dtype=jnp.bfloat16):
    """Beyond-paper TPU-native compressed aggregation (inside shard_map).

    ``params_local`` is THIS client's (unstacked) param pytree; the client
    axis is the mesh axis ``axis_name``.  Uplink: stochastic-round to
    ``wire_dtype`` then ``pmean`` — the collective moves narrow bytes.
    Downlink: C_M with a shared key (key must be identical across the
    client axis; pass a key derived from the step counter, not from
    per-client state).
    """
    k_up, k_master = jax.random.split(key)
    leaves, treedef = jax.tree_util.tree_flatten(params_local)
    up_keys = jax.random.split(k_up, len(leaves))
    narrow = [stochastic_round_cast(k, leaf.astype(jnp.float32), wire_dtype)
              for k, leaf in zip(up_keys, leaves)]
    meaned = [jax.lax.pmean(x, axis_name).astype(jnp.float32) for x in narrow]
    ybar = jax.tree_util.tree_unflatten(treedef, meaned)
    return as_plan(master_comp).apply(k_master, ybar)
