"""Compressed L2GD — Algorithm 1 of the paper, as a jit-able step.

State layout: the n personalized models are a *stacked* pytree whose
leaves have a leading client axis (size n).  In the single-host simulator
that axis lives on one device; in the distributed runtime it is sharded
over the mesh's client ("data" × "pod") axes, and the same code produces
the collectives (see repro/launch).

The probabilistic protocol is a 3-way ``lax.switch``:

  branch 0  (xi_k = 0)                : local gradient step, NO communication
  branch 1  (xi_k = 1, xi_{k-1} = 0)  : aggregation with fresh compressed
                                        communication (uplink C_i, downlink C_M)
  branch 2  (xi_k = 1, xi_{k-1} = 1)  : aggregation against the cached
                                        target, NO communication

Step scalings follow the paper exactly: local ``eta/(n(1-p)) * grad f_i``,
aggregation ``(eta lam)/(n p) * (x_i - target)``.

Caching subtlety (documented deviation-free reading of Algorithm 1): after
a fresh-communication aggregation the devices cache the value they
actually received, ``t = C_M(ybar^k)``, and reuse it for consecutive
aggregation steps; at initialization the cache holds the exact
``xbar^{-1}`` (given as algorithm input).  In the uncompressed case
``t = xbar^k`` and the average is invariant across consecutive aggregation
steps, which is precisely the paper's statement.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import _resolve_uplink, compressed_average
from repro.core.codec import _UNSET, _legacy_transport, as_plan
from repro.core.compressors import Compressor, Identity

__all__ = ["L2GDHyper", "L2GDState", "init_state", "make_hyper", "l2gd_step",
           "local_update", "aggregation_update", "draw_xi"]


@dataclasses.dataclass(frozen=True)
class L2GDHyper:
    """Meta-parameters of Algorithm 1.

    ``eta``/``lam``/``p`` may be Python floats OR jax arrays/tracers: the
    class is a registered pytree (data = the three rates, meta = ``n``),
    so a whole rollout can be ``vmap``-ed over a (p, lambda, eta) grid
    (:func:`repro.core.rollout.rollout_l2gd_grid`) and hypers can cross a
    ``jit`` boundary as arguments instead of burned-in constants.  Python
    scalars still validate eagerly; array values validate in the
    :func:`make_hyper` build helper (a tracer cannot be range-checked)."""

    eta: Any            # stepsize
    lam: Any            # personalization penalty lambda
    p: Any              # aggregation probability
    n: int              # number of clients (static)

    def __post_init__(self):
        if isinstance(self.p, (int, float)) and not (0.0 < self.p < 1.0):
            raise ValueError(f"p must be in (0,1), got {self.p}")
        if isinstance(self.lam, (int, float)) and self.lam < 0.0:
            raise ValueError("lambda must be >= 0")

    @property
    def local_scale(self):
        return self.eta / (self.n * (1.0 - self.p))

    @property
    def agg_scale(self):
        # eta*lam/(n p); the paper observes best behaviour for values ~1 or <=0.17
        return self.eta * self.lam / (self.n * self.p)


jax.tree_util.register_dataclass(L2GDHyper, data_fields=["eta", "lam", "p"],
                                 meta_fields=["n"])


def make_hyper(eta, lam, p, n: int) -> L2GDHyper:
    """Validating build helper for (possibly array-valued) hypers.

    Accepts scalars or same-shaped arrays for ``eta``/``lam``/``p`` (a
    1-D grid axis for :func:`repro.core.rollout.rollout_l2gd_grid`);
    concrete values are range-checked elementwise, tracers pass through
    (validate before entering jit)."""
    for name, v in (("eta", eta), ("lam", lam), ("p", p)):
        if isinstance(v, jax.core.Tracer):
            continue
        a = np.asarray(v)
        if name == "p" and not bool(np.all((a > 0.0) & (a < 1.0))):
            raise ValueError(f"p must be in (0,1) elementwise, got {v}")
        if name == "lam" and not bool(np.all(a >= 0.0)):
            raise ValueError("lambda must be >= 0 elementwise")
    return L2GDHyper(eta=eta, lam=lam, p=p, n=int(n))


class L2GDState(NamedTuple):
    params: Any         # stacked client params, leading axis n
    cache: Any          # cached aggregation target (no client axis)
    xi_prev: jax.Array  # int32 scalar: xi_{k-1}
    step: jax.Array     # int32 scalar


def init_state(params_stacked) -> L2GDState:
    """xi_{-1} = 1 and cache = exact xbar^{-1}, per Algorithm 1's input line."""
    cache = jax.tree.map(lambda a: jnp.mean(a, axis=0), params_stacked)
    return L2GDState(params=params_stacked, cache=cache,
                     xi_prev=jnp.asarray(1, jnp.int32),
                     step=jnp.asarray(0, jnp.int32))


def local_update(params_stacked, grads_stacked, hp: L2GDHyper):
    """x_i <- x_i - eta/(n(1-p)) grad f_i(x_i), all clients at once.

    Precision policy (DESIGN.md §15): the update is computed in float32
    and rounded ONCE back to the parameter dtype.  For float32 params the
    casts are identities, so this is bit-identical to the historic
    ``x - s * g`` path; for bfloat16 params it avoids the silent f32
    promotion that ``f32_scalar * bf16`` would otherwise introduce (the
    result would no longer match the stacked state dtype) and keeps the
    rounding error to one rounding per step."""
    s = hp.local_scale
    return jax.tree.map(
        lambda x, g: (x.astype(jnp.float32)
                      - jnp.asarray(s, jnp.float32) * g.astype(jnp.float32)
                      ).astype(x.dtype),
        params_stacked, grads_stacked)


def aggregation_update(params_stacked, target, hp: L2GDHyper, mask=None):
    """x_i <- x_i - (eta lam)/(n p) (x_i - t); t broadcast over the client axis.

    ``mask`` (optional (n,) 0/1 array over the leading client axis) gates
    the update per client: non-participants of a partial-participation
    aggregation round keep their params (DESIGN.md §9).  ``mask=None`` is
    full participation and bit-identical to the historic path.
    """
    c = hp.agg_scale
    if mask is None:
        def one(x, t):
            xf = x.astype(jnp.float32)
            return (xf - jnp.asarray(c, jnp.float32)
                    * (xf - t[None].astype(jnp.float32))).astype(x.dtype)
        return jax.tree.map(one, params_stacked, target)

    def one(x, t):
        xf = x.astype(jnp.float32)
        mb = mask.reshape((x.shape[0],) + (1,) * (x.ndim - 1)).astype(jnp.float32)
        return (xf - jnp.asarray(c, jnp.float32) * mb
                * (xf - t[None].astype(jnp.float32))).astype(x.dtype)

    return jax.tree.map(one, params_stacked, target)


def draw_xi(key: jax.Array, p: float) -> jax.Array:
    return jax.random.bernoulli(key, p).astype(jnp.int32)


def l2gd_step(state: L2GDState, batch, xi_k: jax.Array, key: jax.Array,
              grad_fn: Callable, hp: L2GDHyper,
              client_comp: Compressor = Identity(),
              master_comp: Compressor = Identity(),
              average_fn: Callable = None, flat=_UNSET, *,
              participation_mask=None, axis_name: str = None,
              local_steps: int = 1):
    """One step of Algorithm 1.

    Args:
      state: current :class:`L2GDState`.
      batch: per-client batch pytree, leaves with leading client axis n.
      xi_k:  int32 scalar Bernoulli(p) draw for this step (drawn by the host
             driver so the bits ledger sees the protocol, or via
             :func:`draw_xi` under jit).
      key:   PRNG key for compressor randomness.
      grad_fn: per-client ``(params_i, batch_i) -> (loss_i, grads_i)``.
      hp:    hyper-parameters.
      client_comp / master_comp: the uplink C_i and downlink C_M — each
             either a :class:`repro.core.codec.CompressionPlan` or a
             plain Compressor (coerced with auto transport: flat-buffer
             engine where supported, the single-host default).
             ``client_comp`` additionally accepts a :class:`repro.fl.
             fleet.FleetPlan` (per-cohort C_i, DESIGN.md §13); a uniform
             fleet unwraps to the single-plan path bit-exactly.
      average_fn: optional override of the compressed-average realization,
             ``(key, params_stacked) -> target`` — used by the beyond-paper
             wire-compressed shard_map aggregation (see repro.launch.steps).
             When ``participation_mask`` is given it is called with a third
             positional argument, the GLOBAL (n,) participation mask.
      flat:  DEPRECATED shim — pass CompressionPlans instead (the pjit
             runtime pins ``transport="leafwise"`` on its plans).
      participation_mask: optional GLOBAL (n,) 0/1 participant mask for
             this step's aggregation round (DESIGN.md §9): only masked-in
             clients contribute to the average and only they move in the
             aggregation update.  Local gradient steps are unaffected
             (local work costs no communication).  ``None`` = full
             participation, bit-identical to the historic step.
      axis_name: client mesh axis when the step executes INSIDE a
             shard_map whose leading client axis is sharded (the
             client-sharded rollout engine, repro.core.rollout.
             rollout_l2gd_sharded): loss means become psum reductions over
             the axis and the participation mask is sliced to this
             shard's clients by ``lax.axis_index``.  Requires an
             ``average_fn`` that performs the cross-shard collective
             (repro.core.aggregation.make_client_sharded_average).
      local_steps: LoCoDL-style local-training burst H >= 1 (DESIGN.md
             §15): a protocol step whose xi draw selects the LOCAL branch
             runs H gradient steps on this step's batch before returning.
             Aggregation branches are unaffected, so the wire cost of a
             round is charged once regardless of H (the ledger replays xi
             transitions, not gradient passes).  ``local_steps=1`` is
             structurally identical to the historic step (the extra-pass
             loop body is simply absent from the trace) — bit-exact.

    Returns: (new_state, metrics dict).  Metrics include the mean client
    loss — evaluated at the PRE-update params on every branch, so the
    loss trace has one entry per protocol step regardless of the xi
    realization (a high-p run used to yield an empty trace) — and the
    branch id.  The aggregation branches only use grad_fn's loss output;
    XLA dead-code-eliminates the gradient computation there.
    """
    if not isinstance(local_steps, int) or local_steps < 1:
        raise ValueError(f"local_steps must be an int >= 1, got {local_steps}")
    transport = None
    if flat is not _UNSET:
        transport = _legacy_transport(flat, "l2gd_step(..., flat=)")
    up_plan = _resolve_uplink(client_comp, transport)
    down_plan = as_plan(master_comp, transport)
    if axis_name is not None and average_fn is None:
        raise ValueError(
            "l2gd_step(axis_name=...) runs inside a client-sharded "
            "shard_map and needs an average_fn that spans the sharded "
            "axis (repro.core.aggregation.make_client_sharded_average); "
            "the default compressed_average would only see this shard's "
            "clients")
    branch = jnp.where(xi_k == 0, 0, jnp.where(state.xi_prev == 0, 1, 2))

    def _reduce_losses(losses):
        # unsharded: the historic jnp.mean (bit-exactness contract with
        # the host loop); sharded: each shard sums its local clients and
        # the psum'd total is divided by the GLOBAL n
        if axis_name is None:
            return jnp.mean(losses).astype(jnp.float32)
        total = jax.lax.psum(jnp.sum(losses), axis_name)
        return (total / hp.n).astype(jnp.float32)

    local_mask = participation_mask
    if participation_mask is not None and axis_name is not None:
        m = jax.tree_util.tree_leaves(state.params)[0].shape[0]
        local_mask = jax.lax.dynamic_slice_in_dim(
            participation_mask, jax.lax.axis_index(axis_name) * m, m)

    def _mean_loss(st):
        with jax.named_scope("loss"):
            losses, _ = jax.vmap(grad_fn)(st.params, batch)
            return _reduce_losses(losses)

    def _local_pass(params):
        with jax.named_scope("grad"):
            losses, grads = jax.vmap(grad_fn)(params, batch)
        with jax.named_scope("update"):
            return losses, local_update(params, grads, hp)

    # each branch runs under a named scope (l2gd.local, l2gd.agg_fresh,
    # l2gd.agg_cached) with named stages inside: a device trace then
    # gives every op its branch and stage
    @jax.named_scope("l2gd.local")
    def branch_local(op):
        st, k = op
        losses, new_params = _local_pass(st.params)
        # LoCoDL burst: H-1 further passes on the SAME batch (unrolled —
        # H is static and small).  The reported loss stays the pre-update
        # loss of the first pass, so the trace semantics match H=1.
        for _ in range(local_steps - 1):
            _, new_params = _local_pass(new_params)
        return (L2GDState(new_params, st.cache, jnp.asarray(0, jnp.int32),
                          st.step + 1),
                _reduce_losses(losses))

    @jax.named_scope("l2gd.agg_fresh")
    def branch_agg_fresh(op):
        st, k = op
        with jax.named_scope("average"):
            if average_fn is not None:
                if participation_mask is None:
                    target = average_fn(k, st.params)
                else:
                    target = average_fn(k, st.params, participation_mask)
            else:
                target = compressed_average(k, st.params, up_plan,
                                            down_plan,
                                            mask=participation_mask)
        with jax.named_scope("apply"):
            new_params = aggregation_update(st.params, target, hp,
                                            mask=local_mask)
        return (L2GDState(new_params, target, jnp.asarray(1, jnp.int32),
                          st.step + 1),
                _mean_loss(st))

    @jax.named_scope("l2gd.agg_cached")
    def branch_agg_cached(op):
        st, k = op
        with jax.named_scope("apply"):
            new_params = aggregation_update(st.params, st.cache, hp,
                                            mask=local_mask)
        return (L2GDState(new_params, st.cache, jnp.asarray(1, jnp.int32),
                          st.step + 1),
                _mean_loss(st))

    new_state, loss = jax.lax.switch(
        branch, [branch_local, branch_agg_fresh, branch_agg_cached],
        (state, key))
    return new_state, {"loss": loss, "branch": branch}
