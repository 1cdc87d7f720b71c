"""Mixture-of-Experts FFN with token-choice top-k routing.

Two dispatch implementations, selectable per config:

* ``gather`` (default): capacity-bounded scatter/gather dispatch.  Tokens
  are assigned positions inside each expert's capacity buffer with a
  cumulative count; an index map (expert, slot) -> token drives a gather
  into (E, C, d) buffers and a gather back for the combine.  No one-hot
  einsum, so HLO FLOPs stay honest (important for the roofline's
  MODEL_FLOPS / HLO_FLOPS ratio) and the big (S, E, C) tensor never exists.
* ``einsum`` (reference): classic GShard one-hot dispatch/combine einsum.
  Used as the oracle in tests and as a fallback if SPMD partitioning of the
  scatter path regresses.

Routing groups: capacity is computed per group (= per sequence in training,
per request batch in decode), C = ceil(S * k / E * capacity_factor).
Overflowing tokens are dropped for the routed contribution (standard
capacity semantics); the shared experts (DeepSeek-style) always run.

The router aux loss is the switch-transformer load-balance loss
``E * sum_e f_e * P_e`` computed per group and averaged.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.blocks import dense_init

__all__ = ["init_moe", "moe_ffn", "moe_capacity"]


def init_moe(key, d_model: int, n_experts: int, n_shared: int, moe_d_ff: int,
             dtype) -> dict:
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d_model, n_experts), dtype),
        "w_gate": dense_init(ks[1], (n_experts, d_model, moe_d_ff), dtype),
        "w_up": dense_init(ks[2], (n_experts, d_model, moe_d_ff), dtype),
        "w_down": dense_init(ks[3], (n_experts, moe_d_ff, d_model), dtype),
    }
    if n_shared > 0:
        ff = n_shared * moe_d_ff
        p["shared_gate"] = dense_init(ks[4], (d_model, ff), dtype)
        p["shared_up"] = dense_init(ks[5], (d_model, ff), dtype)
        p["shared_down"] = dense_init(ks[6], (ff, d_model), dtype)
    return p


def moe_capacity(tokens_per_group: int, n_experts: int, k: int,
                 capacity_factor: float) -> int:
    c = int(math.ceil(tokens_per_group * k / n_experts * capacity_factor))
    return max(c, k)


def _route(x, router, k: int):
    """x: (G,S,d) -> (gates (G,S,E) fp32, topv (G,S,k), topi (G,S,k))."""
    logits = (x @ router).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)
    topv = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
    return gates, topv, topi


def _aux_loss(gates, topi, n_experts: int) -> jax.Array:
    """Switch load-balance loss per group, averaged."""
    G, S, _ = gates.shape
    # fraction of (token, slot) assignments per expert
    assign = jax.nn.one_hot(topi, n_experts, dtype=jnp.float32)  # (G,S,k,E)
    f = jnp.mean(jnp.sum(assign, axis=2), axis=1)                # (G,E)
    P = jnp.mean(gates, axis=1)                                  # (G,E)
    return jnp.mean(jnp.sum(f * P, axis=-1)) * n_experts


def _experts_apply(params, expert_in):
    """expert_in: (G,E,C,d) -> (G,E,C,d) through the gated-MLP experts."""
    h_gate = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, params["w_gate"]))
    h_up = jnp.einsum("gecd,edf->gecf", expert_in, params["w_up"])
    return jnp.einsum("gecf,efd->gecd", h_gate * h_up, params["w_down"])


def _moe_gather(params, x, *, n_experts: int, k: int, capacity: int):
    """Scatter/gather dispatch.  x: (G,S,d).  Its stages are named for a
    device trace: moe.route, moe.dispatch, moe.experts, moe.combine."""
    G, S, d = x.shape
    E, C = n_experts, capacity
    with jax.named_scope("moe.route"):
        gates, topv, topi = _route(x, params["router"], k)

        # position of each (slot, token) inside its expert's capacity
        # buffer.  SLOT-MAJOR priority (all slot-0 assignments first),
        # matching GShard — the einsum reference loops slots the same
        # way, so capacity drops are identical between the two
        # implementations.
        flat_e = topi.swapaxes(1, 2).reshape(G, S * k)             # (G,k*S)
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)            # (G,k*S,E)
        pos_all = jnp.cumsum(oh, axis=1) - oh                # count before
        pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=-1)[..., 0]
        keep = pos < C                                             # (G,k*S)

        # index map (expert*C + pos) -> flat token index (slot-major);
        # dropped -> sentinel
        token_idx = jnp.arange(S * k, dtype=jnp.int32)[None, :] % S
        token_idx = jnp.broadcast_to(token_idx, (G, S * k))
        dest = flat_e * C + pos                                    # (G,S*k)
        dest = jnp.where(keep, dest, E * C)                  # overflow bin
        buf = jnp.full((G, E * C + 1), S, dtype=jnp.int32)   # S = pad token
        buf = jax.vmap(lambda b, d_, t: b.at[d_].set(t))(buf, dest, token_idx)
        idx_map = buf[:, : E * C].reshape(G, E, C)                 # (G,E,C)

    with jax.named_scope("moe.dispatch"):
        x_pad = jnp.concatenate([x, jnp.zeros((G, 1, d), x.dtype)], axis=1)
        expert_in = jnp.take_along_axis(
            x_pad[:, :, None, :].swapaxes(1, 2),             # (G,1,S+1,d)
            jnp.broadcast_to(idx_map[..., None], (G, E, C, 1)), axis=2)
    with jax.named_scope("moe.experts"):
        expert_out = _experts_apply(params, expert_in)             # (G,E,C,d)

    # combine: gather each kept (slot, token)'s output and weight by gate
    with jax.named_scope("moe.combine"):
        out_flat = expert_out.reshape(G, E * C, d)
        src = jnp.where(keep, flat_e * C + pos, 0)
        gathered = jnp.take_along_axis(
            out_flat, src[..., None].astype(jnp.int32), axis=1)    # (G,k*S,d)
        w = (topv.swapaxes(1, 2).reshape(G, S * k) * keep).astype(
            gathered.dtype)
        y = jnp.sum((gathered * w[..., None]).reshape(G, k, S, d), axis=1)
    with jax.named_scope("moe.route"):
        return y, _aux_loss(gates, topi, E)


def _moe_einsum(params, x, *, n_experts: int, k: int, capacity: int):
    """GShard one-hot reference implementation.  x: (G,S,d).  Stages
    named as in :func:`_moe_gather`."""
    G, S, d = x.shape
    E, C = n_experts, capacity
    with jax.named_scope("moe.route"):
        gates, topv, topi = _route(x, params["router"], k)

        counts = jnp.zeros((G, E), jnp.int32)
        combine = jnp.zeros((G, S, E, C), jnp.float32)
        for j in range(k):
            oh = jax.nn.one_hot(topi[..., j], E, dtype=jnp.int32)  # (G,S,E)
            prior = counts[:, None, :] + jnp.cumsum(oh, axis=1) - oh
            pos_tok = jnp.sum(prior * oh, axis=-1)                 # (G,S)
            keep = (pos_tok < C) & (jnp.sum(oh, -1) > 0)
            slot_oh = jax.nn.one_hot(pos_tok, C, dtype=jnp.float32)
            combine = combine + (oh.astype(jnp.float32)[..., None]
                                 * slot_oh[:, :, None, :]
                                 * (topv[..., j] * keep)[..., None, None])
            counts = counts + jnp.sum(oh, axis=1)
    with jax.named_scope("moe.dispatch"):
        dispatch = (combine > 0).astype(x.dtype)
        expert_in = jnp.einsum("gsec,gsd->gecd", dispatch, x)
    with jax.named_scope("moe.experts"):
        expert_out = _experts_apply(params, expert_in)
    with jax.named_scope("moe.combine"):
        y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), expert_out)
    with jax.named_scope("moe.route"):
        return y, _aux_loss(gates, topi, E)


def moe_ffn(params: dict, x: jax.Array, *, n_experts: int, k: int,
            capacity_factor: float = 1.25, impl: str = "gather",
            n_shared: int = 0):
    """MoE FFN over x: (B, S, d) (B = routing groups).  Returns (y, aux)."""
    B, S, d = x.shape
    C = moe_capacity(S, n_experts, k, capacity_factor)
    fn = _moe_gather if impl == "gather" else _moe_einsum
    y, aux = fn(params, x, n_experts=n_experts, k=k, capacity=C)
    if n_shared > 0:
        gate = jax.nn.silu(x @ params["shared_gate"])
        y = y + (gate * (x @ params["shared_up"])) @ params["shared_down"]
    return y, aux
