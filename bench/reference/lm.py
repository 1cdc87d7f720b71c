"""Plain float32 reference of the decoder LMs the benchmark trains:
GQA attention with rotary positions, RMSNorm, a SwiGLU feed-forward
layer or a top-k mixture of experts with per-sequence capacity, and a
unembedding tied to the embedding table.  Written from the equations,
in straightforward ``jax.numpy`` at ``precision="highest"``; it imports
nothing of the program and reads only the parameter tree's names.

``dtype=jnp.bfloat16`` computes the same equations with weights and
activations in bfloat16 (the lower-precision control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, eq):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(scale, x, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
    """x: (B, S, H, D); rotates the two halves of each head."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., : D // 2], xf[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


def attention(p, h, spec):
    B, S, _ = h.shape
    H, K, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q = _mm(h, p["wq"], "bsd,de->bse").reshape(B, S, H, D)
    k = _mm(h, p["wk"], "bsd,de->bse").reshape(B, S, K, D)
    v = _mm(h, p["wv"], "bsd,de->bse").reshape(B, S, K, D)
    q, k = rope(q, spec["rope_theta"]), rope(k, spec["rope_theta"])
    group = H // K
    q = q.reshape(B, S, K, group, D)
    s = _mm(q, k, "bskgd,btkd->bkgst").astype(jnp.float32) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = _mm(w, v, "bkgst,btkd->bskgd").reshape(B, S, H * D)
    return _mm(o, p["wo"], "bse,ed->bsd")


def swiglu(p, h):
    g = _mm(h, p["w_gate"], "bsd,df->bsf")
    u = _mm(h, p["w_up"], "bsd,df->bsf")
    return _mm(jax.nn.silu(g) * u, p["w_down"], "bsf,fd->bsd")


def moe(p, h, spec):
    """Top-k routing per token; each sequence is one routing group with
    capacity C = max(ceil(S k / E * factor), k) per expert.  Slots are
    filled slot-major: every token's first choice before any second
    choice, tokens in order within a slot; an assignment past C is
    dropped.  Returns (output, switch load-balance loss)."""
    B, S, d = h.shape
    E, k = spec["experts"], spec["experts_per_token"]
    C = max(int(math.ceil(S * k / E * spec["capacity_factor"])), k)
    gates = jax.nn.softmax(
        _mm(h, p["router"], "bsd,de->bse").astype(jnp.float32), axis=-1)
    top_v, top_i = jax.lax.top_k(gates, k)                     # (B,S,k)
    top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_i, E, dtype=jnp.int32)         # (B,S,k,E)
    order = onehot.transpose(0, 2, 1, 3).reshape(B, k * S, E)  # slot-major
    pos = jnp.cumsum(order, axis=1) - order                    # before me
    pos = jnp.sum(pos * order, axis=-1).reshape(B, k, S).transpose(0, 2, 1)
    kept = pos < C                                             # (B,S,k)
    slot = jax.nn.one_hot(jnp.where(kept, pos, C), C + 1)[..., :C]
    # combine[b, s, e, c]: gate weight of token s in slot c of expert e
    combine = jnp.einsum("bske,bskc,bsk->bsec", onehot.astype(jnp.float32),
                         slot, top_v * kept, precision=HIGHEST)
    dispatch = (combine > 0).astype(h.dtype)
    xin = _mm(dispatch, h, "bsec,bsd->becd")
    g = _mm(xin, p["w_gate"], "becd,edf->becf")
    u = _mm(xin, p["w_up"], "becd,edf->becf")
    y = _mm(jax.nn.silu(g) * u, p["w_down"], "becf,efd->becd")
    out = _mm(combine.astype(h.dtype), y, "bsec,becd->bsd")
    f = jnp.mean(jnp.sum(onehot.astype(jnp.float32), axis=2), axis=1)
    aux = jnp.mean(jnp.sum(f * jnp.mean(gates, axis=1), axis=-1)) * E
    return out, aux


def forward(params, tokens, spec, dtype=jnp.float32):
    """(logits f32 (B, S, V), summed MoE aux loss)."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed"]["table"][tokens]
    aux = jnp.zeros((), jnp.float32)
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    for i in range(L):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = x + attention(lp["attn"], rmsnorm(lp["ln1"]["scale"], x,
                                              spec["norm_eps"]), spec)
        h = rmsnorm(lp["ln2"]["scale"], x, spec["norm_eps"])
        if spec.get("experts"):
            y, a = moe(lp["ffn"], h, spec)
            aux = aux + a
        else:
            y = swiglu(lp["ffn"], h)
        x = x + y
    x = rmsnorm(params["final_norm"]["scale"], x, spec["norm_eps"])
    logits = _mm(x.astype(jnp.float32),
                 params["embed"]["table"].astype(jnp.float32), "bsd,vd->bsv")
    return logits, aux


def loss(params, tokens, spec, dtype=jnp.float32):
    """Mean next-token cross-entropy plus aux_weight x the MoE aux loss."""
    logits, aux = forward(params, tokens, spec, dtype)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll) + spec.get("aux_loss_weight", 0.0) * aux
