"""Unified config-driven model: dense / MoE / SSM / hybrid decoders plus the
Whisper-style encoder-decoder, with a scan-over-layers training path (HLO
size independent of depth — essential for the 512-device dry-run compiles)
and a per-layer decode path with heterogeneous caches (ring-buffer KV for
sliding-window layers, full KV for global layers, latent cache for MLA,
(conv, h) state for Mamba).

Public API:
  init_params(key, cfg)
  forward(params, cfg, batch)            -> (logits, aux_loss)
  loss_fn(params, cfg, batch)            -> (loss, metrics)
  layer_kinds(cfg)                       -> per-layer static descriptors
  init_caches(cfg, batch, capacity)      -> decode cache pytree
  decode_step(params, cfg, caches, index, batch) -> (logits, caches)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import blocks
from repro.models import mamba as mb
from repro.models import moe as moe_lib

__all__ = ["init_params", "forward", "loss_fn", "layer_kinds", "init_caches",
           "decode_step", "param_count"]


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# layer pattern
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerKind:
    is_global: bool       # full attention (vs sliding window)
    ffn: str              # dense | moe | none


def layer_kinds(cfg: ArchConfig):
    """Static per-layer descriptors (python list, drives cache layout and the
    scanned flag array)."""
    kinds = []
    for i in range(cfg.n_layers):
        if cfg.global_pattern == "every_k":
            is_global = (i % cfg.global_every) == (cfg.global_every - 1)
        elif cfg.global_pattern == "hymba":
            is_global = i in (0, cfg.n_layers // 2, cfg.n_layers - 1)
        else:
            is_global = True
        ffn = cfg.ffn if i >= cfg.first_dense_layers else "dense"
        kinds.append(LayerKind(is_global=is_global, ffn=ffn))
    return kinds


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _init_mixer(key, cfg: ArchConfig, dtype) -> dict:
    if cfg.mixer == "gqa":
        return {"attn": attn.init_gqa(key, cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.hd, dtype,
                                      qk_norm=cfg.qk_norm,
                                      layout=cfg.attn_layout)}
    if cfg.mixer == "mla":
        return {"attn": attn.init_mla(key, cfg.d_model, cfg.n_heads,
                                      cfg.kv_lora_rank, dtype,
                                      nope_dim=cfg.mla_nope_dim,
                                      rope_dim=cfg.mla_rope_dim,
                                      v_dim=cfg.mla_v_dim)}
    if cfg.mixer == "mamba":
        return {"mixer": mb.init_mamba(key, cfg.d_model, cfg.ssm_state,
                                       cfg.ssm_expand, cfg.ssm_conv,
                                       dtype=dtype)}
    if cfg.mixer == "hybrid":
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "attn": attn.init_gqa(k1, cfg.d_model, cfg.n_heads,
                                  cfg.n_kv_heads, cfg.hd, dtype,
                                  layout=cfg.attn_layout),
            "mamba": mb.init_mamba(k2, cfg.d_model, cfg.ssm_state,
                                   cfg.ssm_expand, cfg.ssm_conv, dtype=dtype),
            "norm_attn": blocks.init_rmsnorm(cfg.d_model, dtype),
            "norm_mamba": blocks.init_rmsnorm(cfg.d_model, dtype),
        }
    raise ValueError(cfg.mixer)


def _init_ffn(key, cfg: ArchConfig, kind: str, dtype) -> dict:
    if kind == "dense":
        return {"ffn": blocks.init_mlp(key, cfg.d_model, cfg.d_ff, dtype,
                                       fused=cfg.mlp_fused),
                "ln2": blocks.init_rmsnorm(cfg.d_model, dtype)}
    if kind == "moe":
        return {"ffn": moe_lib.init_moe(key, cfg.d_model, cfg.n_experts,
                                        cfg.n_shared_experts, cfg.moe_d_ff,
                                        dtype),
                "ln2": blocks.init_rmsnorm(cfg.d_model, dtype)}
    return {}  # none (mamba blocks)


def _init_layer(key, cfg: ArchConfig, kind: LayerKind, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"ln1": blocks.init_rmsnorm(cfg.d_model, dtype)}
    p.update(_init_mixer(k1, cfg, dtype))
    p.update(_init_ffn(k2, cfg, kind.ffn, dtype))
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _init_encdec_extra(key, cfg: ArchConfig, dtype) -> dict:
    """Whisper: encoder layer stack + cross-attention params in decoder."""
    ks = jax.random.split(key, cfg.encoder_layers + 1)
    enc_layers = []
    for i in range(cfg.encoder_layers):
        ka, kf = jax.random.split(ks[i])
        enc_layers.append({
            "ln1": blocks.init_rmsnorm(cfg.d_model, dtype),
            "attn": attn.init_mha(ka, cfg.d_model, cfg.n_heads, cfg.hd, dtype),
            "ln2": blocks.init_rmsnorm(cfg.d_model, dtype),
            "ffn": blocks.init_mlp(kf, cfg.d_model, cfg.d_ff, dtype),
        })
    return {"encoder": _stack(enc_layers),
            "encoder_norm": blocks.init_rmsnorm(cfg.d_model, dtype)}


def init_params(key, cfg: ArchConfig) -> dict:
    dtype = _dtype(cfg.param_dtype)
    kinds = layer_kinds(cfg)
    n_dense = cfg.first_dense_layers
    keys = jax.random.split(key, cfg.n_layers + 4)

    params: dict = {
        "embed": blocks.init_embedding(keys[0], cfg.vocab_size, cfg.d_model,
                                       dtype),
        "final_norm": blocks.init_rmsnorm(cfg.d_model, dtype),
    }
    if n_dense:
        params["dense_layers"] = _stack(
            [_init_layer(keys[1 + i], cfg, kinds[i], dtype)
             for i in range(n_dense)])
    params["layers"] = _stack(
        [_init_layer(keys[1 + i], cfg, kinds[i], dtype)
         for i in range(n_dense, cfg.n_layers)])
    if cfg.is_encdec:
        # decoder layers additionally carry cross-attention
        dec_cross = []
        for i in range(cfg.n_layers):
            ka = jax.random.fold_in(keys[-2], i)
            dec_cross.append({
                "ln_cross": blocks.init_rmsnorm(cfg.d_model, dtype),
                "attn": attn.init_mha(ka, cfg.d_model, cfg.n_heads, cfg.hd,
                                      dtype)})
        params["cross"] = _stack(dec_cross)
        params.update(_init_encdec_extra(keys[-1], cfg, dtype))
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# layer application (train / prefill)
# ---------------------------------------------------------------------------

def _attn_impl_train(cfg: ArchConfig) -> str:
    """Train-path attention kernel selection (DESIGN.md §15): the flash
    kernel needs a STATIC causal/window mask, so it is only safe when
    every layer is plain causal — ``sliding_window is None`` (the
    scan-over-layers path traces the per-layer global/local flag into
    the mask otherwise).  ``attn_impl="dense"`` (the default) keeps the
    historic fused-XLA softmax bit-exactly."""
    if cfg.attn_impl == "flash" and cfg.sliding_window is None:
        return "flash"
    return "dense"


def _apply_mixer_train(cfg: ArchConfig, lp: dict, x, positions, mask):
    if cfg.mixer == "gqa":
        out, _ = attn.gqa_attention(
            lp["attn"], x, positions, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.hd, theta=cfg.rope_theta,
            qk_norm=cfg.qk_norm, mask_override=mask,
            impl=_attn_impl_train(cfg))
        return out
    if cfg.mixer == "mla":
        out, _ = attn.mla_attention(
            lp["attn"], x, positions, n_heads=cfg.n_heads,
            kv_lora=cfg.kv_lora_rank, theta=cfg.rope_theta,
            nope_dim=cfg.mla_nope_dim, rope_dim=cfg.mla_rope_dim,
            v_dim=cfg.mla_v_dim)
        return out
    if cfg.mixer == "mamba":
        return mb.mamba_forward(lp["mixer"], x, d_state=cfg.ssm_state,
                                chunk=cfg.scan_chunk)
    if cfg.mixer == "hybrid":
        a, _ = attn.gqa_attention(
            lp["attn"], x, positions, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.hd, theta=cfg.rope_theta,
            mask_override=mask, impl=_attn_impl_train(cfg))
        m = mb.mamba_forward(lp["mamba"], x, d_state=cfg.ssm_state,
                             chunk=cfg.scan_chunk)
        return 0.5 * (blocks.rmsnorm(lp["norm_attn"], a)
                      + blocks.rmsnorm(lp["norm_mamba"], m))
    raise ValueError(cfg.mixer)


def _apply_ffn(cfg: ArchConfig, lp: dict, x, kind: str):
    if kind == "none":
        return x, jnp.zeros((), jnp.float32)
    h = blocks.rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if kind == "dense":
        return x + blocks.mlp(lp["ffn"], h, cfg.activation), \
            jnp.zeros((), jnp.float32)
    y, aux = moe_lib.moe_ffn(
        lp["ffn"], h, n_experts=cfg.n_experts, k=cfg.experts_per_token,
        capacity_factor=cfg.capacity_factor, impl=cfg.moe_impl,
        n_shared=cfg.n_shared_experts)
    return x + y, aux


def _decoder_layer_train(cfg: ArchConfig, ffn_kind: str, lp: dict, x,
                         positions, mask):
    # named for a device trace, as are the embedding and the unembedding
    # with its loss (forward, loss_fn)
    with jax.named_scope("model.attn"):
        h = blocks.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        x = x + _apply_mixer_train(cfg, lp, h, positions, mask)
    with jax.named_scope("model.ffn"):
        return _apply_ffn(cfg, lp, x, ffn_kind)


def _scan_layers(cfg: ArchConfig, stacked, flags, ffn_kind: str, x,
                 positions, mask_g, mask_w):
    """lax.scan over stacked layer params; flags: (L,) bool is_global.

    The causal/window mask is built INSIDE the body from iota (16 MB pred,
    fused into the masked softmax) rather than carried through the scan —
    carrying broadcast mask buffers showed up as a multi-hundred-MB while
    operand in the baseline HLO (§Perf iteration 'iota_mask')."""
    del mask_g, mask_w
    S = x.shape[-2]

    def body(carry, xs):
        h, aux = carry
        lp, flag = xs
        qi = jnp.arange(S)[:, None]
        kj = jnp.arange(S)[None, :]
        m = kj <= qi
        if cfg.sliding_window is not None:
            m = m & (flag | ((qi - kj) < cfg.sliding_window))
        mask = m[None, None]
        h, aux_l = _decoder_layer_train(cfg, ffn_kind, lp, h, positions, mask)
        return (h, aux + aux_l), None

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (stacked, flags))
    return x, aux


# ---------------------------------------------------------------------------
# forward / loss (decoder-only and enc-dec)
# ---------------------------------------------------------------------------

def _build_masks(cfg: ArchConfig, S: int):
    mask_g = attn.causal_mask(S, S)
    mask_w = (attn.causal_mask(S, S, cfg.sliding_window)
              if cfg.sliding_window is not None else None)
    return mask_g, mask_w


def _encoder_forward(params, cfg: ArchConfig, frames):
    B, F, _ = frames.shape
    x = frames + blocks.sinusoidal_positions(F, cfg.d_model)[None].astype(frames.dtype)

    def body(h, lp):
        a, _ = attn.mha_attention(lp["attn"],
                                  blocks.rmsnorm(lp["ln1"], h, cfg.norm_eps),
                                  blocks.rmsnorm(lp["ln1"], h, cfg.norm_eps),
                                  n_heads=cfg.n_heads, head_dim=cfg.hd)
        h = h + a
        h = h + blocks.mlp(lp["ffn"], blocks.rmsnorm(lp["ln2"], h, cfg.norm_eps),
                           cfg.activation)
        return h, None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return blocks.rmsnorm(params["encoder_norm"], x, cfg.norm_eps)


def _encdec_forward(params, cfg: ArchConfig, batch):
    enc_out = _encoder_forward(params, cfg, batch["frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = blocks.embed(params["embed"], tokens)
    x = x + blocks.sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
    mask = attn.causal_mask(S, S)

    def body(h, lps):
        lp, cp = lps
        sa, _ = attn.mha_attention(
            lp["attn"], blocks.rmsnorm(lp["ln1"], h, cfg.norm_eps),
            blocks.rmsnorm(lp["ln1"], h, cfg.norm_eps),
            n_heads=cfg.n_heads, head_dim=cfg.hd, mask=mask)
        h = h + sa
        ca, _ = attn.mha_attention(
            cp["attn"], blocks.rmsnorm(cp["ln_cross"], h, cfg.norm_eps),
            enc_out, n_heads=cfg.n_heads, head_dim=cfg.hd)
        h = h + ca
        h = h + blocks.mlp(lp["ffn"], blocks.rmsnorm(lp["ln2"], h, cfg.norm_eps),
                           cfg.activation)
        return h, None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, (params["layers"], params["cross"]))
    x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return blocks.unembed(params["embed"], x), jnp.zeros((), jnp.float32)


def forward(params, cfg: ArchConfig, batch):
    """batch: {"tokens": (B,S_text)} plus optional {"patches"|"frames":
    (B, n_frontend_tokens, d_model)}.  Returns (logits, aux_loss)."""
    if cfg.is_encdec:
        return _encdec_forward(params, cfg, batch)

    tokens = batch["tokens"]
    cdt = _dtype(cfg.compute_dtype)
    with jax.named_scope("model.embed"):
        x = blocks.embed(params["embed"], tokens).astype(cdt)
        if cfg.frontend == "vision" and "patches" in batch:
            x = jnp.concatenate([batch["patches"].astype(cdt), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mask_g, mask_w = _build_masks(cfg, S)
    kinds = layer_kinds(cfg)
    n_dense = cfg.first_dense_layers

    aux = jnp.zeros((), jnp.float32)
    if n_dense:
        flags = jnp.asarray([k.is_global for k in kinds[:n_dense]])
        x, a = _scan_layers(cfg, params["dense_layers"], flags, "dense", x,
                            positions, mask_g, mask_w)
        aux = aux + a
    flags = jnp.asarray([k.is_global for k in kinds[n_dense:]])
    x, a = _scan_layers(cfg, params["layers"], flags, cfg.ffn, x, positions,
                        mask_g, mask_w)
    aux = aux + a
    with jax.named_scope("model.unembed"):
        x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return blocks.unembed(params["embed"], x), aux


def loss_fn(params, cfg: ArchConfig, batch):
    """Next-token cross-entropy (+ MoE aux).  Frontend positions (vlm) are
    excluded from the loss."""
    logits, aux = forward(params, cfg, batch)
    tokens = batch["tokens"]
    with jax.named_scope("model.unembed"):
        if cfg.frontend == "vision" and "patches" in batch:
            logits = logits[:, batch["patches"].shape[1]:]
        loss = blocks.cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
    total = loss + cfg.aux_loss_weight * aux
    return total, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# decode path (serve_step)
# ---------------------------------------------------------------------------

def _layer_slice(stacked, i: int):
    return jax.tree.map(lambda a: a[i], stacked)


def init_caches(cfg: ArchConfig, batch: int, capacity: int):
    """Heterogeneous per-layer cache list.  Windowed layers get ring buffers
    of size min(window, capacity)."""
    dtype = _dtype(cfg.compute_dtype)
    caches = []
    for kind in layer_kinds(cfg):
        if cfg.mixer == "gqa":
            ring = (not kind.is_global) and cfg.sliding_window is not None
            cap = min(cfg.sliding_window, capacity) if ring else capacity
            caches.append(attn.init_kv_cache(batch, cap, cfg.n_kv_heads,
                                             cfg.hd, dtype))
        elif cfg.mixer == "mla":
            caches.append(attn.init_mla_cache(batch, capacity,
                                              cfg.kv_lora_rank,
                                              cfg.mla_rope_dim, dtype))
        elif cfg.mixer == "mamba":
            caches.append(mb.init_mamba_cache(batch, cfg.d_inner,
                                              cfg.ssm_state, cfg.ssm_conv,
                                              dtype))
        elif cfg.mixer == "hybrid":
            ring = (not kind.is_global) and cfg.sliding_window is not None
            cap = min(cfg.sliding_window, capacity) if ring else capacity
            caches.append({
                "attn": attn.init_kv_cache(batch, cap, cfg.n_kv_heads,
                                           cfg.hd, dtype),
                "mamba": mb.init_mamba_cache(batch,
                                             cfg.ssm_expand * cfg.d_model,
                                             cfg.ssm_state, cfg.ssm_conv,
                                             dtype)})
        else:
            raise ValueError(cfg.mixer)
        if cfg.is_encdec:
            # cross-attention KV over stubbed encoder frames
            caches[-1] = {"self": caches[-1],
                          "cross_k": jnp.zeros((batch, cfg.n_frontend_tokens,
                                                cfg.n_heads, cfg.hd), dtype),
                          "cross_v": jnp.zeros((batch, cfg.n_frontend_tokens,
                                                cfg.n_heads, cfg.hd), dtype)}
    return caches


def _decode_mixer(cfg: ArchConfig, lp, cache, x, index, kind: LayerKind):
    pos = jnp.full((x.shape[0], 1), index, jnp.int32)
    ring = (not kind.is_global) and cfg.sliding_window is not None
    if cfg.mixer == "gqa":
        out, cache = attn.gqa_attention(
            lp["attn"], x, pos, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.hd, theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
            cache=cache, cache_index=index, ring=ring)
        return out, cache
    if cfg.mixer == "mla":
        out, cache = attn.mla_attention(
            lp["attn"], x, pos, n_heads=cfg.n_heads, kv_lora=cfg.kv_lora_rank,
            theta=cfg.rope_theta, nope_dim=cfg.mla_nope_dim,
            rope_dim=cfg.mla_rope_dim, v_dim=cfg.mla_v_dim,
            cache=cache, cache_index=index)
        return out, cache
    if cfg.mixer == "mamba":
        return mb.mamba_decode_step(lp["mixer"], x, cache,
                                    d_state=cfg.ssm_state)
    if cfg.mixer == "hybrid":
        a, c_attn = attn.gqa_attention(
            lp["attn"], x, pos, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.hd, theta=cfg.rope_theta,
            cache=cache["attn"], cache_index=index, ring=ring)
        m, c_mamba = mb.mamba_decode_step(lp["mamba"], x, cache["mamba"],
                                          d_state=cfg.ssm_state)
        out = 0.5 * (blocks.rmsnorm(lp["norm_attn"], a)
                     + blocks.rmsnorm(lp["norm_mamba"], m))
        return out, {"attn": c_attn, "mamba": c_mamba}
    raise ValueError(cfg.mixer)


def decode_step(params, cfg: ArchConfig, caches, index, batch):
    """One-token serve step.  batch: {"tokens": (B,1)}.  ``index`` is the
    current position (cache fill level).  Returns (logits (B,1,V), caches)."""
    tokens = batch["tokens"]
    cdt = _dtype(cfg.compute_dtype)
    x = blocks.embed(params["embed"], tokens).astype(cdt)
    if cfg.is_encdec:
        # sinusoidal position embedding for the current step `index`
        x = x + blocks.sinusoidal_position_at(index, cfg.d_model)[None, None].astype(cdt)

    kinds = layer_kinds(cfg)
    n_dense = cfg.first_dense_layers
    new_caches = []
    for i, kind in enumerate(kinds):
        group = "dense_layers" if i < n_dense else "layers"
        li = i if i < n_dense else i - n_dense
        lp = _layer_slice(params[group], li)
        cache_i = caches[i]
        if cfg.is_encdec:
            cp = _layer_slice(params["cross"], li)
            h = blocks.rmsnorm(lp["ln1"], x, cfg.norm_eps)
            sa, new_self = _decode_mixer_mha(cfg, lp, cache_i["self"], h, index)
            x = x + sa
            hc = blocks.rmsnorm(cp["ln_cross"], x, cfg.norm_eps)
            ca, _ = attn.mha_attention(cp["attn"], hc, hc, n_heads=cfg.n_heads,
                                       head_dim=cfg.hd,
                                       precomputed_kv=(cache_i["cross_k"],
                                                       cache_i["cross_v"]))
            x = x + ca
            x = x + blocks.mlp(lp["ffn"],
                               blocks.rmsnorm(lp["ln2"], x, cfg.norm_eps),
                               cfg.activation)
            new_caches.append({"self": new_self, "cross_k": cache_i["cross_k"],
                               "cross_v": cache_i["cross_v"]})
            continue
        h = blocks.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        out, new_cache = _decode_mixer(cfg, lp, cache_i, h, index, kind)
        x = x + out
        x, _ = _apply_ffn(cfg, lp, x, kind.ffn)
        new_caches.append(new_cache)

    x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return blocks.unembed(params["embed"], x), new_caches


def _decode_mixer_mha(cfg: ArchConfig, lp, cache, x, index):
    """Whisper decoder self-attention decode (no RoPE, linear cache)."""
    B = x.shape[0]
    k = (x @ lp["attn"]["wk"]).reshape(B, 1, cfg.n_heads, cfg.hd)
    v = (x @ lp["attn"]["wv"]).reshape(B, 1, cfg.n_heads, cfg.hd)
    ck = jax.lax.dynamic_update_slice(cache.k, k, (0, index, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v, (0, index, 0, 0))
    q = (x @ lp["attn"]["wq"]).reshape(B, 1, cfg.n_heads, cfg.hd)
    valid = (jnp.arange(ck.shape[1]) <= index)[None, None, None, :]
    out = attn.attention_core(q, ck, cv, valid)
    out = out.reshape(B, 1, cfg.n_heads * cfg.hd) @ lp["attn"]["wo"]
    return out, attn.KVCache(ck, cv)
