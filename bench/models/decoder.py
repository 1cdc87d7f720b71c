"""The decoder LMs of ``configs/*.json`` with ``"model": "decoder"``:
GQA attention with rotary positions, RMSNorm, a SwiGLU feed-forward
layer or a top-k mixture of experts, and an unembedding tied to the
embedding table.  Configuration keys are the Hugging Face names; where
experts exist, ``intermediate_size`` is the expert width.

The five functions every ``models/<model>.py`` gives (see
``bench/harness.py``); the plain reference is ``bench/reference/lm.py``.
"""
from __future__ import annotations

import dataclasses

from bench.reference.lm import loss  # noqa: F401  (the model's reference)
from repro.configs.base import get_config

#: configuration-file keys (Hugging Face names) -> the program's fields
_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
         "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
         "vocab_size": "vocab_size", "rope_theta": "rope_theta",
         "rms_norm_eps": "norm_eps"}
_MOE_KEYS = {"num_local_experts": "n_experts",
             "num_experts_per_tok": "experts_per_token",
             "intermediate_size": "moe_d_ff",
             "capacity_factor": "capacity_factor",
             "router_aux_loss_coef": "aux_loss_weight"}


def program_config(spec: dict):
    """The program's ArchConfig for a configuration file."""
    ch = {field: spec[key] for key, field in _KEYS.items()}
    ch["d_ff"] = spec["intermediate_size"]
    if spec.get("num_local_experts"):
        ch.update({field: spec[key] for key, field in _MOE_KEYS.items()})
    ch["param_dtype"] = ch["compute_dtype"] = spec["dtype"]
    return dataclasses.replace(get_config(spec["program_arch"]), **ch)


def reference_spec(spec: dict) -> dict:
    """The widths the reference and the FLOP count read."""
    out = {"d_model": spec["hidden_size"],
           "layers": spec["num_hidden_layers"],
           "heads": spec["num_attention_heads"],
           "kv_heads": spec["num_key_value_heads"],
           "head_dim": spec["head_dim"], "vocab": spec["vocab_size"],
           "d_ff": spec["intermediate_size"],
           "rope_theta": float(spec["rope_theta"]),
           "norm_eps": float(spec["rms_norm_eps"])}
    if spec.get("num_local_experts"):
        out.update(experts=spec["num_local_experts"],
                   experts_per_token=spec["num_experts_per_tok"],
                   expert_width=spec["intermediate_size"],
                   capacity_factor=float(spec["capacity_factor"]),
                   aux_loss_weight=float(spec["router_aux_loss_coef"]))
    return out


def matmul_params_per_token(rspec: dict) -> int:
    """The matmul parameters one token goes through: the attention
    projections, the feed-forward layer (the router and the k experts
    a token is sent to) and the tied unembedding.  The embedding lookup
    is not a matmul."""
    d, H, K, D = rspec["d_model"], rspec["heads"], rspec["kv_heads"], \
        rspec["head_dim"]
    attn = d * (H + 2 * K) * D + H * D * d
    if rspec.get("experts"):
        ffn = d * rspec["experts"] + rspec["experts_per_token"] * 3 * d \
            * rspec["expert_width"]
    else:
        ffn = 3 * d * rspec["d_ff"]
    return rspec["layers"] * (attn + ffn) + rspec["vocab"] * d


def train_flops_per_token(rspec: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 x the matmul parameters (forward
    2, backward 4) plus causal-free attention scores and values (12 S H
    D per layer, forward and backward: the program computes the full
    masked S x S matrix).  Recomputation under ``remat`` is not
    counted."""
    attn_scores = 12 * seq * rspec["heads"] * rspec["head_dim"] \
        * rspec["layers"]
    return 6.0 * matmul_params_per_token(rspec) + attn_scores


def tiny(spec: dict) -> dict:
    """The keys to change for a CPU size: about 0.6M elements per
    client, enough for the aggregation statistics (in units of
    1/sqrt(d)) to tell a planted fault apart."""
    out = dict(hidden_size=128, intermediate_size=192, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               vocab_size=4096)
    if spec.get("num_local_experts"):
        out.update(num_local_experts=4, num_experts_per_tok=2)
    return out
