import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination against ShapeDtypeStruct inputs — no allocation, 512
placeholder host devices (the two lines above MUST precede every other
import; jax locks the device count on first init).

Per combination this records memory_analysis, cost_analysis, and the
collective schedule (parsed from the optimized HLO) into a JSON artifact
under experiments/dryrun/, which EXPERIMENTS.md §Dry-run / §Roofline and
benchmarks/roofline.py read.

Usage:
  python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, ArchConfig, get_config
from repro.core import L2GDHyper, make_compressor
from repro.launch.mesh import client_axes, make_production_mesh, n_clients_of
from repro.launch.roofline import (analytic_flops, collective_stats,
                                   model_flops, roofline_terms)
from repro.launch.sharding import (batch_pspec, cache_pspecs, param_pspecs,
                                   tree_shardings)
from repro.launch.steps import (build_prefill_step, build_serve_step,
                                build_train_step, cache_specs, input_specs,
                                param_shapes, state_specs)

#: the chip the placeholder-device meshes stand for (a v5e pod slice);
#: its peaks come from repro.launch.roofline.PEAKS
TARGET_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _production_cfg(cfg: ArchConfig) -> ArchConfig:
    """bf16 params/compute for the at-scale dry-run (production numerics)."""
    return dataclasses.replace(cfg, param_dtype="bfloat16",
                               compute_dtype="bfloat16")


def n_params_active(cfg: ArchConfig) -> float:
    """Active parameters per token, for MODEL_FLOPS = 6 N_active D."""
    d, L = cfg.d_model, cfg.n_layers
    if cfg.mixer == "mla":
        attn = d * cfg.n_heads * (cfg.mla_nope_dim + cfg.mla_rope_dim) \
            + d * (cfg.kv_lora_rank + cfg.mla_rope_dim) \
            + cfg.kv_lora_rank * cfg.n_heads * (cfg.mla_nope_dim + cfg.mla_v_dim) \
            + cfg.n_heads * cfg.mla_v_dim * d
    elif cfg.mixer == "mamba":
        e = cfg.ssm_expand * d
        attn = 2 * d * e + e * (max(d // 16, 1) + 2 * cfg.ssm_state) \
            + max(d // 16, 1) * e + e * d
    elif cfg.mixer == "hybrid":
        e = cfg.ssm_expand * d
        attn = d * cfg.hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
            + cfg.n_heads * cfg.hd * d \
            + 2 * d * e + e * (max(d // 16, 1) + 2 * cfg.ssm_state) \
            + max(d // 16, 1) * e + e * d
    else:
        attn = d * cfg.hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
            + cfg.n_heads * cfg.hd * d
    if cfg.ffn == "moe":
        ffn = 3 * d * cfg.moe_d_ff * (cfg.experts_per_token
                                      + cfg.n_shared_experts)
    elif cfg.ffn == "none":
        ffn = 0
    else:
        ffn = 3 * d * cfg.d_ff
    emb = cfg.vocab_size * d  # unembed matmul is per-token compute
    enc = 0
    if cfg.is_encdec:
        enc = cfg.encoder_layers * (4 * d * cfg.n_heads * cfg.hd + 3 * d * cfg.d_ff)
        attn += 4 * d * cfg.n_heads * cfg.hd  # cross attention
    return float(L * (attn + ffn) + emb + enc)


def lower_one(arch: str, shape_name: str, multi_pod: bool,
              donate: bool = True, variant: str = "baseline",
              cfg_overrides: dict = None):
    """Returns (lowered, compiled, meta) for one combination.

    variant:
      baseline  — paper-faithful compressed aggregation (stacked mean +
                  shared-key C_M)
      wire_agg  — beyond-paper shard_map aggregation: stochastic-bf16
                  uplink pmean (narrow wire) + shared-key C_M downlink
      packed_agg / packed_natural_agg
                — shard_map aggregation whose all_gather uplink carries
                  the packed wire payload of a qsgd / natural
                  CompressionPlan (repro.core.codec)
    cfg_overrides — dataclasses.replace kwargs on the arch config (used by
                  §Perf iterations, e.g. {"moe_impl": "einsum"}).
    """
    cfg = _production_cfg(get_config(arch))
    if variant == "split_qkv":
        cfg = dataclasses.replace(cfg, attn_layout="split")
    if variant in ("dots_remat", "elemwise_dots"):
        cfg = dataclasses.replace(cfg, remat_policy="dots")
    if variant == "fused_mlp_dots":
        cfg = dataclasses.replace(cfg, remat_policy="dots", mlp_fused=True)
    if variant == "qkv_fused_dots":
        cfg = dataclasses.replace(cfg, remat_policy="dots",
                                  attn_layout="qkv_fused")
    if variant == "allfused_dots":
        cfg = dataclasses.replace(cfg, remat_policy="dots", mlp_fused=True,
                                  attn_layout="qkv_fused")
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cax = client_axes(mesh)
    n_clients = n_clients_of(mesh)
    model_size = mesh.shape["model"]
    axis_sizes = dict(mesh.shape)

    if shape.kind == "decode" and shape_name == "long_500k" \
            and not cfg.supports_long_context():
        return None, None, {"skipped": "full-attention arch at 500k "
                            "(see DESIGN.md §4)"}

    batch_sds = input_specs(cfg, shape, n_clients)

    with mesh:
        if shape.kind == "train":
            hp = L2GDHyper(eta=0.3, lam=10.0, p=0.25, n=n_clients)
            state_sds = state_specs(cfg, n_clients)
            pspec = param_pspecs(state_sds.params, model_size, cax)
            average_fn = None
            if variant == "wire_agg":
                from repro.launch.steps import build_average_fn
                average_fn = build_average_fn(
                    mesh, cax, pspec, make_compressor("natural"),
                    uplink="wire")
            elif variant in ("packed_agg", "packed_natural_agg"):
                from repro.core.codec import make_plan
                from repro.launch.steps import build_average_fn
                up_name = ("qsgd" if variant == "packed_agg" else "natural")
                one_client = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                    state_sds.params)
                up_plan = make_plan(make_compressor(up_name), one_client,
                                    transport="packed")
                average_fn = build_average_fn(
                    mesh, cax, pspec, make_compressor("natural"),
                    uplink=up_plan)
            step = build_train_step(cfg, hp, make_compressor("natural"),
                                    make_compressor("natural"),
                                    average_fn=average_fn)
            cache_pspec = param_pspecs(state_sds.cache, model_size, ())
            state_sh = type(state_sds)(
                params=tree_shardings(mesh, pspec),
                cache=tree_shardings(mesh, cache_pspec),
                xi_prev=NamedSharding(mesh, P()),
                step=NamedSharding(mesh, P()))
            if variant == "zero3":
                # beyond-paper: shard the per-client batch over the model
                # axis (ZeRO-style) instead of pure tensor parallelism
                batch_sh = jax.tree.map(
                    lambda s: NamedSharding(mesh, P(
                        cax if len(cax) > 1 else cax[0], "model",
                        *([None] * (len(s.shape) - 2)))), batch_sds)
            else:
                batch_sh = jax.tree.map(
                    lambda s: NamedSharding(mesh, batch_pspec(cax, len(s.shape) - 1)),
                    batch_sds)
            xi_sds = jax.ShapeDtypeStruct((), jnp.int32)
            key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
            rep = NamedSharding(mesh, P())
            fn = jax.jit(step,
                         in_shardings=(state_sh, batch_sh, rep, rep),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,) if donate else ())
            lowered = fn.lower(state_sds, batch_sds, xi_sds, key_sds)
        elif shape.kind == "prefill":
            step = build_prefill_step(cfg)
            p_sds = param_shapes(cfg)
            p_sh = tree_shardings(mesh, param_pspecs(p_sds, model_size, ()))
            batch_sh = jax.tree.map(
                lambda s: NamedSharding(
                    mesh, batch_pspec(cax, len(s.shape) - 1)), batch_sds)
            fn = jax.jit(step, in_shardings=(p_sh, batch_sh),
                         out_shardings=None)
            lowered = fn.lower(p_sds, batch_sds)
        else:  # decode
            step = build_serve_step(cfg)
            p_sds = param_shapes(cfg)
            p_sh = tree_shardings(mesh, param_pspecs(p_sds, model_size, (),
                                                     serve_mode=True))
            c_sds = cache_specs(cfg, shape.global_batch, shape.seq_len)
            lead = cax if len(cax) > 1 else cax[0]
            batch_axis = lead if shape.global_batch % n_clients == 0 \
                and shape.global_batch > 1 else None
            seq_axis = lead if batch_axis is None else None
            c_sh = tree_shardings(mesh, cache_pspecs(
                c_sds, model_size, batch_axis=batch_axis, seq_axis=seq_axis,
                axis_sizes=axis_sizes))
            b_sh = jax.tree.map(
                lambda s: NamedSharding(
                    mesh, P(batch_axis, *([None] * (len(s.shape) - 1)))),
                batch_sds)
            idx_sds = jax.ShapeDtypeStruct((), jnp.int32)
            rep = NamedSharding(mesh, P())
            fn = jax.jit(step, in_shardings=(p_sh, c_sh, rep, b_sh),
                         out_shardings=(None, c_sh),
                         donate_argnums=(1,) if donate else ())
            lowered = fn.lower(p_sds, c_sds, idx_sds, batch_sds)
        compiled = lowered.compile()

    tokens = (shape.global_batch * shape.seq_len if shape.kind != "decode"
              else shape.global_batch)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": list(mesh.devices.shape),
            "mesh_axes": list(mesh.axis_names),
            "n_clients": n_clients, "kind": shape.kind, "tokens": tokens}
    return lowered, compiled, meta


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            keep_hlo: bool = False, variant: str = "baseline",
            cfg_overrides: dict = None) -> dict:
    t0 = time.time()
    tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
    if variant != "baseline":
        tag += f"__{variant}"
    try:
        lowered, compiled, meta = lower_one(arch, shape_name, multi_pod,
                                            variant=variant,
                                            cfg_overrides=cfg_overrides)
    except Exception as e:  # a failure here is a bug in the system
        rec = {"tag": tag, "status": "FAIL", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        _write(out_dir, tag, rec)
        return rec
    if lowered is None:
        rec = {"tag": tag, "status": "SKIP", "arch": arch,
               "shape": shape_name, **meta}
        _write(out_dir, tag, rec)
        return rec

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    chips = 512 if multi_pod else 256
    # collectives inside while bodies run once per scanned layer
    coll = collective_stats(hlo, loop_trip=cfg.n_layers)
    n_act = n_params_active(cfg)
    flops_global = analytic_flops(cfg, shape, n_act)
    flops_dev = flops_global / chips
    # HBM traffic proxy: args read + outputs written + 2x temp arena
    arg_b = getattr(mem, "argument_size_in_bytes", 0) or 0
    out_b = getattr(mem, "output_size_in_bytes", 0) or 0
    tmp_b = getattr(mem, "temp_size_in_bytes", 0) or 0
    bytes_dev = float(arg_b + out_b + 2 * tmp_b)
    terms = roofline_terms(flops_dev, bytes_dev,
                           coll["wire_bytes_per_device"], TARGET_KIND)
    # MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (inference)
    mf = model_flops(n_act, meta["tokens"])
    if meta["kind"] != "train":
        mf /= 3.0
    rec = {
        "tag": tag, "status": "OK", **meta,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": arg_b,
            "output_bytes": out_b,
            "temp_bytes": tmp_b,
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        # raw XLA numbers; NB while bodies counted once (see roofline.py)
        "cost_raw": {"flops_per_device": float(cost.get("flops", 0.0)),
                     "bytes_per_device": float(cost.get("bytes accessed", 0.0))},
        "flops": {"analytic_global": flops_global,
                  "analytic_per_device": flops_dev},
        "collectives": coll,
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_device": mf / chips,
        "useful_flops_ratio": mf / flops_global if flops_global else None,
    }
    if keep_hlo:
        with open(os.path.join(out_dir, tag + ".hlo.txt"), "w") as f:
            f.write(hlo)
    _write(out_dir, tag, rec)
    return rec


def _write(out_dir: str, tag: str, rec: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    combos = ([(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
              if args.all else [(args.arch, args.shape)])
    for arch, shape in combos:
        tag = f"{arch}__{shape}__{'pod2' if args.multi_pod else 'pod1'}"
        if args.variant != "baseline":
            tag += f"__{args.variant}"
        path = os.path.join(args.out, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            prev = json.load(open(path))
            if prev.get("status") in ("OK", "SKIP"):
                print(f"[skip] {tag} ({prev['status']})", flush=True)
                continue
        rec = run_one(arch, shape, args.multi_pod, args.out,
                      keep_hlo=args.keep_hlo, variant=args.variant)
        status = rec["status"]
        extra = ""
        if status == "OK":
            r = rec["roofline"]
            extra = (f" compile={rec['compile_s']}s dominant={r['dominant']}"
                     f" c/m/x={r['compute_s']:.3g}/{r['memory_s']:.3g}/"
                     f"{r['collective_s']:.3g}s")
        elif status == "FAIL":
            extra = " " + rec["error"][:160]
        print(f"[{status}] {tag}{extra}", flush=True)


if __name__ == "__main__":
    main()
