"""Serving entry point: multi-tenant personalized serving through the
base+delta store and the continuous-batching engine (DESIGN.md §12).

In the personalized-FL deployment every client has ITS OWN model x_i;
instead of loading one client slice dense, the server keeps the global
mean resident once and each tenant as a compressed delta
(``repro.serve.DeltaModelStore``), materializing tenants on demand into
a bounded LRU.  Generation is two fused ``lax.scan`` dispatches per
batch — prefill (TTFT) and greedy decode — with no per-token host sync.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
      --tenants 4 --cache 2 --codec natural --prompt-len 8 --gen 32

  # serve a federated checkpoint produced by train.py:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
      --ckpt runs/ck.msgpack --codec qsgd4

The architecture flags are train.py's (``--full`` for the published
widths, ``--layers`` to cut depth, ...), built by the same
:func:`repro.launch.train.arch_config`.  ``main`` returns the served
results for callers such as ``chip_smoke.py``.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.core import make_compressor, make_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import add_arch_args, arch_config
from repro.models import init_params
from repro.serve import DeltaModelStore, Request, ServingEngine

CODECS = ("identity", "natural", "qsgd", "qsgd4")


def build_plan(name: str):
    """CLI codec name -> (CompressionPlan, narrow flag).  ``qsgd4`` is
    QSGD levels=7 narrowed to 4-bit storage codes."""
    if name == "identity":
        return make_plan(make_compressor("identity"),
                         transport="leafwise"), False
    if name == "natural":
        return make_plan(make_compressor("natural"),
                         transport="packed"), False
    if name == "qsgd":
        return make_plan(make_compressor("qsgd"), transport="packed"), False
    if name == "qsgd4":
        return make_plan(make_compressor("qsgd", levels=7),
                         transport="packed"), True
    raise ValueError(f"unknown codec {name!r}; have {CODECS}")


def main(argv=None) -> dict:
    """CLI entry point; ``argv`` replaces ``sys.argv[1:]``.  Returns
    ``{"arch", "results", "models_per_gb"}`` — ``results`` is
    :meth:`ServingEngine.serve`'s per-request list."""
    ap = argparse.ArgumentParser()
    add_arch_args(ap, "gemma3-1b")
    ap.add_argument("--tenants", type=int, default=4,
                    help="synthetic tenants when no --ckpt is given")
    ap.add_argument("--cache", type=int, default=2,
                    help="LRU capacity: tenants resident materialized")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--batch-mode", choices=("map", "vmap"), default="map")
    ap.add_argument("--codec", choices=CODECS, default="natural")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--ckpt", default=None,
                    help="federated checkpoint (stacked client params) "
                         "to ingest as tenants")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = arch_config(args)
    plan, narrow = build_plan(args.codec)
    key = jax.random.PRNGKey(args.seed)

    if args.ckpt:
        store = DeltaModelStore.from_checkpoint(
            args.ckpt, plan, key=jax.random.fold_in(key, 1), narrow=narrow)
        print(f"ingested {len(store)} tenants from {args.ckpt}")
    else:
        keys = jax.random.split(jax.random.fold_in(key, 2), args.tenants)
        stacked = jax.vmap(lambda k: init_params(k, cfg))(keys)
        store = DeltaModelStore.from_params(
            stacked, plan, key=jax.random.fold_in(key, 1), narrow=narrow)

    engine = ServingEngine(store, cfg, cache_capacity=args.cache,
                           max_batch=args.max_batch,
                           batch_mode=args.batch_mode)

    # prompt stream from the jax key (device rng, reproducible with the
    # rest of the repo — no host-side numpy generator)
    prompts = jax.random.randint(
        jax.random.fold_in(key, 3), (len(store.tenants), args.prompt_len),
        0, cfg.vocab_size, jnp.int32)
    requests = [Request(tid, tuple(int(t) for t in prompts[i]),
                        gen=args.gen)
                for i, tid in enumerate(store.tenants)]

    results = engine.serve(requests)

    ratio_f32 = store.models_per_gb() / store.dense_models_per_gb(32.0)
    ratio_bf16 = store.models_per_gb() / store.dense_models_per_gb(16.0)
    print(f"arch={cfg.name} codec={args.codec} tenants={len(store)} "
          f"cache={args.cache} mode={args.batch_mode}")
    print(f"residency: {store.models_per_gb():.1f} models/GB "
          f"({ratio_f32:.2f}x dense f32, {ratio_bf16:.2f}x dense bf16)")
    for r in results[:4]:
        print(f"  tenant {r['tenant']}: ttft={r['ttft_s'] * 1e3:.1f}ms "
              f"batch={r['batch_size']} tokens={r['tokens'][:12].tolist()}"
              f"{'...' if len(r['tokens']) > 12 else ''}")
    snap = engine.metrics.snapshot()
    agg_tok = sum(s.tokens_generated for s in engine.metrics.tenants.values())
    agg_t = max(s.gen_time_s for s in engine.metrics.tenants.values())
    print(f"cache: hits={snap['hits']} misses={snap['misses']} "
          f"evictions={snap['evictions']}; "
          f"throughput ~{agg_tok / agg_t:.1f} tokens/s "
          f"over {snap['batches']} batches")
    return {"arch": cfg.name, "results": results,
            "models_per_gb": store.models_per_gb()}


if __name__ == "__main__":
    main()
