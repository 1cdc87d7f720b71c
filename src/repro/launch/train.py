"""Federated training entry point (single-host simulator).

Runs compressed L2GD (Algorithm 1) over n clients on heterogeneous
synthetic token streams for any assigned architecture, with checkpointing
and the bits/n ledger.  ``--full`` starts from the published widths and
``--layers`` cuts depth; without ``--full`` the reduced smoke config is
used.  ``main`` returns the run summary (losses, xi trace, protocol
counters, final protocol state) for callers such as ``chip_smoke.py``.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --clients 4 --steps 200 --compressor natural --p 0.2 --lam 0.5
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.configs.base import ARCH_IDS, get_config
from repro.core import L2GDHyper, make_compressor
from repro.data import TokenStream
from repro.fl import run_l2gd
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, loss_fn, param_count


def build(cfg, overrides):
    changes = {k: v for k, v in overrides.items() if v is not None}
    return dataclasses.replace(cfg, **changes)


def add_arch_args(ap, default_arch: str) -> None:
    """The architecture flags shared by the train and serve entry
    points: ``--arch``, ``--full``, the width/depth overrides and
    ``--dtype``; :func:`arch_config` turns them into an ArchConfig."""
    ap.add_argument("--arch", choices=ARCH_IDS, default=default_arch)
    ap.add_argument("--full", action="store_true",
                    help="use the full assigned config (default: reduced)")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--d-model", type=int)
    ap.add_argument("--d-ff", type=int)
    ap.add_argument("--heads", type=int)
    ap.add_argument("--kv-heads", type=int)
    ap.add_argument("--vocab", type=int)
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default=None,
                    help="override param+compute dtype (bf16 training "
                         "keeps fp32 wire norms/accumulators — DESIGN.md "
                         "§15 precision policy)")


def arch_config(args):
    """ArchConfig from :func:`add_arch_args` flags (plus ``--attn-impl``
    where the parser has it)."""
    base = get_config(args.arch) if args.full \
        else get_config(args.arch).reduced()
    return build(base, {"n_layers": args.layers, "d_model": args.d_model,
                        "d_ff": args.d_ff, "n_heads": args.heads,
                        "n_kv_heads": args.kv_heads,
                        "vocab_size": args.vocab,
                        "head_dim": None if args.d_model else base.head_dim,
                        "param_dtype": args.dtype,
                        "compute_dtype": args.dtype,
                        "attn_impl": getattr(args, "attn_impl", None)})


def tokens_processed(n_local: int, n_agg: int, local_steps: int, n: int,
                     batch: int, seq: int) -> int:
    """Tokens put through the model by a rollout: every protocol step
    forwards the full n x batch x seq token batch at least once (the
    aggregation branches evaluate the pre-update loss), and local steps
    run ``local_steps`` gradient passes over it (DESIGN.md §15) — the
    headline metric of bench_lm.py."""
    passes = n_local * int(local_steps) + n_agg
    return passes * n * batch * seq


def mesh2d_inputs(args, job):
    """(stacked batches, key data) of the mesh2d engine: the whole run
    is one dispatch, so every step's batch is stacked up front."""
    batches = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[job.batch_fn(k) for k in range(args.steps)])
    key_data = jax.random.key_data(jax.random.PRNGKey(args.seed + 3))
    return batches, key_data


def run_mesh2d(args, job) -> dict:
    """The 2-D (clients x model) mesh engine leg of the CLI: ONE
    ``build_sharded_rollout_fn`` dispatch over the whole run (DESIGN.md
    §15), ledger replayed from the trace, tokens/s reported."""
    from repro.core import init_state
    from repro.core.codec import make_plan
    from repro.fl.ledger import BitsLedger
    from repro.launch.mesh import make_train_mesh, model_shards_of
    from repro.launch.steps import build_sharded_rollout_fn

    cfg, n = job.cfg, job.n
    mesh = make_train_mesh(model_shards=args.model_shards)
    print(f"mesh2d: clients axis={mesh.shape['clients']} "
          f"model shards={model_shards_of(mesh)} "
          f"dtype={cfg.param_dtype} local_steps={args.local_steps}",
          flush=True)
    rollout = build_sharded_rollout_fn(
        cfg, job.hp, mesh=mesh, client_comp=job.comp,
        master_comp=job.mcomp, length=args.steps,
        local_steps=args.local_steps)
    state = init_state(job.params)
    # plans BEFORE dispatch: the jit donates state, which aliases params
    one_client = jax.tree.map(lambda a: a[0], job.params)
    up_plan = make_plan(job.comp, one_client, transport="leafwise")
    down_plan = make_plan(job.mcomp, one_client, transport="leafwise")
    batches, key_data = mesh2d_inputs(args, job)

    t0 = time.time()
    state, trace = jax.block_until_ready(rollout(state, batches, key_data))
    dt = time.time() - t0
    ledger = BitsLedger(n)
    xis = np.asarray(trace.xis)
    ledger.replay_xi_trace(xis, up_plan.round_bits(), down_plan.round_bits())
    losses = [float(v) for v in np.asarray(trace.losses)]
    n_local = int(trace.n_local)
    n_comm, n_cached = int(trace.n_agg_comm), int(trace.n_agg_cached)
    toks = tokens_processed(n_local, n_comm + n_cached, args.local_steps,
                            n, args.batch, args.seq)
    if args.ckpt:
        checkpoint.save_state(args.ckpt, state.params,
                              {"arch": cfg.name, "steps": args.steps,
                               "bits_per_client": ledger.bits_per_client})
        print(f"checkpoint -> {args.ckpt}")
    return _summary(args, job, losses, xis, n_local, n_comm, n_cached,
                    ledger, dt, toks, state)


def _summary(args, job, losses, xis, n_local, n_comm, n_cached, ledger, dt,
             toks, state) -> dict:
    """Print the run's loss log and rates; return them as a dict with
    the final protocol state (params and cached aggregation target)."""
    for i in range(0, len(losses), max(args.log_every, 1)):
        print(f"step {i:5d}  client-mean loss {losses[i]:8.4f}")
    if losses:
        print(f"final loss {losses[-1]:.4f}  "
              f"({np.mean(losses[-5:]):.4f} tail-5 mean)")
    print(f"steps/s={args.steps / dt:.2f}  tokens/s={toks / dt:.0f}  "
          f"rounds={ledger.rounds}  "
          f"bits/n={ledger.bits_per_client:.3e}  "
          f"local={n_local} aggC={n_comm} aggK={n_cached}", flush=True)
    return {"arch": job.cfg.name, "clients": job.n,
            "params_per_client": job.params_per_client,
            "losses": losses, "xis": xis, "n_local": n_local,
            "n_agg_comm": n_comm, "n_agg_cached": n_cached,
            "bits_per_client": ledger.bits_per_client, "seconds": dt,
            "tokens": toks, "state": state}


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    add_arch_args(ap, "stablelm-1.6b")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--eta", type=float, default=0.1)
    ap.add_argument("--lam", type=float, default=0.5)
    ap.add_argument("--p", type=float, default=0.2)
    ap.add_argument("--compressor", default="natural")
    ap.add_argument("--master-compressor", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint destination: a file path (legacy "
                         "single-file save at the end), or — with "
                         "--ckpt-every/--resume — a CheckpointManager "
                         "root directory of step-tagged snapshots")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="snapshot the rollout every N scan chunks into "
                         "the --ckpt directory (async sharded commits; "
                         "0 disables)")
    ap.add_argument("--ckpt-keep", type=int, default=0,
                    help="retain only the newest N snapshots (0 = all)")
    ap.add_argument("--resume", action="store_true",
                    help="resume bit-exactly from the latest snapshot "
                         "under --ckpt")
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--local-steps", type=int, default=1,
                    help="gradient passes per LOCAL protocol step "
                         "(LoCoDL amortization, DESIGN.md §15; wire "
                         "bits per round unchanged)")
    ap.add_argument("--engine", choices=("driver", "mesh2d"),
                    default="driver",
                    help="driver: the chunked run_l2gd simulator "
                         "(default); mesh2d: the 2-D (clients x model) "
                         "mesh engine via build_sharded_rollout_fn")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="size of the mesh's model axis (mesh2d engine; "
                         "clients x model-shards devices needed)")
    ap.add_argument("--attn-impl", choices=("dense", "flash"), default=None,
                    help="train-path attention kernel (flash only takes "
                         "effect on all-global-causal configs)")
    return ap


def setup(args):
    """The run's inputs from parsed flags: config, hyper-parameters,
    codecs, the initial stacked client params and the per-step batch
    function — shared by both engines and by callers that rebuild a
    run for comparison."""
    cfg = arch_config(args)
    n = args.clients
    ts = TokenStream(n_clients=n, vocab=cfg.vocab_size, batch=args.batch,
                     seq=args.seq, seed=args.seed)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), n)
    params = jax.vmap(lambda k: init_params(k, cfg))(keys)

    def batch_fn(k):
        batch = {"tokens": jnp.asarray(ts.batch_at(k))}
        if cfg.frontend == "vision":
            key = jax.random.fold_in(jax.random.PRNGKey(args.seed + 1), k)
            batch["patches"] = 0.02 * jax.random.normal(
                key, (n, args.batch, cfg.n_frontend_tokens, cfg.d_model))
        if cfg.is_encdec:
            key = jax.random.fold_in(jax.random.PRNGKey(args.seed + 2), k)
            batch["frames"] = 0.02 * jax.random.normal(
                key, (n, args.batch, cfg.n_frontend_tokens, cfg.d_model))
        return batch

    return types.SimpleNamespace(
        cfg=cfg, n=n, params=params,
        params_per_client=param_count(params) // n, batch_fn=batch_fn,
        hp=L2GDHyper(eta=args.eta, lam=args.lam, p=args.p, n=n),
        comp=make_compressor(args.compressor),
        mcomp=make_compressor(args.master_compressor or args.compressor))


def main(argv=None) -> dict:
    """CLI entry point.  ``argv`` (optional list) replaces
    ``sys.argv[1:]`` — callers compose flag lists explicitly
    (examples/train_federated_lm.py) instead of splicing ``sys.argv``;
    argparse's last-wins ordering then lets trailing user flags override
    a caller's defaults.  Returns the run summary (see :func:`_summary`)."""
    ap = make_parser()
    args = ap.parse_args(argv)
    if (args.ckpt_every or args.resume) and not args.ckpt:
        ap.error("--ckpt-every/--resume need --ckpt (the manager root)")
    if args.engine == "mesh2d" and (args.ckpt_every or args.resume):
        ap.error("--engine mesh2d has no checkpoint manager yet; "
                 "use the driver engine for --ckpt-every/--resume")
    enable_compile_cache()

    job = setup(args)
    print(f"arch={job.cfg.name} params/client={job.params_per_client:,} "
          f"clients={job.n}", flush=True)
    if args.engine == "mesh2d":
        return run_mesh2d(args, job)

    def grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(
            lambda q: loss_fn(q, job.cfg, b), has_aux=True)(p)
        return loss, g

    policy = None
    if args.ckpt_every:
        policy = checkpoint.CheckpointPolicy(
            args.ckpt, every_n_chunks=args.ckpt_every,
            max_to_keep=args.ckpt_keep or None)
    resume_from = args.ckpt if args.resume else None
    if resume_from is not None:
        step = checkpoint.latest_step(resume_from)
        print(f"resuming from {resume_from} step {step}", flush=True)

    # the protocol key folds in a second seed word (the stream the
    # deprecated run_l2gd(seed=) argument used to add)
    key = jax.random.fold_in(jax.random.PRNGKey(args.seed + 3),
                             args.seed + 4)
    t0 = time.time()
    run = run_l2gd(key, job.params, grad_fn, job.hp, job.batch_fn,
                   args.steps, client_comp=job.comp, master_comp=job.mcomp,
                   checkpoint_policy=policy, resume_from=resume_from,
                   local_steps=args.local_steps)
    if policy is not None:
        policy.resolve().close()   # join the in-flight commits
    dt = time.time() - t0

    n_agg = run.n_agg_comm + run.n_agg_cached
    toks = tokens_processed(run.n_local, n_agg, args.local_steps, job.n,
                            args.batch, args.seq)
    if args.ckpt and not (args.ckpt_every or args.resume):
        # legacy single-file path; manager-mode runs already committed
        # step-tagged snapshots during the rollout
        checkpoint.save_state(args.ckpt, run.state.params,
                              {"arch": job.cfg.name, "steps": args.steps,
                               "bits_per_client": run.ledger.bits_per_client})
        print(f"checkpoint -> {args.ckpt}")
    elif args.ckpt_every:
        print(f"checkpoints -> {args.ckpt} "
              f"(latest step {checkpoint.latest_step(args.ckpt)})")
    return _summary(args, job, [l for _, l in run.losses], run.xis,
                    run.n_local, run.n_agg_comm, run.n_agg_cached,
                    run.ledger, dt, toks, run.state)


if __name__ == "__main__":
    main()
