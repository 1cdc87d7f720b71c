#!/usr/bin/env python3
"""Smoke run of the federated compressed-L2GD stack on one TPU chip.

    python3 chip_smoke.py               # one chip: phases (a)-(d)
    python3 chip_smoke.py --four-chips  # four chips: the mesh engines only

One process drives the chip through the entry points a user calls:

  (a) device check — a TPU or a non-zero exit, never a CPU fallback;
  (b) train — ``repro.launch.train.main`` on stablelm-1.6b at its
      published widths (depth cut), two clients, flat transport, once
      with natural compression and once with QSGD;
  (c) kernels — every Pallas kernel of the aggregation round, compiled
      on the chip at the model's flat size, against its jnp reference,
      and the encodes with the hardware PRNG checked for bias and for
      streams shared between tiles;
  (d) serve — ``repro.launch.serve.main`` at the same widths, two
      tenants over one base.

``--four-chips`` runs only what exists across chips: the 2-D
(clients, model) engine on a (2, 2) mesh and the client-sharded
(shard_map) engine on (4, 1), each against the single-device stacked
engine at the same size.

Weights are random, made from seeds.  The timings printed along the way
are smoke timings, compilation included, not metrics.  The last line of
standard output is the JSON device record, printed only when every
phase passed; any failure raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the one-chip cut of stablelm-1.6b: published widths, depth 2.
#: Compiled for a described v5e, the driver engine's rollout at batch 16
#: holds 3.70 GB of arguments, 3.70 GB of outputs and 7.44 GB of temp,
#: 14.8 GB of the chip's 16 GB (13.0 GB at batch 8); the device's
#: peak_bytes_in_use leaves out what a program reserves when it loads.
#: Depth 4 ran out of memory on the chip.
ARCH = ["--arch", "stablelm-1.6b", "--full", "--layers", "2"]
TRAIN = ["--clients", "2", "--batch", "16", "--seq", "256", "--steps", "4",
         "--p", "0.5", "--log-every", "1"]
SERVE = ["--tenants", "2", "--cache", "2", "--max-batch", "2",
         "--codec", "natural", "--prompt-len", "16", "--gen", "16"]
#: the four-chip cut: one layer, bf16 and four clients, so that the
#: single-device comparison run holds all four models on one chip
MESH = ["--arch", "stablelm-1.6b", "--full", "--layers", "1",
        "--dtype", "bfloat16", "--clients", "4", "--batch", "2",
        "--seq", "256", "--steps", "4", "--p", "0.5", "--log-every", "1"]
#: limits of the four-chip comparison with the stacked engine (see
#: :func:`phase_mesh`)
TARGET_RTOL = 0.1
PARAMS_RTOL = 2e-2
DLOSS_LIMIT = 5e-3
#: published stablelm-1.6b widths the smoke must run at
WIDTHS = {"d_model": 2048, "n_heads": 32, "hd": 64, "d_ff": 5632,
          "vocab_size": 100352}


def device_check(count: int) -> dict:
    """Phase (a): the devices JAX sees; exits non-zero without a TPU."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    print(f"devices: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {info['platform']} devices")
    if info["count"] < count:
        raise SystemExit(f"need {count} TPU chips, JAX sees {info['count']}")
    return info


def check_widths(cfg) -> None:
    got = {k: getattr(cfg, k) for k in WIDTHS}
    if got != WIDTHS:
        raise AssertionError(f"not the published widths: {got}")


def peak_bytes(device) -> str:
    """The device's peak of arrays in use and, where reported, of memory
    reserved for programs (their temp), and its limit."""
    stats = device.memory_stats() or {}
    got = [f"{k}={v / 1e9:.2f}GB" for k, v in sorted(stats.items())
           if k.startswith("peak_") or "reserv" in k or k == "bytes_limit"]
    return " ".join(got) or "not reported"


def phase_train(arch, train_flags, compressor: str) -> dict:
    """Phase (b): a few protocol steps through the training CLI; every
    loss finite, the first near ln(vocab), at least one communicated
    round.  Returns the run summary without its params."""
    import jax
    from repro.launch import train
    flags = arch + train_flags + ["--compressor", compressor]
    args = train.make_parser().parse_args(flags)
    cfg = train.arch_config(args)
    t0 = time.time()
    summary = train.main(flags)
    wall = time.time() - t0
    summary.pop("state")
    losses = summary["losses"]
    bound = math.log(cfg.vocab_size)
    print(f"train[{compressor}]: layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}x{cfg.hd} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.param_dtype} "
          f"clients={args.clients} batch={args.batch} seq={args.seq} "
          f"params/client={summary['params_per_client']:,} "
          f"{peak_bytes(jax.devices()[0])}", flush=True)
    print(f"train[{compressor}]: losses={[round(v, 4) for v in losses]} "
          f"aggC={summary['n_agg_comm']} aggK={summary['n_agg_cached']} "
          f"local={summary['n_local']}; smoke timing, not a metric: "
          f"{wall:.1f}s wall incl. compile", flush=True)
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    # small-scale random init: near-uniform logits, loss ~ ln(vocab)
    if abs(losses[0] - bound) > 1.0:
        raise AssertionError(f"first loss {losses[0]:.4f} is not within "
                             f"1 nat of ln(vocab) = {bound:.4f}")
    if summary["n_agg_comm"] < 1:
        raise AssertionError("no communicated aggregation round ran")
    return summary


def aggregation_custom_calls(arch, train_flags, compressor: str) -> int:
    """Mosaic kernels in the compiled aggregation round of the trainer's
    plans on this device: proof that nothing fell back to the jnp path
    or to the interpreter."""
    import jax
    from repro.core import compressed_average, make_compressor, make_plan
    from repro.launch import train
    from repro.models import init_params
    args = train.make_parser().parse_args(
        arch + train_flags + ["--compressor", compressor])
    cfg = train.arch_config(args)
    one = jax.eval_shape(lambda k: init_params(k, cfg),
                         jax.random.PRNGKey(0))
    comp = make_compressor(compressor)
    up, down = make_plan(comp, one), make_plan(comp, one)
    stacked = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((args.clients,) + a.shape, a.dtype),
        one)
    text = jax.jit(lambda k, p: compressed_average(k, p, up, down)).lower(
        jax.ShapeDtypeStruct((2,), "uint32"), stacked).compile().as_text()
    return text.count("tpu_custom_call")


def _diff_stats(got, want, step, rtol: float):
    """(elements not bit-equal, one-step flips, elements outside both,
    worst relative error of the close ones), computed on the device."""
    import jax.numpy as jnp
    g, w = got.astype(jnp.float32), want.astype(jnp.float32)
    err = jnp.abs(g - w)
    tol = rtol * jnp.abs(w)
    close = (got == want) | (err <= tol)
    if step is None:
        flip = jnp.zeros_like(close)
    else:
        flip = ~close & (jnp.abs(err - step) <= tol + rtol * step)
    rel = jnp.where(close & (w != 0), err / jnp.abs(w), 0.0)
    return (jnp.sum(got != want), jnp.sum(flip), jnp.sum(~close & ~flip),
            jnp.max(rel))


def _compare(name: str, got, want, *, rtol: float = 0.0, step=None,
             flip_share: float = 0.0) -> bool:
    """One kernel output against its reference; prints the outcome and
    returns whether it passed.  An element passes when it is bit-equal
    or within ``rtol`` of the reference, relative to it.  Where ``step``
    (one quantization level) is given, an element exactly one level
    away is a flip of the stochastic rounding, allowed on at most a
    ``flip_share`` of the elements."""
    import jax
    if got.shape != want.shape or got.dtype != want.dtype:
        print(f"kernel {name}: {got.shape}/{got.dtype} vs reference "
              f"{want.shape}/{want.dtype}", flush=True)
        return False
    n_diff, n_flip, n_bad, worst = (
        v.item() for v in jax.jit(_diff_stats, static_argnums=3)(
            got, want, step, rtol))
    ok = n_bad == 0 and n_flip <= flip_share * got.size
    print(f"kernel {name}: {'ok' if ok else 'MISMATCH'} — {n_diff} of "
          f"{got.size} elements not bit-equal, {n_flip} one-level flips "
          f"(limit {int(flip_share * got.size)}), {n_bad} outside "
          f"tolerance; worst relative error {worst:.3g} (limit {rtol:g})",
          flush=True)
    return ok


#: f32 unit roundoff
_U = 2.0 ** -24


def _by_rows(f, *stacked, chunks: int = 16):
    """``f`` over ``chunks`` slices of the bucket axis (axis 1) of the
    stacked payload arrays, concatenated.  The natural reduce reference
    unpacks the sign bits into a minor dimension of 8, which the chip
    pads to 128 lanes: at the model's flat size one whole pass would not
    fit its 16 GB.  The reduce is independent across buckets, so the
    pieces give the whole result."""
    import jax.numpy as jnp
    nb = stacked[0].shape[1]
    cuts = [nb * i // chunks for i in range(chunks + 1)]
    return jnp.concatenate([f(*(a[:, lo:hi] for a in stacked))
                            for lo, hi in zip(cuts, cuts[1:])])


def phase_kernels(d: int) -> None:
    """Phase (c): the aggregation kernels, compiled on the chip at the
    model's flat size ``d``, against their jnp references (also run on
    the chip).  The encodes run the counter RNG (``hw_rng=False``),
    which the references replicate bit for bit.  Every comparison runs
    before the phase fails, so one run shows them all.

    Tolerances, and why:

    * natural encode and reduce: bit-exact.  The encode only compares
      integers and exact floats; the reduce adds powers of two scaled
      by weights of two significant bits, exactly.
    * qsgd bucket norms: each compiler sums a bucket's 2048 squares in
      its own order, and either sum is within (2048 - 1) u of the exact
      one; the square root halves the relative error, so the two norms
      stay within 2048 u of each other, plus a few roundings.
    * qsgd codes: a code may land one level away where the dither falls
      between the two computations of ``|x| / norm * levels``, whose gap
      follows the norm's; with Gaussian data that is about 1e-6 of the
      codes, and 1e-4 is allowed.  The dequantized values inherit both.
    * the reduces and the unpack: the same products in the same client
      order, but a compiler may turn the division by ``levels`` into a
      reciprocal multiply, one rounding per term; with two terms that is
      at most 4 u.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.natural.kernel import natural_fused_pallas, natural_pack
    from repro.kernels.natural.ops import natural_reduce_pallas
    from repro.kernels.natural.ref import natural_fused_ref, natural_reduce_ref
    from repro.kernels.qsgd.kernel import (qsgd_fused_pallas,
                                           qsgd_pack_pallas,
                                           qsgd_unpack_pallas)
    from repro.kernels.qsgd.ops import qsgd_reduce_pallas
    from repro.kernels.qsgd.ref import (qsgd_fused_ref, qsgd_pack_ref,
                                        qsgd_reduce_ref, qsgd_unpack_ref)
    levels = 127
    norm_rtol = (2048 + 4) * _U
    key = jax.random.PRNGKey(7)
    seeds = jax.random.bits(key, (2,), jnp.uint32)
    w = jnp.asarray([0.75, 0.25], jnp.float32)
    ref = lambda f, **kw: jax.jit(lambda *a: f(*a, **kw))
    ok = []

    x = jax.random.normal(key, (-(-d // 2048), 2048), jnp.float32)
    rc, rn = ref(qsgd_pack_ref)(x, seeds)
    step = rn / levels
    ok.append(_compare("qsgd_fused", qsgd_fused_pallas(
        x, seeds, interpret=False, hw_rng=False),
        ref(qsgd_fused_ref)(x, seeds), rtol=norm_rtol, step=step,
        flip_share=1e-4))
    codes, norms = qsgd_pack_pallas(x, seeds, interpret=False, hw_rng=False)
    del x
    ok.append(_compare("qsgd_pack codes", codes, rc, step=1.0,
                       flip_share=1e-4))
    ok.append(_compare("qsgd_pack norms", norms, rn, rtol=norm_rtol))
    del rc, rn, step
    ok.append(_compare("qsgd_unpack", qsgd_unpack_pallas(
        codes, norms, interpret=False), ref(qsgd_unpack_ref)(codes, norms),
        rtol=4 * _U))
    c2 = jnp.stack([codes, -codes])
    n2 = jnp.stack([norms, 0.5 * norms])
    del codes, norms
    for wt in (None, w):
        tag = "weighted" if wt is not None else "plain"
        ok.append(_compare(f"qsgd_reduce {tag}", qsgd_reduce_pallas(
            c2, n2, wt, interpret=False), ref(qsgd_reduce_ref)(c2, n2, wt),
            rtol=4 * _U))
    del c2, n2

    x = jax.random.normal(key, (-(-d // 128), 128), jnp.float32)
    ok.append(_compare("natural_fused", natural_fused_pallas(
        x, seeds, interpret=False, hw_rng=False),
        ref(natural_fused_ref)(x, seeds)))
    # the wire payload as the trainer encodes it on the chip
    exps, signs = natural_pack(x, seeds)
    del x
    # the second client: opposite signs, half the magnitude
    e2 = jnp.stack([exps, jnp.where(exps > 1, exps - 1, exps)])
    s2 = jnp.stack([signs, ~signs])
    del exps, signs
    for wt in (None, w):
        tag = "weighted" if wt is not None else "plain"
        ok.append(_compare(f"natural_reduce {tag}", natural_reduce_pallas(
            e2, s2, wt, interpret=False),
            _by_rows(ref(natural_reduce_ref, weights=wt), e2, s2)))
    if not all(ok):
        raise AssertionError("a kernel disagrees with its reference")


#: rows per tile of the constant-input check of the hardware PRNG
_TILE = 128


def _unbiased(name: str, q, x) -> bool:
    """The rounding residual ``Q(x) - x`` has mean zero: within 5 of its
    standard errors (a biased rounding or a stuck stream moves it by
    hundreds)."""
    import jax
    import jax.numpy as jnp

    def stats(q, x):
        r = q.astype(jnp.float32) - x.astype(jnp.float32)
        return jnp.mean(r), jnp.std(r)

    mean, sd = (v.item() for v in jax.jit(stats)(q, x))
    z = mean / (sd / math.sqrt(x.size))
    ok = abs(z) < 5.0
    print(f"hw_rng {name}: {'ok' if ok else 'BIASED'} — mean residual "
          f"{mean:.3g}, {z:.3g} standard errors from zero (limit 5)",
          flush=True)
    return ok


def _tiles_differ(name: str, q) -> bool:
    """On a constant input every tile of ``_TILE`` rows sees the same
    values, so only the tile's stream tells its roundings apart: each
    later tile must disagree with the first in many elements.  Two
    independent streams agree in about 0.69 of them here; one stream
    shared by two tiles agrees in all."""
    import jax.numpy as jnp
    tiles = q.reshape(-1, _TILE, q.shape[-1])
    same = [float(v) for v in jnp.mean(tiles[1:] == tiles[0], axis=(1, 2))]
    ok = max(same) < 0.9
    print(f"hw_rng {name}: {'ok' if ok else 'SHARED STREAM'} — share of "
          f"elements equal to tile 0's, per later tile: "
          f"{[round(v, 4) for v in same]} (limit 0.9)", flush=True)
    return ok


def phase_hw_rng(d: int) -> None:
    """Phase (c), second half: the encodes as the trainer runs them on
    the chip, with the hardware PRNG (``hw_rng=True``), which no jnp
    reference reproduces.  At the model's flat size the rounding must be
    unbiased; on a constant input, tiles must draw distinct streams
    (``tile_uniform`` seeds the PRNG with the tile index folded into the
    first seed word).  The pack kernels draw through the same
    ``tile_uniform``."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels.natural.kernel import natural_fused_pallas
    from repro.kernels.qsgd.kernel import qsgd_fused_pallas
    key = jax.random.PRNGKey(11)
    seeds = jax.random.bits(key, (2,), jnp.uint32)
    ok = []
    for name, fused, width in (("qsgd_fused", qsgd_fused_pallas, 2048),
                               ("natural_fused", natural_fused_pallas, 128)):
        enc = functools.partial(fused, interpret=False, hw_rng=True)
        x = jax.random.normal(key, (-(-d // width), width), jnp.float32)
        ok.append(_unbiased(name, enc(x, seeds), x))
        del x
        const = jnp.full((4 * _TILE, width), 0.3, jnp.float32)
        ok.append(_tiles_differ(name, enc(const, seeds, rows=_TILE)))
    if not all(ok):
        raise AssertionError("the hardware-PRNG encode failed a check")


def phase_serve(arch) -> None:
    """Phase (d): two tenants over one base through the serving CLI;
    every generated token lies in the vocabulary."""
    import numpy as np
    from repro.launch import serve, train
    cfg = train.arch_config(train.make_parser().parse_args(arch))
    out = serve.main(arch + SERVE)
    results = out["results"]
    if len(results) != 2:
        raise AssertionError(f"expected 2 results, got {len(results)}")
    for r in results:
        toks = np.asarray(r["tokens"])
        if toks.min() < 0 or toks.max() >= cfg.vocab_size:
            raise AssertionError(f"tenant {r['tenant']}: token outside "
                                 f"[0, {cfg.vocab_size})")
    print(f"serve: tenants=2 tokens/request={len(results[0]['tokens'])}; "
          f"smoke timing, not a metric: ttft="
          f"{results[0]['ttft_s'] * 1e3:.1f}ms (first batch, incl. "
          f"compile)", flush=True)


def _host_leaves(tree):
    import jax
    import numpy as np
    return [np.asarray(a, np.float32) for a in jax.tree.leaves(tree)]


def _worst_rel_l2(leaves, ref_leaves) -> float:
    """The largest per-leaf relative L2 distance from the reference."""
    import numpy as np
    return max(float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
               for a, b in zip(leaves, ref_leaves))


def _check_layout(name: str, params, clients: int, shards: int) -> None:
    """The run's final params span all four devices, each holding
    ``1/clients`` of the client axis and ``1/shards`` of the model."""
    import jax
    devs = {d for a in jax.tree.leaves(params) for d in a.sharding.device_set}
    embed = params["embed"]["table"]
    shard = embed.addressable_shards[0].data.shape
    want = (embed.shape[0] // clients, embed.shape[1] // shards,
            embed.shape[2])
    print(f"{name}: devices={len(devs)} embed {embed.shape} -> shard "
          f"{shard}", flush=True)
    if len(devs) != 4:
        raise AssertionError(f"{name} spans {len(devs)} devices, not 4")
    if shard != want:
        raise AssertionError(f"{name}: embed shard {shard}, expected {want}")


def phase_mesh(arch_mesh) -> None:
    """``--four-chips``: the 2-D (clients, model) engine on a (2, 2) mesh
    through the training CLI, and the client-sharded engine on (4, 1),
    each against the single-device stacked engine on the same inputs.
    The xi trace must be identical.  The meshes reduce the bf16 matmuls
    and the gathered mean in another order, and a stochastic rounding of
    the compressors may then land on the neighbouring level, so the rest
    agrees within limits:

    * the aggregation target (the state's cache, set by the run's
      communicated round): the mesh's exchange between chips built it,
      so it shows a fault there whole.  Limit ``TARGET_RTOL`` per-leaf
      relative L2;
    * params: ``PARAMS_RTOL`` per-leaf relative L2.  One round moves
      each client only eta*lam/(n*p) = 2.5% of the way to the target,
      so this bound alone would pass a target averaged over half the
      clients;
    * losses: ``DLOSS_LIMIT`` nats."""
    import jax
    import numpy as np
    from repro.core import init_state
    from repro.launch import train
    from repro.launch.mesh import make_client_mesh
    from repro.launch.sharding import (client_sharded_batch_shardings,
                                       client_sharded_shardings)
    from repro.launch.steps import build_rollout_fn, build_sharded_rollout_fn

    args = train.make_parser().parse_args(arch_mesh)
    runs = {}

    t0 = time.time()
    summary = train.main(arch_mesh + ["--engine", "mesh2d",
                                      "--model-shards", "2"])
    state = summary.pop("state")
    _check_layout("mesh2d (2,2)", state.params, 2, 2)
    if summary["n_agg_comm"] < 1:
        raise AssertionError("no communicated aggregation round ran")
    print(f"mesh2d (2,2): losses={[round(v, 4) for v in summary['losses']]}"
          f" aggC={summary['n_agg_comm']}; smoke timing, not a metric: "
          f"{time.time() - t0:.1f}s wall incl. compile", flush=True)
    runs["mesh2d (2,2)"] = (np.asarray(summary["xis"]),
                            np.asarray(summary["losses"]),
                            _host_leaves(state.params),
                            _host_leaves(state.cache))
    del state, summary

    def rollout_run(mesh):
        """(final state, trace) of the stacked engine (``mesh=None``) or
        the client-sharded one on ``mesh``, from the CLI's inputs."""
        job = train.setup(args)
        batches, key_data = train.mesh2d_inputs(args, job)
        state = init_state(job.params)
        kw = dict(client_comp=job.comp, master_comp=job.mcomp,
                  length=args.steps)
        if mesh is None:
            return build_rollout_fn(job.cfg, job.hp, **kw)(
                state, batches, key_data)
        state = jax.device_put(state, client_sharded_shardings(mesh, state))
        batches = jax.device_put(
            batches, client_sharded_batch_shardings(mesh, batches))
        return build_sharded_rollout_fn(job.cfg, job.hp, mesh=mesh, **kw)(
            state, batches, key_data)

    t0 = time.time()
    state, trace = rollout_run(make_client_mesh(4))
    _check_layout("client-sharded (4,1)", state.params, 4, 1)
    losses = np.asarray(trace.losses)
    print(f"client-sharded (4,1): losses={[round(float(v), 4) for v in losses]}"
          f" aggC={int(trace.n_agg_comm)}; smoke timing, not a metric: "
          f"{time.time() - t0:.1f}s wall incl. compile", flush=True)
    runs["client-sharded (4,1)"] = (np.asarray(trace.xis), losses,
                                    _host_leaves(state.params),
                                    _host_leaves(state.cache))
    del state, trace

    state, trace = rollout_run(None)
    ref_xis, ref_losses = np.asarray(trace.xis), np.asarray(trace.losses)
    ref_leaves = _host_leaves(state.params)
    ref_target = _host_leaves(state.cache)
    print(f"stacked (1 device): losses="
          f"{[round(float(v), 4) for v in ref_losses]}", flush=True)
    del state, trace

    for name, (xis, losses, leaves, target) in runs.items():
        if not np.array_equal(xis, ref_xis):
            raise AssertionError(f"{name}: xi trace differs from stacked")
        d_target = _worst_rel_l2(target, ref_target)
        d_params = _worst_rel_l2(leaves, ref_leaves)
        dloss = float(np.max(np.abs(losses - ref_losses)))
        print(f"{name} vs stacked: xi identical; max per-leaf relative L2 "
              f"of the aggregation target {d_target:.3g} (limit "
              f"{TARGET_RTOL:g}), of params {d_params:.3g} (limit "
              f"{PARAMS_RTOL:g}); max |dloss| {dloss:.3g} (limit "
              f"{DLOSS_LIMIT:g})", flush=True)
        if d_target > TARGET_RTOL:
            raise AssertionError(f"{name}: aggregation target differs "
                                 f"from stacked")
        if d_params > PARAMS_RTOL:
            raise AssertionError(f"{name}: params differ from stacked")
        if dloss > DLOSS_LIMIT:
            raise AssertionError(f"{name}: losses differ from stacked")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh engines on four chips")
    args = ap.parse_args(argv)
    info = device_check(4 if args.four_chips else 1)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.four_chips:
        _timed("mesh", phase_mesh, MESH)
    else:
        from repro.launch import train
        cfg = train.arch_config(train.make_parser().parse_args(ARCH))
        check_widths(cfg)
        for compressor in ("natural", "qsgd"):
            _timed(f"train[{compressor}]", phase_train, ARCH, TRAIN,
                   compressor)
            calls = aggregation_custom_calls(ARCH, TRAIN, compressor)
            print(f"aggregation[{compressor}]: {calls} Mosaic kernels in "
                  f"the compiled round", flush=True)
            if calls < 3:
                raise AssertionError("the aggregation round lost a kernel "
                                     "(encode, reduce, downlink)")
        import jax
        from repro.models import init_params, param_count
        d = param_count(jax.eval_shape(lambda k: init_params(k, cfg),
                                       jax.random.PRNGKey(0)))
        _timed("kernels", phase_kernels, d)
        _timed("hw_rng", phase_hw_rng, d)
        _timed("serve", phase_serve, ARCH)
    print(json.dumps({"ok": True, "device": info}), flush=True)


def _timed(name: str, phase, *args):
    """Run one phase and print its wall time (a smoke timing)."""
    t0 = time.time()
    phase(*args)
    print(f"phase {name}: {time.time() - t0:.1f}s wall incl. compile; "
          f"smoke timing, not a metric", flush=True)


if __name__ == "__main__":
    main()
