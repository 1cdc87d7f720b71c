"""End-to-end behaviour tests for the full system: the paper's protocol
driving a real (reduced) transformer across clients, aggregation semantics
under sharding, and the launch-layer spec builders."""
import dataclasses
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, get_config
from repro.core import (L2GDHyper, compressed_average, make_compressor,
                        stochastic_round_cast)
from repro.fl import run_l2gd
from repro.data import TokenStream
from repro.models import init_params, loss_fn


def test_l2gd_trains_a_transformer():
    """Compressed L2GD drives the loss down on a reduced LM across 2
    heterogeneous clients — the full stack (models + core + fl + data)."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b").reduced(),
                              vocab_size=64)
    n = 2
    ts = TokenStream(n_clients=n, vocab=cfg.vocab_size, batch=8, seq=16,
                     seed=0)
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    params = jax.vmap(lambda k: init_params(k, cfg))(keys)

    def grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(
            lambda q: loss_fn(q, cfg, b), has_aux=True)(p)
        return loss, g

    hp = L2GDHyper(eta=0.1, lam=0.5, p=0.2, n=n)
    run = run_l2gd(jax.random.PRNGKey(1), params, grad_fn, hp,
                   lambda k: {"tokens": jnp.asarray(ts.batch_at(k))}, 200,
                   client_comp=make_compressor("natural"),
                   master_comp=make_compressor("natural"))
    losses = [l for _, l in run.losses]
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < 1.5 and last < first - 1.0, (first, last)
    assert run.ledger.rounds > 0


def test_compressed_average_unbiased_lemma2():
    """Lemma 2: E[C_M(ybar)] = xbar."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 64))}
    comp = make_compressor("qsgd", levels=3, bucket=64)
    keys = jax.random.split(jax.random.PRNGKey(1), 3000)
    outs = jax.vmap(lambda k: compressed_average(k, params, comp, comp)["w"])(keys)
    xbar = jnp.mean(params["w"], 0)
    err = float(jnp.max(jnp.abs(jnp.mean(outs, 0) - xbar)))
    assert err < 0.05, err


def test_stochastic_round_cast_unbiased():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3.0
    keys = jax.random.split(jax.random.PRNGKey(1), 4000)
    ys = jax.vmap(lambda k: stochastic_round_cast(k, x, jnp.bfloat16)
                  .astype(jnp.float32))(keys)
    err = float(jnp.max(jnp.abs(jnp.mean(ys, 0) - x)))
    # bf16 ulp at |x|~3 is ~0.0156; MC mean err should be << one ulp
    assert err < 6e-3, err


def test_input_specs_cover_all_pairs():
    """Deliverable (f): every (arch x shape) pair yields well-formed specs."""
    from repro.launch.steps import input_specs
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in INPUT_SHAPES.values():
            spec = input_specs(cfg, shape, n_clients=16)
            assert "tokens" in spec
            for leaf in jax.tree.leaves(spec):
                assert all(d > 0 for d in leaf.shape), (arch, shape.name)
            if shape.kind == "train":
                total = spec["tokens"].shape[0] * spec["tokens"].shape[1]
                assert total == shape.global_batch
            if shape.kind == "decode":
                assert spec["tokens"].shape == (shape.global_batch, 1)


def test_param_pspecs_divisible():
    """Every sharded dim divides the model-axis size for every full arch."""
    from repro.launch.sharding import param_pspecs
    from repro.launch.steps import param_shapes
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        shapes = param_shapes(cfg)
        specs = param_pspecs(shapes, 16, ())
        flat_shapes = jax.tree.leaves(shapes)
        flat_specs = jax.tree.leaves(specs,
                                     is_leaf=lambda x: hasattr(x, "index"))
        for sds, spec in zip(flat_shapes, flat_specs):
            for dim, ax in zip(sds.shape, tuple(spec)):
                if ax == "model":
                    assert dim % 16 == 0, (arch, sds.shape, spec)


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """A reduced-config dry-run on an 8-device (2x4) host mesh in a fresh
    subprocess (device count must be set before jax init)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_config, INPUT_SHAPES
from repro.core import L2GDHyper, make_compressor
from repro.launch.sharding import param_pspecs, tree_shardings, batch_pspec
from repro.launch.steps import build_train_step, state_specs, input_specs
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"), jax.devices())
cfg = get_config("granite-moe-1b-a400m").reduced()
shape = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=32, global_batch=4)
hp = L2GDHyper(eta=0.1, lam=1.0, p=0.3, n=2)
step = build_train_step(cfg, hp, make_compressor("natural"), make_compressor("natural"))
st = state_specs(cfg, 2)
with mesh:
    psh = tree_shardings(mesh, param_pspecs(st.params, 4, ("data",)))
    csh = tree_shardings(mesh, param_pspecs(st.cache, 4, ()))
    ssh = type(st)(params=psh, cache=csh, xi_prev=NamedSharding(mesh, P()),
                   step=NamedSharding(mesh, P()))
    bsds = input_specs(cfg, shape, 2)
    bsh = jax.tree.map(lambda s: NamedSharding(mesh, batch_pspec(("data",), len(s.shape)-1)), bsds)
    rep = NamedSharding(mesh, P())
    fn = jax.jit(step, in_shardings=(ssh, bsh, rep, rep), out_shardings=(ssh, None))
    lowered = fn.lower(st, bsds, jax.ShapeDtypeStruct((), jnp.int32),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    assert ca["flops"] > 0
    # the compiled module must actually contain cross-client collectives
    txt = compiled.as_text()
    assert ("all-reduce" in txt) or ("all-gather" in txt) or ("reduce-scatter" in txt)
print("MINI-DRYRUN-OK")
"""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert "MINI-DRYRUN-OK" in out.stdout, out.stderr[-3000:]


def test_roofline_peaks_keyed_by_device_kind():
    """Roofline terms use the named chip's published peaks; a device
    kind missing from the table raises instead of borrowing v5e's."""
    from repro.launch.roofline import PEAKS, roofline_terms
    v5e = PEAKS["TPU v5 lite"]
    t = roofline_terms(v5e["flops"], 2 * v5e["hbm_bw"], 0.0, "TPU v5 lite")
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)
    assert t["dominant"] == "memory"
    with pytest.raises(KeyError, match="cpu"):
        roofline_terms(1.0, 1.0, 1.0, "cpu")
