"""Tests for the §Perf layout re-parameterizations: qkv_fused and split
attention layouts must be numerically equivalent model families (same
family, different parameterization), and the beyond-paper sharded
aggregation must be unbiased."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import decode_step, forward, init_caches, init_params


@pytest.mark.parametrize("layout", ["split", "qkv_fused"])
@pytest.mark.parametrize("arch", ["stablelm-1.6b", "gemma3-1b", "hymba-1.5b"])
def test_layout_forward_and_decode(arch, layout):
    cfg = dataclasses.replace(get_config(arch).reduced(), attn_layout=layout)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    full, _ = forward(params, cfg, {"tokens": toks})
    assert full.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(full)))
    caches = init_caches(cfg, B, S)
    errs = []
    for i in range(S):
        lg, caches = decode_step(params, cfg, caches,
                                 jnp.asarray(i, jnp.int32),
                                 {"tokens": toks[:, i:i + 1]})
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full[:, i]))))
    assert max(errs) < 2e-4, (arch, layout, max(errs))


def test_mlp_fused_equivalent_family():
    cfg = dataclasses.replace(get_config("stablelm-1.6b").reduced(),
                              mlp_fused=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # fused param exists, unfused don't
    leaf_names = set()
    jax.tree_util.tree_map_with_path(
        lambda p, x: leaf_names.add(str(p[-1])), params)
    assert any("w_in" in n for n in leaf_names)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    logits, _ = forward(params, cfg, {"tokens": toks})
    assert bool(jnp.all(jnp.isfinite(logits)))


def _mesh_1x1():
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"), jax.devices()[:1])


def test_sharded_average_unbiased_single_device():
    """make_sharded_average on a 1x1 mesh == plain mean in expectation."""
    from jax.sharding import PartitionSpec as P
    from repro.core import make_compressor
    from repro.core.aggregation import make_sharded_average

    mesh = _mesh_1x1()
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 32))}
    pspecs = {"w": P("data", None)}
    avg_fn = make_sharded_average(mesh, ("data",), pspecs,
                                  make_compressor("natural"))
    with mesh:
        keys = jax.random.split(jax.random.PRNGKey(1), 1500)
        outs = jax.vmap(lambda k: avg_fn(k, params)["w"])(keys)
    xbar = jnp.mean(params["w"], 0)
    err = float(jnp.max(jnp.abs(jnp.mean(outs, 0) - xbar)))
    assert err < 0.05, err
