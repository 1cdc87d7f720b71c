"""Named scopes of the training program.

A compiled rollout chunk carries ``jax.named_scope`` names in every op's
``op_name`` metadata, where a device trace reads them: the three
branches of Algorithm 1 (``l2gd.local``, ``l2gd.agg_fresh``,
``l2gd.agg_cached``) and the random streams made before the scan
(``rollout.streams``), the stages inside them, the codec's stages, the
model's blocks and the MoE routing.  Backward ops keep the scopes of
their forward inside JAX's ``transpose(jvp(...))`` wrappers.  The
scopes add metadata only: the rollout's numbers are pinned elsewhere
(tests/test_rollout.py, tests/test_sharded_rollout.py).
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core import (init_state, make_compressor, make_hyper, make_plan,
                        rollout_l2gd, rollout_l2gd_sharded)
from repro.launch.mesh import make_client_mesh
from repro.models import init_params, loss_fn

N, B, S, STEPS = 2, 2, 16, 4

BRANCHES = {"l2gd.local", "l2gd.agg_fresh", "l2gd.agg_cached",
            "rollout.streams"}
STAGES = {"grad", "update", "loss", "average", "apply"}
CODEC = {"uplink_encode", "server_reduce", "downlink"}
MODEL = {"model.embed", "model.attn", "model.ffn", "model.unembed"}
MOE = {"moe.route", "moe.dispatch", "moe.experts", "moe.combine"}

_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def _ops(text):
    """Each op_name in compiled (or lowered) text: (components with the
    transformation wrappers stripped, whether it is a backward op)."""
    out = []
    for name in re.findall(r'op_name="([^"]*)"', text) + \
            re.findall(r'loc\("([^"]*)"', text):
        name = name.split(";", 1)[0]
        parts = []
        for part in name.split("/"):
            m = _WRAPPER.match(part)
            while m:
                part = m.group(1)
                m = _WRAPPER.match(part)
            parts.append(part)
        out.append((set(parts), "transpose(" in name))
    return out


def _chunk(arch, codec, sharded=False):
    """A tiny rollout chunk of ``arch`` (reduced widths, two clients)
    with ``codec`` on the flat transport up and down, jitted and
    lowered."""
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    stacked = jax.tree.map(lambda a: jnp.stack([a] * N), params)
    plan = make_plan(codec, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
        transport="flat")

    def grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(
            lambda q: loss_fn(q, cfg, b), has_aux=True)(p)
        return loss, g

    kw = dict(grad_fn=grad_fn, steps=STEPS, client_comp=plan,
              master_comp=plan)
    roll = functools.partial(rollout_l2gd_sharded, mesh=make_client_mesh(1),
                             **kw) if sharded else \
        functools.partial(rollout_l2gd, **kw)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (STEPS, N, B, S), 0,
                                cfg.vocab_size)
    return jax.jit(roll).lower(
        jax.random.PRNGKey(2), init_state(stacked),
        make_hyper(eta=0.1, lam=0.5, p=0.5, n=N), {"tokens": tokens})


def _compiled(lowered):
    """Compiled text, with the persistent compilation cache off: its key
    leaves locations out, so an entry compiled before the scopes existed
    would come back without them."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def dense_text():
    return _compiled(_chunk("stablelm-1.6b", make_compressor("natural")))


@pytest.fixture(scope="module")
def dense_natural(dense_text):
    return _ops(dense_text)


@pytest.fixture(scope="module")
def moe_qsgd():
    return _ops(_compiled(_chunk(
        "granite-moe-1b-a400m",
        make_compressor("qsgd", levels=127, bucket=128))))


@pytest.fixture(scope="module")
def sharded_natural():
    lowered = _chunk("stablelm-1.6b", make_compressor("natural"),
                     sharded=True)
    # the all_gather over a one-device mesh is compiled away: the
    # lowered text's locations still name it
    return _ops(_compiled(lowered)), _ops(lowered.as_text(debug_info=True))


def _scopes(ops):
    return set().union(*(parts for parts, _ in ops))


def test_dense_chunk_carries_every_scope(dense_natural):
    assert BRANCHES | STAGES | CODEC | MODEL <= _scopes(dense_natural)


def test_moe_chunk_carries_every_scope(moe_qsgd):
    assert BRANCHES | STAGES | CODEC | MODEL | MOE <= _scopes(moe_qsgd)


def test_sharded_chunk_carries_every_scope(sharded_natural):
    compiled, lowered = sharded_natural
    assert BRANCHES | STAGES | CODEC | MODEL <= _scopes(compiled)
    assert "gather" in _scopes(lowered)


@pytest.mark.parametrize("case, scopes", [
    ("dense", MODEL), ("moe", MODEL | MOE)])
def test_backward_ops_keep_their_forward_scopes(case, scopes, dense_natural,
                                                moe_qsgd):
    ops = dense_natural if case == "dense" else moe_qsgd
    backward = [parts for parts, bwd in ops
                if bwd and "l2gd.local" in parts and "grad" in parts]
    for scope in scopes:
        assert any(scope in parts for parts in backward), scope


@pytest.mark.parametrize("case", ["dense", "moe"])
def test_branches_do_not_nest(case, dense_natural, moe_qsgd):
    """An op lies under at most one of the top-level scopes, so their
    self times add up to the whole chunk's."""
    ops = dense_natural if case == "dense" else moe_qsgd
    assert all(len(parts & BRANCHES) <= 1 for parts, _ in ops)


def test_scope_names_add_no_hlo_op_name(dense_text):
    """Tests look for HLO op names in compiled text (``all-gather``,
    ``rng-bit-generator``); the scopes' metadata adds none to a chunk
    that has no such op."""
    assert "l2gd.local" in dense_text
    for op in ("all-gather", "rng-bit-generator", "rng-get-and-update-state"):
        assert op not in dense_text, op
