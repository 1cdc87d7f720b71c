"""Compiles for a described TPU v5e at stablelm-1.6b widths — no chip.

The TPU compiler is installed next to the CPU backend, so every kernel
the trainer dispatches on TPU is compiled here for one chip of a
described ``v5e:2x2`` topology, at the flat size of the smoke
configuration (published widths, two layers).  A compile refuses what
the chip would refuse: a block that breaks the (8, 128) tiling rule, a
cast Mosaic lacks, more VMEM than a kernel may use.  Nothing runs, so
these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import make_compressor, make_plan
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.natural.kernel import natural_fused_pallas
from repro.kernels.natural.ops import (_natural_reduce_pallas,
                                       natural_reduce_pallas)
from repro.kernels.qsgd.kernel import (qsgd_fused_pallas, qsgd_pack_pallas,
                                       qsgd_unpack_pallas)
from repro.kernels.qsgd.ops import _qsgd_reduce_pallas, qsgd_reduce_pallas
from repro.models import init_params, param_count

#: clients of the smoke configuration; the reduces stack this many
N_CLIENTS = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` -> a ShapeDtypeStruct on one v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def flat_d():
    """Elements of one client's flat buffer: stablelm-1.6b at published
    widths, depth cut to two layers."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), n_layers=2)
    return param_count(jax.eval_shape(lambda k: init_params(k, cfg),
                                      jax.random.PRNGKey(0)))


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Route every backend dispatch of the repo (``dispatch.on_tpu``) to
    its TPU branch, as on the chip: the backend here is the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _kernel_case(name, flat_d, spec):
    n = N_CLIENTS
    nb = -(-flat_d // 2048)                 # qsgd: 2048-element buckets
    nn = -(-flat_d // 128)                  # natural: one lane row each
    f32, u32, i8, u8 = jnp.float32, jnp.uint32, jnp.int8, jnp.uint8
    cases = {
        "qsgd_fused": (
            lambda x, s: qsgd_fused_pallas(x, s, interpret=False,
                                           hw_rng=True),
            spec((nb, 2048), f32), spec((2,), u32)),
        "qsgd_pack": (
            lambda x, s: qsgd_pack_pallas(x, s, interpret=False,
                                          hw_rng=True),
            spec((nb, 2048), f32), spec((2,), u32)),
        "qsgd_unpack": (
            lambda c, m: qsgd_unpack_pallas(c, m, interpret=False),
            spec((nb, 2048), i8), spec((nb, 1), f32)),
        "qsgd_reduce": (
            lambda c, m: qsgd_reduce_pallas(c, m, interpret=False),
            spec((n, nb, 2048), i8), spec((n, nb, 1), f32)),
        "qsgd_reduce_weighted": (
            lambda c, m, w: qsgd_reduce_pallas(c, m, w, interpret=False),
            spec((n, nb, 2048), i8), spec((n, nb, 1), f32),
            spec((n,), f32)),
        "natural_fused": (
            lambda x, s: natural_fused_pallas(x, s, interpret=False,
                                              hw_rng=True),
            spec((nn, 128), f32), spec((2,), u32)),
        "natural_reduce": (
            lambda e, s: natural_reduce_pallas(e, s, interpret=False),
            spec((n, nn, 128), u8), spec((n, nn, 16), u8)),
        "natural_reduce_weighted": (
            lambda e, s, w: natural_reduce_pallas(e, s, w,
                                                  interpret=False),
            spec((n, nn, 128), u8), spec((n, nn, 16), u8),
            spec((n,), f32)),
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, causal=True, bq=512,
                                            bk=512, interpret=False),
            spec((1, 32, 2048, 64), f32), spec((1, 32, 2048, 64), f32),
            spec((1, 32, 2048, 64), f32)),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "qsgd_fused", "qsgd_pack", "qsgd_unpack", "qsgd_reduce",
    "qsgd_reduce_weighted", "natural_fused", "natural_reduce",
    "natural_reduce_weighted", "flash_attention"])
def test_kernel_compiles_for_v5e(name, flat_d, spec):
    """Every kernel the trainer dispatches on TPU (the encodes with the
    hardware PRNG) compiles for the chip into a Mosaic custom call."""
    fn, *args = _kernel_case(name, flat_d, spec)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("codec", ["qsgd", "natural"])
def test_client_encode_compiles_for_v5e(codec, flat_d, spec, tpu_dispatch):
    """The aggregation round encodes every client's flat buffer with
    :func:`repro.core.flatbuf.encode_clients`, the encode kernel once
    per client, through the dispatch the trainer takes on the chip."""
    from repro.core.flatbuf import encode_clients
    one = {"w": jax.ShapeDtypeStruct((flat_d,), jnp.float32)}
    plan = make_plan(make_compressor(codec), one)
    compiled = _compile(lambda ks, p: encode_clients(plan, ks, p),
                        spec((N_CLIENTS, 2), jnp.uint32),
                        {"w": spec((N_CLIENTS, flat_d), jnp.float32)})
    assert "tpu_custom_call" in compiled.as_text()


def test_leafwise_natural_wire_compiles_lean_for_v5e(spec, tpu_dispatch):
    """The client-sharded engine's wire path for natural compression:
    leafwise encode of n clients' models, then decode of the gathered
    payloads and their mean.  Through the lane-dense bit packing of 1-D
    buffers no temp reaches half of one f32 copy of the stacked leaves;
    the (bytes, 8) view of the shift form pads to 128 lanes on the chip
    and took about 270 MB here, 64 bytes per element."""
    n, d = 4, 512 * 2048
    one = {"w": jax.ShapeDtypeStruct((512, 2048), jnp.bfloat16),
           "b": jax.ShapeDtypeStruct((2048,), jnp.bfloat16)}
    plan = make_plan(make_compressor("natural"), one, transport="leafwise")

    def wire_mean(key_data, params):
        keys = jax.vmap(jax.random.wrap_key_data)(key_data)
        payload = jax.vmap(plan.encode)(keys, params)
        return jax.tree.map(lambda a: a.astype(jnp.float32).mean(0),
                            jax.vmap(plan.decode)(payload))

    compiled = _compile(wire_mean, spec((n, 2), jnp.uint32),
                        jax.tree.map(lambda a: spec((n,) + a.shape, a.dtype),
                                     one))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < n * d * 4 // 2, (temp, n * d * 4)


def test_qsgd_reduce_allocates_no_nd_fp32_on_v5e(spec, tpu_dispatch):
    """The O(d)-server claim on the chip's compiler: the fused
    decode->reduce of n stacked qsgd payloads, compiled for v5e through
    the dispatch the trainer takes there, keeps its temp bytes under half
    of ONE (n, d) fp32 buffer — no per-client decoded tree exists."""
    from repro.core.flatbuf import reduce_payload_mean
    n, d = 16, 64 * 2048                       # (n, d) fp32 = 8 MiB
    plan = make_plan(make_compressor("qsgd"), {"w": jnp.zeros((d,))})
    payload = jax.eval_shape(
        lambda ks, p: jax.vmap(plan.encode)(ks, p),
        jax.random.split(jax.random.PRNGKey(0), n),
        {"w": jax.ShapeDtypeStruct((n, d), jnp.float32)})
    payload = jax.tree.map(lambda a: spec(a.shape, a.dtype), payload)
    compiled = _compile(lambda p: reduce_payload_mean(p, None), payload)
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < n * d * 4 // 2, (temp, n * d * 4)


def _named_case(name, spec):
    """The jitted function that makes kernel ``name``'s pallas_call,
    called without its jit, on small shapes."""
    f32, u32, i8, u8 = jnp.float32, jnp.uint32, jnp.int8, jnp.uint8
    cases = {
        "natural_fused_pallas": (
            lambda x, s: natural_fused_pallas.__wrapped__(
                x, s, interpret=False, hw_rng=True),
            spec((64, 128), f32), spec((2,), u32)),
        "_natural_reduce_pallas": (
            lambda e, s: _natural_reduce_pallas.__wrapped__(
                e, s, None, rows=32, interpret=False, has_w=False),
            spec((2, 64, 128), u8), spec((2, 64, 16), u8)),
        "qsgd_fused_pallas": (
            lambda x, s: qsgd_fused_pallas.__wrapped__(
                x, s, interpret=False, hw_rng=True),
            spec((32, 2048), f32), spec((2,), u32)),
        "qsgd_pack_pallas": (
            lambda x, s: qsgd_pack_pallas.__wrapped__(
                x, s, interpret=False, hw_rng=True),
            spec((32, 2048), f32), spec((2,), u32)),
        "_qsgd_reduce_pallas": (
            lambda c, m: _qsgd_reduce_pallas.__wrapped__(
                c, m, None, levels=127, rows=32, interpret=False,
                has_w=False),
            spec((2, 32, 2048), i8), spec((2, 32, 1), f32)),
        "qsgd_unpack_pallas": (
            lambda c, m: qsgd_unpack_pallas.__wrapped__(
                c, m, interpret=False),
            spec((32, 2048), i8), spec((32, 1), f32)),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "natural_fused_pallas", "_natural_reduce_pallas", "qsgd_fused_pallas",
    "qsgd_pack_pallas", "_qsgd_reduce_pallas", "qsgd_unpack_pallas"])
def test_codec_kernel_op_keeps_its_name_without_its_jit(name, spec):
    """A codec kernel's device op, the event a chip trace shows, is
    named by its pallas_call's ``name=``: called without the jitted
    function around it, it keeps the name the benchmark's kernel
    readers match."""
    fn, *args = _named_case(name, spec)
    text = _compile(fn, *args).as_text()
    assert re.search(rf"^\s*(ROOT )?%{re.escape(name)}(\.\d+)? = .*"
                     r"custom-call\(", text, re.M), name
