"""Fused decode->reduce aggregation engine (DESIGN.md §10).

The server half of an aggregation round folds a STACKED packed payload
batch into the (optionally masked) mean in ONE pass with an O(d) f32
accumulator (`repro.core.flatbuf.reduce_payload_mean` over the
`kernels/{qsgd,natural}` reduce kernels) — no per-client dequantized
tree ever exists.  Pinned here:

  * fused reduce == decode-then-mean for every flat-engine codec x
    {full, masked-participation, single-participant, n=1} — bit-exact
    where the sums are trivial (n=1, one participant), documented
    allclose otherwise (the fused path adds clients in index order
    0..n-1; XLA's axis-0 reduce may associate differently);
  * the Pallas reduce kernels (interpret mode) are bit-exact vs the jnp
    scan refs, weights and no-weights, and unroll-invariant; the
    natural one's lane-dense sign decode at every sign byte, the edge
    exponent codes and a ragged last tile;
  * `compressed_average` routes flat/packed plans through the fused
    engine and every other codec through the historic path bit-exactly;
  * stacked and client-sharded aggregation stay BIT-EXACT with each
    other on a 1-device mesh (they share the fused reduce), and the
    forced-xi-trace rollout equality extends over the new path with
    sampled participation;
  * HLO-level memory analysis: the fused aggregation allocates no
    (n, d)-shaped fp32 temporary, the decode-then-mean reference does
    (the metric detects exactly what the engine removes);
  * the donated state carry of the launch builders aliases the stacked
    params buffer input->output (no full-size copy inside a chunk);
  * the narrow-width `pack_bits`/`unpack_bits` fast paths and the
    one-pass `natural_pack` are bit-exact incl. zeros/subnormals/Inf/NaN.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import quad_batch, quad_grad_fn, zero_params
from repro.core import (Identity, compressed_average, flatbuf, init_state,
                        make_compressor, make_hyper, make_plan,
                        masked_client_mean, reduce_payload_mean,
                        rollout_l2gd, supports_fused_reduce)

D = 700          # not a lane/bucket multiple: exercises the padded tail
N = 8


def _stacked_params(n=N, d=D, seed=0):
    return {"a": jax.random.normal(jax.random.PRNGKey(seed), (n, d)),
            "b": jax.random.normal(jax.random.PRNGKey(seed + 1),
                                   (n, 3, 11))}


def _one_model(d=D):
    return {"a": jnp.zeros((d,)), "b": jnp.zeros((3, 11))}


def _payload(plan, stacked, n):
    keys = jax.random.split(jax.random.PRNGKey(2), n)
    return jax.vmap(plan.encode)(keys, stacked)


MASKS = {
    "full": lambda n: None,
    "masked": lambda n: jnp.asarray([1.0, 0.0] * (n // 2))
    if n > 1 else jnp.ones((1,)),
    "single": lambda n: jnp.zeros((n,)).at[n // 2].set(1.0),
}


@pytest.mark.parametrize("codec", ["qsgd", "natural"])
@pytest.mark.parametrize("case", ["full", "masked", "single", "n1"])
def test_fused_reduce_matches_decode_then_mean(codec, case):
    n = 1 if case == "n1" else N
    mask = None if case == "n1" else MASKS[case](n)
    plan = make_plan(make_compressor(codec), _one_model())
    payload = _payload(plan, _stacked_params(n), n)
    assert supports_fused_reduce(payload)
    fused = reduce_payload_mean(payload, mask)
    ref = masked_client_mean(jax.vmap(plan.decode)(payload), mask)
    for k in ref:
        a, b = np.asarray(fused[k]), np.asarray(ref[k])
        if case in ("n1", "single"):
            # trivial sums: one decoded message (times weight 1) — the
            # two paths perform identical float ops
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("codec", ["qsgd", "natural"])
@pytest.mark.parametrize("weighted", [False, True])
def test_reduce_kernels_interpret_bit_exact(codec, weighted):
    """Pallas (interpret) == jnp scan ref, and the unroll factor never
    changes the result (same addition order)."""
    plan = make_plan(make_compressor(codec), _one_model())
    payload = _payload(plan, _stacked_params(), N)
    w = jnp.asarray([1, 0, 1, 1, 0, 1, 0, 1], jnp.float32) if weighted \
        else None
    if codec == "qsgd":
        from repro.kernels.qsgd.ops import qsgd_reduce_pallas
        from repro.kernels.qsgd.ref import qsgd_reduce_ref
        got = qsgd_reduce_pallas(payload.codes, payload.norms, w,
                                 levels=payload.levels, interpret=True)
        ref = qsgd_reduce_ref(payload.codes, payload.norms, w,
                              levels=payload.levels)
        ref_u1 = qsgd_reduce_ref(payload.codes, payload.norms, w,
                                 levels=payload.levels, unroll=1)
    else:
        from repro.kernels.natural.ops import natural_reduce_pallas
        from repro.kernels.natural.ref import natural_reduce_ref
        got = natural_reduce_pallas(payload.exps, payload.signs, w,
                                    interpret=True)
        ref = natural_reduce_ref(payload.exps, payload.signs, w)
        ref_u1 = natural_reduce_ref(payload.exps, payload.signs, w,
                                    unroll=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(ref_u1), np.asarray(ref))


def _natural_rows():
    """Rows per tile the natural reduce picks for 128-wide buckets."""
    from repro.kernels.natural.ops import _reduce_rows
    return _reduce_rows(10 ** 7, 128)


@pytest.mark.parametrize("n", [1, 2, 3])
@pytest.mark.parametrize("rows", ["small", "auto"])
@pytest.mark.parametrize("weighted", [False, True])
def test_natural_reduce_pallas_edges_bit_exact(n, rows, weighted):
    """The lane-dense sign decode of the natural reduce (interpret mode)
    == the jnp scan ref at the uint32 bit level: every sign byte 0-255,
    exponent codes 0 and 254, zero weights, and a ragged last tile both
    at a small row count and at the one the VMEM budget picks."""
    from repro.kernels.natural.ops import natural_reduce_pallas
    from repro.kernels.natural.ref import natural_reduce_ref
    r = 32 if rows == "small" else _natural_rows()
    nb = r + r // 2 + 4                       # two tiles, the last ragged
    rng = np.random.default_rng(n)
    exps = rng.integers(0, 255, (n, nb, 128), dtype=np.uint8)
    exps[:, 0, :64], exps[:, 0, 64:] = 0, 254
    signs = rng.integers(0, 256, (n, nb, 16), dtype=np.uint8)
    signs[:, 1:17] = np.arange(256, dtype=np.uint8).reshape(16, 16)
    w = None
    if weighted:
        w = jnp.asarray([0.0, 1.5, -0.25][:n] if n > 1 else [-0.75],
                        jnp.float32)
    got = natural_reduce_pallas(jnp.asarray(exps), jnp.asarray(signs), w,
                                rows=r, interpret=True)
    ref = natural_reduce_ref(jnp.asarray(exps), jnp.asarray(signs), w)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(ref).view(np.uint32))


def test_natural_reduce_row_budget_counts_the_lane_dense_decode():
    """The VMEM accounting of the natural reduce buys more rows per tile
    than the 320 the (rows, 16, 8) shift unpack left room for."""
    assert _natural_rows() > 320


@pytest.mark.parametrize("codec", ["identity", "qsgd", "natural",
                                   "terngrad", "randk", "bernoulli"])
def test_compressed_average_all_codecs(codec):
    """Every codec still averages correctly through compressed_average:
    flat-engine codecs ride the fused reduce (allclose vs the manual
    reference), every other codec takes the HISTORIC path bit-exactly."""
    comp = Identity() if codec == "identity" else make_compressor(codec)
    from repro.core.codec import as_plan
    plan = as_plan(comp)
    stacked = _stacked_params()
    key = jax.random.PRNGKey(5)
    mask = jnp.asarray([1, 0, 1, 1, 0, 1, 0, 1], jnp.float32)
    got = compressed_average(key, stacked, comp, Identity(), mask=mask)
    # the manual reference = the pre-engine semantics
    k_clients, k_master = jax.random.split(key)
    keys = jax.random.split(k_clients, N)
    ref = masked_client_mean(
        jax.vmap(lambda k, p: plan.apply(k, p))(keys, stacked), mask)
    for k in ref:
        if plan.transport in ("flat", "packed"):
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(ref[k]),
                                       rtol=1e-6, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]))


def test_sharded_aggregation_bit_exact_with_stacked():
    """make_client_sharded_average on a 1-device mesh == the stacked
    compressed_average bit-for-bit, masked and unmasked — both sides are
    the SAME fused reduce over the same gathered wire arrays."""
    from jax.sharding import PartitionSpec as P
    from repro.core import make_client_sharded_average
    from repro.core.aggregation import _shard_map
    from repro.launch.mesh import make_client_mesh

    mesh = make_client_mesh(1)
    stacked = _stacked_params()
    for codec in ("qsgd", "natural"):
        comp = make_compressor(codec)
        for mask in (None, jnp.asarray([1, 0, 1, 1, 0, 1, 0, 1],
                                       jnp.float32)):
            key = jax.random.PRNGKey(3)
            want = compressed_average(key, stacked, comp, comp, mask=mask)
            avg_fn = make_client_sharded_average("clients", N, comp, comp)
            in_specs = (P(), jax.tree.map(lambda a: P("clients"), stacked))
            if mask is None:
                fn = lambda k, p: avg_fn(k, p)
                args = (key, stacked)
            else:
                fn = avg_fn
                in_specs = in_specs + (P(),)
                args = (key, stacked, mask)
            got = _shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=jax.tree.map(lambda a: P(), want))(
                *args)
            for k in want:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(want[k]))


def test_rollout_forced_xi_over_fused_path():
    """Forced-xi-trace equality extended to the fused aggregation: the
    scanned rollout and the legacy host loop agree bit-for-bit for
    flat-engine codecs WITH sampled participation (both route every
    aggregation round through the fused reduce)."""
    from repro.fl import run_l2gd

    xi = np.array([1, 1, 0, 0, 1, 1, 0, 1, 0, 0, 1, 1, 1, 0], np.int32)
    hp = make_hyper(eta=0.3, lam=1.0, p=0.5, n=4)
    batch = quad_batch()
    for codec in ("qsgd", "natural"):
        comp = make_compressor(codec)
        runs = {}
        for mode in ("scan", "host"):
            runs[mode] = run_l2gd(
                jax.random.PRNGKey(1), zero_params(), quad_grad_fn, hp,
                lambda k: batch, len(xi), client_comp=comp,
                master_comp=comp, mode=mode, xi_trace=xi,
                participation=0.5)
        a, b = runs["scan"], runs["host"]
        np.testing.assert_array_equal(np.asarray(a.state.params["w"]),
                                      np.asarray(b.state.params["w"]))
        np.testing.assert_array_equal(a.xis, b.xis)
        assert a.ledger.history == b.ledger.history


# ---------------------------------------------------------------------------
# HLO / memory-analysis guarantees
# ---------------------------------------------------------------------------

def _temp_bytes(fn, *specs):
    return jax.jit(fn).lower(*specs).compile() \
        .memory_analysis().temp_size_in_bytes


def test_aggregation_allocates_no_nd_fp32():
    """The O(d)-accumulator claim at the HLO level, on the CPU route:
    compiled temp bytes of the fused aggregation stay well under ONE
    (n, d) fp32 buffer.  The model is a single (d,) leaf with d a bucket
    multiple, so the encode side adds no ravel/pad copies and the bound
    isolates the server reduce.

    There is no lower bound on the decode-then-mean reference: XLA:CPU
    fuses the decode into the mean and allocates no (n, d) temp for it,
    so it no longer shows what an unfused server costs.  The device-side
    proof is the TPU compile of the Pallas reduce in
    tests/test_tpu_compile.py."""
    n, d = 16, 64 * 2048                       # (n, d) fp32 = 8 MiB
    plan = make_plan(make_compressor("qsgd"), {"w": jnp.zeros((d,))})
    payload_spec = jax.eval_shape(
        lambda ks, p: jax.vmap(plan.encode)(ks, p),
        jax.random.split(jax.random.PRNGKey(0), n),
        {"w": jax.ShapeDtypeStruct((n, d), jnp.float32)})

    nd_bytes = n * d * 4
    fused = _temp_bytes(lambda p: reduce_payload_mean(p, None),
                        payload_spec)
    assert fused < nd_bytes // 2, (fused, nd_bytes)

    # end-to-end: the whole compressed_average (encode + reduce + C_M).
    # The CLIENT-side encode runs one client at a time
    # (flatbuf.encode_clients), and the SERVER side adds only the O(d)
    # accumulator: total temps stay within a few KiB of one (n, d)
    # buffer instead of the decode path's extra (n, d) dequantized tree.
    e2e = _temp_bytes(
        lambda k, p: compressed_average(k, p, plan, Identity()),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        {"w": jax.ShapeDtypeStruct((n, d), jnp.float32)})
    assert e2e < nd_bytes + 64 * 1024, (e2e, nd_bytes)


def test_rollout_builders_donate_state_carry():
    """build_rollout_fn / build_sharded_rollout_fn / build_train_step
    donate the state carry: the compiled module aliases the stacked
    params buffer input->output (no full-size copy of the params inside
    a chunk), and a donated dispatch consumes its input."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.core import L2GDHyper
    from repro.launch.mesh import make_client_mesh
    from repro.launch.steps import (build_rollout_fn,
                                    build_sharded_rollout_fn,
                                    build_train_step, input_specs,
                                    state_specs)
    from repro.models import init_params

    cfg = dataclasses.replace(get_config("stablelm-1.6b").reduced(),
                              vocab_size=32)
    n, steps = 2, 2
    hp = L2GDHyper(eta=0.05, lam=0.5, p=0.4, n=n)
    state_sds = state_specs(cfg, n)
    params_bytes = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(state_sds.params))
    toks = jax.ShapeDtypeStruct((steps, n, 2, 8), jnp.int32)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)

    roll = build_rollout_fn(cfg, hp, length=steps)
    compiled = roll.lower(state_sds, {"tokens": toks}, key_sds).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= params_bytes, \
        (ma.alias_size_in_bytes, params_bytes)
    assert "input_output_alias" in compiled.as_text()

    mesh = make_client_mesh(1)
    sroll = build_sharded_rollout_fn(cfg, hp, mesh=mesh, length=steps)
    scompiled = sroll.lower(state_sds, {"tokens": toks}, key_sds).compile()
    assert scompiled.memory_analysis().alias_size_in_bytes >= params_bytes

    from repro.configs.base import INPUT_SHAPES
    step = build_train_step(cfg, hp)
    batch_sds = input_specs(cfg, dataclasses.replace(
        INPUT_SHAPES["train_4k"], seq_len=8, global_batch=n * 2), n)
    xi_sds = jax.ShapeDtypeStruct((), jnp.int32)
    tcompiled = step.lower(state_sds, batch_sds, xi_sds, key_sds).compile()
    assert tcompiled.memory_analysis().alias_size_in_bytes >= params_bytes

    # donation is real: a donated input is consumed by the dispatch
    params = jax.vmap(lambda k: init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), n))
    st = init_state(params)
    toks_arr = jax.random.randint(jax.random.PRNGKey(1), toks.shape, 0,
                                  cfg.vocab_size)
    out_st, _ = roll(st, {"tokens": toks_arr},
                     jax.random.key_data(jax.random.PRNGKey(2)))
    leaf = jax.tree.leaves(st.params)[0]
    assert leaf.is_deleted()
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(out_st.params))


# ---------------------------------------------------------------------------
# wire encode fast paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [1, 2, 4])
def test_pack_bits_narrow_widths_roundtrip(width):
    """The uint8 fast path packs/unpacks exactly like the generic uint32
    formula, ragged values included."""
    from repro.core.codec import pack_bits, unpack_bits
    per = 8 // width
    fields = jax.random.randint(jax.random.PRNGKey(0), (6, 5 * per), 0,
                                2 ** width).astype(jnp.uint32)
    packed = pack_bits(fields, width)
    assert packed.dtype == jnp.uint8 and packed.shape == (6, 5)
    # independent numpy reference
    f = np.asarray(fields).reshape(6, 5, per).astype(np.uint32)
    want = np.zeros((6, 5), np.uint32)
    for i in range(per):
        want |= f[..., i] << (i * width)
    np.testing.assert_array_equal(np.asarray(packed), want.astype(np.uint8))
    out = unpack_bits(packed, width)
    assert out.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(fields))


def test_natural_pack_one_pass_bit_exact_edges():
    """The one-pass bits-domain natural encode == split(fused)+pack for
    every input class: normals, zeros of both signs, subnormals, Inf,
    NaN (integer dither compare + exponent-field passthrough)."""
    from repro.core.codec import natural_split, pack_bits
    from repro.kernels.natural.kernel import natural_fused, natural_pack

    x = jax.random.normal(jax.random.PRNGKey(0), (40, 128)) * 100
    x = x.at[0, :8].set(jnp.asarray([0.0, -0.0, jnp.inf, -jnp.inf,
                                     jnp.nan, 1e-40, -1e-40, 3.5]))
    x = x.at[1].set(jnp.full((128,), 1e-39))   # dense subnormal row
    seeds = flatbuf.seeds_of(jax.random.PRNGKey(1))
    exps, packed = natural_pack(x, seeds)
    e_ref, signs = natural_split(natural_fused(x, seeds))
    np.testing.assert_array_equal(np.asarray(exps), np.asarray(e_ref))
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(pack_bits(signs, 1)))


def test_natural_fused_wide_view_bit_exact():
    """The wide-row evaluation of the natural oracle is invariant: the
    counter stream is keyed by the FLAT index, so any row-major view
    gives identical bits (here vs an explicit-noise evaluation at the
    original shape)."""
    from repro.kernels.natural.ref import (natural_compress_ref,
                                           natural_fused_ref)
    from repro.kernels.rng import counter_uniform_2d

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128)) * 2
    seeds = flatbuf.seeds_of(jax.random.PRNGKey(1))
    got = natural_fused_ref(x, seeds)                 # wide view inside
    want = natural_compress_ref(x, counter_uniform_2d(seeds, x.shape))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_reduce_payload_mean_rejects_leafwise():
    plan = make_plan(make_compressor("qsgd"), _one_model(),
                     transport="leafwise")
    payload = _payload(plan, _stacked_params(), N)
    assert not supports_fused_reduce(payload)
    with pytest.raises(ValueError, match="fused reduce"):
        reduce_payload_mean(payload, None)


def test_fused_reduce_empty_tree():
    plan = make_plan(make_compressor("qsgd"), {})
    payload = jax.vmap(plan.encode)(
        jax.random.split(jax.random.PRNGKey(0), 3), {})
    assert reduce_payload_mean(payload, None) == {}
