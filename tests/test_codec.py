"""Codec-API tests (ISSUE 2 acceptance criteria): Payload.nbits ==
tree_wire_bits for every compressor/transport combo, encode->decode
round-trip bit-exactness (incl. ragged last bucket), the apply ==
decode(encode(...)) guard for codecs with a custom fast path, the
ledger-reads-payload-spec lockstep property, the empty-pytree /
wire-bits edge cases, the deprecation shims, and the packed-natural
sharded aggregation."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import quad_grad_fn as _grad_fn
from repro.core import (L2GDHyper, QSGD, flatbuf, make_compressor,
                        make_plan, tree_apply, tree_wire_bits)
from repro.core.codec import (CompressionPlan, NaturalPayload, QSGDPayload,
                              TreePayload, as_plan, index_bits, pack_bits,
                              unpack_bits)
from repro.fl import run_l2gd

ALL = ["identity", "qsgd", "natural", "terngrad", "bernoulli", "randk",
       "topk"]
FLAT = ("qsgd", "natural")
COMBOS = [(n, t) for n in ALL
          for t in (["leafwise"] + (["flat", "packed"] if n in FLAT else []))]


def _tree(seed=0):
    """Multi-leaf, mixed-shape/dtype pytree; total size NOT a bucket or
    lane multiple (exercises the ragged last bucket)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "emb": jax.random.normal(ks[0], (17, 8)) * 3.0,
        "layers": [
            {"w": jax.random.normal(ks[1], (64, 33)),
             "b": jax.random.normal(ks[2], (64,)).astype(jnp.bfloat16)},
        ],
        "head": jax.random.normal(ks[3], (5,)),
    }


def _assert_trees_bitequal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# bit packing helpers
# --------------------------------------------------------------------------

@pytest.mark.parametrize("width", [1, 2, 4])
def test_pack_unpack_bits_roundtrip(width):
    rng = np.random.default_rng(0)
    fields = jnp.asarray(rng.integers(0, 1 << width, size=(3, 16)),
                         jnp.uint32)
    packed = pack_bits(fields, width)
    assert packed.dtype == jnp.uint8
    assert packed.shape == (3, 16 * width // 8)
    np.testing.assert_array_equal(np.asarray(unpack_bits(packed, width)),
                                  np.asarray(fields))


@pytest.mark.parametrize("width", [1, 2, 4])
@pytest.mark.parametrize("size", [16, 2056, 4096, 3 * 4096 + 8])
def test_lane_dense_pack_bits_matches(width, size):
    """The form of pack/unpack for 1-D buffers (a matmul over 128-byte
    rows) gives the same bytes and fields as the shift form that 2-D
    buffers take, also where the buffer is not a whole number of rows."""
    rng = np.random.default_rng(width)
    fields = jnp.asarray(rng.integers(0, 1 << width, size=size), jnp.uint8)
    shift = pack_bits(fields[None], width)[0]
    lanes = pack_bits(fields, width)
    assert lanes.dtype == shift.dtype and lanes.shape == shift.shape
    np.testing.assert_array_equal(np.asarray(lanes), np.asarray(shift))
    unpacked = unpack_bits(lanes, width)
    assert unpacked.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpacked),
                                  np.asarray(unpack_bits(shift[None],
                                                         width)[0]))
    np.testing.assert_array_equal(np.asarray(unpacked), np.asarray(fields))


# --------------------------------------------------------------------------
# Payload.nbits == tree_wire_bits == plan.round_bits (acceptance)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name,transport", COMBOS)
def test_payload_nbits_is_the_accounting(name, transport):
    comp = make_compressor(name)
    tree = _tree()
    plan = make_plan(comp, tree, transport=transport)
    payload = plan.encode(jax.random.PRNGKey(0), tree)
    nbits = float(payload.nbits)
    assert nbits > 0
    assert nbits == plan.round_bits()
    assert nbits == tree_wire_bits(comp, tree, transport=transport)


@pytest.mark.parametrize("name,transport", COMBOS)
def test_encode_decode_roundtrip_bit_exact(name, transport):
    """decode(encode(key, tree)) == plan.apply(key, tree) bit-exactly —
    including the flat engine's fused fast path and the ragged last
    bucket (_tree's total size is not a bucket multiple)."""
    comp = make_compressor(name)
    tree = _tree(seed=3)
    plan = make_plan(comp, tree, transport=transport)
    key = jax.random.PRNGKey(7)
    _assert_trees_bitequal(plan.apply(key, tree),
                           plan.decode(plan.encode(key, tree)))


@pytest.mark.parametrize("name", ALL)
def test_apply_equals_decode_encode_per_array(name):
    """The Codec guard: apply(key, x) == decode(encode(key, x)) for every
    codec — in particular the elementwise fast paths (identity, natural,
    bernoulli) must stay bit-exact to the wire path."""
    comp = make_compressor(name)
    key = jax.random.PRNGKey(11)
    for shape, dtype in [((7, 13), jnp.float32), ((129,), jnp.float32),
                         ((6, 4), jnp.bfloat16)]:
        x = (jax.random.normal(jax.random.PRNGKey(5), shape) * 2.7) \
            .astype(dtype)
        a = comp.apply(key, x)
        b = comp.decode(comp.encode(key, x))
        assert a.shape == b.shape == x.shape and a.dtype == b.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_natural_payload_bit_exact_vs_fused_kernel():
    """NaturalPayload (uint8 sign+exponent codes) decodes bit-exactly to
    the fused kernel's output (satellite #1)."""
    tree = _tree(seed=9)
    key = jax.random.PRNGKey(13)
    comp = make_compressor("natural")
    payload, layout = flatbuf.pack_tree_natural(key, tree)
    assert isinstance(payload, NaturalPayload)
    assert payload.exps.dtype == jnp.uint8
    assert payload.signs.dtype == jnp.uint8
    assert payload.nbits == 9 * layout.padded  # 8 exp bits + packed sign
    _assert_trees_bitequal(flatbuf.unpack_tree(payload),
                           flatbuf.flat_tree_apply(comp, key, tree))


def test_payload_carries_layout_and_survives_tree_map():
    payload, layout = flatbuf.pack_tree_qsgd(jax.random.PRNGKey(0),
                                             _tree(), bucket=2048)
    assert payload.layout == layout
    mapped = jax.tree_util.tree_map(lambda a: a[None], payload)
    assert isinstance(mapped, QSGDPayload)
    assert mapped.layout == layout          # static meta preserved
    codes, norms = payload                  # NamedTuple-compat unpacking
    assert codes is payload.codes and norms is payload.norms


# --------------------------------------------------------------------------
# ledger reads the payload spec (acceptance: perturb spec -> ledger moves)
# --------------------------------------------------------------------------

def _run(comp, plan, steps=40):
    n, d = 4, 60
    hp = L2GDHyper(eta=0.3, lam=1.0, p=0.5, n=n)
    batch = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    return run_l2gd(jax.random.PRNGKey(1), {"w": jnp.zeros((n, d))},
                    _grad_fn, hp, lambda k: batch, steps,
                    client_comp=comp, master_comp=comp,
                    plan=(plan, plan))


def test_ledger_reads_payload_nbits_lockstep():
    """Perturbing a codec's payload spec (levels > 127 widens the code
    dtype int8 -> int16) moves the ledger by exactly the payload delta —
    no independent re-derivation in the driver."""
    d = 60
    one = {"w": jnp.zeros((d,))}

    def per_round_bits(levels):
        comp = QSGD(levels=levels)
        plan = make_plan(comp, one, transport="leafwise")
        r = _run(comp, plan)
        assert r.ledger.rounds > 0
        payload = plan.encode(jax.random.PRNGKey(0), one)
        # every recorded number IS rounds * Payload.nbits
        assert r.ledger.uplink_bits_per_client == \
            r.ledger.rounds * float(payload.nbits)
        assert r.ledger.downlink_bits_per_client == \
            r.ledger.rounds * float(payload.nbits)
        return r.ledger.uplink_bits_per_client / r.ledger.rounds

    b127 = per_round_bits(127)
    b255 = per_round_bits(255)
    assert b255 - b127 == 8 * d  # codes widened by 8 bits/element


def test_run_l2gd_packed_natural_plan():
    """The packed transport is no longer qsgd-only: a packed-natural plan
    drives run_l2gd and the ledger charges its exact payload."""
    comp = make_compressor("natural")
    one = {"w": jnp.zeros((60,))}
    plan = make_plan(comp, one, transport="packed")
    r = _run(comp, plan)
    assert r.ledger.rounds > 0
    assert r.ledger.uplink_bits_per_client == \
        r.ledger.rounds * plan.round_bits()
    # 9 bits/element over the lane-padded buffer (60 -> 128)
    assert plan.round_bits() == 9 * 128


# --------------------------------------------------------------------------
# wire-bits edge cases (satellite #6)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name,transport", COMBOS)
def test_empty_pytree_costs_zero(name, transport):
    comp = make_compressor(name)
    assert tree_wire_bits(comp, {}, transport=transport) == 0.0
    plan = make_plan(comp, {}, transport=transport)
    payload = plan.encode(jax.random.PRNGKey(0), {})
    assert float(payload.nbits) == 0.0
    assert jax.tree.leaves(plan.decode(payload)) == []


def test_empty_leaf_costs_zero_both_paths():
    tree = {"z": jnp.zeros((0,), jnp.float32)}
    for name in ALL:
        comp = make_compressor(name)
        assert comp.wire_bits((0,)) == 0.0, name
        assert tree_wire_bits(comp, tree, transport="leafwise") == 0.0, name
    for name in FLAT:
        assert tree_wire_bits(make_compressor(name), tree,
                              transport="flat") == 0.0, name
    assert flatbuf.packed_wire_bits(tree) == 0


def test_bernoulli_index_width_n1():
    """Bernoulli charges at least one presence bit per expected survivor
    even for n=1 (the historic under-charge), and index widths are
    ceil(log2 d)."""
    comp = make_compressor("bernoulli", q=0.25)
    assert comp.wire_bits((1,)) == 0.25 * (32.0 + 1.0)
    assert index_bits(1) == 1.0
    assert index_bits(2) == 1.0
    assert index_bits(100000) == 17.0  # ceil(log2 1e5), not 16.6


# --------------------------------------------------------------------------
# deprecation shims (zero in-repo callers; still work, warn by name)
# --------------------------------------------------------------------------

def test_tree_apply_flat_shim_warns_and_matches_plan():
    comp = make_compressor("qsgd")
    tree = _tree(seed=4)
    key = jax.random.PRNGKey(0)
    with pytest.warns(DeprecationWarning, match="CompressionPlan"):
        legacy = tree_apply(comp, key, tree, flat=True)
    _assert_trees_bitequal(
        legacy, make_plan(comp, transport="flat").apply(key, tree))
    with pytest.warns(DeprecationWarning, match="CompressionPlan"):
        tree_wire_bits(comp, tree, flat=False)


def test_run_l2gd_packed_uplink_shim():
    comp = make_compressor("qsgd")
    n, d = 4, 60
    hp = L2GDHyper(eta=0.3, lam=1.0, p=0.5, n=n)
    batch = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    with pytest.warns(DeprecationWarning, match="make_plan"):
        r = run_l2gd(jax.random.PRNGKey(1), {"w": jnp.zeros((n, d))},
                     _grad_fn, hp, lambda k: batch, 30,
                     client_comp=comp, master_comp=comp,
                     packed_uplink=True)
    plan = make_plan(comp, {"w": jnp.zeros((d,))}, transport="packed")
    assert r.ledger.uplink_bits_per_client == \
        r.ledger.rounds * plan.round_bits()


def test_build_average_fn_kind_shim():
    from jax.sharding import PartitionSpec as P
    from repro.launch.steps import build_average_fn
    from test_layouts import _mesh_1x1

    mesh = _mesh_1x1()
    pspecs = {"w": P("data", None)}
    comp = make_compressor("natural")
    with pytest.warns(DeprecationWarning, match="uplink"):
        legacy = build_average_fn("packed", mesh, ("data",), pspecs, comp,
                                  bucket=128)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 32))}
    with mesh:
        out = legacy(jax.random.PRNGKey(1), params)
    assert out["w"].shape == (32,)


def test_l2gd_step_flat_shim_warns():
    from repro.core import init_state, l2gd_step
    st = init_state({"w": jnp.ones((2, 4))})
    with pytest.warns(DeprecationWarning, match="CompressionPlan"):
        l2gd_step(st, jnp.zeros((2, 4)), jnp.asarray(0, jnp.int32),
                  jax.random.PRNGKey(0), _grad_fn,
                  L2GDHyper(eta=0.1, lam=1.0, p=0.5, n=2), flat=False)


def test_as_plan_passthrough():
    comp = make_compressor("qsgd")
    plan = make_plan(comp, transport="packed")
    assert as_plan(plan) is plan
    auto = as_plan(comp)
    assert isinstance(auto, CompressionPlan) and auto.transport == "flat"
    assert as_plan(make_compressor("randk")).transport == "leafwise"
    with pytest.raises(ValueError, match="flat-engine"):
        make_plan(make_compressor("randk"), transport="packed")
    with pytest.raises(ValueError, match="unbound"):
        make_plan(comp).round_bits()


def test_flat_rejects_wide_qsgd_levels():
    """levels > 127 exceeds the flat engine's int8 wire format: the plan
    is rejected up front (the leafwise transport widens to int16
    instead; a silent int8 clamp would break unbiasedness)."""
    wide = QSGD(levels=255)
    for transport in ("flat", "packed"):
        with pytest.raises(ValueError, match="int8"):
            make_plan(wide, transport=transport)
    with pytest.raises(ValueError, match="int8"):
        flatbuf.pack_tree_qsgd(jax.random.PRNGKey(0),
                               {"w": jnp.ones((16,))}, levels=255)
    # leafwise handles it exactly: int16 codes, decode == apply
    plan = make_plan(wide, {"w": jnp.ones((16,))}, transport="leafwise")
    x = {"w": jnp.asarray([10.0] + [0.01] * 15)}
    key = jax.random.PRNGKey(1)
    payload = plan.encode(key, x)
    assert payload.leaves[0].codes.dtype == jnp.int16
    _assert_trees_bitequal(plan.decode(payload), plan.apply(key, x))


def test_build_average_fn_rejects_stray_kwargs():
    from jax.sharding import PartitionSpec as P
    from repro.launch.steps import build_average_fn
    from test_layouts import _mesh_1x1

    plan = make_plan(make_compressor("qsgd"), transport="packed")
    with pytest.raises(TypeError, match="unexpected keyword"):
        build_average_fn(_mesh_1x1(), ("data",), {"w": P("data", None)},
                         make_compressor("natural"), uplink=plan, bucket=128)


# --------------------------------------------------------------------------
# packed-payload sharded aggregation for the new transport
# --------------------------------------------------------------------------

def test_payload_sharded_average_natural_unbiased():
    """make_payload_sharded_average with a packed-natural plan on a 1x1
    mesh == plain mean in expectation (uint8 sign+exponent codes on the
    wire, Lemma 2 intact)."""
    from jax.sharding import PartitionSpec as P
    from repro.core.aggregation import make_payload_sharded_average
    from test_layouts import _mesh_1x1

    mesh = _mesh_1x1()
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 32))}
    pspecs = {"w": P("data", None)}
    plan = make_plan(make_compressor("natural"), transport="packed")
    avg_fn = make_payload_sharded_average(mesh, ("data",), pspecs,
                                          make_compressor("identity"), plan)
    with mesh:
        keys = jax.random.split(jax.random.PRNGKey(1), 1500)
        outs = jax.vmap(lambda k: avg_fn(k, params)["w"])(keys)
    xbar = jnp.mean(params["w"], 0)
    err = float(jnp.max(jnp.abs(jnp.mean(outs, 0) - xbar)))
    assert err < 0.05, err


def test_no_deprecation_warnings_on_plan_paths():
    """The migrated in-repo surface emits no DeprecationWarnings (the CI
    -W error::DeprecationWarning leg enforces the same globally)."""
    comp = make_compressor("qsgd")
    tree = _tree(seed=6)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        plan = make_plan(comp, tree, transport="packed")
        plan.decode(plan.encode(jax.random.PRNGKey(0), tree))
        plan.round_bits()
        tree_apply(comp, jax.random.PRNGKey(0), tree)   # bare call: clean
        tree_wire_bits(comp, tree)
        _run(comp, make_plan(comp, {"w": jnp.zeros((60,))}), steps=12)
