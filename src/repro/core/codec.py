"""Wire-first codec layer: Payloads + the per-model CompressionPlan.

This is the single compression API every layer consumes (DESIGN.md §7):

  * a ``Payload`` is a pytree-registered dataclass carrying the ACTUAL
    wire arrays of one compressed message (int8 codes + per-bucket norms
    for QSGD, uint8 sign+exponent codes for natural, packed 2-bit fields
    for terngrad, (indices, values) for rand-k/top-k, bitmap + values for
    bernoulli) plus an exact ``nbits`` property.  The bits ledger, the
    packed all_gather uplink and ``tree_wire_bits`` all read the same
    number from the same object.
  * every compressor implements the ``Codec`` protocol —
    ``encode(key, x) -> Payload`` / ``decode(Payload) -> x`` — with
    ``apply = decode ∘ encode`` as the derived default
    (repro.core.compressors).
  * a :class:`CompressionPlan` is built ONCE per model from
    (codec, transport, one-model shapes) via :func:`make_plan` and
    replaces the scattered ``flat=`` / ``packed_uplink=`` / ``kind=``
    flags.  ``plan.round_bits()`` is the shape-static wire cost of one
    message, derived from the payload spec via ``jax.eval_shape`` — NO
    independent re-derivation anywhere.

Transports:

  leafwise — per-leaf encode/decode (every codec; the pjit-safe path:
             no cross-leaf ravel, so model-axis-sharded leaves are never
             rematerialized)
  flat     — whole-pytree flat-buffer engine, ONE fused kernel launch
             (qsgd/natural; repro.core.flatbuf); ``apply`` skips payload
             materialization via the fused quantize-dequantize kernel
  packed   — same payload spec as ``flat`` but the payload arrays are
             what crosses the aggregation collective
             (repro.core.aggregation.make_payload_sharded_average) and
             ``apply`` materializes the payload (encode -> decode)

``nbits`` is exact for every codec except Bernoulli, whose survivor
count is a random variable: its payload carries the exact bitmap plus
the dense value buffer, and ``nbits`` charges the bitmap exactly plus
the EXPECTED compacted value bytes (q * d * 32) — the only
stochastic-size codec (DESIGN.md §7).

This module depends only on jax/numpy; ``repro.core.flatbuf`` imports
the payload classes from here and is imported lazily by the plan's
flat-path methods.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Payload", "DensePayload", "QSGDPayload", "NaturalPayload",
    "TernPayload", "SparsePayload", "BernoulliPayload", "TreePayload",
    "NarrowQSGDPayload", "CompressionPlan", "make_plan", "as_plan",
    "TRANSPORTS", "index_bits", "pack_bits", "unpack_bits",
    "natural_split", "natural_merge", "decode_payload",
    "plan_spec", "plan_from_spec",
]

TRANSPORTS = ("leafwise", "flat", "packed")

# sentinel for deprecated keyword arguments (distinguishes "not passed"
# from an explicit None); shared by the back-compat shims repo-wide
_UNSET = object()


def _legacy_transport(flat, where: str) -> Optional[str]:
    """THE ``flat=`` deprecation shim, shared by every legacy keyword
    site (tree_apply, tree_wire_bits, compressed_average, l2gd_step):
    warn with the replacement plan spelling and map the boolean to a
    transport name (None stays None = auto)."""
    warnings.warn(
        f"{where} is deprecated; build a CompressionPlan once per model "
        "(repro.core.codec.make_plan(comp, params, transport="
        "'flat'|'leafwise'|'packed')) and use plan.apply / "
        "plan.round_bits()", DeprecationWarning, stacklevel=3)
    if flat is None:
        return None
    return "flat" if flat else "leafwise"


def _nelem(shape) -> int:
    return int(np.prod(shape)) if len(shape) else 1


def _itembits(a) -> float:
    return 8.0 * np.dtype(a.dtype).itemsize


def index_bits(d: int) -> float:
    """Wire width of one coordinate index into a size-``d`` array:
    ceil(log2 d), never below 1 (a 1-element array still spends one
    presence bit — the historic ``Bernoulli.wire_bits`` under-charge)."""
    if d <= 1:
        return 1.0
    return float(max(math.ceil(math.log2(d)), 1))


def _register(cls, data_fields, meta_fields):
    jax.tree_util.register_dataclass(cls, data_fields=list(data_fields),
                                     meta_fields=list(meta_fields))
    return cls


# --------------------------------------------------------------------------
# bit packing helpers (shared by natural / terngrad / bernoulli codecs)
# --------------------------------------------------------------------------

def pack_bits(fields: jax.Array, width: int) -> jax.Array:
    """Pack small unsigned ints (< 2**width) along the last axis into
    uint8 bytes, little-endian within the byte.  The last axis must be a
    multiple of ``8 // width``.

    The natural 1-bit sign bitmap and the ternary 2-bit fields (every
    in-repo width divides 8) stay entirely in uint8 arithmetic: the
    shifted fields and their byte sum are exact in 8 bits (all-ones at
    width 1 sums to exactly 255), so the intermediates carry 1 byte per
    field instead of the 4 of a uint32 pipeline — this pack is on the
    wire-encode hot path (``pack_tree_natural``, ``TernGrad.encode``).
    A 1-D buffer takes the lane-dense :func:`_pack_bits_lanes`."""
    if fields.ndim == 1:
        return _pack_bits_lanes(fields, width)
    per = 8 // width
    if 8 % width == 0:
        b = fields.astype(jnp.uint8).reshape(fields.shape[:-1] + (-1, per))
        shifts = jnp.arange(per, dtype=jnp.uint8) * jnp.uint8(width)
        return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint8)
    b = fields.astype(jnp.uint32).reshape(fields.shape[:-1] + (-1, per))
    shifts = jnp.arange(per, dtype=jnp.uint32) * jnp.uint32(width)
    return jnp.sum(b << shifts, axis=-1).astype(jnp.uint8)


def unpack_bits(packed: jax.Array, width: int) -> jax.Array:
    """Inverse of :func:`pack_bits` (returns uint32 fields).  Widths
    dividing 8 shift/mask in uint8 (4x narrower intermediates than the
    generic uint32 path); the final widening cast fuses into consumers.
    A 1-D buffer takes the lane-dense :func:`_unpack_bits_lanes`."""
    if packed.ndim == 1:
        return _unpack_bits_lanes(packed, width)
    per = 8 // width
    if 8 % width == 0:
        shifts = jnp.arange(per, dtype=jnp.uint8) * jnp.uint8(width)
        mask = jnp.uint8((1 << width) - 1)
        out = (packed.astype(jnp.uint8)[..., None] >> shifts) & mask
        return out.reshape(packed.shape[:-1] + (-1,)).astype(jnp.uint32)
    shifts = jnp.arange(per, dtype=jnp.uint32) * jnp.uint32(width)
    mask = jnp.uint32((1 << width) - 1)
    out = (packed.astype(jnp.uint32)[..., None] >> shifts) & mask
    return out.reshape(packed.shape[:-1] + (-1,))


# The forms of pack_bits / unpack_bits for 1-D buffers (what the
# leafwise codecs pack), on every backend.  The shift forms above view
# the fields as (bytes, 8 // width), a minor dimension of 8 or fewer,
# which a TPU pads to 128 lanes: 16x the bytes, and a program that packs
# and unpacks (the client-sharded engine's encode -> all_gather ->
# decode) kept the TPU compiler busy for minutes at stablelm-1.6b
# widths.  Here the buffer is cut into rows of 128 bytes and the
# per-byte shifts become a matmul with power-of-two weights, so every
# minor dimension is 128 bytes or 128 * per fields.  It is exact: fields
# below 2**width and byte values up to 255 are exact in bf16, and the
# matmul sums them in f32.  On the CPU a pack and unpack of 2^24 fields
# takes about 1.6x the shift form's time.  2-D buffers (the flat
# transport's (buckets, 128) rows, already 128 lanes wide) keep the
# shift form: packed lane-dense, the flat encode of two stablelm-1.6b
# clients took the TPU compiler over four minutes.
_LANES = 128


def _lane_rows(flat: jax.Array, row: int) -> jax.Array:
    """The 1-D buffer as (rows, ``row``), zero-padded at the end."""
    pad = (-flat.shape[0]) % row
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, row)


def _pack_bits_lanes(fields: jax.Array, width: int) -> jax.Array:
    per = 8 // width
    weights = np.zeros((_LANES * per, _LANES), np.float32)
    for k in range(per):
        weights[np.arange(_LANES) * per + k, np.arange(_LANES)] = \
            2.0 ** (width * k)
    rows = _lane_rows(fields.astype(jnp.bfloat16), _LANES * per)
    packed = jnp.dot(rows, jnp.asarray(weights, jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    packed = packed.astype(jnp.int32).astype(jnp.uint8).reshape(-1)
    return packed[:fields.shape[0] // per]


def _unpack_bits_lanes(packed: jax.Array, width: int) -> jax.Array:
    per = 8 // width
    spread = np.zeros((_LANES, _LANES * per), np.float32)
    spread[np.arange(_LANES * per) // per, np.arange(_LANES * per)] = 1.0
    rows = _lane_rows(packed.astype(jnp.bfloat16), _LANES)
    # every byte repeated per times along the lanes, then field k of it
    # shifted down where lane % per == k
    byte = jnp.dot(rows, jnp.asarray(spread, jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, byte.shape, 1)
    fields = (byte >> ((lane % per) * width)) & ((1 << width) - 1)
    return fields.astype(jnp.uint32).reshape(-1)[:packed.shape[0] * per]


def natural_split(y: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Bit-split the OUTPUT of natural compression (finite float32 values
    with zero mantissa: ±2^e or ±0) into its 9 wire bits per element:
    (uint8 biased-exponent codes, 0/1 sign fields).  NaN/Inf inputs are
    not representable (their mantissa/semantics exceed 9 bits)."""
    bits = jax.lax.bitcast_convert_type(y.astype(jnp.float32), jnp.uint32)
    exps = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.uint8)
    signs = (bits >> 31).astype(jnp.uint8)
    return exps, signs


def natural_merge(exps: jax.Array, signs: jax.Array) -> jax.Array:
    """Inverse of :func:`natural_split` — bit-exact reconstruction."""
    bits = (signs.astype(jnp.uint32) << 31) | (exps.astype(jnp.uint32) << 23)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


# --------------------------------------------------------------------------
# payloads — what actually crosses the wire
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DensePayload:
    """Uncompressed transport (identity codec): the raw float32 values."""

    values: Any
    shape: Optional[tuple] = None      # original array shape (static)
    dtype: Any = None                  # original array dtype (static)

    @property
    def nbits(self) -> float:
        return float(self.values.size) * _itembits(self.values)


_register(DensePayload, ("values",), ("shape", "dtype"))


@dataclasses.dataclass(frozen=True)
class QSGDPayload:
    """QSGD wire message: sign*magnitude integer codes (int8 while
    ``levels <= 127``, int16 beyond) plus one float32 norm per bucket.
    Flat/packed transports carry ``codes`` as the bucketized
    ``(n_buckets, bucket)`` view (padding included — that is what the
    all_gather moves); the leafwise transport carries the unpadded
    ``(d,)`` prefix."""

    codes: Any
    norms: Any
    levels: int = 127                  # static
    layout: Any = None                 # FlatLayout for tree payloads (static)
    shape: Optional[tuple] = None
    dtype: Any = None

    @property
    def nbits(self) -> float:
        return (float(self.codes.size) * _itembits(self.codes)
                + 32.0 * float(self.norms.size))

    def __iter__(self):  # back-compat with the PR-1 NamedTuple payload
        return iter((self.codes, self.norms))


_register(QSGDPayload, ("codes", "norms"),
          ("levels", "layout", "shape", "dtype"))


@dataclasses.dataclass(frozen=True)
class NaturalPayload:
    """Natural-compression wire message: one uint8 biased-exponent code
    per element plus the packed sign bitmap (8 signs/byte) — 9
    bits/element, bit-exact against the fused kernel output."""

    exps: Any
    signs: Any
    layout: Any = None
    shape: Optional[tuple] = None
    dtype: Any = None

    @property
    def nbits(self) -> float:
        return 8.0 * float(self.exps.size) + 8.0 * float(self.signs.size)


_register(NaturalPayload, ("exps", "signs"), ("layout", "shape", "dtype"))


@dataclasses.dataclass(frozen=True)
class TernPayload:
    """TernGrad wire message: packed 2-bit ternary fields (4
    elements/byte; 0 -> 0, 1 -> +1, 2 -> -1) plus one float32
    ||x||_inf scale per bucket."""

    codes: Any
    scales: Any
    bucket: int = 2048                 # static
    shape: Optional[tuple] = None
    dtype: Any = None

    @property
    def nbits(self) -> float:
        return 8.0 * float(self.codes.size) + 32.0 * float(self.scales.size)


_register(TernPayload, ("codes", "scales"), ("bucket", "shape", "dtype"))


@dataclasses.dataclass(frozen=True)
class SparsePayload:
    """rand-k / top-k wire message: the k surviving (index, value)
    pairs.  Indices are carried as int32 but charged at their true width
    ceil(log2 d) (:func:`index_bits`)."""

    indices: Any
    values: Any
    shape: Optional[tuple] = None
    dtype: Any = None

    @property
    def nbits(self) -> float:
        d = _nelem(self.shape) if self.shape is not None else 0
        return float(self.indices.size) * index_bits(d) \
            + 32.0 * float(self.values.size)


_register(SparsePayload, ("indices", "values"), ("shape", "dtype"))


@dataclasses.dataclass(frozen=True)
class BernoulliPayload:
    """Bernoulli-sparsifier wire message: the exact survivor bitmap (8
    elements/byte) plus the dense scaled value buffer.  On the wire the
    buffer is compacted by the bitmap, so ``nbits`` charges the bitmap
    exactly plus the EXPECTED compacted size 32*q*d — the one codec
    whose message size is a random variable (DESIGN.md §7)."""

    mask: Any
    values: Any
    q: float = 0.25                    # static
    shape: Optional[tuple] = None
    dtype: Any = None

    @property
    def nbits(self) -> float:
        return 8.0 * float(self.mask.size) \
            + 32.0 * float(self.q) * float(self.values.size)


_register(BernoulliPayload, ("mask", "values"), ("q", "shape", "dtype"))


@dataclasses.dataclass(frozen=True)
class NarrowQSGDPayload:
    """Storage repack of a flat-engine :class:`QSGDPayload` with small
    ``levels``: the int8 sign-magnitude codes shrink to ``width``-bit
    fields (sign in the top bit, magnitude below — ``levels <= 1`` fits
    2 bits, ``levels <= 7`` fits 4) packed 8/width per byte.  This is a
    RESIDENCY format, not a wire format: the serving delta store
    (repro.serve.store) holds tenants in it and widens back to the exact
    int8 payload on materialization (bit-exact round-trip,
    ``flatbuf.widen_tree_qsgd``)."""

    codes: Any                         # packed uint8, (n_buckets, bucket*width/8)
    norms: Any
    levels: int = 7                    # static
    width: int = 4                     # static bits per code
    layout: Any = None
    shape: Optional[tuple] = None
    dtype: Any = None

    @property
    def nbits(self) -> float:
        return (float(self.codes.size) * _itembits(self.codes)
                + 32.0 * float(self.norms.size))


_register(NarrowQSGDPayload, ("codes", "norms"),
          ("levels", "width", "layout", "shape", "dtype"))


@dataclasses.dataclass(frozen=True)
class TreePayload:
    """Leafwise transport: one per-leaf payload per tree leaf, in
    ``tree_flatten`` order."""

    leaves: tuple
    treedef: Any = None                # static

    @property
    def nbits(self) -> float:
        return float(sum(p.nbits for p in self.leaves))


_register(TreePayload, ("leaves",), ("treedef",))

#: union of every payload class (for isinstance checks / docs)
Payload = (DensePayload, QSGDPayload, NaturalPayload, TernPayload,
           SparsePayload, BernoulliPayload, TreePayload,
           NarrowQSGDPayload)


def decode_payload(payload, codec=None):
    """Standalone dequantize of ANY payload — the decode-only entry point
    the read-heavy serving path consumes (no :class:`CompressionPlan`
    instance, no encode machinery on the hot path).

    Flat-engine payloads (``QSGDPayload`` / ``NaturalPayload`` /
    ``NarrowQSGDPayload`` carrying their :class:`~repro.core.flatbuf.
    FlatLayout`) decode through the fused unpack kernels and need no
    codec.  Leaf payloads and ``TreePayload`` dispatch to
    ``codec.decode`` (the codec that produced them — required because
    bucket geometry lives on the compressor); a ``DensePayload`` decodes
    without one."""
    from repro.core import flatbuf
    if isinstance(payload, (QSGDPayload, NaturalPayload, NarrowQSGDPayload)) \
            and getattr(payload, "layout", None) is not None:
        if isinstance(payload, NarrowQSGDPayload):
            payload = flatbuf.widen_tree_qsgd(payload)
        return flatbuf.unpack_tree(payload)
    if isinstance(payload, TreePayload):
        if codec is None:
            raise ValueError("decode_payload(TreePayload) needs the codec "
                             "that produced the per-leaf payloads")
        return jax.tree_util.tree_unflatten(
            payload.treedef, [codec.decode(p) for p in payload.leaves])
    if isinstance(payload, DensePayload) and codec is None:
        return payload.values.reshape(payload.shape).astype(payload.dtype)
    if codec is None:
        raise ValueError(f"decode_payload({type(payload).__name__}) needs "
                         "its codec (bucket geometry lives on the "
                         "compressor)")
    return codec.decode(payload)


# --------------------------------------------------------------------------
# the plan
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class CompressionPlan:
    """One model's compression recipe: (codec, transport, shapes).

    Built via :func:`make_plan`; every layer (driver, pjit step,
    shard_map aggregation, benchmarks) consumes plans instead of
    ``flat=`` / ``packed_uplink=`` / ``kind=`` flags.  ``encode`` /
    ``decode`` / ``apply`` operate on whole pytrees; ``round_bits()`` is
    the exact, shape-static wire cost of one message, read from the
    payload spec (``jax.eval_shape`` over ``encode`` -> ``nbits``).

    Layouts are recomputed from the pytree actually passed in (static
    Python work at trace time), so a plan bound to global one-model
    shapes can still encode shard-local trees inside ``shard_map``; the
    bound ``specs`` exist purely so ``round_bits()`` has a model to
    measure.
    """

    codec: Any                          # the Codec (a Compressor)
    transport: str = "leafwise"
    specs: Any = None                   # one-model ShapeDtypeStruct pytree
    bucket: Optional[int] = None        # flat-engine bucket override
    narrow: bool = False                # sub-byte QSGD wire (levels <= 7)

    def bind(self, params) -> "CompressionPlan":
        """Return a copy bound to ``params``' shapes (enables
        ``round_bits``); accepts arrays or ShapeDtypeStructs."""
        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), params)
        return dataclasses.replace(self, specs=specs)

    # -- wire path ----------------------------------------------------------
    def encode(self, key: jax.Array, tree):
        """Quantize a whole pytree to its wire Payload."""
        if self.transport == "leafwise":
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            keys = jax.random.split(key, max(len(leaves), 1))
            return TreePayload(tuple(self.codec.encode(k, leaf)
                                     for k, leaf in zip(keys, leaves)),
                               treedef)
        from repro.core import flatbuf
        payload = flatbuf.pack_tree(self.codec, key, tree, bucket=self.bucket)
        if self.narrow:
            # sub-byte wire: repack the int8 QSGD codes into width-bit
            # fields (lossless — widen_tree_qsgd is the bit-exact
            # inverse), so small-levels plans pay ~levels-worth of wire
            # instead of a full byte per element.  nbits (and therefore
            # round_bits / the ledger) reads the packed buffer.
            payload = flatbuf.narrow_tree_qsgd(payload)
        return payload

    def decode(self, payload):
        """Dequantize a Payload back to the pytree."""
        if isinstance(payload, TreePayload):
            return jax.tree_util.tree_unflatten(
                payload.treedef,
                [self.codec.decode(p) for p in payload.leaves])
        from repro.core import flatbuf
        return flatbuf.unpack_tree(payload)

    def apply(self, key: jax.Array, tree):
        """C(tree) == decode(encode(key, tree)) bit-exactly; the flat
        transport takes the fused quantize-dequantize kernel instead of
        materializing the payload (kernel-level bit-exactness is
        test-enforced), the packed transport materializes it."""
        if self.transport == "flat":
            from repro.core import flatbuf
            return flatbuf.flat_tree_apply(self.codec, key, tree,
                                           bucket=self.bucket)
        if self.transport == "packed":
            return self.decode(self.encode(key, tree))
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, max(len(leaves), 1))
        return jax.tree_util.tree_unflatten(
            treedef, [self.codec.apply(k, leaf)
                      for k, leaf in zip(keys, leaves)])

    # -- accounting ---------------------------------------------------------
    def round_bits(self) -> float:
        """Exact wire bits of ONE message under this plan — the number
        the ledger records.  Shape-static: evaluated on the payload SPEC
        (``jax.eval_shape`` over ``encode``), so it is derived from the
        same object the transport moves, never re-derived."""
        if self.specs is None:
            raise ValueError(
                "unbound plan: build with make_plan(codec, params, ...) or "
                "call plan.bind(params) before round_bits()")
        payload = jax.eval_shape(self.encode, jax.random.PRNGKey(0),
                                 self.specs)
        return float(payload.nbits)


def make_plan(codec, params=None, *, transport: Optional[str] = None,
              bucket: Optional[int] = None,
              narrow: bool = False) -> CompressionPlan:
    """Build the once-per-model :class:`CompressionPlan`.

    Args:
      codec: a compressor implementing the Codec protocol.
      params: one-model pytree (arrays or ShapeDtypeStructs, NO client
        axis) to bind for ``round_bits``; ``None`` gives an unbound plan
        (encode/decode/apply still work).
      transport: ``"leafwise"`` | ``"flat"`` | ``"packed"``; ``None``
        auto-selects ``"flat"`` for codecs with a fused flat engine
        (qsgd/natural) and ``"leafwise"`` otherwise.  Pin ``"leafwise"``
        under pjit with model-axis-sharded params (DESIGN.md §7
        sharding table).
      bucket: flat-engine bucket override (defaults to the codec's).
      narrow: carry QSGD codes as packed sub-byte fields on the wire
        (flat/packed transport, ``levels <= 7``): 4 bits/code at
        levels 2..7, 2 bits at levels 1 — lossless vs the int8 payload
        (``flatbuf.widen_tree_qsgd`` round-trips bit-exactly), so
        ``round_bits`` drops from ~8 to ~4 (or ~2) bits/element.  This
        is what makes small qsgd levels a REAL bandwidth knob for the
        fleet controller (DESIGN.md §13).
    """
    from repro.core import flatbuf
    if transport is None:
        transport = "flat" if flatbuf.supports_flat(codec) else "leafwise"
    if transport not in TRANSPORTS:
        raise ValueError(f"unknown transport {transport!r}; "
                         f"have {TRANSPORTS}")
    if transport in ("flat", "packed") and not flatbuf.supports_flat(codec):
        raise ValueError(
            f"transport {transport!r} needs a flat-engine codec "
            f"(qsgd/natural), got {getattr(codec, 'name', codec)!r}")
    if transport in ("flat", "packed") \
            and getattr(codec, "name", None) == "qsgd" and codec.levels > 127:
        raise ValueError(
            f"levels={codec.levels} does not fit the flat engine's int8 "
            "wire payload; use transport='leafwise' (int16 codes) or "
            "levels <= 127")
    if narrow:
        if transport not in ("flat", "packed"):
            raise ValueError("narrow=True needs the flat-engine payload "
                             "(transport='flat' or 'packed'), not "
                             f"{transport!r}")
        if getattr(codec, "name", None) != "qsgd":
            raise ValueError("narrow=True is a QSGD sub-byte repack; got "
                             f"codec {getattr(codec, 'name', codec)!r}")
        if codec.levels > 7:
            raise ValueError(f"levels={codec.levels} does not fit a 4-bit "
                             "narrow code (sign + 3 magnitude bits); use "
                             "levels <= 7 or narrow=False")
    plan = CompressionPlan(codec=codec, transport=transport, bucket=bucket,
                           narrow=narrow)
    return plan.bind(params) if params is not None else plan


def plan_spec(plan: CompressionPlan) -> dict:
    """Serializable recipe for a plan built from a registry compressor
    (name + constructor kwargs + transport/bucket) — enough for
    :func:`plan_from_spec` to rebuild an equivalent plan on load.  The
    persistence face of the plan API: the serve store and the delta
    checkpoints both stamp payloads with this spec."""
    comp = plan.codec
    kwargs = {f.name: getattr(comp, f.name)
              for f in dataclasses.fields(comp) if f.init}
    return {"codec": comp.name, "kwargs": kwargs,
            "transport": plan.transport, "bucket": plan.bucket,
            "narrow": plan.narrow}


def plan_from_spec(spec: dict) -> CompressionPlan:
    from repro.core.compressors import make_compressor
    comp = make_compressor(spec["codec"], **spec.get("kwargs", {}))
    return make_plan(comp, transport=spec["transport"],
                     bucket=spec.get("bucket"),
                     narrow=spec.get("narrow", False))


def as_plan(codec_or_plan, transport: Optional[str] = None,
            params=None) -> CompressionPlan:
    """Coerce a Compressor (or an existing plan, returned as-is) to a
    CompressionPlan — the adapter every plan-taking API uses so plain
    compressors keep working."""
    if isinstance(codec_or_plan, CompressionPlan):
        return codec_or_plan
    if hasattr(codec_or_plan, "cohorts"):    # FleetPlan (duck-typed: no
        # fl import from core at module scope — DESIGN.md §13)
        raise TypeError(
            "got a FleetPlan where a single CompressionPlan is expected; "
            "only uplink arguments accept fleets (repro.fl.fleet."
            "resolve_uplink) — the downlink C_M is one broadcast plan")
    return make_plan(codec_or_plan, params, transport=transport)
