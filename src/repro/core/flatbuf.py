"""Flat-buffer compression engine — one-launch whole-pytree compression.

The legacy path compressed pytrees leaf-by-leaf: per-leaf PRNG splits,
per-leaf pad/reshape, per-leaf kernel dispatch — O(n_leaves) launches of a
bandwidth-bound elementwise op.  This engine ravels the entire parameter
pytree into ONE contiguous float32 buffer with precomputed static offsets
(:class:`FlatLayout`), buckets it once, and compresses it in a single
fused pass with in-kernel RNG (see DESIGN.md §2, repro/kernels).

Public surface:

  layout_of / ravel / unravel   — pytree <-> flat buffer, static offsets
  bucketize / unbucketize       — THE pad/bucket/reshape logic (shared by
                                  kernels/qsgd/ops.py and compressors.QSGD)
  seeds_of                      — PRNG key -> (2,) uint32 kernel seeds
  flat_tree_apply               — fused whole-pytree C(x); the fast path
                                  behind CompressionPlan(transport="flat")
  pack_tree / unpack_tree       — whole-pytree wire payloads for every
                                  flat-engine codec (QSGDPayload,
                                  NaturalPayload — repro.core.codec);
                                  bit-exact decode vs the fused kernels
  pack_tree_qsgd / pack_tree_natural / unpack_tree_qsgd
                                — codec-specific entry points
  reduce_payload_mean           — fused decode->reduce: the masked MEAN
                                  of a stacked payload batch in ONE
                                  pass, O(d) accumulator state — the
                                  server side of every aggregation
                                  round (DESIGN.md §10)
  packed_wire_bits / payload_wire_bits
                                — exact packed-payload bit accounting;
                                  both read ``Payload.nbits``
                                  (DESIGN.md §3)

Sharding note: raveling concatenates leaves, so under SPMD a
model-axis-sharded weight is re-laid-out before compression.  For the
single-host simulator and the shard_map runtime (where leaves are local
shards) this is free; for the pjit runtime with sharded stacked params
the leafwise transport is pinned (``make_plan(..., transport=
"leafwise")`` in launch/steps.build_train_step).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import (NarrowQSGDPayload, NaturalPayload, QSGDPayload,
                              natural_merge, natural_split, pack_bits,
                              unpack_bits)
from repro.kernels.natural.kernel import natural_fused, natural_pack
from repro.kernels.natural.ops import natural_reduce
from repro.kernels.qsgd.kernel import qsgd_fused, qsgd_pack, qsgd_unpack
from repro.kernels.qsgd.ops import qsgd_reduce

__all__ = [
    "FlatLayout", "QSGDPayload", "NaturalPayload", "layout_of", "ravel",
    "unravel", "bucketize", "unbucketize", "seeds_of", "supports_flat",
    "supports_fused_reduce", "flat_tree_apply", "pack_tree", "unpack_tree",
    "pack_tree_qsgd", "pack_tree_natural", "unpack_tree_qsgd",
    "narrow_tree_qsgd", "widen_tree_qsgd", "encode_clients",
    "payload_finite_mask", "sanitize_payload", "reduce_payload_acc",
    "reduce_payload_mean", "payload_wire_bits", "packed_wire_bits",
]

_LANE = 128          # natural compression buckets = one VPU lane row


def supports_flat(comp) -> bool:
    """True for compressors with a fused flat-engine kernel."""
    return getattr(comp, "name", None) in ("qsgd", "natural")


# --------------------------------------------------------------------------
# layout: pytree <-> flat buffer
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static metadata of a raveled pytree: leaf shapes/dtypes and their
    offsets into the flat float32 buffer, plus the bucket geometry."""

    treedef: Any
    shapes: tuple          # per-leaf shapes
    dtypes: tuple          # per-leaf dtypes
    offsets: tuple         # per-leaf start offset into the flat buffer
    d: int                 # total element count
    bucket: int

    @property
    def n_buckets(self) -> int:
        return max(-(-self.d // self.bucket), 1)

    @property
    def padded(self) -> int:
        return self.n_buckets * self.bucket

    @property
    def pad(self) -> int:
        return self.padded - self.d


def layout_of(tree, bucket: int = 2048) -> FlatLayout:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(leaf.shape) for leaf in leaves)
    dtypes = tuple(leaf.dtype for leaf in leaves)
    sizes = [int(np.prod(s)) if len(s) else 1 for s in shapes]
    offsets = tuple(int(o) for o in np.cumsum([0] + sizes[:-1]))
    return FlatLayout(treedef=treedef, shapes=shapes, dtypes=dtypes,
                      offsets=offsets, d=int(sum(sizes)), bucket=int(bucket))


def ravel(layout: FlatLayout, tree) -> jax.Array:
    """Concatenate all leaves into one (d,) float32 buffer.  A
    single-leaf tree skips the concatenate — a pure reshape/cast, so the
    encode side of the aggregation engine adds no (n, d) copy for the
    common one-buffer layout (the §10 HLO memory test measures this)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), jnp.float32)
    if len(leaves) == 1:
        return leaves[0].reshape(-1).astype(jnp.float32)
    return jnp.concatenate(
        [leaf.reshape(-1).astype(jnp.float32) for leaf in leaves])


def unravel(layout: FlatLayout, flat: jax.Array):
    """Slice the flat buffer back into the original pytree (dtypes
    restored per leaf)."""
    leaves = []
    for shape, dtype, off in zip(layout.shapes, layout.dtypes,
                                 layout.offsets):
        n = int(np.prod(shape)) if len(shape) else 1
        leaves.append(flat[off:off + n].reshape(shape).astype(dtype))
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def bucketize(x: jax.Array, bucket: int) -> jax.Array:
    """Pad a flat buffer to a bucket multiple and view it (n_buckets,
    bucket).  This is the single pad/bucket/reshape implementation shared
    by the engine, kernels/qsgd/ops.py and compressors.QSGD."""
    flat = x.reshape(-1)
    d = flat.shape[0]
    pad = (-d) % bucket
    if d == 0:
        return jnp.zeros((1, bucket), flat.dtype)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, bucket)


def unbucketize(x2d: jax.Array, d: int) -> jax.Array:
    return x2d.reshape(-1)[:d]


def seeds_of(key: jax.Array) -> jax.Array:
    """Fold a JAX PRNG key (typed or raw uint32) into the (2,) uint32 seed
    pair consumed by the in-kernel counter RNG.  Pure bit movement — no
    threefry invocation, so no noise-sized intermediate ever exists."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = jnp.asarray(key)
    data = data.reshape(-1).astype(jnp.uint32)
    # XOR-fold ALL words down to two (threefry keys are exactly two; rbg
    # keys are four), alternating words between the lanes so any
    # differing word changes the stream; decorrelate the lanes when only
    # one word is distinct.
    words = [data[i] for i in range(data.shape[0])]
    s0 = words[0]
    for w in words[2::2]:
        s0 = s0 ^ w
    odds = words[1::2] or [words[0]]
    s1 = odds[0]
    for w in odds[1:]:
        s1 = s1 ^ w
    return jnp.stack([s0, s1 ^ jnp.uint32(0x9E3779B9)])


# --------------------------------------------------------------------------
# fused whole-pytree compression
# --------------------------------------------------------------------------

def _engine_bucket(comp) -> int:
    return int(getattr(comp, "bucket", None) or _LANE)


def _clamp_bucket(bucket: int, d: int) -> int:
    """A model smaller than one bucket is a single bucket at ANY bucket
    size (one norm over all d values; trailing zeros do not change it),
    so pad only to the next lane multiple instead of the full bucket —
    identical statistics, minimal wire padding (a 124-element model costs
    128 codes, not 2048)."""
    if d and d < bucket:
        return max(-(-d // _LANE) * _LANE, _LANE)
    return bucket


def flat_tree_apply(comp, key: jax.Array, tree, *, bucket: int = None):
    """Compress a whole pytree in ONE fused pass: ravel -> bucketize ->
    kernel with in-kernel RNG -> unravel.  Statistically equivalent to the
    leaf-wise path (every bucket remains unbiased; buckets may span leaf
    boundaries) with O(1) instead of O(n_leaves) dispatches and zero
    full-size noise arrays.  Bit-exact vs ``unpack_tree(pack_tree(...))``
    under the same key (kernel invariant, test-enforced)."""
    if not supports_flat(comp):
        raise ValueError(f"no flat engine for compressor {comp!r}")
    bucket = int(bucket or _engine_bucket(comp))
    layout = layout_of(tree, bucket)
    if layout.d == 0:
        return tree
    bucket = _clamp_bucket(bucket, layout.d)
    layout = layout_of(tree, bucket)
    x2d = bucketize(ravel(layout, tree), bucket)
    seeds = seeds_of(key)
    if comp.name == "qsgd":
        y2d = qsgd_fused(x2d, seeds, levels=comp.levels)
    else:
        y2d = natural_fused(x2d, seeds)
    return unravel(layout, unbucketize(y2d, layout.d))


# --------------------------------------------------------------------------
# whole-pytree wire payloads (QSGDPayload / NaturalPayload live in
# repro.core.codec; this is where they are produced and consumed)
# --------------------------------------------------------------------------

def pack_tree(comp, key: jax.Array, tree, *, bucket: int = None):
    """Quantize a whole pytree to its wire Payload with the flat-buffer
    engine — the encode path of ``CompressionPlan(transport="flat"|
    "packed")``.  The returned payload carries its :class:`FlatLayout`
    (static), so :func:`unpack_tree` needs nothing else."""
    if not supports_flat(comp):
        raise ValueError(f"no flat engine for compressor {comp!r}")
    bucket = int(bucket or _engine_bucket(comp))
    if comp.name == "qsgd":
        return pack_tree_qsgd(key, tree, levels=comp.levels,
                              bucket=bucket)[0]
    return pack_tree_natural(key, tree, bucket=bucket)[0]


def unpack_tree(payload):
    """Dequantize a flat-engine Payload back to its pytree — bit-exact
    vs :func:`flat_tree_apply` under the same key."""
    if isinstance(payload, NarrowQSGDPayload):
        payload = widen_tree_qsgd(payload)
    layout = payload.layout
    if layout is None:
        raise ValueError("payload carries no FlatLayout; it was not "
                         "produced by the flat engine (pack_tree)")
    if layout.d == 0:
        return unravel(layout, jnp.zeros((0,), jnp.float32))
    if isinstance(payload, QSGDPayload):
        y2d = qsgd_unpack(payload.codes, payload.norms,
                          levels=payload.levels)
    else:
        signs = unpack_bits(payload.signs, 1)
        y2d = natural_merge(payload.exps, signs)
    return unravel(layout, unbucketize(y2d, layout.d))


def pack_tree_qsgd(key: jax.Array, tree, *, levels: int = 127,
                   bucket: int = 2048):
    """Quantize a whole pytree to its QSGD wire payload (int8 codes +
    per-bucket norms).  Returns (payload, layout); the payload also
    carries the layout, so :func:`unpack_tree` alone suffices."""
    if levels > 127:
        # the engine's wire format is int8; the leafwise transport widens
        # to int16 instead (compressors.QSGD._code_dtype)
        raise ValueError(f"levels={levels} does not fit the int8 flat "
                         "payload; use transport='leafwise' (int16 codes) "
                         "or levels <= 127")
    layout = layout_of(tree, bucket)
    if layout.d == 0:
        payload = QSGDPayload(jnp.zeros((0, bucket), jnp.int8),
                              jnp.zeros((0, 1), jnp.float32),
                              levels=levels, layout=layout)
        return payload, layout
    bucket = _clamp_bucket(bucket, layout.d)
    layout = layout_of(tree, bucket)
    x2d = bucketize(ravel(layout, tree), bucket)
    codes, norms = qsgd_pack(x2d, seeds_of(key), levels=levels)
    return QSGDPayload(codes, norms, levels=levels, layout=layout), layout


def pack_tree_natural(key: jax.Array, tree, *, bucket: int = _LANE):
    """Quantize a whole pytree to its natural-compression wire payload
    (uint8 exponent codes + packed sign bitmap, 9 bits/element): run the
    fused kernel, then bit-split its output — decode is bit-exact against
    :func:`flat_tree_apply` by construction (finite inputs)."""
    layout = layout_of(tree, bucket)
    if layout.d == 0:
        payload = NaturalPayload(jnp.zeros((0, bucket), jnp.uint8),
                                 jnp.zeros((0, bucket // 8), jnp.uint8),
                                 layout=layout)
        return payload, layout
    bucket = _clamp_bucket(bucket, layout.d)
    layout = layout_of(tree, bucket)
    x2d = bucketize(ravel(layout, tree), bucket)
    exps, packed = natural_pack(x2d, seeds_of(key))
    return NaturalPayload(exps, packed, layout=layout), layout


def unpack_tree_qsgd(payload: QSGDPayload, layout: FlatLayout = None, *,
                     levels: int = 127):
    """Dequantize a QSGD payload back to the pytree — bit-exact vs the
    dequantized output of :func:`flat_tree_apply` under the same key.
    ``layout``/``levels`` are only read for hand-built payloads; engine
    payloads carry their own."""
    if getattr(payload, "layout", None) is not None:
        return unpack_tree(payload)
    y2d = qsgd_unpack(payload.codes, payload.norms, levels=levels)
    return unravel(layout, unbucketize(y2d, layout.d))


def _narrow_width(levels: int) -> int:
    """Smallest pack_bits-compatible field width holding sign +
    magnitude <= levels: 2 bits for ternary codes (levels 1), 4 bits for
    levels <= 7.  Wider levels keep the int8 wire format — there is no
    byte-aligned win below 8 bits for them."""
    if levels <= 1:
        return 2
    if levels <= 7:
        return 4
    raise ValueError(
        f"levels={levels} has no sub-byte storage pack (magnitude needs "
        f"{max(int(np.ceil(np.log2(levels + 1))), 1)} bits + sign); use "
        "levels <= 7 or store the int8 QSGDPayload as-is")


def narrow_tree_qsgd(payload: QSGDPayload) -> NarrowQSGDPayload:
    """Repack a flat-engine :class:`QSGDPayload` with ``levels <= 7``
    into its sub-byte residency format (:class:`NarrowQSGDPayload`):
    sign-magnitude fields of ``width`` bits, 8/width codes per byte —
    4.02 bits/param at levels 7 / bucket 2048 instead of the wire's
    8.02.  Lossless: :func:`widen_tree_qsgd` restores the int8 codes
    bit-exactly (the serving delta store's storage win, DESIGN.md §12)."""
    width = _narrow_width(payload.levels)
    codes = payload.codes
    mag = jnp.abs(codes.astype(jnp.int32)).astype(jnp.uint8)
    sign = (codes < 0).astype(jnp.uint8)
    fields = (sign << jnp.uint8(width - 1)) | mag
    return NarrowQSGDPayload(pack_bits(fields, width), payload.norms,
                             levels=payload.levels, width=width,
                             layout=payload.layout, shape=payload.shape,
                             dtype=payload.dtype)


def widen_tree_qsgd(payload: NarrowQSGDPayload) -> QSGDPayload:
    """Inverse of :func:`narrow_tree_qsgd` — bit-exact int8 code
    reconstruction, so every downstream consumer (``unpack_tree``, the
    fused §10 reduce) sees the exact wire payload."""
    width = payload.width
    fields = unpack_bits(payload.codes, width)
    mag = (fields & jnp.uint32((1 << (width - 1)) - 1)).astype(jnp.int8)
    sign = (fields >> jnp.uint32(width - 1)).astype(jnp.int8)
    codes = jnp.where(sign > 0, -mag, mag)
    return QSGDPayload(codes, payload.norms, levels=payload.levels,
                       layout=payload.layout, shape=payload.shape,
                       dtype=payload.dtype)


def encode_clients(plan, keys, params_stacked):
    """Stacked wire payloads of every client: ``plan.encode(keys[i],
    params_i)`` over the leading client axis, one client per step of a
    ``lax.map``, so each encode kernel sees one client's flat buffer.
    The vmapped form batches the bucket reshape of the whole (n, d)
    buffer instead, and the TPU compiler spent over four minutes on that
    at stablelm-1.6b widths with two clients (2.5 GB), against two
    seconds mapped.

    Leafwise plans have no flat buffer and stay vmapped: inside the
    client-sharded shard_map the mapped loop tripled the compile's temp
    bytes at the same widths."""
    if plan.transport == "leafwise":
        return jax.vmap(plan.encode)(keys, params_stacked)
    return jax.lax.map(lambda kp: plan.encode(kp[0], kp[1]),
                       (keys, params_stacked))


def supports_fused_reduce(payload) -> bool:
    """True for stacked flat-engine payloads the one-pass server reduce
    (:func:`reduce_payload_mean`) can consume directly.  Narrow QSGD
    payloads qualify: the reduce widens them to the exact int8 codes
    first (lossless), then folds on the same O(d) accumulator."""
    return isinstance(payload,
                      (QSGDPayload, NaturalPayload, NarrowQSGDPayload)) \
        and getattr(payload, "layout", None) is not None


def payload_finite_mask(payload) -> jax.Array:
    """(n,) 0/1 float32 over a STACKED flat-engine payload batch: 1 where
    client i's message decodes entirely finite.  A poisoned client shows
    up on the wire as non-finite bucket norms (QSGD: the norm is a max /
    sum over the client's buffer) or as biased-exponent code 255 (natural:
    ``(exp << 23)`` bitcasts to ±Inf) — both are O(n * wire) scans of the
    SMALL wire arrays, not of decoded f32 buffers."""
    if isinstance(payload, (QSGDPayload, NarrowQSGDPayload)):
        ok = jnp.all(jnp.isfinite(payload.norms),
                     axis=tuple(range(1, payload.norms.ndim)))
    else:
        ok = jnp.all(payload.exps != jnp.uint8(255),
                     axis=tuple(range(1, payload.exps.ndim)))
    return ok.astype(jnp.float32)


def sanitize_payload(payload, finite_mask: jax.Array):
    """Zero the scale-carrying wire arrays of non-finite clients (QSGD
    norms -> 0.0, natural exponent codes -> 0, which decodes to ±0.0).

    Required in ADDITION to zeroing the client's reduce weight: the
    kernels accumulate ``decode_i * w_i``, and NaN * 0 is still NaN — a
    weight alone cannot keep a poisoned payload out of the accumulator.
    For all-finite payloads the ``where`` selects every original element,
    so the sanitized payload is bit-identical to the input."""
    if isinstance(payload, (QSGDPayload, NarrowQSGDPayload)):
        m = finite_mask.reshape((-1,) + (1,) * (payload.norms.ndim - 1))
        return dataclasses.replace(
            payload, norms=jnp.where(m > 0, payload.norms, 0.0))
    m = finite_mask.reshape((-1,) + (1,) * (payload.exps.ndim - 1))
    return dataclasses.replace(
        payload, exps=jnp.where(m > 0, payload.exps, jnp.uint8(0)))


def reduce_payload_acc(payload, weights) -> jax.Array:
    """The RAW (n_buckets, bucket) float32 accumulator ``sum_i w_i *
    decode(payload_i)`` of a stacked flat-engine payload batch — the
    incremental-fold half of :func:`reduce_payload_mean`, exposed so the
    arrival-ordered async server (repro.core.async_engine, DESIGN.md §11)
    can fold arrival cohorts into ring-buffer slots and divide by the
    total weight only when a round completes.  ``weights`` is an (n,)
    float32 vector (staleness weights are arbitrary non-negative floats,
    not just 0/1 masks); pass ``None`` for the unweighted sum.

    Narrow (sub-byte wire) QSGD payloads widen to the bit-exact int8
    codes first — ``unpack_bits``/``jnp.where`` are shape-generic, so the
    widening maps over the stacked client axis unchanged — and then fold
    on the identical kernel, so narrow and int8 wires reduce to the same
    accumulator bits."""
    if isinstance(payload, NarrowQSGDPayload):
        payload = widen_tree_qsgd(payload)
    if isinstance(payload, QSGDPayload):
        return qsgd_reduce(payload.codes, payload.norms, weights,
                           levels=payload.levels)
    return natural_reduce(payload.exps, payload.signs, weights)


def reduce_payload_mean(payload, mask=None):
    """Fused decode->reduce: the (optionally mask-weighted) MEAN pytree of
    a STACKED flat-engine payload batch, in ONE pass (DESIGN.md §10).

    ``payload`` is a :class:`QSGDPayload` / :class:`NaturalPayload` whose
    wire arrays carry a leading client axis of size n (built by
    :func:`encode_clients` or by all_gathering per-client payloads); the
    static ``layout`` is the shared one-model :class:`FlatLayout`.
    ``mask`` (optional (n,) 0/1 array) restricts the mean to a sampled
    participant subset: ``sum_i m_i x_i / sum_i m_i``.

    Fail-fast payload validation (mask-and-count, not checkify — the
    guard must run inside jitted scans): clients whose message decodes
    non-finite (:func:`payload_finite_mask`) are excluded from BOTH the
    numerator (their wire arrays are sanitized — NaN * 0 weight is still
    NaN) and the denominator, so one corrupt client shrinks the mean's
    support instead of NaN-ing the fleet.  If every contributor is
    excluded the denominator clamps to 1 and the mean degrades to the
    zeros tree (the caller's cached-target fallback handles the rest).
    For all-finite payloads the guard is bit-free: sanitize selects the
    original elements, the weights multiply by exactly 1.0, and the
    summed denominator equals the historic count/mask sum bit-for-bit.

    The kernel accumulates ``code_ij * scale_j`` client-by-client into a
    single (n_buckets, bucket) float32 accumulator — no per-client
    dequantized buffer ever exists, so server memory is O(d) instead of
    the O(n*d) of decode-then-mean (HLO-test-enforced).  Accumulation in
    f32 in client index order 0..n-1 on every backend; results agree
    with ``masked_client_mean(vmap(decode)(payload), mask)`` to
    reduction-order ulps (XLA's axis-0 reduce may associate differently)
    and are used consistently by BOTH the stacked and client-sharded
    engines, which therefore stay bit-exact with each other."""
    if not supports_fused_reduce(payload):
        raise ValueError(
            f"no fused reduce for payload {type(payload).__name__}; "
            "expected a stacked flat-engine QSGDPayload/NaturalPayload "
            "carrying its FlatLayout")
    layout = payload.layout
    if layout.d == 0:
        return unravel(layout, jnp.zeros((0,), jnp.float32))
    fin = payload_finite_mask(payload)
    if mask is None:
        weights = fin
    else:
        weights = mask.reshape(-1).astype(jnp.float32) * fin
    payload = sanitize_payload(payload, fin)
    denom = jnp.sum(weights)
    acc = reduce_payload_acc(payload, weights)
    return unravel(layout,
                   unbucketize(acc / jnp.where(denom > 0, denom, 1.0),
                               layout.d))


def payload_wire_bits(payload) -> int:
    """Exact bits moved by a payload — reads ``Payload.nbits``."""
    return int(payload.nbits)


def packed_wire_bits(tree, *, bucket: int = 2048) -> int:
    """Exact packed QSGD payload size for a pytree, without materializing
    it: 8/code (padding included; sub-bucket models clamp to the next
    lane multiple) plus a 32-bit norm per bucket.  An empty pytree costs
    0 (consistent with the leafwise sum)."""
    layout = layout_of(tree, bucket)
    if layout.d == 0:
        return 0
    layout = layout_of(tree, _clamp_bucket(bucket, layout.d))
    return layout.padded * 8 + layout.n_buckets * 32
