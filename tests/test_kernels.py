"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode)
against its pure-jnp ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.natural.kernel import natural_compress_2d
from repro.kernels.natural.ops import natural_compress
from repro.kernels.natural.ref import natural_compress_ref
from repro.kernels.qsgd.kernel import qsgd_dequantized
from repro.kernels.qsgd.ops import qsgd_compress
from repro.kernels.qsgd.ref import qsgd_dequantized_ref
from repro.kernels.selective_scan.ops import selective_scan_op
from repro.kernels.selective_scan.ref import selective_scan_ref


@pytest.mark.parametrize("shape", [(1, 128), (8, 256), (33, 512), (128, 2048)])
@pytest.mark.parametrize("levels", [7, 127])
def test_qsgd_kernel_sweep(shape, levels):
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * 3.0
    u = jax.random.uniform(jax.random.PRNGKey(1), shape)
    got = qsgd_dequantized(x, u, levels=levels)
    want = qsgd_dequantized_ref(x, u, levels=levels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_qsgd_zero_bucket():
    x = jnp.zeros((4, 128))
    u = jax.random.uniform(jax.random.PRNGKey(0), x.shape)
    assert float(jnp.max(jnp.abs(qsgd_dequantized(x, u)))) == 0.0


@pytest.mark.parametrize("n", [7, 128, 1000, 4096])
def test_qsgd_ops_arbitrary_shape(n):
    x = jax.random.normal(jax.random.PRNGKey(2), (n,))
    y = qsgd_compress(jax.random.PRNGKey(3), x, bucket=256)
    assert y.shape == x.shape
    # quantization error bounded by norm/levels per bucket
    assert float(jnp.max(jnp.abs(y - x))) < float(jnp.linalg.norm(x)) / 64


@pytest.mark.parametrize("shape", [(1, 128), (16, 128), (64, 384)])
@pytest.mark.parametrize("scale", [1e-3, 1.0, 1e4])
def test_natural_kernel_sweep(shape, scale):
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * scale
    u = jax.random.uniform(jax.random.PRNGKey(1), shape)
    got = natural_compress_2d(x, u)
    want = natural_compress_ref(x, u)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_natural_special_values():
    # NB: denormals are excluded — the interpreted kernel and the jnp path
    # differ in flush-to-zero behaviour on CPU (TPU flushes denormals anyway).
    x = jnp.asarray([[0.0, -0.0, jnp.inf, -jnp.inf, jnp.nan, 1.5, -2.75, 1e-30]
                     + [1.0] * 120])
    u = jnp.full(x.shape, 0.3)
    got = np.asarray(natural_compress_2d(x, u))
    want = np.asarray(natural_compress_ref(x, u))
    np.testing.assert_array_equal(got, want)
    assert got[0, 0] == 0.0 and np.isinf(got[0, 2]) and np.isnan(got[0, 4])


def test_natural_matches_core_compressor_distribution():
    """kernel output magnitudes are powers of two and unbiased."""
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 128)) * 2.7
    keys = jax.random.split(jax.random.PRNGKey(6), 600)
    ys = jax.vmap(lambda k: natural_compress(k, x))(keys)
    err = jnp.abs(jnp.mean(ys, 0) - x)
    assert float(jnp.mean(err)) < 0.02      # unbiased on average
    assert float(jnp.max(err)) < 0.5        # 5-sigma-ish max over 8k elems


@pytest.mark.parametrize("B,L,E,N,chunk,eblk", [
    (1, 16, 8, 4, 8, 8), (2, 64, 32, 16, 16, 16), (1, 100, 48, 16, 32, 16),
    (3, 33, 16, 8, 16, 8),
])
def test_selective_scan_sweep(B, L, E, N, chunk, eblk):
    k = jax.random.PRNGKey(0)
    dt = jax.nn.softplus(jax.random.normal(k, (B, L, E))) * 0.2
    Bm = jax.random.normal(jax.random.PRNGKey(1), (B, L, N))
    Cm = jax.random.normal(jax.random.PRNGKey(2), (B, L, N))
    x = jax.random.normal(jax.random.PRNGKey(3), (B, L, E))
    A = -jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (E, N)))
    got = selective_scan_op(dt, Bm, Cm, x, A, chunk=chunk, e_blk=eblk)
    want = selective_scan_ref(dt, Bm, Cm, x, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_dtypes(dtype):
    k = jax.random.PRNGKey(0)
    B, L, E, N = 1, 32, 16, 8
    dt = (jax.nn.softplus(jax.random.normal(k, (B, L, E))) * 0.2).astype(dtype)
    Bm = jax.random.normal(jax.random.PRNGKey(1), (B, L, N)).astype(dtype)
    Cm = jax.random.normal(jax.random.PRNGKey(2), (B, L, N)).astype(dtype)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, L, E)).astype(dtype)
    A = -jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (E, N)))
    got = selective_scan_op(dt, Bm, Cm, x, A, chunk=16, e_blk=16)
    want = selective_scan_ref(dt, Bm, Cm, x, A)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("B,H,S,T,D,causal,window,bq,bk", [
    (1, 2, 128, 128, 64, True, None, 64, 64),
    (2, 1, 64, 64, 128, False, None, 32, 32),
    (1, 2, 256, 256, 64, True, 64, 64, 64),
    (1, 1, 128, 128, 256, True, 32, 32, 64),
])
def test_flash_attention_sweep(B, H, S, T, D, causal, window, bq, bk):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, T, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, T, D))
    got = flash_attention(q, k, v, causal=causal, window=window, bq=bq, bk=bk)
    want = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtype_and_gqa(dtype):
    B, S, H, Kv, D = 2, 128, 8, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Kv, D)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Kv, D)).astype(dtype)
    # interpret=True pins the Pallas kernel (the dispatcher would route
    # CPU to the dense oracle, see test_flash_attention_op_dispatch)
    got = flash_attention_op(q, k, v, bq=64, bk=64, interpret=True)
    # oracle via repeat + ref
    kr = jnp.repeat(k, H // Kv, axis=2).swapaxes(1, 2)
    vr = jnp.repeat(v, H // Kv, axis=2).swapaxes(1, 2)
    want = flash_attention_ref(q.swapaxes(1, 2), kr, vr).swapaxes(1, 2)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_attention_op_dispatch():
    """DESIGN.md §5 routing for attention: off-TPU the dispatched entry
    point returns the dense oracle's result BIT-EXACTLY (the interpret
    kernel is validation-only and 2.5x slower on CPU); pinning
    ``interpret=True`` still runs the Pallas kernel (allclose)."""
    B, S, H, Kv, D = 1, 128, 4, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Kv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Kv, D))
    kr = jnp.repeat(k, H // Kv, axis=2).swapaxes(1, 2)
    vr = jnp.repeat(v, H // Kv, axis=2).swapaxes(1, 2)
    want = flash_attention_ref(q.swapaxes(1, 2), kr, vr).swapaxes(1, 2)
    assert jax.default_backend() != "tpu"
    np.testing.assert_array_equal(
        np.asarray(flash_attention_op(q, k, v)), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(flash_attention_op(q, k, v, interpret=True)),
        np.asarray(want), rtol=2e-5, atol=2e-5)


def test_autotune_attn_blocks():
    """Blocks are MXU-aligned, clamped to the sequence lengths, and fit
    the VMEM budget."""
    from repro.kernels.dispatch import autotune_attn_blocks
    bq, bk = autotune_attn_blocks(512, 512, 64)
    assert bq % 128 == 0 and bk % 128 == 0
    assert 2 * 4 * bq * (4 * 64 + bk) <= 4 * 1024 * 1024
    assert autotune_attn_blocks(64, 64, 64) == (64, 64)   # clamped
    bq2, bk2 = autotune_attn_blocks(4096, 4096, 256)
    assert bq2 % 128 == 0
    assert 2 * 4 * bq2 * (4 * 256 + bk2) <= 4 * 1024 * 1024
    # blocks must DIVIDE the sequence lengths (kernel precondition): 384
    # and 640 admit 128 but not the VMEM-maximal power of two
    for S in (384, 640):
        bq3, bk3 = autotune_attn_blocks(S, S, 64)
        assert S % bq3 == 0 and S % bk3 == 0, (S, bq3, bk3)


def test_autotune_rows():
    """Rows fit the VMEM budget, align to the native tile of the
    narrowest blocked dtype (8 rows of f32, 32 of int8/uint8) and clamp
    to the grid; the natural reduce counts its own live bytes."""
    from repro.kernels.dispatch import autotune_rows
    from repro.kernels.natural.ops import _reduce_row_bytes, _reduce_rows
    budget = 4 * 1024 * 1024
    assert autotune_rows(10 ** 6, 2 * 2048 * 4) == 256
    rows = autotune_rows(10 ** 6, 3 * 2048 * 4)
    assert rows % 8 == 0 and rows * 3 * 2048 * 4 <= budget
    rows = autotune_rows(10 ** 6, 3 * 2048 * 4, min_itemsize=1)
    assert rows % 32 == 0 and rows * 3 * 2048 * 4 <= budget
    # its row bytes count both pipeline buffers: two stages' budget
    rows = _reduce_rows(10 ** 6, 128)
    assert rows % 32 == 0 and rows * _reduce_row_bytes(128) <= 2 * budget
    assert autotune_rows(5, 2 * 128 * 4) == 5            # clamped
    assert autotune_rows(10 ** 6, budget, min_itemsize=1) == 32


def test_flash_attention_op_autotuned_nonpow2_seq():
    """The autotuned dispatch path runs (not crashes) on sequence
    lengths the maximal block would not divide."""
    B, S, H, D = 1, 384, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    got = flash_attention_op(q, k, v, interpret=True)
    want = flash_attention_ref(q.swapaxes(1, 2), k.swapaxes(1, 2),
                               v.swapaxes(1, 2)).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_model_attention_core():
    """flash kernel == the model's dense attention_core on a causal case."""
    from repro.models.attention import attention_core, causal_mask
    B, S, H, D = 1, 128, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    dense = attention_core(q, k, v, causal_mask(S, S))
    flash = flash_attention_op(q, k, v, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
