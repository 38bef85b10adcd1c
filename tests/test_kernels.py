"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret=True executes the Pallas kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing import given, settings, st  # hypothesis, with fallback

from repro.kernels.decode_attention import ops as da_ops, ref as da_ref
from repro.kernels.embedding_reduce import ops as er_ops, ref as er_ref
from repro.kernels.stream_copy import ops as sc_ops, ref as sc_ref
from repro.kernels.wkv6 import ops as wkv_ops, ref as wkv_ref


@pytest.mark.parametrize("V,D,B,K", [(32, 64, 2, 4), (128, 128, 8, 16),
                                     (256, 256, 4, 32), (64, 512, 1, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_reduce_sweep(V, D, B, K, dtype, key):
    table = jax.random.normal(key, (V, D), jnp.float32).astype(dtype)
    idx = jax.random.randint(key, (B, K), 0, V)
    w = jax.random.uniform(key, (B, K), jnp.float32)
    out = er_ops.embedding_reduce(table, idx, w)
    ref = er_ref.embedding_reduce(table, idx, w)
    tol = 1e-4 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=0.05)


@given(st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_embedding_reduce_property(seed):
    """Kernel == oracle for arbitrary index multisets incl. duplicates."""
    rng = np.random.default_rng(seed)
    V, D = 64, 128
    B, K = int(rng.integers(1, 6)), int(rng.integers(1, 12))
    table = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, V, size=(B, K)))
    w = jnp.asarray(rng.uniform(size=(B, K)), jnp.float32)
    np.testing.assert_allclose(
        er_ops.embedding_reduce(table, idx, w),
        er_ref.embedding_reduce(table, idx, w), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape,block", [((256, 64), 64), ((512, 128), 256),
                                         ((128, 32), 128)])
@pytest.mark.parametrize("dtype,out_dtype", [
    (jnp.float32, None), (jnp.float32, jnp.bfloat16), (jnp.bfloat16, None)])
def test_stream_copy_sweep(shape, block, dtype, out_dtype, key):
    x = jax.random.normal(key, shape, jnp.float32).astype(dtype)
    out = sc_ops.stream_copy(x, out_dtype=out_dtype, block_rows=block)
    ref = sc_ref.stream_copy(x, out_dtype)
    assert out.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("shape", [(1, 8), (7, 16), (100, 16), (300, 8),
                                   (513, 4), (5, 1), (64, 3), (1024, 128),
                                   (7, 128), (100, 64), (513, 384)])
@pytest.mark.parametrize("block", [4, 64, 256])
def test_stream_copy_ragged_sweep(shape, block, key):
    """Row counts need not divide ``block_rows``: the double-buffered
    migration kernel ships the ragged tail through its dedicated staging
    slot, overlapped with the full-chunk pipeline, and XLA writes the
    last rows of a partial sublane tile.  Rows narrower than 128 lanes
    are copied as a lane-dense view where one exists."""
    x = jax.random.normal(key, shape, jnp.float32)
    out = sc_ops.stream_copy(x, block_rows=block)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    # fused dtype casts on the same ragged shapes, both directions
    down = sc_ops.stream_copy(x, out_dtype=jnp.bfloat16, block_rows=block)
    np.testing.assert_array_equal(
        np.asarray(down), np.asarray(sc_ref.stream_copy(x, jnp.bfloat16)))
    xb = x.astype(jnp.bfloat16)
    up = sc_ops.stream_copy(xb, out_dtype=jnp.float32, block_rows=block)
    np.testing.assert_array_equal(
        np.asarray(up), np.asarray(sc_ref.stream_copy(xb, jnp.float32)))


@pytest.mark.parametrize("B,H,K,hd,T,block", [
    (2, 8, 2, 32, 128, 32), (1, 4, 4, 64, 256, 64),
    (3, 8, 1, 16, 64, 64), (2, 16, 16, 32, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, K, hd, T, block, dtype, key):
    q = jax.random.normal(key, (B, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, K, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, K, hd),
                          jnp.float32).astype(dtype)
    lengths = jnp.asarray(np.random.default_rng(0).integers(1, T + 1, size=B))
    out = da_ops.decode_attention(q, k, v, lengths, block_t=block)
    ref = da_ref.decode_attention(q, k, v, lengths)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol * 10)


def test_decode_attention_ragged_lengths(key):
    """Blocks past each row's length contribute nothing (skip correctness)."""
    B, H, K, hd, T = 4, 4, 2, 16, 256
    q = jax.random.normal(key, (B, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, K, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, K, hd))
    lengths = jnp.array([1, 17, 100, 256])
    out = da_ops.decode_attention(q, k, v, lengths, block_t=64)
    ref = da_ref.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("B,T,H,hd,block", [
    (2, 64, 2, 16, 16), (1, 128, 4, 32, 64), (2, 32, 1, 64, 32)])
def test_wkv6_sweep(B, T, H, hd, block, key):
    r = jax.random.normal(key, (B, T, H, hd)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, hd)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3),
                                         (B, T, H, hd))) * 0.5 + 0.5
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, hd)) * 0.1
    s0 = jnp.zeros((B, H, hd, hd))
    y1, s1 = wkv_ops.wkv6(r, k, v, w, u, s0, block_t=block)
    y2, s2 = wkv_ref.wkv6(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_wkv6_state_carry(key):
    """Chunked kernel with carried state == one long exact scan."""
    B, T, H, hd = 1, 64, 2, 16
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i), (B, T, H, hd))
    r, k, v = mk(0) * 0.5, mk(1) * 0.5, mk(2)
    w = jax.nn.sigmoid(mk(3)) * 0.4 + 0.6
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, hd)) * 0.1
    s0 = jnp.zeros((B, H, hd, hd))
    # two kernel calls of T/2 with carried state
    y_a, s_a = wkv_ops.wkv6(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u, s0,
                            block_t=16)
    y_b, s_b = wkv_ops.wkv6(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s_a,
                            block_t=16)
    y_full, s_full = wkv_ref.wkv6(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y_a, y_b], 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_b), np.asarray(s_full), atol=1e-4)
