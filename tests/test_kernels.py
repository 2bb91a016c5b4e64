"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes (deliverable (c))."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas

jax.config.update("jax_enable_x64", False)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ------------------------------ flash attention ------------------------------
FLASH_CASES = [
    # (B, Sq, Sk, H, K, D, causal, window, dtype)
    (1, 128, 128, 4, 4, 64, True, None, jnp.float32),
    (2, 256, 256, 4, 2, 64, True, None, jnp.float32),
    (1, 128, 128, 8, 1, 128, True, None, jnp.bfloat16),
    (2, 128, 128, 4, 4, 32, False, None, jnp.float32),
    (1, 256, 256, 2, 2, 64, True, 64, jnp.float32),  # sliding window
    (1, 512, 512, 2, 1, 64, True, 128, jnp.bfloat16),
    (2, 128, 256, 4, 4, 64, False, None, jnp.float32),  # cross (Sq != Sk)
    (1, 200, 200, 4, 2, 32, True, 64, jnp.float32),  # padded to a block multiple
    (1, 100, 330, 4, 2, 32, False, None, jnp.float32),  # padded keys masked
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_pallas_matches_ref(case):
    B, Sq, Sk, H, K, D, causal, window, dtype = case
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(42), 3)
    q = rand(kq, (B, Sq, H, D), dtype)
    k = rand(kk, (B, Sk, K, D), dtype)
    v = rand(kv, (B, Sk, K, D), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_pallas_matches_xla_path():
    B, S, H, K, D = 2, 256, 4, 2, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = rand(kq, (B, S, H, D), jnp.float32), rand(kk, (B, S, K, D), jnp.float32), rand(kv, (B, S, K, D), jnp.float32)
    a = ops.flash_attention(q, k, v, causal=True, q_chunk=64, impl="xla")
    b = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    bq=st.sampled_from([32, 64, 128]),
    bk=st.sampled_from([32, 64, 128]),
    s=st.sampled_from([128, 256]),
    window=st.sampled_from([None, 32, 100]),
)
def test_flash_pallas_block_size_sweep(bq, bk, s, window):
    """Property: output is block-size invariant."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = rand(kq, (1, s, 2, 64), jnp.float32)
    k = rand(kk, (1, s, 2, 64), jnp.float32)
    v = rand(kv, (1, s, 2, 64), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=bq, block_k=bk, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


# ------------------------------ paged attention ------------------------------
def make_paged(key, B, S, T, K, D, dtype, window=None, extra_pages=1):
    """Build a filled paged cache (via prefill layout) + a fresh query."""
    kk, kv, kq = jax.random.split(key, 3)
    k = rand(kk, (B, S, K, D), dtype)
    v = rand(kv, (B, S, K, D), dtype)
    pool_k, pool_v, tables, page_pos = ops.prefill_into_pages(k, v, T, extra_pages=extra_pages)
    q = rand(kq, (B, K * (D // D) * 4, D), dtype)  # placeholder, replaced by caller
    return k, v, pool_k, pool_v, tables, page_pos


PAGED_CASES = [
    # (B, S, T, H, K, D, window, dtype)
    (2, 64, 8, 4, 4, 64, None, jnp.float32),
    (3, 128, 16, 8, 2, 64, None, jnp.float32),
    (2, 64, 8, 4, 1, 128, None, jnp.bfloat16),
    (2, 128, 16, 4, 4, 32, 48, jnp.float32),  # sliding window
    (1, 256, 32, 2, 2, 64, 100, jnp.bfloat16),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_pallas_matches_ref(case):
    B, S, T, H, K, D, window, dtype = case
    key = jax.random.PRNGKey(3)
    kk, kv, kq = jax.random.split(key, 3)
    k = rand(kk, (B, S, K, D), dtype)
    v = rand(kv, (B, S, K, D), dtype)
    pool_k, pool_v, tables, page_pos = ops.prefill_into_pages(k, v, T)
    q = rand(kq, (B, H, D), dtype)
    lengths = jnp.full((B,), S, jnp.int32)

    o, m, l = paged_attention_pallas(
        q, pool_k, pool_v, tables, page_pos, lengths, window=window, interpret=True
    )
    got = o / np.maximum(np.asarray(l)[..., None], 1e-30)
    want = ref.paged_attention_ref(q, pool_k, pool_v, tables, page_pos, lengths, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_paged_pallas_engine_tables():
    """The serving engine's batch: scattered pool pages, rows of different
    lengths, table tails holding the out-of-range sentinel, and an idle row
    (length 0, all sentinel) that must come out as exact zeros."""
    B, T, H, K, D, R, P = 4, 8, 8, 2, 32, 6, 24
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(17), 3)
    pool_k = rand(kk, (P, T, K, D), jnp.bfloat16)
    pool_v = rand(kv, (P, T, K, D), jnp.bfloat16)
    q = rand(kq, (B, H, D), jnp.bfloat16)
    perm = np.random.default_rng(0).permutation(P)
    lengths = np.array([45, 8, 0, 17], np.int32)
    tables = np.full((B, R), P, np.int32)  # P: the engine's sentinel
    for b, n in enumerate(lengths):
        used = -(-n // T)
        tables[b, :used] = perm[b * R : b * R + used]
    page_pos = np.tile(np.arange(R, dtype=np.int32) * T, (B, 1))

    o, m, l = paged_attention_pallas(
        q, pool_k, pool_v, tables, page_pos, lengths, interpret=True
    )
    got = np.asarray(o) / np.maximum(np.asarray(l)[..., None], 1e-30)
    want = ref.paged_attention_ref(q, pool_k, pool_v, jnp.asarray(tables),
                                   jnp.asarray(page_pos), jnp.asarray(lengths))
    live = lengths > 0
    np.testing.assert_allclose(got[live], np.asarray(want, np.float32)[live],
                               rtol=3e-2, atol=3e-2)
    np.testing.assert_array_equal(np.asarray(l)[~live], 0.0)
    np.testing.assert_array_equal(np.asarray(o)[~live], 0.0)


@pytest.mark.parametrize("case", PAGED_CASES[:3])
def test_paged_xla_matches_ref(case):
    B, S, T, H, K, D, window, dtype = case
    key = jax.random.PRNGKey(5)
    kk, kv, kq = jax.random.split(key, 3)
    k = rand(kk, (B, S, K, D), dtype)
    v = rand(kv, (B, S, K, D), dtype)
    pool_k, pool_v, tables, page_pos = ops.prefill_into_pages(k, v, T)
    q = rand(kq, (B, H, D), dtype)
    lengths = jnp.full((B,), S, jnp.int32)
    got = ops.paged_attention(q, pool_k, pool_v, tables, page_pos, lengths,
                              window=window, impl="xla")
    want = ref.paged_attention_ref(q, pool_k, pool_v, tables, page_pos, lengths, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_paged_split_k_combine_across_shards():
    """Simulate the pool sharded in two halves: combined partials must equal
    the unsharded result (the shard_map split-K correctness)."""
    B, S, T, H, K, D = 2, 128, 8, 4, 2, 64
    key = jax.random.PRNGKey(9)
    kk, kv, kq = jax.random.split(key, 3)
    k = rand(kk, (B, S, K, D), jnp.float32)
    v = rand(kv, (B, S, K, D), jnp.float32)
    pool_k, pool_v, tables, page_pos = ops.prefill_into_pages(k, v, T)
    q = rand(kq, (B, H, D), jnp.float32)
    lengths = jnp.full((B,), S, jnp.int32)
    P = pool_k.shape[0]
    half = P // 2

    parts = []
    for off in (0, half):
        o, m, l = ops._paged_local_xla(
            q, pool_k[off : off + half], pool_v[off : off + half],
            tables, page_pos, lengths, window=None, page_offset=off,
            n_pages_total=P,
        )
        parts.append((o, m, l))
    o = ref.online_softmax_combine(
        jnp.stack([p[0] for p in parts]),
        jnp.stack([p[1] for p in parts]),
        jnp.stack([p[2] for p in parts]),
    )
    want = ref.paged_attention_ref(q, pool_k, pool_v, tables, page_pos, lengths)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_paged_update_then_attend_ring_rollover():
    """SWA ring: after the ring wraps, attention must see exactly the last
    `window` tokens."""
    B, T, K, D, H = 1, 4, 2, 32, 4
    window = 8
    R = window // T + 1  # 3 ring pages
    pool_k = jnp.zeros((B * R, T, K, D), jnp.float32)
    pool_v = jnp.zeros((B * R, T, K, D), jnp.float32)
    tables = jnp.arange(B * R, dtype=jnp.int32).reshape(B, R)
    page_pos = (jnp.arange(R, dtype=jnp.int32) * T)[None]
    ks, vs = [], []
    key = jax.random.PRNGKey(11)
    for t in range(14):  # wraps the 3-page ring
        key, k1, k2 = jax.random.split(key, 3)
        nk = rand(k1, (B, K, D), jnp.float32)
        nv = rand(k2, (B, K, D), jnp.float32)
        ks.append(nk)
        vs.append(nv)
        pool_k, pool_v, page_pos = ops.paged_update(
            pool_k, pool_v, tables, page_pos, jnp.full((B,), t, jnp.int32), nk, nv
        )
    q = rand(jax.random.PRNGKey(12), (B, H, D), jnp.float32)
    lengths = jnp.full((B,), 14, jnp.int32)
    got = ops.paged_attention(q, pool_k, pool_v, tables, page_pos, lengths,
                              window=window, impl="xla")
    # oracle: plain attention over the last `window` tokens
    k_all = jnp.stack(ks, axis=1)  # (B, 14, K, D)
    v_all = jnp.stack(vs, axis=1)
    out = ref.attention_ref(q[:, None].reshape(B, 1, H, D), k_all[:, -window:],
                            v_all[:, -window:], causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(out[:, 0]), rtol=3e-5, atol=3e-5)

def test_paged_attention_int8_close_to_fp():
    """Int8 per-token-scale KV quantization: decode attention within ~2% of
    the fp reference (the §Perf hillclimb-3 numerics check)."""
    B, S, T, H, K, D = 2, 128, 16, 8, 2, 64
    key = jax.random.PRNGKey(21)
    kk, kv, kq = jax.random.split(key, 3)
    k = rand(kk, (B, S, K, D), jnp.float32)
    v = rand(kv, (B, S, K, D), jnp.float32)
    q = rand(kq, (B, H, D), jnp.float32)
    pool_k, pool_v, tables, page_pos = ops.prefill_into_pages(k, v, T)
    lengths = jnp.full((B,), S, jnp.int32)
    want = ref.paged_attention_ref(q, pool_k, pool_v, tables, page_pos, lengths)

    qk, sk = ops.quantize_token(pool_k)
    qv, sv = ops.quantize_token(pool_v)
    got = ops.paged_attention(q, qk, qv, tables, page_pos, lengths,
                              scale_k=sk, scale_v=sv, impl="xla")
    err = np.abs(np.asarray(got) - np.asarray(want)).max()
    scale = np.abs(np.asarray(want)).max()
    assert err / scale < 0.02, (err, scale)


def test_int8_decode_update_roundtrip():
    """paged_update into an int8 pool: written token is recoverable within
    quantization error."""
    B, T, K, D, R = 2, 8, 2, 32, 4
    pool_k = jnp.zeros((B * R, T, K, D), jnp.int8)
    pool_v = jnp.zeros((B * R, T, K, D), jnp.int8)
    sk = jnp.zeros((B * R, T, K), jnp.float32)
    sv = jnp.zeros((B * R, T, K), jnp.float32)
    tables = jnp.arange(B * R, dtype=jnp.int32).reshape(B, R)
    page_pos = (jnp.arange(R, dtype=jnp.int32) * T)[None].repeat(B, 0)
    nk = rand(jax.random.PRNGKey(1), (B, K, D), jnp.float32)
    nv = rand(jax.random.PRNGKey(2), (B, K, D), jnp.float32)
    lengths = jnp.zeros((B,), jnp.int32)
    pool_k, pool_v, page_pos, sk, sv = ops.paged_update(
        pool_k, pool_v, tables, page_pos, lengths, nk, nv, scale_k=sk, scale_v=sv
    )
    deq = ops.dequantize_pool(pool_k, sk)
    got = np.asarray(deq[tables[:, 0], 0], np.float32)  # (B, K, D) slot 0
    np.testing.assert_allclose(got, np.asarray(nk), atol=np.abs(np.asarray(nk)).max() / 100)
