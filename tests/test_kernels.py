"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.arena_scan.ops import arena_scan
from repro.kernels.arena_scan.ref import arena_scan_ref

pytestmark = [pytest.mark.kernels]


@pytest.mark.parametrize("B,N,D,k,blk_n", [
    (1, 512, 128, 4, 128),
    (4, 2048, 128, 5, 512),
    (8, 1000, 96, 10, 512),    # N not a block multiple -> padding path
    (3, 513, 64, 8, 256),      # odd everything
    (2, 4096, 256, 16, 1024),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_filtered_topk_sweep(B, N, D, k, blk_n, dtype, rng):
    q = jnp.asarray(rng.standard_normal((B, D), dtype=np.float32)).astype(dtype)
    emb = jnp.asarray(rng.standard_normal((N, D), dtype=np.float32)).astype(dtype)
    tenant = jnp.asarray(rng.integers(-1, 6, N, dtype=np.int32))
    ts = jnp.asarray(rng.integers(0, 1000, N, dtype=np.int32))
    cat = jnp.asarray(rng.integers(0, 6, N, dtype=np.int32))
    acl = jnp.asarray(rng.integers(1, 16, N, dtype=np.int64).astype(np.uint32))
    pred = jnp.array([2, 300, 0b10110, 0b0101], jnp.int32)
    gids = jnp.zeros((B,), jnp.int32)
    s_p, i_p = arena_scan(q, emb, tenant, ts, cat, acl, gids, pred[None, :], k,
                          use_kernel=True, blk_n=blk_n)
    meta = jnp.stack([tenant, ts, cat, acl.astype(jnp.int32)], 0)
    s_r, i_r = jax.jit(arena_scan_ref, static_argnames="k")(
        q, emb, meta, gids, pred[None, :], k)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r), rtol=tol, atol=tol)
    # predicate safety on the kernel path
    tn, tsn = np.asarray(tenant), np.asarray(ts)
    ip = np.asarray(i_p)
    ok = ip < 0
    ok |= (np.take(tn, np.maximum(ip, 0)) == 2) & (np.take(tsn, np.maximum(ip, 0)) >= 300)
    assert ok.all()


@pytest.mark.parametrize("B,S,KV,G,hd,blk", [
    (2, 1024, 4, 8, 128, 256),
    (1, 2048, 2, 1, 64, 512),
    (4, 512, 8, 4, 128, 128),
    (2, 512, 1, 16, 64, 512),   # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, S, KV, G, hd, blk, dtype, rng):
    H = KV * G
    q = jnp.asarray(rng.standard_normal((B, H, hd), dtype=np.float32)).astype(dtype)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd), dtype=np.float32)).astype(dtype)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd), dtype=np.float32)).astype(dtype)
    lengths = jnp.asarray(rng.integers(1, S + 1, B, dtype=np.int32))
    out = decode_attention(q, k, v, lengths, n_kv=KV, blk_s=blk)
    ref = decode_attention_ref(q.reshape(B, KV, G, hd), k, v, lengths).reshape(B, H, hd)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_decode_attention_length_zero_guard(rng):
    """length=1 minimum: a single cached token attends only to itself."""
    B, S, KV, G, hd = 2, 256, 2, 2, 64
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd), dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd), dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd), dtype=np.float32))
    lengths = jnp.asarray([1, 1], jnp.int32)
    out = decode_attention(q, k, v, lengths, n_kv=KV)
    # softmax over one position = that position's value
    want = v[:, 0]  # (B, KV, hd)
    got = np.asarray(out).reshape(B, KV, G, hd)
    for g in range(G):
        np.testing.assert_allclose(got[:, :, g], np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,S,KV,G,hd,blkq,blkk", [
    (2, 256, 2, 4, 64, 64, 64),
    (1, 512, 4, 2, 128, 128, 128),
    (2, 256, 1, 8, 64, 128, 64),   # MQA, rectangular blocks
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, KV, G, hd, blkq, blkk, causal, rng):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    H = KV * G
    q = jnp.asarray(rng.standard_normal((B, S, H, hd), dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd), dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd), dtype=np.float32))
    out = flash_attention(q, k, v, n_kv=KV, causal=causal, blk_q=blkq, blk_k=blkk)
    ref = flash_attention_ref(q.reshape(B, S, KV, G, hd), k, v, causal=causal)
    # bf16 PV matmul inside the kernel -> bf16-level tolerance
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref).reshape(B, S, H, hd),
                               rtol=1e-2, atol=8e-3)
