"""jit'd wrappers for the flash-decode kernel.

  decode_attention          single-device: normalize acc/l, (B,H,hd) layout
  decode_attention_sharded  sequence-parallel KV cache: per-shard partial
                            (acc, m, l) merged with the logsumexp combine —
                            flash-decode split-K across a mesh axis.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _split_heads(q: jax.Array, n_kv: int) -> jax.Array:
    B, H, hd = q.shape
    return q.reshape(B, n_kv, H // n_kv, hd)


@partial(jax.jit, static_argnames=("n_kv", "blk_s", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, n_kv: int,
                     blk_s: int = 512, interpret: bool | None = None):
    """q: (B, H, hd); caches (B, S, KV, hd); lengths (B,). -> (B, H, hd)."""
    from repro.kernels.decode_attention.decode_attention import decode_attention_pallas
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, hd = q.shape
    qg = _split_heads(q, n_kv)
    acc, m, l = decode_attention_pallas(qg, k_cache, v_cache, lengths,
                                        blk_s=min(blk_s, k_cache.shape[1]),
                                        interpret=interpret)
    out = acc / l[..., :1]
    return out.reshape(B, H, hd).astype(q.dtype)


def decode_attention_sharded(mesh: Mesh, seq_axis: str | tuple[str, ...],
                             q, k_cache, v_cache, lengths, n_kv: int,
                             blk_s: int = 512, interpret: bool | None = None):
    """KV cache sharded along S over `seq_axis`; q/lengths replicated.

    Each shard runs the kernel over its local S slice (masked by its own
    local live prefix), then partials merge: m* = max m_i; l* = Σ l_i e^{m_i-m*};
    acc* = Σ acc_i e^{m_i-m*}; out = acc*/l*. The collective payload is
    O(B·H·hd) per shard — independent of S.
    """
    from repro.kernels.decode_attention.decode_attention import decode_attention_pallas
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    axes = (seq_axis,) if isinstance(seq_axis, str) else tuple(seq_axis)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    B, H, hd = q.shape
    S = k_cache.shape[1]
    s_local = S // n_shards

    def local_fn(q_l, k_l, v_l, len_l):
        shard = jax.lax.axis_index(axes)
        # global position of this shard's slice: clamp the live prefix into it
        local_len = jnp.clip(len_l - shard * s_local, 0, s_local)
        qg = _split_heads(q_l, n_kv)
        acc, m, l = decode_attention_pallas(qg, k_l, v_l, local_len,
                                            blk_s=min(blk_s, s_local),
                                            interpret=interpret)
        m1, l1 = m[..., :1], l[..., :1]                     # (B,KV,G,1)
        m_glob = jax.lax.pmax(m1, axes)
        w = jnp.exp(m1 - m_glob)
        # guard shards with zero live rows (m = -inf -> w = 0)
        w = jnp.where(l1 > 0, w, 0.0)
        acc_glob = jax.lax.psum(acc * w, axes)
        l_glob = jax.lax.psum(l1 * w, axes)
        out = acc_glob / jnp.maximum(l_glob, 1e-30)
        return out.reshape(B, H, hd).astype(q_l.dtype)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), P(None, axes), P(None, axes), P()),
                       out_specs=P(),
                       check_vma=False)  # pallas outs carry no vma info
    return fn(q, k_cache, v_cache, lengths)
