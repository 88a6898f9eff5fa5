"""Decoder-only transformer (dense + MoE), scan-over-layers with remat.

One config covers the whole assigned LM family:
  yi-6b           dense GQA(kv=4)
  qwen3-4b        dense GQA(kv=8) + qk-norm + decoupled head_dim
  qwen1.5-0.5b    dense GQA(kv=16) + QKV bias
  granite-moe     MoE 32e top-8
  grok-1-314b     MoE 8e top-2

Entry points (all pure functions over plain pytrees):
  init(key, cfg)                       -> params
  forward(params, cfg, tokens)         -> (logits, aux_loss)          # train
  loss_fn(params, cfg, batch)          -> scalar fp32                 # train
  prefill(params, cfg, tokens, cache_len) -> (logits_last, cache)     # serve
  decode_step(params, cfg, token, cache, cur_index) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.moe import MoESpec, moe_apply, moe_init

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None          # None -> d_model // n_heads
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # MoE (n_experts == 0 -> dense)
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # numerics / compilation
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    remat: bool = True
    attn_impl: str = "auto"      # "naive" | "chunked" | "auto" (see layers)
    moe_group: int = 1024        # tokens per MoE dispatch group
    unroll_layers: bool = False  # python-loop layers instead of lax.scan
    # (roofline costing: XLA cost_analysis reports 0 for while-loop bodies,
    # so per-layer costs are measured on small unrolled variants)
    moe_impl: str = "einsum"     # "einsum" | "scatter" (§Perf iteration 2)

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def attn_spec(self) -> L.AttentionSpec:
        return L.AttentionSpec(
            d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.hd, qk_norm=self.qk_norm, qkv_bias=self.qkv_bias,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps)

    def moe_spec(self) -> MoESpec:
        return MoESpec(d_model=self.d_model, d_ff=self.d_ff, n_experts=self.n_experts,
                       top_k=self.top_k, capacity_factor=self.capacity_factor,
                       impl=self.moe_impl)

    def param_count(self) -> int:
        """Exact parameter count (for 6·N·D roofline accounting)."""
        D, hd, H, KV, F, V = self.d_model, self.hd, self.n_heads, self.n_kv_heads, self.d_ff, self.vocab_size
        attn = D * H * hd + 2 * D * KV * hd + H * hd * D
        if self.qkv_bias:
            attn += H * hd + 2 * KV * hd
        if self.qk_norm:
            attn += 2 * hd
        if self.is_moe:
            ffn = D * self.n_experts + self.n_experts * 3 * D * F
        else:
            ffn = 3 * D * F
        per_layer = attn + ffn + 2 * D
        head = 0 if self.tie_embeddings else D * V
        return V * D + self.n_layers * per_layer + D + head

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top_k experts only)."""
        if not self.is_moe:
            return self.param_count()
        D, F = self.d_model, self.d_ff
        dense_like = self.param_count() - self.n_layers * self.n_experts * 3 * D * F
        return dense_like + self.n_layers * self.top_k * 3 * D * F


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: TransformerConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    k_attn, k_ffn = jax.random.split(key)
    p: Params = {
        "attn_norm": jnp.ones((cfg.d_model,), dtype),
        "attn": L.attention_init(k_attn, cfg.attn_spec(), dtype),
        "ffn_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.is_moe:
        p["moe"] = moe_init(k_ffn, cfg.moe_spec(), dtype)
    else:
        p["ffn"] = L.swiglu_init(k_ffn, cfg.d_model, cfg.d_ff, dtype)
    return p


def init(key, cfg: TransformerConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys)
    params: Params = {
        "embed": L.embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# layer body (shared by train / prefill / decode via mode switch)
# ---------------------------------------------------------------------------

def _ffn_block(lp: Params, cfg: TransformerConfig, x: jax.Array):
    """x: (B,S,D) -> (y, aux)."""
    h = L.rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    if cfg.is_moe:
        B, S, D = h.shape
        # dispatch groups of <= moe_group tokens keep the one-hot dispatch
        # tensors (G, T, E, C) small relative to expert compute
        t = min(cfg.moe_group, S)
        hg = h.reshape(B * S // t, t, D)
        y, aux = moe_apply(lp["moe"], cfg.moe_spec(), hg)
        return y.reshape(B, S, D), aux
    return L.swiglu(lp["ffn"], h), jnp.float32(0.0)


def _train_layer(lp: Params, cfg: TransformerConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    h = L.rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    x = x + L.attention_full(lp["attn"], cfg.attn_spec(), h, causal=True,
                             impl=cfg.attn_impl, unroll=cfg.unroll_layers)
    y, aux = _ffn_block(lp, cfg, x)
    return x + y, aux


# ---------------------------------------------------------------------------
# forward / loss (training)
# ---------------------------------------------------------------------------

def _layer_slice(stacked: Params, i: int) -> Params:
    return jax.tree.map(lambda x: x[i], stacked)


def backbone(params: Params, cfg: TransformerConfig, tokens: jax.Array):
    """tokens: (B,S) -> (final-norm hidden states (B,S,D), aux_loss fp32)."""
    x = jnp.take(params["embed"], tokens, axis=0)

    def body(carry, lp):
        x = carry
        x, aux = _train_layer(lp, cfg, x)
        return x, aux

    if cfg.remat:
        body = jax.checkpoint(body)
    if cfg.unroll_layers:
        auxs = []
        for i in range(cfg.n_layers):
            x, aux = body(x, _layer_slice(params["layers"], i))
            auxs.append(aux)
        auxs = jnp.stack(auxs)
    else:
        x, auxs = jax.lax.scan(body, x, params["layers"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.sum(auxs)


def lm_head_matrix(params: Params, cfg: TransformerConfig) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params: Params, cfg: TransformerConfig, tokens: jax.Array):
    """tokens: (B,S) int32 -> (logits (B,S,V) compute-dtype, aux_loss fp32)."""
    x, aux = backbone(params, cfg, tokens)
    return x @ lm_head_matrix(params, cfg), aux


def loss_fn(params: Params, cfg: TransformerConfig, batch: dict[str, jax.Array]) -> jax.Array:
    """batch: {tokens (B,S), labels (B,S)}; labels == -1 are masked."""
    logits, aux = forward(params, cfg, batch["tokens"])
    labels = batch["labels"]
    mask = labels >= 0
    labels = jnp.maximum(labels, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    xent = nll.sum() / jnp.maximum(mask.sum(), 1)
    return xent + cfg.moe_aux_weight * aux


def make_vp_loss_fn(cfg: TransformerConfig, mesh, *, tp_axis: str = "model"):
    """Vocab-parallel cross-entropy (Megatron-LM style) as a shard_map region.

    The naive GSPMD loss materializes fp32 logits over the model-sharded
    vocab and reshards them for take_along_axis — tens of GiB of temp + an
    all-gather of the full logits (see EXPERIMENTS.md §Perf iteration 1).
    Here each TP shard keeps ONLY its (tokens, V/tp) logits slice:

        m     = pmax_tp(max_local(logits))           # fp32 scalars/token
        logz  = m + log(psum_tp(sum exp(logits-m)))
        gold  = psum_tp(logits[label] if label in my vocab range else 0)
        loss  = mean over labeled tokens (psum over the dp axes)

    Collective payload per token: 3 scalars — independent of vocab size.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    dp_axes = tuple(a for a in mesh.axis_names if a != tp_axis)
    n_tp = mesh.shape[tp_axis]
    v_real = cfg.vocab_size
    v_pad = (-v_real) % n_tp          # pad vocab to a tp multiple (e.g. 49155)

    def local_xent(x, head, labels):
        # x (b_l, S, D) local; head (D, V_padded/tp) local slice; labels (b_l, S)
        v_local = head.shape[1]
        off = jax.lax.axis_index(tp_axis) * v_local
        logits = (x @ head).astype(jnp.float32)              # (b_l, S, v_l)
        # mask padded vocab columns out of the softmax
        col = off + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        logits = jnp.where(col < v_real, logits, jnp.finfo(jnp.float32).min)
        # global max via all_gather (differentiable, unlike pmax; logz is
        # mathematically independent of m so its grad contribution is 0)
        m = jnp.max(jax.lax.all_gather(jnp.max(logits, axis=-1), tp_axis),
                    axis=0)                                   # (b_l, S)
        se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        logz = m + jnp.log(jax.lax.psum(se, tp_axis))
        mask = labels >= 0
        lab = jnp.maximum(labels, 0)
        in_range = (lab >= off) & (lab < off + v_local)
        lab_local = jnp.clip(lab - off, 0, v_local - 1)
        gold_l = jnp.take_along_axis(logits, lab_local[..., None], axis=-1)[..., 0]
        gold = jax.lax.psum(jnp.where(in_range, gold_l, 0.0), tp_axis)
        nll_sum = jnp.sum((logz - gold) * mask)
        cnt = jnp.sum(mask)
        # reduce over data-parallel shards -> identical scalar everywhere
        nll_sum = jax.lax.psum(nll_sum, dp_axes)
        cnt = jax.lax.psum(cnt, dp_axes)
        return nll_sum / jnp.maximum(cnt, 1)

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    xent_sharded = jax.shard_map(
        local_xent, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, tp_axis), P(dp, None)),
        out_specs=P(), check_vma=False)

    def loss(params: Params, batch: dict[str, jax.Array]) -> jax.Array:
        x, aux = backbone(params, cfg, batch["tokens"])
        head = lm_head_matrix(params, cfg)
        if v_pad:
            head = jnp.pad(head, ((0, 0), (0, v_pad)))
        return xent_sharded(x, head, batch["labels"]) + cfg.moe_aux_weight * aux

    return loss


# ---------------------------------------------------------------------------
# serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------

def make_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params: Params, cfg: TransformerConfig, tokens: jax.Array, cache_len: int):
    """tokens: (B,S) -> (last-position logits (B,V), cache dict)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    spec = cfg.attn_spec()

    def body(carry, lp):
        x = carry
        h = L.rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        attn_out, (kc, vc) = L.attention_prefill(lp["attn"], spec, h, cache_len,
                                                 impl=cfg.attn_impl,
                                                 unroll=cfg.unroll_layers)
        x = x + attn_out
        y, _ = _ffn_block(lp, cfg, x)
        return x + y, (kc, vc)

    if cfg.remat:
        body = jax.checkpoint(body)
    if cfg.unroll_layers:
        kcs, vcs = [], []
        for i in range(cfg.n_layers):
            x, (kc, vc) = body(x, _layer_slice(params["layers"], i))
            kcs.append(kc)
            vcs.append(vc)
        k_caches, v_caches = jnp.stack(kcs), jnp.stack(vcs)
    else:
        x, (k_caches, v_caches) = jax.lax.scan(body, x, params["layers"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x[:, -1, :] @ head
    return logits, {"k": k_caches, "v": v_caches}


def decode_step(params: Params, cfg: TransformerConfig, token: jax.Array,
                cache: Params, cur_index: jax.Array):
    """token: (B,) int32; cache from make_cache/prefill; cur_index: scalar int32.

    Returns (logits (B,V), new cache). Cost is O(S_max) per token — linear,
    which is what makes the long_500k decode cell feasible for full attention.
    """
    x = jnp.take(params["embed"], token[:, None], axis=0)
    spec = cfg.attn_spec()

    def body(carry, scans):
        x = carry
        lp, kc, vc = scans
        h = L.rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        attn_out, (kc, vc) = L.attention_decode(lp["attn"], spec, h, kc, vc, cur_index)
        x = x + attn_out
        y, _ = _ffn_block(lp, cfg, x)
        return x + y, (kc, vc)

    if cfg.unroll_layers:
        kcs, vcs = [], []
        for i in range(cfg.n_layers):
            x, (kc, vc) = body(x, (_layer_slice(params["layers"], i),
                                   cache["k"][i], cache["v"][i]))
            kcs.append(kc)
            vcs.append(vc)
        k_caches, v_caches = jnp.stack(kcs), jnp.stack(vcs)
    else:
        x, (k_caches, v_caches) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x[:, -1, :] @ head
    return logits, {"k": k_caches, "v": v_caches}
