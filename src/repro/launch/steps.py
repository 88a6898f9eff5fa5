"""Cell builders: (arch x shape x mesh) -> lowerable step function.

A Cell carries everything dryrun.py needs:
  fn             the step function (NOT jitted)
  args           ShapeDtypeStruct stand-ins for every input (no allocation)
  in_shardings   NamedSharding pytree matching args
  out_shardings  NamedSharding pytree or None (compiler-chosen)
  model_flops    napkin "useful" FLOPs for the roofline ratio
  note           one-line description

Design decisions recorded here:
  * LM train: FSDP over ('pod','data') x TP over 'model'; optimizer by scale
    (Adafactor >= 100B else AdamW); scan-over-layers + remat; chunked
    attention (flash-style) so 4k x 256 and 32k prefill lower without O(S^2)
    buffers.
  * LM decode: KV cache seq-sharded over 'model' (batch over data); the
    long_500k cell shards seq over EVERY axis (batch=1) — GSPMD emits the
    partial-softmax reductions (flash-decode split-K across the mesh).
  * RecSys: embedding tables row-sharded over 'model' (vocab dim);
    interaction/MLP batch-parallel.
  * GNN: edges + nodes row-sharded over all axes; weights replicated (16-dim
    hidden); XW-before-propagate keeps message width at d_hidden.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import Arch, get
from repro.distributed import sharding as shd
from repro.models import gnn as gnn_mod
from repro.models import recsys as rec
from repro.models import transformer as tfm
from repro.training.optimizer import adafactor, adamw
from repro.training.train_loop import make_train_step


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    fn: Callable
    args: tuple
    in_shardings: Any
    out_shardings: Any
    model_flops: float
    note: str
    model_bytes: float = 0.0   # minimal HBM traffic floor (global, bytes)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _shard(mesh, spec_tree, sds_tree):
    """NamedShardings with every spec fit_spec'd against the matching
    ShapeDtypeStruct (divisibility-safe)."""
    specs = jax.tree.map(lambda spec, sds: shd.fit_spec(mesh, spec, sds.shape),
                         spec_tree, sds_tree,
                         is_leaf=lambda x: isinstance(x, P))
    return _named(mesh, specs)


def _dp(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _all_axes(mesh: Mesh):
    return tuple(mesh.axis_names)


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def _lm_optimizer(cfg: tfm.TransformerConfig):
    if cfg.param_count() >= 100e9:
        return adafactor(1e-3)
    return adamw(3e-4, weight_decay=0.1)


def _lm_state_sds(cfg, opt):
    params = jax.eval_shape(lambda: tfm.init(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(opt.init, params)
    return {"params": params, "opt": opt_state, "step": _sds((), jnp.int32)}


def _lm_train_cell(arch: Arch, shape: dict, mesh: Mesh) -> Cell:
    cfg: tfm.TransformerConfig = arch.full
    B, S = shape["batch"], shape["seq"]
    opt = _lm_optimizer(cfg)
    state_sds = _lm_state_sds(cfg, opt)
    batch_sds = {"tokens": _sds((B, S), jnp.int32), "labels": _sds((B, S), jnp.int32)}

    rules = shd.lm_rules(mesh)
    state_sh = shd.state_shardings(mesh, state_sds, rules)
    dp = _dp(mesh)
    batch_sh = _named(mesh, {"tokens": P(dp, None), "labels": P(dp, None)})

    import os as _os
    if _os.environ.get("REPRO_LM_VP_LOSS", "0") == "1":
        # §Perf iteration 1: vocab-parallel cross-entropy (see transformer.py)
        loss = tfm.make_vp_loss_fn(cfg, mesh)
    else:
        loss = lambda p, b: tfm.loss_fn(p, cfg, b)
    step = make_train_step(loss, opt, donate=False)
    fn = step.__wrapped__  # the raw python fn under jax.jit

    tokens = B * S
    flops = 6.0 * cfg.active_param_count() * tokens
    pbytes = cfg.param_count() * 2.0
    # floor: read params (fwd+bwd) + grads + opt state r/w + residual stream
    mbytes = 4.0 * pbytes + 2.0 * cfg.n_layers * tokens * cfg.d_model * 2.0
    return Cell(arch.arch_id, "train", fn, (state_sds, batch_sds),
                (state_sh, batch_sh), (state_sh, _named(mesh, {"loss": P(), "grad_norm": P()})),
                flops, f"train {B}x{S}, opt={opt.name}, FSDP{dp}xTP", mbytes)


def _lm_prefill_cell(arch: Arch, shape: dict, mesh: Mesh) -> Cell:
    cfg: tfm.TransformerConfig = arch.full
    B, S = shape["batch"], shape["seq"]
    params_sds = jax.eval_shape(lambda: tfm.init(jax.random.PRNGKey(0), cfg))
    rules = shd.lm_rules(mesh)
    params_sh = shd.named(mesh, shd.param_pspecs(params_sds, rules, mesh))
    dp = _dp(mesh)
    tokens_sh = _named(mesh, P(dp, None))

    def fn(params, tokens):
        return tfm.prefill(params, cfg, tokens, cache_len=S)

    cache_sds = _sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.hd), jnp.dtype(cfg.dtype))
    cache_spec = {"k": P(None, dp, "model", None, None),
                  "v": P(None, dp, "model", None, None)}
    out_sh = (_shard(mesh, P(dp, "model"), _sds((B, cfg.vocab_size), jnp.dtype(cfg.dtype))),
              _shard(mesh, cache_spec, {"k": cache_sds, "v": cache_sds}))
    flops = 2.0 * cfg.active_param_count() * B * S \
        + 4.0 * cfg.n_layers * cfg.n_heads * cfg.hd * B * S * S / 2
    kv_bytes = 2.0 * cfg.n_layers * B * S * cfg.n_kv_heads * cfg.hd * 2.0
    mbytes = cfg.param_count() * 2.0 + kv_bytes \
        + 2.0 * cfg.n_layers * B * S * cfg.d_model * 2.0
    return Cell(arch.arch_id, "prefill", fn,
                (params_sds, _sds((B, S), jnp.int32)),
                (params_sh, tokens_sh), out_sh, flops,
                f"prefill {B}x{S}, cache seq-sharded over model", mbytes)


def _lm_decode_cell(arch: Arch, shape: dict, mesh: Mesh) -> Cell:
    cfg: tfm.TransformerConfig = arch.full
    B, S = shape["batch"], shape["seq"]
    params_sds = jax.eval_shape(lambda: tfm.init(jax.random.PRNGKey(0), cfg))
    rules = shd.lm_rules(mesh)
    params_sh = shd.named(mesh, shd.param_pspecs(params_sds, rules, mesh))
    dp = _dp(mesh)
    cache_sds = {"k": _sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.hd), jnp.dtype(cfg.dtype)),
                 "v": _sds((cfg.n_layers, B, S, cfg.n_kv_heads, cfg.hd), jnp.dtype(cfg.dtype))}
    if B == 1:
        # long-context: batch unshardable -> sequence over EVERY axis
        cache_spec = P(None, None, _all_axes(mesh), None, None)
        tok_spec = P()
        note = f"decode B=1 S={S}: KV seq-sharded over ALL axes (split-K decode)"
    else:
        cache_spec = P(None, dp, "model", None, None)
        tok_spec = P(dp)
        note = f"decode B={B} S={S}: batch over {dp}, KV seq over model"
    cache_sh = _shard(mesh, {"k": cache_spec, "v": cache_spec}, cache_sds)

    def fn(params, cache, token, index):
        return tfm.decode_step(params, cfg, token, cache, index)

    out_sh = (_shard(mesh, P(dp if B > 1 else None, "model"),
                     _sds((B, cfg.vocab_size), jnp.dtype(cfg.dtype))), cache_sh)
    flops = 2.0 * cfg.active_param_count() * B \
        + 4.0 * cfg.n_layers * cfg.n_heads * cfg.hd * B * S
    kv_bytes = 2.0 * cfg.n_layers * B * S * cfg.n_kv_heads * cfg.hd * 2.0
    mbytes = cfg.active_param_count() * 2.0 + kv_bytes
    return Cell(arch.arch_id, "decode", fn,
                (params_sds, cache_sds, _sds((B,), jnp.int32), _sds((), jnp.int32)),
                (params_sh, cache_sh, _named(mesh, tok_spec), _named(mesh, P())),
                out_sh, flops, note, mbytes)


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

def _recsys_batch(arch: Arch, B: int):
    """(batch_sds, batch_pspec fn(dp), loss_fn, serve_fn, dense_params_fn)."""
    cfg = arch.full
    if arch.arch_id == "dlrm-rm2":
        sds = {"dense": _sds((B, cfg.n_dense), jnp.float32),
               "sparse_ids": _sds((B, cfg.n_sparse, cfg.multi_hot), jnp.int32),
               "label": _sds((B,), jnp.int32)}
        spec = lambda dp: {"dense": P(dp, None), "sparse_ids": P(dp, None, None),
                           "label": P(dp)}
        loss = lambda p, b: rec.dlrm_loss(p, cfg, b)
        serve = lambda p, b: rec.dlrm_forward(p, cfg, b["dense"], b["sparse_ids"])
    elif arch.arch_id == "fm":
        sds = {"sparse_ids": _sds((B, cfg.n_sparse), jnp.int32),
               "label": _sds((B,), jnp.int32)}
        spec = lambda dp: {"sparse_ids": P(dp, None), "label": P(dp)}
        loss = lambda p, b: rec.fm_loss(p, cfg, b)
        serve = lambda p, b: rec.fm_forward(p, cfg, b["sparse_ids"])
    elif arch.arch_id == "mind":
        L = cfg.hist_len
        sds = {"hist_ids": _sds((B, L), jnp.int32), "hist_mask": _sds((B, L), jnp.bool_),
               "label_id": _sds((B,), jnp.int32)}
        spec = lambda dp: {"hist_ids": P(dp, None), "hist_mask": P(dp, None),
                           "label_id": P(dp)}
        loss = lambda p, b: rec.mind_loss(p, cfg, b)
        serve = lambda p, b: rec.mind_score(p, cfg, b["hist_ids"], b["hist_mask"],
                                            b["label_id"][:, None])[:, 0]
    elif arch.arch_id == "bert4rec":
        S, M = cfg.seq_len, max(1, cfg.seq_len // 10)
        sds = {"ids": _sds((B, S), jnp.int32), "pad_mask": _sds((B, S), jnp.bool_),
               "mask_positions": _sds((B, M), jnp.int32),
               "mask_targets": _sds((B, M), jnp.int32)}
        spec = lambda dp: {"ids": P(dp, None), "pad_mask": P(dp, None),
                           "mask_positions": P(dp, None), "mask_targets": P(dp, None)}
        loss = lambda p, b: rec.bert4rec_loss(p, cfg, b)
        serve = lambda p, b: rec.bert4rec_score(p, cfg, b["ids"], b["pad_mask"],
                                                b["mask_targets"][:, :1])[:, 0]
    else:
        raise KeyError(arch.arch_id)
    return sds, spec, loss, serve


def _recsys_init(arch: Arch):
    cfg = arch.full
    key = jax.random.PRNGKey(0)
    if arch.arch_id == "dlrm-rm2":
        return jax.eval_shape(lambda: rec.dlrm_init(key, cfg))
    if arch.arch_id == "fm":
        return jax.eval_shape(lambda: rec.fm_init(key, cfg))
    if arch.arch_id == "mind":
        return jax.eval_shape(lambda: rec.mind_init(key, cfg))
    if arch.arch_id == "bert4rec":
        return jax.eval_shape(lambda: rec.bert4rec_init(key, cfg))
    raise KeyError(arch.arch_id)


def _recsys_flops(arch: Arch, B: int, train: bool) -> float:
    cfg = arch.full
    mul = 6.0 if train else 2.0
    if arch.arch_id == "dlrm-rm2":
        dims = cfg.bot_mlp
        d_inter = cfg.embed_dim + (cfg.n_sparse + 1) * cfg.n_sparse // 2
        tdims = (d_inter,) + cfg.top_mlp[1:]
        dense = sum(a * b for a, b in zip(dims, dims[1:])) + \
            sum(a * b for a, b in zip(tdims, tdims[1:])) + \
            (cfg.n_sparse + 1) ** 2 * cfg.embed_dim
        return mul * B * dense
    if arch.arch_id == "fm":
        return mul * B * cfg.n_sparse * cfg.embed_dim * 3
    if arch.arch_id == "mind":
        return mul * B * cfg.hist_len * cfg.embed_dim * cfg.embed_dim
    if arch.arch_id == "bert4rec":
        d, S = cfg.embed_dim, cfg.seq_len
        per = cfg.n_blocks * (12 * d * d + 4 * S * d) * S
        return mul * B * (per + S * d * cfg.vocab) / S  # per-sequence avg
    raise KeyError(arch.arch_id)


def _recsys_train_cell(arch: Arch, shape: dict, mesh: Mesh) -> Cell:
    B = shape["batch"]
    opt = adamw(1e-3, weight_decay=0.0)
    params_sds = _recsys_init(arch)
    state_sds = {"params": params_sds, "opt": jax.eval_shape(opt.init, params_sds),
                 "step": _sds((), jnp.int32)}
    rules = shd.recsys_rules(mesh)
    state_sh = shd.state_shardings(mesh, state_sds, rules)
    dp = _dp(mesh)
    batch_sds, spec_fn, loss, _ = _recsys_batch(arch, B)
    batch_sh = _shard(mesh, spec_fn(dp), batch_sds)
    step = make_train_step(loss, opt, donate=False)
    emb_touched = B * 64.0 * 4.0 * 8  # ids touched x dim x fp32 x (r+w, grad, opt)
    return Cell(arch.arch_id, "train", step.__wrapped__, (state_sds, batch_sds),
                (state_sh, batch_sh),
                (state_sh, _named(mesh, {"loss": P(), "grad_norm": P()})),
                _recsys_flops(arch, B, True),
                f"train B={B}, tables row-sharded over model", emb_touched)


def _recsys_serve_cell(arch: Arch, shape: dict, mesh: Mesh) -> Cell:
    B = shape["batch"]
    params_sds = _recsys_init(arch)
    rules = shd.recsys_rules(mesh)
    params_sh = shd.named(mesh, shd.param_pspecs(params_sds, rules, mesh))
    dp = _dp(mesh)
    batch_sds, spec_fn, _, serve = _recsys_batch(arch, B)
    batch_sh = _shard(mesh, spec_fn(dp), batch_sds)
    return Cell(arch.arch_id, "serve", serve, (params_sds, batch_sds),
                (params_sh, batch_sh), None,
                _recsys_flops(arch, B, False), f"serve B={B}",
                B * 64.0 * 4.0 * 2)


def _recsys_retrieval_cell(arch: Arch, shape: dict, mesh: Mesh) -> Cell:
    """1 query x 1M candidates — the paper's hot path, batched-dot (no loop)."""
    C = shape["n_candidates"]
    cfg = arch.full
    params_sds = _recsys_init(arch)
    rules = shd.recsys_rules(mesh)
    params_sh = shd.named(mesh, shd.param_pspecs(params_sds, rules, mesh))
    all_ax = _all_axes(mesh)

    if arch.arch_id in ("mind", "bert4rec"):
        # two-tower style: encode the user once, batched-dot against C items
        if arch.arch_id == "mind":
            L = cfg.hist_len
            args = (params_sds, _sds((1, L), jnp.int32), _sds((1, L), jnp.bool_),
                    _sds((1, C), jnp.int32))
            in_sh = (params_sh, _named(mesh, P(None, None)), _named(mesh, P(None, None)),
                     _shard(mesh, P(None, all_ax), _sds((1, C), jnp.int32)))
            fn = lambda p, h, m, c: rec.mind_score(p, cfg, h, m, c)
        else:
            S = cfg.seq_len
            args = (params_sds, _sds((1, S), jnp.int32), _sds((1, S), jnp.bool_),
                    _sds((1, C), jnp.int32))
            in_sh = (params_sh, _named(mesh, P(None, None)), _named(mesh, P(None, None)),
                     _shard(mesh, P(None, all_ax), _sds((1, C), jnp.int32)))
            fn = lambda p, i, m, c: rec.bert4rec_score(p, cfg, i, m, c)
        flops = 2.0 * C * cfg.embed_dim
        note = f"retrieval 1x{C}: user tower once, candidates sharded over {all_ax}"
    else:
        # pair-scoring models: candidate-major batch (user features broadcast)
        batch_sds, spec_fn, _, serve = _recsys_batch(arch, C)
        args = (params_sds, batch_sds)
        in_sh = (params_sh, _shard(mesh, spec_fn(all_ax), batch_sds))
        fn = serve
        flops = _recsys_flops(arch, C, False)
        note = f"retrieval 1x{C}: candidate-major pair scoring over {all_ax}"
    mbytes = C * float(getattr(cfg, "embed_dim", 64)) * 4.0
    return Cell(arch.arch_id, "retrieval", fn, args, in_sh, None, flops, note, mbytes)


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

def _gcn_cfg_for(arch: Arch, shape: dict) -> gnn_mod.GCNConfig:
    return dataclasses.replace(arch.full, d_feat=shape["d_feat"],
                               n_classes=shape["n_classes"])


def _gnn_cell(arch: Arch, shape: dict, mesh: Mesh) -> Cell:
    kind = shape["kind"]
    cfg = _gcn_cfg_for(arch, shape)
    opt = adamw(1e-2, weight_decay=0.0)
    all_ax = _all_axes(mesh)
    dp = _dp(mesh)

    if kind == "gnn_batched":
        B, Nn, Ne = shape["batch"], shape["n_nodes"], shape["n_edges"]
        params_sds = jax.eval_shape(lambda: gnn_mod.gcn_init(jax.random.PRNGKey(0), cfg))
        batch_sds = {"feats": _sds((B, Nn, cfg.d_feat), jnp.float32),
                     "src": _sds((B, Ne), jnp.int32), "dst": _sds((B, Ne), jnp.int32),
                     "edge_mask": _sds((B, Ne), jnp.bool_),
                     "node_mask": _sds((B, Nn), jnp.bool_),
                     "labels": _sds((B,), jnp.int32)}
        spec = {"feats": P(all_ax, None, None), "src": P(all_ax, None),
                "dst": P(all_ax, None), "edge_mask": P(all_ax, None),
                "node_mask": P(all_ax, None), "labels": P(all_ax)}
        loss = lambda p, b: gnn_mod.gcn_loss_batched(p, cfg, b)
        flops = 6.0 * B * (Ne * cfg.d_hidden + Nn * cfg.d_feat * cfg.d_hidden)
        note = f"batched {B} graphs x ({Nn}n, {Ne}e)"
    else:
        n_dev = 1
        for a in all_ax:
            n_dev *= mesh.shape[a]
        if kind == "gnn_sampled":
            Bn = shape["batch_nodes"]
            f1, f2 = shape["fanouts"]
            Nn = Bn * (1 + f1 + f1 * f2)
            Ne = Bn * f1 + Bn * f1 * f2
            note = f"sampled fanout{shape['fanouts']} -> {Nn}n/{Ne}e per batch"
        else:
            Nn, Ne = shape["n_nodes"], shape["n_edges"]
            note = f"full graph {Nn}n/{Ne}e"
        # pad rows/edges up to mesh-divisible sizes (padded edges carry
        # edge_mask=False; padded nodes are isolated and label-masked)
        Nn = -(-Nn // n_dev) * n_dev
        Ne = -(-Ne // n_dev) * n_dev
        params_sds = jax.eval_shape(lambda: gnn_mod.gcn_init(jax.random.PRNGKey(0), cfg))
        batch_sds = {"feats": _sds((Nn, cfg.d_feat), jnp.float32),
                     "src": _sds((Ne,), jnp.int32), "dst": _sds((Ne,), jnp.int32),
                     "edge_mask": _sds((Ne,), jnp.bool_),
                     "labels": _sds((Nn,), jnp.int32),
                     "label_mask": _sds((Nn,), jnp.float32)}
        spec = {"feats": P(all_ax, None), "src": P(all_ax), "dst": P(all_ax),
                "edge_mask": P(all_ax), "labels": P(all_ax), "label_mask": P(all_ax)}
        loss = lambda p, b: gnn_mod.gcn_loss(p, cfg, b)
        flops = 6.0 * (Ne * cfg.d_hidden + Nn * cfg.d_feat * cfg.d_hidden)

    state_sds = {"params": params_sds, "opt": jax.eval_shape(opt.init, params_sds),
                 "step": _sds((), jnp.int32)}
    state_sh = shd.state_shardings(mesh, state_sds, shd.gnn_rules(mesh))
    step = make_train_step(loss, opt, donate=False)
    feat_bytes = float(jnp.prod(jnp.asarray(batch_sds["feats"].shape))) * 4.0
    edge_bytes = float(batch_sds["src"].shape[-1]) * 8.0
    return Cell(arch.arch_id, shape["kind"], step.__wrapped__, (state_sds, batch_sds),
                (state_sh, _shard(mesh, spec, batch_sds)),
                (state_sh, _named(mesh, {"loss": P(), "grad_norm": P()})),
                flops, note, 2.0 * feat_bytes + 3.0 * edge_bytes)


# ---------------------------------------------------------------------------
# RAG (the paper's own system)
# ---------------------------------------------------------------------------

def _rag_cell(arch: Arch, shape: dict, mesh: Mesh) -> Cell:
    from repro.core.query import unified_query_ref
    from repro.core.store import StoreConfig
    scfg: StoreConfig = arch.full
    N, D = scfg.capacity, scfg.dim
    all_ax = _all_axes(mesh)
    store_sds = {
        "emb": _sds((N, D), jnp.float32), "tenant": _sds((N,), jnp.int32),
        "category": _sds((N,), jnp.int32), "updated_at": _sds((N,), jnp.int32),
        "acl": _sds((N,), jnp.uint32), "doc_id": _sds((N,), jnp.int32),
        "version": _sds((N,), jnp.int32), "commit_ts": _sds((), jnp.int32),
        "n_live": _sds((), jnp.int32),
    }
    row = P(all_ax)
    store_spec = {"emb": P(all_ax, None), "tenant": row, "category": row,
                  "updated_at": row, "acl": row, "doc_id": row, "version": row,
                  "commit_ts": P(), "n_live": P()}
    store_sh = _named(mesh, store_spec)

    if shape["kind"] == "rag_query":
        B, k = shape["batch"], shape["k"]
        import os as _os
        if _os.environ.get("REPRO_RAG_SHARDED", "0") == "1":
            # §Perf iteration: local top-k per shard + constant-size merge
            from repro.core.query import make_sharded_query
            fn = make_sharded_query(mesh, all_ax, N, k)
            note = f"unified query B={B} k={k}: per-shard top-k + O(shards*k) merge"
        else:
            fn = partial(unified_query_ref, k=k)
            note = f"unified query B={B} k={k} over {N}x{D} row-sharded corpus"
        args = (store_sds, _sds((B, D), jnp.float32), _sds((4,), jnp.int32))
        in_sh = (store_sh, _named(mesh, P(None, None)), _named(mesh, P()))
        flops = 2.0 * B * N * D
        return Cell(arch.arch_id, "rag_query", fn, args, in_sh, None, flops, note,
                    N * (D * 4.0 + 16.0))

    # ingest: one atomic transactional write (embedding + metadata together)
    from repro.core import transactions as txn
    M = shape["batch"]

    def fn(store, slots, emb, tenant, category, updated_at, acl, doc_id):
        return txn.ingest(store, scfg, slots, emb, tenant, category,
                          updated_at, acl, doc_id)

    args = (store_sds, _sds((M,), jnp.int32), _sds((M, D), jnp.float32),
            _sds((M,), jnp.int32), _sds((M,), jnp.int32), _sds((M,), jnp.int32),
            _sds((M,), jnp.uint32), _sds((M,), jnp.int32))
    in_sh = (store_sh, _named(mesh, P()), _named(mesh, P(None, None)),
             _named(mesh, P()), _named(mesh, P()), _named(mesh, P()),
             _named(mesh, P()), _named(mesh, P()))
    return Cell(arch.arch_id, "rag_ingest", fn, args, in_sh, store_sh,
                2.0 * M * D, f"atomic ingest of {M} docs", M * D * 8.0)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def build_cell(arch_id: str, shape_name: str, mesh: Mesh,
               cfg_override=None) -> Cell:
    """cfg_override replaces arch.full (e.g. a 1-layer variant for the
    roofline's while-loop cost correction)."""
    arch = get(arch_id)
    if cfg_override is not None:
        arch = dataclasses.replace(arch, full=cfg_override)
    shape = arch.shapes[shape_name]
    if arch.family == "lm" and getattr(arch.full, "is_moe", False):
        from repro.models.moe import set_moe_mesh
        set_moe_mesh(mesh, _dp(mesh))   # used by the scatter_shmap dispatch
    kind = shape["kind"]
    if arch.family == "lm":
        cell = {"train": _lm_train_cell, "prefill": _lm_prefill_cell,
                "decode": _lm_decode_cell}[kind](arch, shape, mesh)
    elif arch.family == "recsys":
        cell = {"train": _recsys_train_cell, "serve": _recsys_serve_cell,
                "retrieval": _recsys_retrieval_cell}[kind](arch, shape, mesh)
    elif arch.family == "gnn":
        cell = _gnn_cell(arch, shape, mesh)
    elif arch.family == "rag":
        cell = _rag_cell(arch, shape, mesh)
    else:
        raise KeyError(arch.family)
    cell.shape_name = shape_name
    return cell
