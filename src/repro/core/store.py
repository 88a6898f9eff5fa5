"""Unified document store — the paper's "one database" as a device-resident
columnar tensor arena.

Everything a production RAG query needs lives in ONE pytree:
  emb        (N, D)  embeddings (unit-normalized when metric == cosine)
  tenant     (N,)    int32 tenant id (-1 = free/tombstoned slot)
  category   (N,)    int32 category id (< 32 so predicate sets are bitmasks)
  updated_at (N,)    int32 seconds since store epoch
  acl        (N,)    uint32 bitmask of permitted principal groups
  doc_id     (N,)    int32 external document id
  version    (N,)    int32 row version (bumped on every update)
  commit_ts  ()      int32 store-level commit watermark
  n_live     ()      int32 number of live rows

The store is immutable: every write produces the next state in ONE XLA
program, so embedding + metadata can never be observed out of sync — this is
the tensor-level analogue of the paper's single-transaction COMMIT, and the
structural reason the unified stack's inconsistency window is 0 by design.

Capacity is a fixed pre-allocated arena (production stores pre-size their
slabs the same way); `StoreConfig.capacity` rows, free slots carry tenant=-1.
On a device mesh (`ShardPlacement` with a ``mesh``) every lane is
row-sharded over the mesh axes from the moment it is allocated, so each
device holds only its own contiguous region.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

Store = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    capacity: int                 # arena rows (power of two preferred)
    dim: int                      # embedding dim
    metric: str = "cosine"        # "cosine" | "dot"
    dtype: str = "float32"
    n_categories: int = 32        # must stay <= 32 (bitmask predicates)
    n_acl_groups: int = 32


def _empty_lanes(cfg: StoreConfig) -> Store:
    N, D = cfg.capacity, cfg.dim
    return {
        "emb": jnp.zeros((N, D), jnp.dtype(cfg.dtype)),
        "tenant": jnp.full((N,), -1, jnp.int32),
        "category": jnp.zeros((N,), jnp.int32),
        "updated_at": jnp.zeros((N,), jnp.int32),
        "acl": jnp.zeros((N,), jnp.uint32),
        "doc_id": jnp.full((N,), -1, jnp.int32),
        "version": jnp.zeros((N,), jnp.int32),
        "commit_ts": jnp.int32(0),
        "n_live": jnp.int32(0),
    }


def empty(cfg: StoreConfig, placement: "ShardPlacement | None" = None) -> Store:
    """A fresh arena. With a mesh placement the lanes are built directly
    in their sharded layout (never materialized whole on one device)."""
    shardings = placement.shardings() if placement is not None else None
    if shardings is None:
        return _empty_lanes(cfg)
    return jax.jit(lambda: _empty_lanes(cfg), out_shardings=shardings)()


def normalize(cfg: StoreConfig, emb: jax.Array) -> jax.Array:
    if cfg.metric == "cosine":
        norm = jnp.linalg.norm(emb.astype(jnp.float32), axis=-1, keepdims=True)
        return (emb / jnp.maximum(norm, 1e-12)).astype(emb.dtype)
    return emb


@dataclasses.dataclass(frozen=True)
class ShardPlacement:
    """Row placement over a device mesh: the arena is split into
    ``n_shards`` contiguous, equally sized regions (slot-aligned with every
    lane — vector, lexical, metadata — because they all index by slot), and
    shard s owns the slot range [s * rows_per_shard, (s+1) * rows_per_shard).

    kind:
      * ``"hash"``   — docs route by ``doc_id % n_shards`` (balanced; the
        perf-bench default).
      * ``"tenant"`` — docs route by ``tenant % n_shards`` (tenant-affine: a
        tenant's rows live on ONE known shard, so a tenant-scoped query can
        skip every other shard and cross-shard leakage is auditable by
        per-shard ``rows_scanned``, not just masked by predicates).

    The placement IS the global→(shard, local slot) id map: global slot g
    lives on shard ``g // rows_per_shard`` at local offset
    ``g % rows_per_shard`` — no lookup table, because regions are contiguous.
    """
    n_shards: int
    capacity: int
    kind: str = "hash"            # "hash" | "tenant"
    mesh: Any = None              # jax.sharding.Mesh the regions live on
    axes: tuple[str, ...] = ()    # mesh axes the rows are sharded over

    def __post_init__(self):
        if self.kind not in ("hash", "tenant"):
            raise ValueError(f"unknown placement kind {self.kind!r}")
        if self.capacity % self.n_shards:
            raise ValueError(
                f"capacity {self.capacity} not divisible by {self.n_shards} shards")

    @property
    def rows_per_shard(self) -> int:
        return self.capacity // self.n_shards

    def shardings(self) -> dict | None:
        """Per-lane `NamedSharding`s of a mesh-placed arena (rows over
        ``axes``, scalars replicated), or None without a mesh."""
        if self.mesh is None:
            return None
        row = NamedSharding(self.mesh, P(self.axes))
        rep = NamedSharding(self.mesh, P())
        out = {k: row for k in ("tenant", "category", "updated_at", "acl",
                                "doc_id", "version")}
        out["emb"] = NamedSharding(self.mesh, P(self.axes, None))
        out["commit_ts"] = out["n_live"] = rep
        return out

    def region(self, shard: int) -> tuple[int, int]:
        """Slot range [start, stop) owned by ``shard``."""
        return shard * self.rows_per_shard, (shard + 1) * self.rows_per_shard

    def shard_of_slot(self, slot: int) -> int:
        return slot // self.rows_per_shard

    def locate(self, slot: int) -> tuple[int, int]:
        """Global slot -> (shard, shard-local slot)."""
        return divmod(slot, self.rows_per_shard)

    def shard_of_doc(self, tenant: int, doc_id: int) -> int:
        """Write-path routing: which shard's region a new doc allocates in."""
        if self.kind == "tenant":
            return int(tenant) % self.n_shards
        return int(doc_id) % self.n_shards


@dataclasses.dataclass(frozen=True)
class DocBatch:
    """A batch of documents headed into the store (host-side container).

    ``terms``/``tfs`` are the optional lexical lanes ((M, T) term ids + term
    frequencies) consumed by an attached `repro.index.lexical.LexicalArena`;
    None means the batch carries no lexical content (its rows write empty
    lanes, so recycled slots never inherit a previous doc's postings)."""
    emb: jax.Array          # (M, D)
    tenant: jax.Array       # (M,) int32
    category: jax.Array     # (M,) int32
    updated_at: jax.Array   # (M,) int32
    acl: jax.Array          # (M,) uint32
    doc_id: jax.Array       # (M,) int32
    terms: jax.Array | None = None   # (M, T) int32 term ids, -1 empty lane
    tfs: jax.Array | None = None     # (M, T) int32 term frequencies

    @property
    def size(self) -> int:
        return self.emb.shape[0]


def live_mask(store: Store) -> jax.Array:
    return store["tenant"] >= 0
