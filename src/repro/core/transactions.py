"""Transactional writes for the unified store.

The paper's claim: because document + embedding live in one engine, a write is
ONE atomic commit and the retrieval layer can never observe a half-applied
update (inconsistency window = 0 by construction). Here a "transaction" is a
single jitted program mapping store -> store'; the caller swaps the returned
pytree under `TransactionLog.commit`, so readers hold either the old snapshot
or the new one — never a mix (MVCC by immutability).

The split-stack counterpart (splitstack.py) performs the vector write and the
metadata write as TWO separate programs with a host gap in between; that gap
is the measurable inconsistency window of Table 2.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.store import (DocBatch, ShardPlacement, Store, StoreConfig,
                              normalize)


# ---------------------------------------------------------------------------
# atomic write programs (each is ONE XLA program = one commit)
# ---------------------------------------------------------------------------

# NOTE: no buffer donation on these programs — readers may pin old snapshots
# (MVCC). A deployment that doesn't expose snapshots would donate for in-place
# updates; that trade-off is deliberate and documented in DESIGN.md.
# A mesh-placed log compiles them with its lanes' shardings as out_shardings
# (`_commit_programs`), so a commit never gathers a sharded arena.
def ingest(store: Store, cfg: StoreConfig, slots: jax.Array, batch_emb: jax.Array,
           tenant: jax.Array, category: jax.Array, updated_at: jax.Array,
           acl: jax.Array, doc_id: jax.Array) -> Store:
    """Insert M documents at the given slots. Embedding AND metadata columns
    are updated in the same program: atomic by construction."""
    emb = normalize(cfg, batch_emb.astype(store["emb"].dtype))
    was_free = store["tenant"][slots] < 0
    new = dict(store)
    new["emb"] = store["emb"].at[slots].set(emb)
    new["tenant"] = store["tenant"].at[slots].set(tenant)
    new["category"] = store["category"].at[slots].set(category)
    new["updated_at"] = store["updated_at"].at[slots].set(updated_at)
    new["acl"] = store["acl"].at[slots].set(acl)
    new["doc_id"] = store["doc_id"].at[slots].set(doc_id)
    new["version"] = store["version"].at[slots].add(1)
    new["commit_ts"] = store["commit_ts"] + 1
    new["n_live"] = store["n_live"] + jnp.sum(was_free).astype(jnp.int32)
    return new


def update(store: Store, cfg: StoreConfig, slots: jax.Array, new_emb: jax.Array,
           updated_at: jax.Array) -> Store:
    """Re-embed existing documents (the staleness-critical path): the fresh
    embedding and the fresh timestamp commit together."""
    emb = normalize(cfg, new_emb.astype(store["emb"].dtype))
    new = dict(store)
    new["emb"] = store["emb"].at[slots].set(emb)
    new["updated_at"] = store["updated_at"].at[slots].set(updated_at)
    new["version"] = store["version"].at[slots].add(1)
    new["commit_ts"] = store["commit_ts"] + 1
    return new


def delete(store: Store, slots: jax.Array) -> Store:
    """Tombstone rows (tenant = -1 makes them invisible to every predicate)."""
    was_live = store["tenant"][slots] >= 0
    new = dict(store)
    new["tenant"] = store["tenant"].at[slots].set(-1)
    new["doc_id"] = store["doc_id"].at[slots].set(-1)
    new["version"] = store["version"].at[slots].add(1)
    new["commit_ts"] = store["commit_ts"] + 1
    new["n_live"] = store["n_live"] - jnp.sum(was_live).astype(jnp.int32)
    return new


def _commit_programs(shardings: dict | None):
    """The jitted (ingest, update, delete) commit programs; with lane
    ``shardings`` (a mesh-placed arena) every output lane keeps its
    sharding."""
    kw = {} if shardings is None else {"out_shardings": shardings}
    return (jax.jit(ingest, static_argnames=("cfg",), **kw),
            jax.jit(update, static_argnames=("cfg",), **kw),
            jax.jit(delete, **kw))


_PROGRAMS = _commit_programs(None)


# ---------------------------------------------------------------------------
# write-ahead intent journal (crash consistency for the host-side publish)
# ---------------------------------------------------------------------------

#: publish steps in order; "commit" is the atomic flip, the rest are
#: host-side write-through that the journal makes redo-safe.
WRITE_STEPS = ("commit", "alloc", "ivf", "lex")

#: crash points the fault injector may fire between write steps, in order.
#: "prepare" = before the device program ran; "intent" = after the journal
#: record exists but before anything published; the rest = after that step.
CRASH_POINTS = ("prepare", "intent") + WRITE_STEPS


@dataclasses.dataclass
class IntentRecord:
    """One write's journal entry: everything needed to redo its host-side
    publish steps, plus a done-set so redo after a crash replays each step
    exactly once (the ivf/lex write-through hooks are redo-safe but not
    blindly re-runnable without double-counting churn)."""
    op: str                                   # "ingest" | "update" | "delete"
    epoch: int                                # commit_count after this write
    store: Store                              # post-write device snapshot
    state: str = "intent"                     # intent -> committed -> done
    done: set = dataclasses.field(default_factory=set)
    slot_updates: tuple = ()                  # (doc_id, slot) pairs (ingest)
    slot_removals: tuple = ()                 # doc_ids leaving the map (delete)
    free_take: int = 0                        # recycled slots consumed (ingest)
    free_add: tuple = ()                      # slots returned (delete)
    cursor_after: int | None = None           # fresh-frontier cursor (ingest)
    # sharded-arena allocator fields (ShardPlacement logs only; the legacy
    # fields above stay () / None so the two allocators never mix):
    shard_free_take: tuple = ()               # per-shard recycled counts
    shard_free_add: tuple = ()                # (shard, slot) pairs (delete)
    shard_cursors_after: tuple | None = None  # per-shard fresh frontiers
    ivf_op: tuple | None = None               # ("add", slots, emb) | ("remove", slots)
    lex_op: tuple | None = None               # (slots, terms, tfs)


# ---------------------------------------------------------------------------
# host-side commit log (slot allocation + snapshot swap + instrumentation)
# ---------------------------------------------------------------------------

class TransactionLog:
    """Owns the current store snapshot and allocates slots.

    Readers call `snapshot()` and get an immutable pytree — a consistent view
    for the whole query, regardless of concurrent commits (snapshot
    isolation). Writers go through ingest/update/delete, which measure commit
    wall-time for Table 2.
    """

    def __init__(self, cfg: StoreConfig, store: Store,
                 placement: ShardPlacement | None = None):
        self.cfg = cfg
        self._store = store
        self._cursor = 0
        self._slot_of_doc: dict[int, int] = {}
        self._free_slots: list[int] = []      # tombstoned slots, LIFO recycled
        # sharded arena: rows route to their owning shard's contiguous slot
        # region, each with its OWN fresh-frontier cursor and LIFO free list
        # (shard-local slot recycling — a freed slot can only be reused by a
        # doc that routes to the same shard, so placement never drifts).
        self.placement = placement
        shardings = placement.shardings() if placement is not None else None
        self._ingest, self._update, self._delete = (
            _PROGRAMS if shardings is None else _commit_programs(shardings))
        if placement is not None:
            if placement.capacity != cfg.capacity:
                raise ValueError("placement capacity != store capacity")
            self._shard_cursor = [placement.region(s)[0]
                                  for s in range(placement.n_shards)]
            self._shard_free: list[list[int]] = [
                [] for _ in range(placement.n_shards)]
        self.write_latencies_s: list[float] = []
        # host mirror of the device commit_ts watermark: every commit bumps
        # both, so (snapshot identity) == (commit_count value) without a
        # device sync — the result cache keys on this.
        self.commit_count = 0
        # attached IVFIndex (RagDB.build_index sets it): commits write
        # through — new rows join their nearest centroid, freed rows leave
        # the member table — so the index never serves deleted slots and
        # fresh rows are probeable without waiting for a rebuild.
        self.ivf = None
        # attached LexicalArena (RagDB wires it when built with a
        # lexical_cfg): the postings lanes are slot-aligned with this
        # arena, and every commit writes through — including EMPTY lanes
        # for batches without lexical content, so a recycled slot can never
        # serve the previous occupant's postings.
        self.lex = None
        # optional FaultPlan (serving.faults): when attached, every write
        # checks the txn.<op>.<point> crash sites between publish steps.
        self.faults = None
        # write-ahead intent journal: at most one in-flight record (writes
        # are serial); recover() consults it after a CrashError.
        self._wal: IntentRecord | None = None
        # bounded audit trail of journal outcomes for explain()/debugging.
        self.journal: list[str] = []

    # -- reads ---------------------------------------------------------
    def snapshot(self) -> Store:
        return self._store

    def slot_of(self, doc_id: int) -> int:
        return self._slot_of_doc[doc_id]

    def has_doc(self, doc_id: int) -> bool:
        return int(doc_id) in self._slot_of_doc

    # -- crash consistency ---------------------------------------------
    def _crash(self, op: str, point: str) -> None:
        """Injected crash point BETWEEN write steps (serving.faults site
        txn.<op>.<point>). The real failure this models is the process dying
        mid-publish; the chaos grid proves recover() then lands bit-identical
        to pre- or post-write state."""
        if self.faults is not None:
            self.faults.crashes(op, point)

    def _publish(self, rec: IntentRecord, *, inject: bool) -> None:
        """Run the host-side publish steps of a journaled write.

        The first step is THE commit: journal state, snapshot reference, and
        the host commit counter flip together in one uninterruptible host
        step (no crash point inside), so readers — and the result cache,
        which keys on commit_count — can never observe a new snapshot under
        an old epoch or vice versa. Every later step is guarded by the
        record's done-set, so redo after a crash replays it exactly once.
        """
        crash = self._crash if inject else (lambda op, pt: None)
        if "commit" not in rec.done:
            rec.state = "committed"
            self._store = rec.store
            self.commit_count = rec.epoch
            rec.done.add("commit")
        crash(rec.op, "commit")
        if "alloc" not in rec.done:
            if rec.free_take:
                del self._free_slots[len(self._free_slots) - rec.free_take:]
            for sh, take in enumerate(rec.shard_free_take):
                if take:
                    free = self._shard_free[sh]
                    del free[len(free) - take:]
            for d, s in rec.slot_updates:
                self._slot_of_doc[d] = s
            for d in rec.slot_removals:
                self._slot_of_doc.pop(d, None)
            if rec.free_add:
                self._free_slots.extend(rec.free_add)
            for sh, slot in rec.shard_free_add:
                self._shard_free[sh].append(slot)
            if rec.cursor_after is not None:
                self._cursor = rec.cursor_after
            if rec.shard_cursors_after is not None:
                self._shard_cursor = list(rec.shard_cursors_after)
            rec.done.add("alloc")
        crash(rec.op, "alloc")
        if "ivf" not in rec.done:
            if self.ivf is not None and rec.ivf_op is not None:
                if rec.ivf_op[0] == "add":
                    self.ivf.add_rows(rec.ivf_op[1], rec.ivf_op[2])
                else:
                    self.ivf.remove_slots(rec.ivf_op[1])
            rec.done.add("ivf")
        crash(rec.op, "ivf")
        if "lex" not in rec.done:
            if self.lex is not None and rec.lex_op is not None:
                self.lex.write_rows(*rec.lex_op)
            rec.done.add("lex")
        crash(rec.op, "lex")
        rec.state = "done"
        self._wal = None
        self._log_outcome(rec, "done")

    def _log_outcome(self, rec: IntentRecord, outcome: str) -> None:
        self.journal.append(f"{rec.op}@{rec.epoch} {outcome}")
        if len(self.journal) > 64:
            del self.journal[:-64]

    def recover(self) -> str:
        """Recover from a crash at any injected point. Returns the action:

        - ``"noop"``: no in-flight record (crash before intent, or none) —
          state is the pre-write snapshot already.
        - ``"rolled-back"``: intent journaled but commit never happened —
          discard the record; nothing was mutated, state is pre-write.
        - ``"rolled-forward"``: the commit flip happened — finish the
          remaining done-guarded publish steps with injection disabled;
          state becomes exactly the post-write state.
        """
        rec = self._wal
        if rec is None:
            return "noop"
        if rec.state == "intent":
            self._wal = None
            self._log_outcome(rec, "rolled-back")
            return "rolled-back"
        self._publish(rec, inject=False)
        self.journal[-1] = f"{rec.op}@{rec.epoch} rolled-forward"
        return "rolled-forward"

    # -- writes --------------------------------------------------------
    def _alloc_slots(self, batch: DocBatch, m: int):
        """Pick the m slots an ingest will write. Peek (don't pop) in both
        allocators: state only advances at the journaled alloc step below, so
        a failed device write leaks nothing. Returns (slot_list, the
        IntentRecord alloc fields that publish the allocation)."""
        if self.placement is None:
            n_fresh_avail = self.cfg.capacity - self._cursor
            if m > len(self._free_slots) + n_fresh_avail:
                raise RuntimeError("store arena full — grow capacity or compact")
            # recycle tombstoned slots first, then extend the fresh frontier
            n_recycled = min(m, len(self._free_slots))
            recycled = self._free_slots[len(self._free_slots) - n_recycled:][::-1]
            n_fresh = m - n_recycled
            slot_list = recycled + list(range(self._cursor, self._cursor + n_fresh))
            return slot_list, dict(free_take=n_recycled,
                                   cursor_after=self._cursor + n_fresh)
        # sharded arena: each doc routes to its owning shard's slot region
        # (hash or tenant-affine), recycling THAT shard's tombstones first
        # (LIFO), then extending that shard's fresh frontier.
        pl = self.placement
        tenants = np.asarray(batch.tenant)
        doc_ids = np.asarray(batch.doc_id)
        take = [0] * pl.n_shards
        cursors = list(self._shard_cursor)
        slot_list: list[int] = []
        for t, d in zip(tenants, doc_ids):
            sh = pl.shard_of_doc(int(t), int(d))
            free = self._shard_free[sh]
            if take[sh] < len(free):
                take[sh] += 1
                slot_list.append(free[len(free) - take[sh]])
            else:
                if cursors[sh] >= pl.region(sh)[1]:
                    raise RuntimeError(
                        f"shard {sh} region full — grow capacity or rebalance")
                slot_list.append(cursors[sh])
                cursors[sh] += 1
        return slot_list, dict(shard_free_take=tuple(take),
                               shard_cursors_after=tuple(cursors))

    def ingest(self, batch: DocBatch) -> None:
        m = batch.size
        slot_list, alloc_fields = self._alloc_slots(batch, m)
        slots = jnp.asarray(slot_list, jnp.int32)
        self._crash("ingest", "prepare")
        t0 = time.perf_counter()
        new = self._ingest(self._store, self.cfg, slots, batch.emb,
                           batch.tenant, batch.category, batch.updated_at,
                           batch.acl, batch.doc_id)
        jax.block_until_ready(new["commit_ts"])
        self.write_latencies_s.append(time.perf_counter() - t0)
        doc_ids = [int(d) for d in jax.device_get(batch.doc_id)]
        rec = IntentRecord(
            op="ingest", epoch=self.commit_count + 1, store=new,
            slot_updates=tuple(zip(doc_ids, slot_list)),
            ivf_op=(("add", slot_list, np.asarray(batch.emb))
                    if self.ivf is not None else None),
            lex_op=(slot_list,
                    None if batch.terms is None else np.asarray(batch.terms),
                    None if batch.tfs is None else np.asarray(batch.tfs)),
            **alloc_fields)
        self._wal = rec                     # write-ahead: journal the intent
        self._crash("ingest", "intent")
        self._publish(rec, inject=True)

    def update(self, doc_ids, new_emb, updated_at) -> None:
        slot_list = [self._slot_of_doc[int(d)] for d in doc_ids]
        slots = jnp.asarray(slot_list, jnp.int32)
        self._crash("update", "prepare")
        t0 = time.perf_counter()
        new = self._update(self._store, self.cfg, slots, new_emb,
                           jnp.asarray(updated_at, jnp.int32))
        jax.block_until_ready(new["commit_ts"])
        self.write_latencies_s.append(time.perf_counter() - t0)
        rec = IntentRecord(
            op="update", epoch=self.commit_count + 1, store=new,
            # re-embedded rows move to their new centroid
            ivf_op=(("add", slot_list, np.asarray(new_emb))
                    if self.ivf is not None else None))
        self._wal = rec
        self._crash("update", "intent")
        self._publish(rec, inject=True)

    def delete(self, doc_ids) -> list[int]:
        """Tombstone the given docs. Returns the freed slots (one per unique
        doc_id, in dedup order) so callers can attribute the frees without
        re-deriving the dedupe/lookup."""
        # dedupe: a repeated doc_id must not double-free its slot
        slot_list = [self._slot_of_doc[d]
                     for d in dict.fromkeys(int(d) for d in doc_ids)]
        self._crash("delete", "prepare")
        new = self._delete(self._store, jnp.asarray(slot_list, jnp.int32))
        jax.block_until_ready(new["commit_ts"])
        rec = IntentRecord(
            op="delete", epoch=self.commit_count + 1, store=new,
            slot_removals=tuple(int(d) for d in doc_ids),
            # tombstoned slots return to the allocator (free-slot recycling —
            # to their OWNING shard's list under a placement, so a recycled
            # slot is only ever reused by a doc that routes there); they leave
            # the ivf member table and drop their postings (df refunds) in
            # the ivf/lex steps.
            free_add=() if self.placement is not None else tuple(slot_list),
            shard_free_add=(tuple((self.placement.shard_of_slot(s), s)
                                  for s in slot_list)
                            if self.placement is not None else ()),
            ivf_op=("remove", slot_list),
            lex_op=(slot_list, None, None))
        self._wal = rec
        self._crash("delete", "intent")
        self._publish(rec, inject=True)
        return slot_list

    @property
    def inconsistency_window_s(self) -> float:
        """0 by construction: embedding + metadata commit in one program.

        There is no intermediate state a reader could observe — `snapshot()`
        returns either the pre-commit or post-commit pytree."""
        return 0.0
