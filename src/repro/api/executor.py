"""Physical-plan execution — the ONLY module that issues retrieval device
calls for the front-door API (and, via shims, for TieredRouter and
RAGEngine). Centralizing the dispatch is what makes the headline behaviors
enforceable and testable:

  * predicate-group batching: a batch of B concurrent queries is grouped by
    `PhysicalPlan.group_key` (predicate, k, engine, route) and each group
    runs as ONE device program over the stacked query rows — B requests with
    G unique predicate groups cost G device calls, not B;
  * grouped-scan fusion: exact-engine groups sharing a `fuse_key` (same k,
    engine, route) collapse further into ONE grouped arena scan that
    streams the arena once for ALL of them — `rows_scanned` drops from G*N
    to N and G compiled programs become 1 (planner.fuse_batch decides,
    `ExecStats.fused_groups / fused_scans` audit);
  * async dispatch: every group's hot-tier device program (fused or not) is
    launched before the FIRST `device_get`, and warm-tier probes are issued
    while the hot scans are in flight — the per-group
    launch->sync->launch->sync ladder is gone;
  * bucketed batching: each dispatch unit's row count is padded up to a
    power-of-two bucket (`plan.bucket_rows`) so every batch size in a bucket
    reuses ONE compiled program shape instead of recompiling per distinct
    size; the resident shape working set is tracked by a small
    `CompiledShapes` LRU whose hit/miss counters surface in `RagDB.explain()`;
  * tier merge: "hot+warm" plans probe the warm similarity tier and merge
    the two k-lists host-side, exactly as TieredRouter.query always did.

Tests count calls by monkeypatching `executor.unified_query` (per-group
scans) and `executor.unified_query_grouped` (fused scans).
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.plan import PhysicalPlan, bucket_rows
from repro.obs.tracer import FanSpan
from repro.core.query import (BLOCK_ALL, Predicate, stack_predicates,
                              unified_query, unified_query_grouped)
from repro.core.store import Store
from repro.core.ivf import ivf_probe
from repro.kernels.arena_scan.ops import arena_scan, default_blk_b
from repro.kernels.arena_scan.stages import ScanSpec, hybrid_spec

#: tier tags in the returned `tiers` array
TIER_HOT = 0
TIER_WARM = 1

#: Launch families that run the arena-scan kernel on a TPU (all compile as
#: ``jit__run``). The kernel streams the arena once per query-row block of
#: `default_blk_b` rows, so a launch of ``bucket`` rows makes
#: ceil(bucket / blk_b) passes: one for a dense bucket up to 128 rows.
SCAN_FAMILIES = ("filtered", "grouped", "hybrid")


def _family(engine: str, fused: bool) -> str:
    """A launch's family: ``filtered`` or ``grouped`` (the exact Pallas
    engine, one group or fused), else the engine (``hybrid``, ``ivf``,
    ``sharded``, ``ref``)."""
    if engine == "pallas":
        return "grouped" if fused else "filtered"
    return engine


@dataclasses.dataclass
class ExecStats:
    """Per-RagDB execution counters (device work only — result-cache hits
    never reach the executor and are counted by `ResultCache` itself)."""
    device_calls: int = 0         # retrieval programs launched on-device
    queries: int = 0              # logical queries answered
    hot_queries: int = 0
    warm_queries: int = 0
    padded_rows: int = 0          # bucket-padding rows added across calls
    rows_scanned: int = 0         # hot-tier arena rows scored across calls:
                                  # arena N per exact scan (ONCE per fused
                                  # grouped scan, not once per group),
                                  # candidate rows per ivf probe — the
                                  # auditable savings
    fused_groups: int = 0         # predicate groups answered by fused scans
    fused_scans: int = 0          # fused grouped-scan programs launched
    padded_groups: int = 0        # BLOCK_ALL blocker lanes launched for pow2
                                  # group padding (k=0 semantics: asserted
                                  # to allocate no result rows)
    terms_scanned: int = 0        # postings lanes streamed by hybrid scans
                                  # (N * doc_terms per one-pass scan) — the
                                  # lexical bandwidth audit trail
    lex_bucket_joins: int = 0     # hybrid launches that joined groups of two
                                  # or more query-term buckets, their terms
                                  # padded to the largest (planner.fuse_batch)
    paged_scans: int = 0          # hot-tier programs launched in the paged
                                  # arena-scan regime (plan.page_rows set):
                                  # the memory-traffic audit — bits are
                                  # identical to resident, only the DMA
                                  # schedule differs
    degraded_plans: int = 0       # plans executed with a non-empty
                                  # degradation ladder (planner.degrade_plan)
                                  # — the serving-pressure audit trail
    stale_serves: int = 0         # cache results served PAST their snapshot
                                  # under a declared staleness bound
                                  # (RagDB.execute stale_within_s); never
                                  # incremented by exact-key hits
    warm_failovers: int = 0       # hot+warm plans served hot-only because the
                                  # guarded warm probe gave up (retries
                                  # exhausted or breaker open) — every one
                                  # carries an explicit degraded annotation
    stale_epoch_rejected: int = 0 # poisoned cache reads refused because the
                                  # entry's commit-epoch key no longer matches
                                  # the live snapshot (chaos site cache.stale)
    shards_used: int = 0          # mesh shard count S of the sharded engine's
                                  # programs (0 = never dispatched sharded)
    collective_bytes: int = 0     # cross-device wire bytes moved by sharded
                                  # launches, accumulated from the compiled
                                  # HLO's collective ops — the O(S*B*k)
                                  # merge-payload audit (constant in arena N)
    shard_rows_scanned: list = dataclasses.field(default_factory=list)
                                  # per-shard rows scored by sharded launches
                                  # (index = shard id). Under tenant-affine
                                  # placement a tenant-scoped query credits
                                  # ONLY its owning shard — the structural-
                                  # skip audit explain() surfaces.


class CompiledShapes:
    """Small LRU tracking the resident compiled retrieval-program shapes.

    A shape is ``(engine, bucket_rows, k)`` — fused grouped scans append
    their pow2-padded group count (the (G, 4) predicate block is part of
    the program shape), and hybrid scans additionally their score-mix
    identity (fusion mode + the launch's query-term bucket + weights, which
    bake into the compiled program). Paged launches key on their page size
    too: paged and resident regimes compile different programs (different grid
    + DMA schedule), and sharded launches on their mesh shard count (the
    merge gathers S*k candidates — an S-dependent shape). Bucketed batching guarantees that any group whose
    shape is in this set reuses the already-compiled program (XLA caches by
    shape). `touch()` returns True on a hit and records the miss otherwise;
    evicting past ``cap`` models a bounded compile cache, so a shape falling
    out of the working set is reported as a recompile when it returns.

    >>> shapes = CompiledShapes(cap=2)
    >>> shapes.touch("ref", 8, 5)          # first sight: miss
    False
    >>> shapes.touch("ref", 8, 5)          # resident: hit
    True
    >>> shapes.touch("ref", 16, 5), shapes.touch("ref", 32, 5)  # evicts (8, 5)
    (False, False)
    >>> shapes.touch("ref", 8, 5)
    False
    >>> (shapes.hits, shapes.misses)
    (1, 4)
    """

    def __init__(self, cap: int = 32):
        self.cap = cap
        self._lru: OrderedDict[tuple, None] = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._lru)

    def touch(self, engine: str, bucket: int, k: int,
              groups: int | None = None, lex=None,
              page_rows: int | None = None,
              shards: int | None = None) -> bool:
        key = (engine, bucket, k, groups, lex, page_rows, shards)
        if key in self._lru:
            self.hits += 1
            self._lru.move_to_end(key)
            return True
        self.misses += 1
        self._lru[key] = None
        while len(self._lru) > self.cap:
            self._lru.popitem(last=False)
        return False


def _pad_rows(q: np.ndarray, bucket: int) -> np.ndarray:
    """Pad a (B, D) block with zero rows up to ``bucket`` rows (B <= bucket).
    Retrieval is row-parallel, so padding rows cannot perturb real rows —
    verified bit-exact in tests/test_adaptive.py."""
    if q.shape[0] == bucket:
        return q
    return np.concatenate(
        [q, np.zeros((bucket - q.shape[0], q.shape[1]), q.dtype)], axis=0)


@dataclasses.dataclass(frozen=True)
class ShardedHandle:
    """Compiled sharded-engine entry the RagDB caches per (k, n_rows,
    placement): the shard-mapped program plus the static facts the stats
    audit needs. ``fn(store, q, pred) -> (scores (B,k), slots (B,k),
    rows_scanned (S,))`` — see kernels/arena_scan/sharded.py.
    ``collective_bytes`` is measured ONCE from the compiled HLO
    (`sharded_collective_bytes`), not re-lowered per launch."""
    fn: object
    n_shards: int
    collective_bytes: int
    placement: str = "hash"


@dataclasses.dataclass
class _Hot:
    """One in-flight hot-tier device program: launched, NOT yet synced.
    ``rescan`` carries the ivf completeness-net context so the under-fill
    check (which must read results) happens at finish time, after every
    other launch went out. ``pad_check`` is the real row count of a fused
    grouped launch whose padding rows point at a BLOCK_ALL blocker lane:
    finish asserts those rows allocated no result rows (k=0 semantics).
    ``extra`` carries the second per-signal list of an unfused-rrf hybrid
    launch (synced into ``extra_np`` at finish). ``shard_rows`` is the
    sharded engine's per-shard rows-scanned audit vector (a device future
    until finish, a numpy (S,) after), with ``shard_meta`` carrying the
    (n_shards, collective_bytes) facts of the launching handle."""
    s: jax.Array
    sl: jax.Array
    rows: int                     # arena rows this program scored
    rescan: tuple | None = None   # (store, q, pred, k, exact_engine, nv, ivf)
    pad_check: int | None = None  # first padded (blocker-lane) row index
    extra: tuple | None = None    # (lex_s, lex_i) futures (hybrid rrf lists)
    extra_np: tuple | None = None # synced extra
    shard_rows: object = None     # (S,) per-shard rows scanned (sharded only)
    shard_meta: tuple | None = None  # (n_shards, collective_bytes)
    launch_ms: float = 0.0        # host-side dispatch cost (perf_counter)
    sync_ms: float = 0.0          # finish-time device_get wait (+ rescans)
    terms: int = 0                # postings lanes this program streamed
                                  # (hybrid only) — the calibration audit's
                                  # per-unit twin of stats.terms_scanned
    unit: int | None = None       # dispatch unit's sequence number (tracing
                                  # only: `Tracer.next_unit`)


def _launch_hot(store: Store, q: jax.Array, pred: Predicate, k: int,
                engine: str, sharded_fn=None, ivf=None, nprobe=None,
                n_valid: int | None = None, skip_rescan: bool = False,
                page_rows: int | None = None) -> _Hot:
    """Launch one retrieval device program WITHOUT syncing on its result
    (jax dispatch is async: the arrays are futures until device_get).

    `sharded_fn` is the RagDB's cached `ShardedHandle` (or a bare 2-output
    callable, the legacy contract without the per-shard audit) when engine
    == 'sharded'; `ivf`/`nprobe` are the IVFIndex and probe depth when engine
    == 'ivf'; `n_valid` is the real row count when q is bucket-padded (the
    probe union must come from real rows — zero padding rows would drag
    arbitrary clusters into the union). ``skip_rescan`` waives the ivf
    completeness net: degraded plans set it, because their contract is
    already "recall narrows" — an under-filled k-list IS the degraded
    answer, and paying a full-arena exact rescan on top of the shallow
    probe would make every rung BELOW the default nprobe cost MORE than
    the undegraded plan (the ladder would be a cost inversion, not a
    shed)."""
    n_arena = store["emb"].shape[0]
    if engine == "sharded":
        if sharded_fn is None:
            raise ValueError("engine='sharded' requires a mesh-built RagDB")
        if isinstance(sharded_fn, ShardedHandle):
            s, sl, rows_vec = sharded_fn.fn(store, q, pred.as_array())
            return _Hot(s, sl, n_arena, shard_rows=rows_vec,
                        shard_meta=(sharded_fn.n_shards,
                                    sharded_fn.collective_bytes))
        # bare callable (legacy 2-output contract): no per-shard audit
        s, sl = sharded_fn(store, q, pred.as_array())
        return _Hot(s, sl, n_arena)
    if engine == "ivf":
        if ivf is None:
            raise ValueError("engine='ivf' requires a built index — "
                             "call RagDB.build_index() first")
        nv = q.shape[0] if n_valid is None else n_valid
        exact = "pallas" if jax.default_backend() == "tpu" else "ref"
        if (pred, k) in ivf.starved:
            # learned: the WHOLE arena can't fill k for this predicate —
            # probing first would be pure waste (memo clears on any write)
            s, sl = unified_query(store, q, pred, k, engine=exact,
                                  page_rows=page_rows)
            return _Hot(s, sl, n_arena)
        clusters, _, rows = ivf.probe(np.asarray(q[:nv]),
                                      nprobe or ivf.cfg.nprobe)
        dev = ivf.device_arrays()
        s, sl = ivf_probe(q, store, dev["members"], dev["overflow"],
                          clusters, pred.as_array(), k)
        rescan = None if skip_rescan else (store, q, pred, k, exact, nv, ivf)
        return _Hot(s, sl, rows, rescan=rescan)
    s, sl = unified_query(store, q, pred, k, engine=engine,
                          page_rows=page_rows)
    return _Hot(s, sl, n_arena)


def _finish_hot(hot: _Hot, trace_fan=None) -> tuple[np.ndarray, np.ndarray]:
    """Sync one launched program. The ivf completeness net runs HERE: a
    pruned scan can under-fill the k-list when qualifying rows sit outside
    the probed clusters (e.g. a tight recency bound, or a forced
    .using("ivf") on a selective predicate). An under-filled row falls back
    to ONE exact rescan — completeness beats speed, and the extra arena
    scan shows up in `hot.rows` so the audit trail stays honest.

    ``trace_fan`` (member request traces, tracer-enabled path only) nests
    a ``rescan`` span under the caller's open ``device_sync`` span exactly
    when the completeness net fires."""
    s, sl = jax.device_get((hot.s, hot.sl))
    if hot.shard_rows is not None:
        # sharded: the per-shard audit vector replaces the whole-arena row
        # count — under the tenant-affine gate only the owning shard scans,
        # and rows_scanned must reflect the rows actually scored
        hot.shard_rows = np.asarray(jax.device_get(hot.shard_rows))
        hot.rows = int(hot.shard_rows.sum())
    if hot.extra is not None:
        hot.extra_np = tuple(np.asarray(a) for a in jax.device_get(hot.extra))
        if hot.pad_check is not None:
            assert (hot.extra_np[1][hot.pad_check:] == -1).all(), (
                "blocker-lane padding rows allocated result rows (lex list)")
    if hot.pad_check is not None and sl.shape[0] > hot.pad_check:
        # padded rows point at a BLOCK_ALL blocker lane: their k-lists must
        # be empty — a hit here means a padding lane allocated result rows
        assert (sl[hot.pad_check:] == -1).all(), (
            "blocker-lane padding rows allocated result rows")
    if hot.rescan is not None:
        store, q, pred, k, exact, nv, ivf = hot.rescan
        if bool((sl[:nv] < 0).any()):
            fan = (FanSpan(trace_fan, "rescan", engine=exact, unit=hot.unit)
                   if trace_fan is not None else None)
            s, sl = unified_query(store, q, pred, k, engine=exact)
            s, sl = jax.device_get((s, sl))
            if bool((sl[:nv] < 0).any()):
                ivf.starved.add((pred, k))
            hot.rows += store["emb"].shape[0]
            if fan is not None:
                fan.end(rows=store["emb"].shape[0])
    return s, sl


def _note_sharded(stats: ExecStats | None, hot: _Hot) -> None:
    """Credit one finished sharded launch to the stats: shard count, the
    compiled program's collective wire bytes, and the per-shard rows-scanned
    vector (extended if a later mesh is wider)."""
    if stats is None or hot.shard_meta is None:
        return
    n_shards, cbytes = hot.shard_meta
    stats.shards_used = max(stats.shards_used, n_shards)
    stats.collective_bytes += cbytes
    if hot.shard_rows is not None:
        rows = [int(r) for r in hot.shard_rows]
        if len(stats.shard_rows_scanned) < len(rows):
            stats.shard_rows_scanned.extend(
                [0] * (len(rows) - len(stats.shard_rows_scanned)))
        for i, r in enumerate(rows):
            stats.shard_rows_scanned[i] += r


def _dispatch(store: Store, q: jax.Array, pred: Predicate, k: int,
              engine: str, sharded_fn=None, ivf=None, nprobe=None,
              n_valid: int | None = None, page_rows: int | None = None,
              stats: ExecStats | None = None):
    """One retrieval device program, launched and synced. Returns
    (scores, slots, rows_scanned) where rows_scanned is the arena rows this
    program scored — the full arena for the exact engines, the probed
    candidate set (plus any completeness rescan) for ivf, the per-shard sum
    for sharded (whose shard-level audit lands in ``stats`` directly)."""
    hot = _launch_hot(store, q, pred, k, engine, sharded_fn, ivf, nprobe,
                      n_valid, page_rows=page_rows)
    s, sl = _finish_hot(hot)
    _note_sharded(stats, hot)
    return s, sl, hot.rows


def _pad_group_launch(q: np.ndarray, gids: np.ndarray,
                      preds: list[Predicate], k: int, engine: str, *,
                      stats: ExecStats | None,
                      shapes: CompiledShapes | None, lex=None,
                      page_rows: int | None = None,
                      groups_per_row: bool = False):
    """Shared bucket/blocker padding for fused grouped launches.

    Pads the predicate stack to a pow2 group count with `BLOCK_ALL` rows
    and (when ``shapes`` tracks program-shape reuse) the query rows to
    their pow2 bucket. Padding query rows point at a BLOCKER lane — never
    a real group — so a padded lane carries k=0 semantics: it can match no
    row, allocates no result rows (asserted via `_Hot.pad_check` at
    finish), and cannot waste a real group's predicate on dead queries.
    When row padding is needed and every lane is real, one extra blocker
    bucket is opened to hold the padding rows. ``groups_per_row`` (hybrid)
    pads the groups to the row bucket itself, which always has room for
    the blocker (a group holds at least one real row): one program per row
    bucket instead of one per (rows, groups) bucket pair. The group select
    is an exact 0/1 one-hot, so blocker lanes change no bits. ``lex`` is a
    hybrid launch's (mode, launched query-term bucket, weights), the shape
    key of the program that runs.

    Returns (q, gids, preds, n_valid) with every array launch-ready."""
    n_valid = q.shape[0]
    g_real = len(preds)
    bucket = bucket_rows(n_valid) if shapes is not None else n_valid
    g_bucket = bucket_rows(g_real)
    if groups_per_row:
        g_bucket = bucket_rows(bucket)
    elif bucket > n_valid and g_bucket == g_real:
        g_bucket = bucket_rows(g_real + 1)   # open a lane for the blocker
    preds = list(preds) + [BLOCK_ALL] * (g_bucket - g_real)
    if stats is not None:
        stats.padded_groups += g_bucket - g_real
    if shapes is not None:
        shapes.touch(engine, bucket, k, groups=g_bucket, lex=lex,
                     page_rows=page_rows)
        if stats is not None:
            stats.padded_rows += bucket - n_valid
        q = _pad_rows(q, bucket)
        gids = np.concatenate(
            [gids, np.full(bucket - n_valid, g_real, np.int32)])
    return q, gids, preds, n_valid


def _launch_grouped(store: Store, q: np.ndarray, gids: np.ndarray,
                    preds: list[Predicate], k: int, engine: str, *,
                    stats: ExecStats | None = None,
                    shapes: CompiledShapes | None = None,
                    page_rows: int | None = None) -> _Hot:
    """Launch ONE fused grouped scan answering every predicate group in
    ``preds``. Pads query rows to their pow2 bucket (pointed at a blocker
    lane — sliced off AND asserted empty) and the predicate stack to a
    pow2 group count with `BLOCK_ALL` rows, so a varying group mix reuses
    a small set of compiled shapes."""
    q, gids, preds, n_valid = _pad_group_launch(
        q, gids, preds, k, engine, stats=stats, shapes=shapes,
        page_rows=page_rows)
    s, sl = unified_query_grouped(store, jnp.asarray(q), jnp.asarray(gids),
                                  stack_predicates(preds), k, engine=engine,
                                  page_rows=page_rows)
    return _Hot(s, sl, store["emb"].shape[0], pad_check=n_valid)


def _launch_hybrid(store: Store, lex_snap: dict, q: np.ndarray,
                   gids: np.ndarray, preds: list[Predicate],
                   qterms: np.ndarray, k: int, *, mode: str,
                   w_dense: float, w_lex: float, rrf_c: float,
                   lists: bool = False,
                   stats: ExecStats | None = None,
                   shapes: CompiledShapes | None = None,
                   lex_key=None, page_rows: int | None = None) -> _Hot:
    """Launch ONE fused hybrid dense+BM25 scan answering every predicate
    group in ``preds`` — the hybrid engine's only dispatch shape (a single
    group is G=1). ``lex_snap`` is `LexicalArena.snapshot()`; ``qterms``
    is (B, QT) int32 per-row query terms, padded to the launch's QT: the
    largest query-term bucket of its member groups (``lex_key`` names it).
    A padding term adds exactly +0.0 to a row's BM25, so every row scores
    the bits it would at its own bucket. ``lists=True`` (rrf + tiered
    route) keeps the two per-signal lists unfused: dense rides
    `_Hot.s/.sl`, bm25 rides `_Hot.extra`, and the finish phase rank-fuses
    after the tier merges."""
    q, gids, preds, n_valid = _pad_group_launch(
        q, gids, preds, k, "hybrid", stats=stats, shapes=shapes, lex=lex_key,
        page_rows=page_rows, groups_per_row=True)
    if q.shape[0] != qterms.shape[0]:
        qterms = np.concatenate(
            [qterms, np.full((q.shape[0] - qterms.shape[0], qterms.shape[1]),
                             -1, np.int32)])
    out = arena_scan(jnp.asarray(q), store["emb"], store["tenant"],
                     store["updated_at"], store["category"], store["acl"],
                     jnp.asarray(gids), stack_predicates(preds), k,
                     spec=hybrid_spec(mode), terms=lex_snap["terms"],
                     lexnorm=lex_snap["lexnorm"], idf=lex_snap["idf"],
                     qterms=jnp.asarray(qterms), w_dense=w_dense,
                     w_lex=w_lex, rrf_c=rrf_c, lists=lists,
                     page_rows=page_rows)
    n_arena = store["emb"].shape[0]
    terms = n_arena * int(lex_snap["terms"].shape[1])
    if stats is not None:
        stats.terms_scanned += terms
    if lists:
        d_s, d_i, l_s, l_i = out
        return _Hot(d_s, d_i, n_arena, pad_check=n_valid,
                    extra=(l_s, l_i), terms=terms)
    s, sl = out
    return _Hot(s, sl, n_arena, pad_check=n_valid, terms=terms)


def run_grouped(store: Store, q: np.ndarray, preds: list[Predicate], k: int,
                engine: str = "ref", *, sharded_fn=None, ivf=None,
                nprobe=None, stats: ExecStats | None = None,
                shapes: CompiledShapes | None = None,
                page_rows: int | None = None):
    """Predicate-group batched retrieval over one store — the per-group
    LOOP: one device call per unique predicate, each streaming the arena.

    q: (B, D) host array, preds: B predicates (one per row). Rows sharing a
    predicate are stacked and answered by one device call; with ``shapes``
    given, each group is padded to its power-of-two bucket so the device
    program shape is reused across batch sizes. Returns
    (scores (B, k) f32, slots (B, k) i32, n_device_calls).

    `run_grouped_fused` is the scan-once alternative for exact engines.
    """
    B = q.shape[0]
    groups: dict[Predicate, list[int]] = {}
    for i, p in enumerate(preds):
        groups.setdefault(p, []).append(i)
    scores = np.full((B, k), np.float32(np.finfo(np.float32).min), np.float32)
    slots = np.full((B, k), -1, np.int32)
    for pred, idxs in groups.items():
        q_g = np.asarray(q[np.asarray(idxs)], np.float32)
        n_valid = q_g.shape[0]
        if shapes is not None:
            bucket = bucket_rows(n_valid)
            shapes.touch(engine, bucket, k, page_rows=page_rows)
            if stats is not None:
                stats.padded_rows += bucket - n_valid
            q_g = _pad_rows(q_g, bucket)
        s, sl, rows = _dispatch(store, jnp.asarray(q_g), pred, k, engine,
                                sharded_fn, ivf, nprobe, n_valid,
                                page_rows=page_rows, stats=stats)
        s, sl = np.asarray(s), np.asarray(sl)
        scores[idxs], slots[idxs] = s[:n_valid], sl[:n_valid]
        if stats is not None:
            stats.rows_scanned += rows
    if stats is not None:
        stats.device_calls += len(groups)
        stats.queries += B
        stats.hot_queries += B
    return scores, slots, len(groups)


def run_grouped_fused(store: Store, q: np.ndarray, preds: list[Predicate],
                      k: int, engine: str = "ref", *,
                      stats: ExecStats | None = None,
                      shapes: CompiledShapes | None = None,
                      page_rows: int | None = None):
    """Scan-once counterpart of `run_grouped` for the exact engines: the G
    unique predicates stack into one (G, 4) block and ONE fused
    grouped arena scan answers every row — `rows_scanned` is the arena
    N, not G*N. Same contract and return shape as `run_grouped`
    (n_device_calls is always 1)."""
    B = q.shape[0]
    uniq: dict[Predicate, int] = {}
    for p in preds:
        if p not in uniq:
            uniq[p] = len(uniq)
    gids = np.asarray([uniq[p] for p in preds], np.int32)
    hot = _launch_grouped(store, np.asarray(q, np.float32), gids,
                          list(uniq), k, engine, stats=stats, shapes=shapes,
                          page_rows=page_rows)
    s, sl = _finish_hot(hot)
    if stats is not None:
        stats.device_calls += 1
        stats.queries += B
        stats.hot_queries += B
        stats.rows_scanned += hot.rows
        stats.fused_groups += len(uniq)
        stats.fused_scans += 1
    return np.asarray(s)[:B], np.asarray(sl)[:B], 1


def merge_tiers(hs, hi, ws, wi, k: int):
    """Merge hot and warm k-lists into the global top-k (host-side).

    On every hot+warm query's critical path, so the selection is
    argpartition (O(m)) + a small sort of the k winners, not a full
    argsort of the concatenated 2k-wide lists; ties break toward the
    lowest concatenated column (hot before warm), deterministically — also
    AT the k boundary, where raw argpartition would split tied scores
    arbitrarily (the partition only bounds the kth value; the selection
    among columns tied at that value is re-derived in column order).

    >>> import numpy as np
    >>> hs = np.array([[3.0, 1.0]]); hi = np.array([[7, 5]])
    >>> ws = np.array([[2.0, 0.5]]); wi = np.array([[9, 4]])
    >>> s, i, t = merge_tiers(hs, hi, ws, wi, k=3)
    >>> i.tolist(), t.tolist()
    ([[7, 9, 5]], [[0, 1, 0]])
    """
    scores = np.concatenate([hs, ws], axis=1)
    slots = np.concatenate([hi, wi], axis=1)
    tiers = np.concatenate([np.full_like(hi, TIER_HOT),
                            np.full_like(wi, TIER_WARM)], axis=1)
    m = scores.shape[1]
    if k < m:
        # the partition only fixes the kth VALUE; select deterministically:
        # every column strictly above it, then lowest columns tied at it
        kth = np.take_along_axis(
            scores, np.argpartition(-scores, k - 1, axis=1)[:, k - 1:k],
            axis=1)                                        # (B, 1)
        gt = scores > kth
        eq = scores == kth
        n_eq = k - gt.sum(axis=1, keepdims=True)
        sel = gt | (eq & (np.cumsum(eq, axis=1) <= n_eq))
        cols = np.nonzero(sel)[1].reshape(scores.shape[0], k)  # ascending
        order = np.take_along_axis(
            cols, np.argsort(-np.take_along_axis(scores, cols, axis=1),
                             axis=1, kind="stable"), axis=1)
    else:
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    gather = lambda a: np.take_along_axis(a, order, axis=1)
    return gather(scores), gather(slots), gather(tiers)


def _rrf_merge_np(ds, di, dt, ls, li, lt, k: int, c: float):
    """Host-side reciprocal-rank fusion of two TIER-MERGED per-signal
    k-lists (numpy twin of kernels.arena_scan.ref.rrf_fuse, with tier
    tags carried through): candidates are identified by (slot, tier) — the
    hot and warm tiers are separate arenas, so a bare slot number is
    ambiguous across the merge. A candidate in both lists is represented
    by its dense-list copy; ties break dense-first then rank order,
    deterministically (stable argsort over the [dense | lex] concat)."""
    neg = np.float32(np.finfo(np.float32).min)
    kd, kl = di.shape[1], li.shape[1]
    rd = (1.0 / (c + np.arange(1, kd + 1))).astype(np.float32)
    rl = (1.0 / (c + np.arange(1, kl + 1))).astype(np.float32)
    d_valid = di >= 0
    l_valid = li >= 0
    cross = ((di[:, :, None] == li[:, None, :])
             & (dt[:, :, None] == lt[:, None, :])
             & d_valid[:, :, None] & l_valid[:, None, :])
    d_score = (np.where(d_valid, rd[None, :], neg)
               + (cross * rl[None, None, :]).sum(axis=2, dtype=np.float32))
    in_dense = cross.any(axis=1)
    l_score = np.where(l_valid & ~in_dense, rl[None, :], neg)
    all_s = np.concatenate([d_score, l_score], axis=1)
    all_i = np.concatenate([di, li], axis=1)
    all_t = np.concatenate([dt, lt], axis=1)
    order = np.argsort(-all_s, axis=1, kind="stable")[:, :k]
    gather = lambda a: np.take_along_axis(a, order, axis=1)
    s, sl, tr = gather(all_s), gather(all_i), gather(all_t)
    live = s > neg
    return (np.where(live, s, neg), np.where(live, sl, -1),
            np.where(live, tr, TIER_HOT))


def query_tiered(hot_store: Store, warm, q: jax.Array, pred: Predicate,
                 k: int, *, engine: str = "ref", probe_warm: bool = False,
                 sharded_fn=None, ivf=None, nprobe=None,
                 stats: ExecStats | None = None,
                 n_valid: int | None = None,
                 page_rows: int | None = None):
    """Single-predicate tiered retrieval (TieredRouter.query's engine room).

    The hot device program is LAUNCHED first and synced last: the warm probe
    (its own host/device round trip) runs while the hot scan is in flight,
    so the two tiers overlap instead of serializing.

    ``n_valid`` is the count of real query rows when the caller padded q to
    a bucket — only the hot device dispatch needs the bucketed shape; stats
    count logical queries, and the warm probe sees the UNPADDED rows (a
    padding row's probe is pure waste). Returns (scores, slots, tiers)
    numpy arrays of q's full row count without a warm probe, and of
    ``n_valid`` rows with one; callers slice ``[:n_valid]``, which is exact
    either way."""
    n_logical = q.shape[0] if n_valid is None else n_valid
    hot = _launch_hot(hot_store, q, pred, k, engine, sharded_fn, ivf, nprobe,
                      n_logical, page_rows=page_rows)
    ws = wi = None
    warm_calls = 0
    if probe_warm:
        # the warm client's round trips are device programs too — count
        # them, or device_calls would under-report exactly when the
        # expensive route runs. The lowered predicate is PUSHED DOWN into
        # the warm store: it filters server-side inside the scan instead of
        # post-filtering host-side, so the probe is one round trip with no
        # under-fill retries.
        rt0 = warm.stats.round_trips
        ws, wi = warm.query(q[:n_logical], pred, k, pushdown=True)
        warm_calls = warm.stats.round_trips - rt0
    hs, hi = _finish_hot(hot)
    _note_sharded(stats, hot)
    if stats is not None:
        stats.device_calls += 1 + warm_calls
        stats.queries += n_logical
        stats.hot_queries += n_logical
        stats.rows_scanned += hot.rows
        if probe_warm:
            stats.warm_queries += n_logical
    if not probe_warm:
        return hs, hi, np.full_like(hi, TIER_HOT)
    return merge_tiers(hs[:n_logical], hi[:n_logical], ws, wi, k)


def _qterms_rows(row_plans, idxs, qt_bucket: int) -> np.ndarray:
    """Per-row query-term matrix for a hybrid dispatch: row i's plan
    supplies its lowered match() ids, padded with -1 to ``qt_bucket`` — a
    launch's largest member bucket, or a warm probe's own bucket — so every
    row fits."""
    qt = np.full((len(idxs), qt_bucket), -1, np.int32)
    for r, i in enumerate(idxs):
        t = row_plans[i].logical.match_terms or ()
        qt[r, :len(t)] = t
    return qt


@dataclasses.dataclass
class InFlightPlans:
    """A launched-but-unsynced `launch_plans` batch: every hot device
    program is in flight and every warm probe has been issued, but no
    `device_get` has happened. `finish_plans` consumes it. The serving
    scheduler pipelines by holding several of these at once — batch N+1's
    hot scans launch while batch N's results are still on the device."""
    inflight: list               # (FusedGroup, member row-index lists, _Hot)
    warm_results: list           # per unit: list of probe tuples (an entry is
                                 # None when the guarded probe gave up), or
                                 # None for hot-route units
    B: int                       # total query rows across plans
    k: int
    stats: "ExecStats | None"
    lex: object                  # hot-tier LexicalArena (rrf merge needs it)
    warm_failed: set = dataclasses.field(default_factory=set)
                                 # group_keys whose warm probe failed over to
                                 # hot-only (RagDB.finish stamps the explicit
                                 # degraded annotation and skips the cache)
    row_traces: list | None = None   # per query row: the owning request's
                                 # obs.Trace (tracer-enabled path only) —
                                 # finish_plans records device_sync/rescan/
                                 # merge spans into these across the async
                                 # launch/finish boundary
    calib: object = None         # obs.CalibrationTable (always-on audit):
                                 # finish_plans records one predicted-vs-
                                 # measured row per dispatch unit


def execute_plans(hot_store: Store, warm, plans: list[PhysicalPlan], *,
                  sharded_fn=None, stats: ExecStats | None = None,
                  shapes: CompiledShapes | None = None, index=None,
                  planner_cfg=None, lex=None):
    """Batched execution of compiled plans, in three async phases:

      1. LAUNCH — group plans by `group_key`, hand the distinct groups to
         `planner.fuse_batch` (exact-engine groups sharing a fuse key
         collapse into one grouped scan; hybrid groups sharing a score mix
         collapse into one fused dense+BM25 scan), and launch EVERY
         dispatch unit's hot device program without syncing;
      2. WARM — with all hot scans in flight, issue the warm-tier probes
         for every 'hot+warm' group (per member predicate, pushed down —
         hybrid groups push the lexical clause down too);
      3. FINISH — first `device_get` happens here: sync each unit, run any
         ivf completeness rescans, merge tiers (rrf-mode hybrid merges per
         SIGNAL across tiers, then rank-fuses), scatter into row order.

    ``index`` is the RagDB's IVFIndex, consumed by groups whose plan chose
    engine 'ivf'; ``lex`` the RagDB's hot-tier `LexicalArena`, consumed by
    engine-'hybrid' groups; ``planner_cfg`` supplies the fusion rule's
    knobs and cost model (None = planner defaults, fusion on at >= 2
    groups).

    Every plan must carry its query rows (`logical.q`, shape (B_i, D)).
    Returns (scores (B, k), slots (B, k), tiers (B, k)) with B = total query
    rows across plans, in plan order. All plans must share one k.

    Phases 1+2 are exposed standalone as `launch_plans` (returns an
    `InFlightPlans`) and phase 3 as `finish_plans` — the serving
    scheduler's pipelined batching uses the split directly.
    """
    return finish_plans(launch_plans(
        hot_store, warm, plans, sharded_fn=sharded_fn, stats=stats,
        shapes=shapes, index=index, planner_cfg=planner_cfg, lex=lex))


def launch_plans(hot_store: Store, warm, plans: list[PhysicalPlan], *,
                 sharded_fn=None, stats: ExecStats | None = None,
                 shapes: CompiledShapes | None = None, index=None,
                 planner_cfg=None, lex=None, warm_guard=None,
                 obs=None, tracer=None, calib=None) -> InFlightPlans:
    """Phases 1+2 of `execute_plans` (see there): launch every hot device
    program and issue every warm probe WITHOUT a single device_get, and
    return the in-flight handle `finish_plans` syncs.

    ``warm_guard`` (serving.faults.WarmGuard, optional) wraps each warm
    probe with timeout / bounded retry / hedge / circuit breaker; when the
    guard gives up, that group fails over to hot-only serving (its probe
    entry is None and its group_key lands in `InFlightPlans.warm_failed`)
    instead of propagating the warm tier's failure.

    ``obs`` (one obs.Trace per plan, aligned to ``plans``) threads the
    span-tree instrumentation through: every dispatch unit records a
    ``launch`` span and every warm round trip a ``warm_probe`` span into
    each member request's trace (batch-shared work is measured ONCE and
    fanned out). ``tracer`` supplies the active-sink stack warm-tier
    faults and WarmGuard decisions annotate through; ``calib`` (the
    RagDB's CalibrationTable) is carried to finish_plans, which records
    the per-unit predicted-vs-measured audit. All three default to None —
    the uninstrumented path is unchanged."""
    from repro.api.planner import PlannerConfig, fuse_batch

    ks = {p.logical.k for p in plans}
    if len(ks) != 1:
        raise ValueError(f"batched execution needs a single k, got {sorted(ks)}")
    k = ks.pop()
    if stats is not None:
        stats.degraded_plans += sum(1 for p in plans if p.degraded)

    # flatten plan -> row spans
    row_plans: list[PhysicalPlan] = []
    qs: list[np.ndarray] = []
    for p in plans:
        if p.logical.q is None:
            raise ValueError("plan carries no query embedding")
        q = np.atleast_2d(np.asarray(p.logical.q, np.float32))
        qs.append(q)
        row_plans.extend([p] * q.shape[0])
    q_all = np.concatenate(qs, axis=0)
    B = q_all.shape[0]

    # per-row trace handles (span fan-out targets); None = tracing off
    row_traces = None
    if obs is not None:
        row_traces = []
        for tr, q in zip(obs, qs):
            row_traces.extend([tr] * q.shape[0])

    groups: dict[tuple, list[int]] = {}
    for i, p in enumerate(row_plans):
        groups.setdefault(p.group_key, []).append(i)
    reps = {key: row_plans[idxs[0]] for key, idxs in groups.items()}
    units = fuse_batch(list(reps.values()),
                       cfg=planner_cfg or PlannerConfig(),
                       rows=[len(groups[key]) for key in reps])

    # -- phase 1: launch every hot program (no device_get yet) -----------
    # each entry: (unit, member row-index lists, real row count, _Hot)
    inflight = []
    batch = (tracer.next_batch()
             if row_traces is not None and tracer is not None else None)
    for unit in units:
        member_idxs = [groups[p.group_key] for p in unit.plans]
        rep = unit.plans[0]
        fan = seq = None
        if row_traces is not None:
            seq = tracer.next_unit() if tracer is not None else None
            fan = FanSpan([row_traces[i] for m in member_idxs for i in m],
                          "launch", engine=rep.engine, fused=unit.fused,
                          groups=len(unit.plans), unit=seq)
        t_launch0 = time.perf_counter()
        if rep.engine == "hybrid":
            # hybrid always dispatches through the grouped fused scan (a
            # single predicate group is simply G=1): ONE pass computes
            # dense + BM25 + predicate masks for every member group
            if lex is None:
                raise ValueError("engine='hybrid' requires a lexical arena "
                                 "— construct the RagDB with lexical_cfg")
            idxs = [i for m in member_idxs for i in m]
            gids = np.concatenate(
                [np.full(len(m), g, np.int32)
                 for g, m in enumerate(member_idxs)])
            # a joined launch runs at its largest member bucket
            mode, _, w_d, w_l = rep.lex
            qts = {p.lex[1] for p in unit.plans}
            qt = max(qts)
            qterms = _qterms_rows(row_plans, idxs, qt)
            hot = _launch_hybrid(
                hot_store, lex.snapshot(), q_all[np.asarray(idxs)], gids,
                [p.pred for p in unit.plans], qterms, k, mode=mode,
                w_dense=w_d, w_lex=w_l, rrf_c=lex.cfg.rrf_c,
                lists=(mode == "rrf" and rep.route == "hot+warm"),
                stats=stats, shapes=shapes, lex_key=(mode, qt, w_d, w_l),
                page_rows=rep.page_rows)
            if stats is not None:
                stats.lex_bucket_joins += len(qts) > 1
                if unit.fused:
                    stats.fused_groups += len(unit.plans)
                    stats.fused_scans += 1
        elif unit.fused:
            idxs = [i for m in member_idxs for i in m]
            gids = np.concatenate(
                [np.full(len(m), g, np.int32)
                 for g, m in enumerate(member_idxs)])
            hot = _launch_grouped(hot_store, q_all[np.asarray(idxs)], gids,
                                  [p.pred for p in unit.plans], k,
                                  unit.plans[0].engine, stats=stats,
                                  shapes=shapes, page_rows=rep.page_rows)
            if stats is not None:
                stats.fused_groups += len(unit.plans)
                stats.fused_scans += 1
        else:
            (plan,) = unit.plans
            (idxs,) = member_idxs
            q_g = q_all[np.asarray(idxs)]
            n_valid = q_g.shape[0]
            if shapes is not None:
                bucket = bucket_rows(n_valid)
                shapes.touch(plan.engine, bucket, k,
                             page_rows=plan.page_rows, shards=plan.shards)
                if stats is not None:
                    stats.padded_rows += bucket - n_valid
                q_g = _pad_rows(q_g, bucket)
            hot = _launch_hot(hot_store, jnp.asarray(q_g), plan.pred, k,
                              plan.engine, sharded_fn, index, plan.nprobe,
                              n_valid, skip_rescan=bool(plan.degraded),
                              page_rows=plan.page_rows)
        hot.launch_ms = (time.perf_counter() - t_launch0) * 1e3
        if fan is not None:
            # the launched shape: real rows, the padded rows the scan got,
            # and (arena-scan kernel) its query-row block and its passes,
            # one arena stream per block
            hot.unit = seq
            bucket = int(hot.s.shape[0])
            family = _family(rep.engine, unit.fused)
            shape = {"rows": sum(len(m) for m in member_idxs),
                     "bucket": bucket, "family": family,
                     "page_rows": rep.page_rows, "batch": batch}
            if family in SCAN_FAMILIES:
                spec = (hybrid_spec(rep.lex[0]) if family == "hybrid"
                        else ScanSpec())
                blk_b = default_blk_b(bucket, spec)
                shape["block_rows"] = blk_b
                shape["passes"] = -(-bucket // blk_b)
            if family == "hybrid":
                # the lexical stage's loop: ``lanes`` x ``qt`` (the launched
                # QT) compares a row, of which ``qterms`` (summed over the
                # rows) are real; ``qt_joined`` query-term buckets joined
                shape.update(mode=rep.lex[0], qt=qt, qt_joined=len(qts),
                             qterms=int((qterms >= 0).sum()),
                             lanes=int(lex.cfg.doc_terms))
            fan.end(**shape)
        inflight.append((unit, member_idxs, hot))
        if stats is not None:
            n_rows_unit = sum(len(m) for m in member_idxs)
            stats.device_calls += 1
            stats.queries += n_rows_unit
            stats.hot_queries += n_rows_unit
            if rep.page_rows is not None:
                stats.paged_scans += 1

    # -- phase 2: warm probes while the hot scans are in flight ----------
    warm_results: list[list[tuple] | None] = []
    warm_failed: set = set()
    for unit, member_idxs, hot in inflight:
        if unit.plans[0].route != "hot+warm":
            warm_results.append(None)
            continue
        probes = []
        for plan, m in zip(unit.plans, member_idxs):
            rt0 = warm.stats.round_trips
            if plan.engine == "hybrid":
                # warm-tier LEXICAL pushdown: predicate AND query terms
                # travel into the warm scan — one round trip, and the
                # warm rows are scored by the same fused formula (global
                # idf/avgdl), so the tier merge compares like with like
                mode, qt_bucket, w_d, w_l = plan.lex

                def probe(plan=plan, m=m, mode=mode, qt_bucket=qt_bucket,
                          w_d=w_d, w_l=w_l):
                    return warm.query_hybrid(
                        q_all[np.asarray(m)],
                        _qterms_rows(row_plans, m, qt_bucket), plan.pred, k,
                        mode=mode, w_dense=w_d, w_lex=w_l,
                        rrf_c=lex.cfg.rrf_c, lists=(mode == "rrf"))
            else:
                def probe(plan=plan, m=m):
                    return warm.query(q_all[np.asarray(m)], plan.pred, k,
                                      pushdown=True)
            wspan = None
            if row_traces is not None:
                wspan = FanSpan([row_traces[i] for i in m], "warm_probe",
                                engine=plan.engine, unit=hot.unit)
                if tracer is not None:
                    # warm faults + WarmGuard retry/hedge/breaker decisions
                    # annotate the active sink — this probe's span
                    tracer.push(wspan)
            try:
                res = (warm_guard.call(probe) if warm_guard is not None
                       else probe())
            finally:
                if wspan is not None and tracer is not None:
                    tracer.pop()
            if wspan is not None:
                wspan.end(failover=res is None)
            if stats is not None:
                # real round trips issued, successful or not (retries count)
                stats.device_calls += warm.stats.round_trips - rt0
            if res is None:
                # guard gave up: this group serves hot-only, explicitly
                warm_failed.add(plan.group_key)
                probes.append(None)
                if stats is not None:
                    stats.warm_failovers += 1
                continue
            probes.append(res)
            if stats is not None:
                stats.warm_queries += len(m)
                if plan.engine == "hybrid" and warm.lex is not None:
                    stats.terms_scanned += (warm.cfg.capacity
                                            * warm.lex.cfg.doc_terms)
        warm_results.append(probes)
    return InFlightPlans(inflight=inflight, warm_results=warm_results,
                         B=B, k=k, stats=stats, lex=lex,
                         warm_failed=warm_failed, row_traces=row_traces,
                         calib=calib)


def finish_plans(pending: InFlightPlans):
    """Phase 3 of `execute_plans`: the FIRST device_get. Syncs every
    in-flight unit, runs ivf completeness rescans, merges tiers, scatters
    into row order. Returns (scores, slots, tiers).

    Observability rides the same loop: each unit's sync is a
    ``device_sync`` span (rescans nest inside it) and the per-group merge
    a ``merge`` span in every member request's trace, and each unit lands
    one predicted-vs-measured row in `pending.calib` (the cost-model
    calibration audit — always-on, tracing or not)."""
    B, k, stats, lex = pending.B, pending.k, pending.stats, pending.lex
    row_traces, calib = pending.row_traces, pending.calib
    scores = np.full((B, k), np.float32(np.finfo(np.float32).min), np.float32)
    slots = np.full((B, k), -1, np.int32)
    tiers = np.full((B, k), TIER_HOT, np.int32)
    for (unit, member_idxs, hot), probes in zip(pending.inflight,
                                                pending.warm_results):
        unit_traces = ([row_traces[i] for m in member_idxs for i in m]
                       if row_traces is not None else None)
        sync_fan = (FanSpan(unit_traces, "device_sync",
                            engine=unit.plans[0].engine, unit=hot.unit)
                    if unit_traces is not None else None)
        t_sync0 = time.perf_counter()
        hs, hi = _finish_hot(hot, trace_fan=unit_traces)
        hot.sync_ms = (time.perf_counter() - t_sync0) * 1e3
        _note_sharded(stats, hot)
        if sync_fan is not None:
            if hot.shard_meta is not None:
                sync_fan.annotate("shards", hot.shard_meta[0])
                sync_fan.annotate("collective_bytes", hot.shard_meta[1])
            sync_fan.end(rows_scanned=hot.rows)
        if calib is not None:
            rep = unit.plans[0]
            calib.record_unit(
                engine=rep.engine, n_rows=rep.n_rows,
                groups=len(unit.plans), k=k,
                rows=sum(len(m) for m in member_idxs),
                predicted_ms=rep.est_cost_ms, launch_ms=hot.launch_ms,
                sync_ms=hot.sync_ms, rows_scanned=hot.rows,
                terms_scanned=hot.terms)
        if stats is not None:
            stats.rows_scanned += hot.rows
        merge_fan = (FanSpan(unit_traces, "merge", groups=len(member_idxs),
                             unit=hot.unit)
                     if unit_traces is not None else None)
        off = 0
        for gi, m in enumerate(member_idxs):
            span = slice(off, off + len(m))
            if probes is None:
                s_m, sl_m = hs[span], hi[span]
                t_m = np.full_like(sl_m, TIER_HOT)
            elif probes[gi] is None and hot.extra_np is not None:
                # guarded warm probe failed for an rrf hybrid group: the hot
                # program ran in lists mode, so rank-fuse the two HOT
                # per-signal lists — hot-only, explicitly degraded upstream
                h_ls, h_li = hot.extra_np
                s_m, sl_m, t_m = _rrf_merge_np(
                    hs[span], hi[span], np.full_like(hi[span], TIER_HOT),
                    h_ls[span], h_li[span],
                    np.full_like(h_li[span], TIER_HOT), k, lex.cfg.rrf_c)
            elif probes[gi] is None:
                # guarded warm probe failed: serve this group hot-only
                s_m, sl_m = hs[span], hi[span]
                t_m = np.full_like(sl_m, TIER_HOT)
            elif hot.extra_np is not None:
                # rrf hybrid across tiers: merge per SIGNAL first (hot and
                # warm dense lists into one, hot and warm bm25 lists into
                # one), then rank-fuse — ranks are only meaningful over the
                # complete per-signal candidate list
                w_ds, w_di, w_ls, w_li = probes[gi]
                ds, di, dt = merge_tiers(hs[span], hi[span], w_ds, w_di, k)
                h_ls, h_li = hot.extra_np
                ls2, li2, lt2 = merge_tiers(h_ls[span], h_li[span],
                                            w_ls, w_li, k)
                s_m, sl_m, t_m = _rrf_merge_np(ds, di, dt, ls2, li2, lt2, k,
                                               lex.cfg.rrf_c)
            else:
                ws, wi = probes[gi]
                s_m, sl_m, t_m = merge_tiers(hs[span], hi[span], ws, wi, k)
            scores[m], slots[m], tiers[m] = s_m, sl_m, t_m
            off += len(m)
        if merge_fan is not None:
            merge_fan.end()
    return scores, slots, tiers
