"""IVF cluster index — the TPU-native scale-out of the unified scan.

HNSW (the paper's index) is pointer-chasing and does not map to the TPU
memory system. The TPU-idiomatic equivalent of "don't scan everything" is
IVF: a coarse quantizer (one small matmul over C centroids) selects nprobe
clusters, and the fused filtered scan runs only over those clusters' rows.

Layout: a padded cluster-major MEMBER table (C, cap) of arena slot ids. The
probe takes the deduplicated union of the predicate group's probed clusters
and gathers those members' embeddings + metadata from the ARENA once per
group (`ivf_probe`: the arena scan's slot-lane preset over that candidate
set) — slot-indirect, so the arena stays the single source of truth and the
index never carries a second copy of any column.

Rows that don't fit their cluster's cap land in an explicit ``overflow``
tail that every probe scans exactly — overfull clusters cost a little
speed, never recall.

The predicate mask still runs INSIDE the probe scan, on arena metadata:
IVF changes which rows are scored, never which rows may be returned —
isolation is preserved even against a corrupted member table.

Maintenance is incremental: writes assign new rows to their nearest
centroid (recycling member-table slots), `epoch` bumps on every (re)build so
snapshot-keyed caches stay exact, and accumulated churn past
``drift_rebuild_frac`` of the built size marks the index for a rebuild. The
device mirror is maintained incrementally too: a write patches only the
touched member-table rows in place on the next probe (upload bytes scale
with the write, not the table — see `IVFIndex.device_arrays`).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.store import Store
from repro.kernels.arena_scan.ops import arena_scan
from repro.kernels.arena_scan.stages import ScanSpec


@dataclasses.dataclass(frozen=True)
class IVFConfig:
    n_clusters: int = 64
    nprobe: int = 8
    cluster_cap: int | None = None   # padded rows per cluster; None = auto
                                     # (largest built cluster, 128-rounded)
    kmeans_iters: int = 10
    seed: int = 0
    drift_rebuild_frac: float = 0.25  # churn fraction that flags a rebuild


@partial(jax.jit, static_argnames=("n_clusters", "iters", "seed"))
def _kmeans(emb: jax.Array, live: jax.Array, n_clusters: int, iters: int,
            seed: int):
    """Spherical Lloyd iterations over live rows; centroids (C, D) f32."""
    C = n_clusters
    key = jax.random.PRNGKey(seed)
    # init: random live-ish rows (weighted by liveness)
    probs = live.astype(jnp.float32)
    probs = probs / jnp.maximum(probs.sum(), 1)
    init_idx = jax.random.choice(key, emb.shape[0], (C,), p=probs, replace=False)
    cent = emb[init_idx].astype(jnp.float32)

    def step(cent, _):
        sims = emb.astype(jnp.float32) @ cent.T                     # (N, C)
        assign = jnp.argmax(sims, axis=1)
        w = live.astype(jnp.float32)
        oh = jax.nn.one_hot(assign, C, dtype=jnp.float32) * w[:, None]
        sums = oh.T @ emb.astype(jnp.float32)                        # (C, D)
        counts = oh.sum(0)[:, None]
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), cent)
        norm = jnp.linalg.norm(new, axis=1, keepdims=True)
        return new / jnp.maximum(norm, 1e-12), None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    return cent


def _pow2(n: int, floor: int = 1) -> int:
    return 1 << max(max(int(n), floor) - 1, 0).bit_length()


class IVFIndex:
    """Host-managed coarse index over the hot arena.

    Mutable on the host (incremental upkeep rides every commit), consumed on
    device through cached mirrors (`device_arrays`) that are PATCHED in
    place: a write marks the member-table rows it touched and the next probe
    uploads only those rows (`.at[rows].set`), so upload bytes scale with
    the write, not with the (C, cap) table. `epoch` identifies the centroid
    generation — result caches key ivf-engine entries on it because a
    rebuild changes which rows get *scored* without any arena commit.
    """

    def __init__(self, cfg: IVFConfig, centroids: np.ndarray,
                 members: np.ndarray, fill: np.ndarray, overflow: list[int],
                 n_at_build: int, epoch: int = 0):
        self.cfg = cfg
        self.centroids = centroids          # (C, D) f32, unit rows
        self.members = members              # (C, cap) i32 arena slots, -1 pad
        self.fill = fill                    # (C,) live entries per cluster
        self.overflow = list(overflow)      # spilled slots — scanned exactly
        self.n_at_build = n_at_build
        self.epoch = epoch
        self.churn = 0                      # incremental ops since (re)build
        # predicates the WHOLE arena cannot fill k for (learned by the
        # executor's exact-rescan net): probing them is pure waste, so the
        # dispatch goes straight to the exact engine. Any data change can
        # un-starve a predicate, so mutations clear the memo.
        self.starved: set = set()
        self._slot_pos: dict[int, tuple[int, int]] = {}
        for c in range(members.shape[0]):
            for p in range(int(fill[c])):
                self._slot_pos[int(members[c, p])] = (c, p)
        for i, s in enumerate(self.overflow):
            self._slot_pos[int(s)] = (-1, i)
        self._dev: dict | None = None
        # incremental-mirror bookkeeping: writes mark the touched member-table
        # rows (cluster ids) dirty instead of dropping the whole mirror, and
        # device_arrays patches only those rows in place. The byte counter is
        # the auditable trail a write-heavy deployment watches.
        self._dirty_clusters: set[int] = set()
        self._overflow_dirty = False
        self.mirror_uploads = 0           # full mirror uploads
        self.mirror_patches = 0           # in-place row patches
        self.mirror_bytes_uploaded = 0    # cumulative host->device bytes

    # -- shape facts ------------------------------------------------------
    @property
    def n_clusters(self) -> int:
        return self.members.shape[0]

    @property
    def cluster_cap(self) -> int:
        return self.members.shape[1]

    @property
    def overflow_padded(self) -> int:
        """Device length of the overflow tail (pow2-bucketed for shape reuse)."""
        return _pow2(len(self.overflow), 8) if self.overflow else 0

    def candidate_rows(self, nprobe: int, rows: int = 1) -> int:
        """Upper bound on rows ONE probe scans for a ``rows``-row batch —
        execution dedups the union of all rows' probed clusters, and the
        union is pow2-bucketed, so the bound is _pow2(min(rows*nprobe, C))
        clusters (explain()'s estimate; grouped execution stacking several
        plans unions further, each plan's explain bounds its own rows)."""
        u = min(max(int(rows), 1) * max(1, min(int(nprobe), self.n_clusters)),
                self.n_clusters)
        return _pow2(u) * self.cluster_cap + self.overflow_padded

    # -- device mirrors ---------------------------------------------------
    def _overflow_device(self) -> jax.Array:
        over = np.full(self.overflow_padded, -1, np.int32)
        over[:len(self.overflow)] = self.overflow
        return jnp.asarray(over)

    def device_arrays(self) -> dict[str, jax.Array]:
        """Cached device view, maintained INCREMENTALLY: a write marks only
        the member-table rows (clusters) it touched, and the next probe
        patches those rows in place with ``.at[rows].set`` instead of
        re-uploading the whole (C, cap) table — upload bytes scale with the
        write, not the index (the ROADMAP write-heavy-deployment item;
        asserted by count in tests/test_ivf_engine.py). The overflow tail
        re-uploads whole when touched (it is pow2-padded and small); a
        padded-length change forces that anyway. Centroids only change on
        rebuild, which constructs a fresh index (and mirror)."""
        if self._dev is None:
            over = self._overflow_device()
            self._dev = {"centroids": jnp.asarray(self.centroids),
                         "members": jnp.asarray(self.members),
                         "overflow": over}
            self.mirror_uploads += 1
            self.mirror_bytes_uploaded += (self.centroids.nbytes
                                           + self.members.nbytes
                                           + over.nbytes)
        else:
            if self._dirty_clusters:
                rows = np.asarray(sorted(self._dirty_clusters), np.int64)
                self._dev["members"] = self._dev["members"].at[
                    jnp.asarray(rows)].set(jnp.asarray(self.members[rows]))
                self.mirror_patches += 1
                self.mirror_bytes_uploaded += self.members[rows].nbytes
            if self._overflow_dirty:
                over = self._overflow_device()
                self._dev["overflow"] = over
                self.mirror_bytes_uploaded += over.nbytes
        self._dirty_clusters.clear()
        self._overflow_dirty = False
        return self._dev

    # -- the coarse quantizer (host side: centroids are tiny) -------------
    def probe(self, q: np.ndarray, nprobe: int):
        """Deduplicated probed-cluster union for a batch of query rows.

        Returns (clusters (U_pad,) i32 — -1 padded; n_probed — real
        clusters in the union; rows_scanned — padded candidate rows the
        device program will score). U_pad is `candidate_rows`'s bound,
        _pow2(min(B*nprobe, C)) — a function of (B, nprobe) alone, NOT of
        the actual union size. Determinism here is a serving contract: the
        device program's shape keys on U_pad, so a data-dependent pad
        would compile a fresh program whenever a query batch's clusters
        happened to overlap differently (an unboundable compile-stall
        source in a latency-SLO path), while this pad keeps the shape
        space enumerable by warm-up at a modest masked-padding cost."""
        q = np.atleast_2d(np.asarray(q, np.float32))
        nprobe = max(1, min(int(nprobe), self.n_clusters))
        sims = q @ self.centroids.T                         # (B, C)
        if nprobe < self.n_clusters:
            top = np.argpartition(-sims, nprobe - 1, axis=1)[:, :nprobe]
        else:
            top = np.broadcast_to(np.arange(self.n_clusters), sims.shape)
        uniq = np.unique(top)
        u_pad = _pow2(min(q.shape[0] * nprobe, self.n_clusters))
        clusters = np.full(u_pad, -1, np.int32)
        clusters[:len(uniq)] = uniq
        rows = len(clusters) * self.cluster_cap + self.overflow_padded
        return clusters, len(uniq), rows

    # -- incremental maintenance (rides every commit) ----------------------
    def add_rows(self, slots, emb) -> None:
        """Assign fresh/re-embedded rows to their nearest centroid,
        recycling member-table slots; overfull clusters spill to the
        exact-scan overflow tail."""
        slots = [int(s) for s in slots]
        emb = np.asarray(emb, np.float32).reshape(len(slots), -1)
        assign = np.argmax(emb @ self.centroids.T, axis=1)
        for slot, c in zip(slots, assign):
            if slot in self._slot_pos:      # re-embed: move, don't duplicate
                self._remove(slot)
            c = int(c)
            if self.fill[c] < self.cluster_cap:
                pos = int(self.fill[c])
                self.members[c, pos] = slot
                self.fill[c] += 1
                self._slot_pos[slot] = (c, pos)
                self._dirty_clusters.add(c)
            else:
                self._slot_pos[slot] = (-1, len(self.overflow))
                self.overflow.append(slot)
                self._overflow_dirty = True
            self.churn += 1
        self.starved.clear()

    def remove_slots(self, slots) -> None:
        for s in slots:
            self._remove(int(s))
            self.churn += 1
        self.starved.clear()

    def _remove(self, slot: int) -> None:
        ent = self._slot_pos.pop(slot, None)
        if ent is None:
            return
        c, pos = ent
        if c < 0:                            # overflow tail: swap-with-last
            last = self.overflow.pop()
            if pos < len(self.overflow):
                self.overflow[pos] = last
                self._slot_pos[last] = (-1, pos)
            self._overflow_dirty = True
        else:                                # member table: swap-with-last
            last_pos = int(self.fill[c]) - 1
            last_slot = int(self.members[c, last_pos])
            self.members[c, last_pos] = -1
            self.fill[c] = last_pos
            if pos != last_pos:
                self.members[c, pos] = last_slot
                self._slot_pos[last_slot] = (c, pos)
            self._dirty_clusters.add(c)

    def needs_rebuild(self) -> bool:
        """Drift rule: incremental churn past ``drift_rebuild_frac`` of the
        built size means the centroids no longer describe the data."""
        return self.churn > self.cfg.drift_rebuild_frac * max(self.n_at_build, 1)


def build_ivf(store: Store, cfg: IVFConfig, *, epoch: int = 0) -> IVFIndex:
    """Cluster the live rows into a cluster-major member table.

    Fully vectorized (one argsort + searchsorted scatter — the old O(C*N)
    Python loop is gone); rows beyond a cluster's cap spill into the
    overflow tail, which probes scan exactly, so capacity pressure degrades
    speed, never recall."""
    live = store["tenant"] >= 0
    n_live = int(jnp.sum(live))
    C = max(1, min(cfg.n_clusters, n_live))
    cent = _kmeans(store["emb"], live, C, cfg.kmeans_iters, cfg.seed)
    sims = store["emb"].astype(jnp.float32) @ cent.T
    assign = np.asarray(jnp.where(live, jnp.argmax(sims, axis=1), -1))

    order = np.argsort(assign, kind="stable")
    sorted_assign = assign[order]
    first_live = np.searchsorted(sorted_assign, 0)
    rows = order[first_live:].astype(np.int64)
    ca = sorted_assign[first_live:]
    counts = np.bincount(ca, minlength=C)
    if cfg.cluster_cap is not None:
        cap = cfg.cluster_cap
    else:
        cap = max(128, int(np.ceil(max(int(counts.max(initial=0)), 1) / 128)) * 128)
    start = np.searchsorted(ca, np.arange(C))
    pos = np.arange(len(rows)) - start[ca]
    members = np.full((C, cap), -1, np.int32)
    in_cap = pos < cap
    members[ca[in_cap], pos[in_cap]] = rows[in_cap]
    overflow = rows[~in_cap].astype(int).tolist()
    fill = np.minimum(counts, cap).astype(np.int64)
    return IVFIndex(cfg, np.asarray(cent), members, fill, overflow,
                    n_at_build=len(rows), epoch=epoch)


def candidate_slots(members, overflow, clusters):
    """The arena slots one predicate group's probe scores: the probed
    clusters' member rows (cluster-list padding -1 scores nothing) plus
    the overflow tail. Returns (P,) int32, -1 padding. The B stacked query
    rows of the group share this ONE candidate stream — no per-row
    (B, P, D) gather."""
    m = members[jnp.maximum(clusters, 0)]                  # (U, cap)
    m = jnp.where((clusters >= 0)[:, None], m, -1)         # cluster-list pad
    return jnp.concatenate([m.reshape(-1), overflow])      # (P,)


def ivf_probe(q, store: Store, members, overflow, clusters, pred, k: int, *,
              use_kernel: bool | None = None, interpret: bool | None = None):
    """Fused probe over one predicate group's candidate set: the arena
    scan's slot-lane preset, candidate assembly and scan in ONE jitted
    program.

    q: (B, D) stacked query rows; store: the ARENA columns (source of
    truth); members: (C, cap) i32 member table; overflow: (O,) i32
    exact-scan tail; clusters: (U,) i32 probed cluster ids, -1-padded to a
    bucketed length; pred: (4,) int32. Returns (scores (B, k) f32, ARENA
    slots (B, k) i32, -1 past the fill). The predicate mask runs on
    metadata gathered from the arena, so a stale or poisoned member table
    can only waste score work."""
    return arena_scan(q, store["emb"], store["tenant"], store["updated_at"],
                      store["category"], store["acl"],
                      jnp.zeros((q.shape[0],), jnp.int32),
                      jnp.asarray(pred, jnp.int32)[None, :], k,
                      spec=ScanSpec(slot_lane=True),
                      candidates=(candidate_slots,
                                  (members, overflow,
                                   jnp.asarray(clusters, jnp.int32))),
                      use_kernel=use_kernel, interpret=interpret)


def ivf_query(store: Store, index: IVFIndex, q: jax.Array, pred, k: int,
              nprobe: int | None = None, *, use_kernel: bool | None = None):
    """Single-call convenience over probe + fused scan (the executor drives
    the two stages itself so it can count rows_scanned).

    ``pred`` is a Predicate or its packed (4,) int32 array. Returns
    (scores (B, k), ARENA slots (B, k))."""
    from repro.core.query import Predicate
    pa = pred.as_array() if isinstance(pred, Predicate) else jnp.asarray(pred)
    clusters, _, _ = index.probe(np.asarray(q), nprobe or index.cfg.nprobe)
    dev = index.device_arrays()
    return ivf_probe(q, store, dev["members"], dev["overflow"], clusters, pa,
                     k, use_kernel=use_kernel)
