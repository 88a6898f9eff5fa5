"""Fixed-width postings arena — the lexical columns of the unified layer.

The paper's critique of split stacks is that every extra signal bolted onto
retrieval (metadata, permissions, freshness) grows a sidecar system with its
own consistency domain. Lexical scoring is the canonical example: production
deployments run a separate BM25 engine next to the vector DB and merge
app-side. Here the postings live as two more columns of the SAME arena:

  terms (N, T) int32   term ids, -1 = empty lane (T = LexicalConfig.doc_terms)
  tfs   (N, T) int32   term frequency per lane (0 on empty lanes)

Row i is slot i of the vector arena — one slot allocator, one tombstone
convention, one commit counter. `TransactionLog` write hooks (ingest /
delete) call `write_rows` / `clear_rows` exactly as they call the IVF
index's maintenance hooks, so MVCC slot recycling and snapshot keying apply
verbatim: a query observes embedding, metadata, and postings from one
consistent snapshot, never a mix.

Corpus-level BM25 statistics (df / n_docs / total length) live in
`LexicalStats`, shared by every tier that scores lexically — hot arena and
warm split-stack lanes both feed one df table, so idf and avgdl are global
and BM25 scores are comparable across the tier merge.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_TOKEN_RE = re.compile(r"[a-z0-9_]+")


@dataclasses.dataclass(frozen=True)
class LexicalConfig:
    """Shape and scoring knobs of the postings arena. The defaults are
    the program's test widths; a deployment states its own (MS MARCO
    passages take 64 lanes of BERT-base's 30,522 WordPiece ids, a width at
    which the hybrid kernel compiles and runs).

    >>> LexicalConfig().doc_terms
    16
    """
    vocab_size: int = 2048        # term-id space (ids in [0, vocab_size))
    doc_terms: int = 16           # T: fixed-width term lanes per document
    max_query_terms: int = 16     # match() clause cap (QT pads to pow2 bucket)
    k1: float = 1.2               # BM25 tf saturation
    b: float = 0.75               # BM25 length normalization
    rrf_c: int = 60               # reciprocal-rank-fusion damping constant


class LexicalStats:
    """Corpus-level BM25 statistics: document frequency per term, live doc
    count, total token mass. One instance is SHARED by every tier's lanes
    (hot arena + warm client), so idf/avgdl are corpus-global and the tier
    merge compares like with like. ``version`` bumps on every mutation —
    result-cache keys include it, because a warm-tier lexical write changes
    idf and therefore hot-tier hybrid scores without any hot commit.

    >>> st = LexicalStats(8)
    >>> st.add(np.array([[0, 3, -1]]), np.array([[2, 1, 0]]))
    >>> st.n_docs, st.total_len, st.df[:4].tolist()
    (1, 3, [1, 0, 0, 1])
    >>> st.remove(np.array([[0, 3, -1]]), np.array([[2, 1, 0]]))
    >>> st.n_docs, int(st.df.sum()), st.version
    (0, 0, 2)
    """

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size
        self.df = np.zeros(vocab_size, np.int64)
        self.n_docs = 0               # docs carrying at least one term
        self.total_len = 0            # sum of tf over all live lanes
        self.version = 0
        self._idf_cache: tuple[int, jax.Array] | None = None

    def add(self, terms: np.ndarray, tfs: np.ndarray) -> None:
        """Credit (M, T) rows of lanes. Lanes hold UNIQUE term ids per row
        (writers sanitize), so df is a straight bincount of valid lanes."""
        valid = terms >= 0
        if valid.any():
            self.df += np.bincount(terms[valid].ravel(),
                                   minlength=self.vocab_size)
        self.n_docs += int(valid.any(axis=1).sum())
        self.total_len += int(tfs[valid].sum())
        self.version += 1

    def remove(self, terms: np.ndarray, tfs: np.ndarray) -> None:
        valid = terms >= 0
        if valid.any():
            self.df -= np.bincount(terms[valid].ravel(),
                                   minlength=self.vocab_size)
        self.n_docs -= int(valid.any(axis=1).sum())
        self.total_len -= int(tfs[valid].sum())
        self.version += 1

    @property
    def avgdl(self) -> float:
        return self.total_len / max(self.n_docs, 1)

    def idf(self) -> jax.Array:
        """(V,) f32 device array of BM25 idf values, cached per version.
        The +1 inside the log keeps idf non-negative for common terms."""
        if self._idf_cache is None or self._idf_cache[0] != self.version:
            n = max(self.n_docs, 0)
            v = np.log1p((n - self.df + 0.5) / (self.df + 0.5))
            self._idf_cache = (self.version,
                               jnp.asarray(np.maximum(v, 0.0), jnp.float32))
        return self._idf_cache[1]


def sanitize_lanes(terms, tfs, *, doc_terms: int, vocab_size: int):
    """Normalize caller-supplied lanes to the arena contract: (M, T) int32,
    ids clipped to the vocab, duplicate ids within a row blanked (first lane
    wins — df counts DOCS per term, so a duplicate would double-count), tf
    forced >= 1 on occupied lanes and 0 on empty ones.

    >>> t, f = sanitize_lanes([[3, 3, 9]], [[1, 2, 0]], doc_terms=4,
    ...                       vocab_size=8)
    >>> t.tolist(), f.tolist()
    ([[3, -1, -1, -1]], [[1, 0, 0, 0]])
    """
    terms = np.asarray(terms, np.int64)
    tfs = np.asarray(tfs, np.int64)
    m, t_in = terms.shape
    t = min(t_in, doc_terms)
    out_t = np.full((m, doc_terms), -1, np.int32)
    out_f = np.zeros((m, doc_terms), np.int32)
    tt = terms[:, :t].copy()
    ff = tfs[:, :t].copy()
    tt[(tt < 0) | (tt >= vocab_size)] = -1
    # blank duplicate ids within a row (keep the first occurrence)
    for j in range(1, t):
        dup = (tt[:, j:j + 1] == tt[:, :j]).any(axis=1) & (tt[:, j] >= 0)
        tt[dup, j] = -1
    ff = np.where(tt >= 0, np.maximum(ff, 1), 0)
    out_t[:, :t] = tt
    out_f[:, :t] = ff
    return out_t, out_f


@partial(jax.jit, static_argnames=("k1", "b"))
def _lexnorm(tfs: jax.Array, avgdl: jax.Array, k1: float, b: float):
    """BM25 per-lane weight WITHOUT idf: tf*(k1+1)/(tf + k1*lennorm).
    Precomputed per snapshot so the scan kernel only multiplies by the
    query-side idf. Empty lanes (tf=0) are exactly 0."""
    dl = jnp.sum(tfs, axis=1, keepdims=True).astype(jnp.float32)
    denom = tfs.astype(jnp.float32) + k1 * (1.0 - b + b * dl
                                            / jnp.maximum(avgdl, 1.0))
    return tfs.astype(jnp.float32) * (k1 + 1.0) / denom


class LexicalArena:
    """Per-tier postings lanes, slot-aligned with that tier's row arena.

    Device state is immutable-per-commit (every write produces new arrays
    via ``.at[].set``), so a reader holding ``snapshot()`` keeps a
    consistent view across concurrent commits — the same MVCC-by-immutability
    contract as the vector store. ``commit_count`` mirrors the device state
    host-side for snapshot-exact cache keys.

    >>> arena = LexicalArena(4, LexicalConfig(vocab_size=16, doc_terms=2))
    >>> arena.write_rows([0, 2], [[1, 5], [5, -1]], [[2, 1], [3, 0]])
    >>> snap = arena.snapshot()
    >>> np.asarray(snap["terms"])[2].tolist(), arena.stats.df[5].item()
    ([5, -1], 2)
    >>> arena.clear_rows([2])
    >>> arena.stats.df[5].item(), arena.commit_count
    (1, 2)
    """

    def __init__(self, capacity: int, cfg: LexicalConfig,
                 stats: LexicalStats | None = None):
        self.cfg = cfg
        self.stats = stats if stats is not None else LexicalStats(cfg.vocab_size)
        self._terms = jnp.full((capacity, cfg.doc_terms), -1, jnp.int32)
        self._tfs = jnp.zeros((capacity, cfg.doc_terms), jnp.int32)
        self.commit_count = 0
        self._snap_cache: tuple[tuple, dict] | None = None

    @property
    def capacity(self) -> int:
        return self._terms.shape[0]

    # -- writes (TransactionLog / warm-client hooks) ---------------------
    def write_rows(self, slots, terms, tfs) -> None:
        """(Over)write the lanes at ``slots``. Recycled slots first return
        their old lanes' df/length contributions, so corpus statistics stay
        exact under MVCC slot reuse. ``terms=None`` writes empty lanes."""
        idx = np.asarray(slots, np.int64).reshape(-1)
        if idx.size == 0:
            return
        dev = jnp.asarray(idx, jnp.int32)
        # gather on device: only the written rows cross to the host
        old_t = np.asarray(self._terms[dev])
        old_f = np.asarray(self._tfs[dev])
        if (old_t >= 0).any():
            self.stats.remove(old_t, old_f)
        if terms is None:
            new_t = np.full((idx.size, self.cfg.doc_terms), -1, np.int32)
            new_f = np.zeros((idx.size, self.cfg.doc_terms), np.int32)
        else:
            new_t, new_f = sanitize_lanes(
                np.asarray(terms), np.asarray(tfs),
                doc_terms=self.cfg.doc_terms,
                vocab_size=self.cfg.vocab_size)
        if (new_t >= 0).any():
            self.stats.add(new_t, new_f)
        self._terms = self._terms.at[dev].set(jnp.asarray(new_t))
        self._tfs = self._tfs.at[dev].set(jnp.asarray(new_f))
        self.commit_count += 1

    def clear_rows(self, slots) -> None:
        self.write_rows(slots, None, None)

    def rows(self, slots) -> tuple[np.ndarray, np.ndarray]:
        """Host copies of (terms, tfs) at ``slots`` — tier-promotion reads
        the warm lanes through this before deleting them."""
        idx = np.asarray(slots, np.int64).reshape(-1)
        return np.asarray(self._terms)[idx], np.asarray(self._tfs)[idx]

    # -- reads -----------------------------------------------------------
    def snapshot(self) -> dict:
        """Consistent device view for one scan: the lanes plus everything
        BM25 needs, cached per (commit, stats version) — ``lexnorm`` is the
        per-lane tf/length weight (idf excluded) and ``idf`` the (V,) table
        the query side gathers from. A stats-only change (e.g. a write on
        the OTHER tier moving avgdl) refreshes the derived arrays without
        touching the lanes."""
        key = (self.commit_count, self.stats.version)
        if self._snap_cache is None or self._snap_cache[0] != key:
            self._snap_cache = (key, {
                "terms": self._terms,
                "tfs": self._tfs,
                "lexnorm": _lexnorm(self._tfs,
                                    jnp.float32(self.stats.avgdl),
                                    self.cfg.k1, self.cfg.b),
                "idf": self.stats.idf(),
            })
        return self._snap_cache[1]

    # -- query-side lowering ---------------------------------------------
    def token_id(self, token: str) -> int:
        """Stable string -> term-id hash (the synthetic corpus addresses
        term ids directly; real text lowers through this)."""
        h = hashlib.blake2b(token.lower().encode(), digest_size=8).digest()
        return int.from_bytes(h, "little") % self.cfg.vocab_size

    def lower_terms(self, text) -> tuple[int, ...]:
        """Lower a match() argument to unique term ids: a string tokenizes
        and hashes; an iterable of ints passes through. Order-preserving
        dedupe, capped at ``max_query_terms``.

        >>> arena = LexicalArena(1, LexicalConfig(vocab_size=64))
        >>> arena.lower_terms([7, 7, 3])
        (7, 3)
        """
        if isinstance(text, str):
            ids = [self.token_id(t) for t in _TOKEN_RE.findall(text.lower())]
        else:
            ids = [int(t) for t in text]
        out: list[int] = []
        for t in ids:
            if 0 <= t < self.cfg.vocab_size and t not in out:
                out.append(t)
        return tuple(out[:self.cfg.max_query_terms])
