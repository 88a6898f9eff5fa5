"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the system under test. It makes the
corpus again from the seed (`bench.corpus`), replays the write log the
benchmark itself issued, and answers each sampled read in two steps:

1. a device scan over the regenerated chunks in float32 at
   ``Precision.HIGHEST`` picks, per read and per signal (dense, BM25 and
   their sum), the ``CANDIDATES`` best rows under the read's predicate.
   Docs that any write touched are left out of this scan;
2. on the host, every candidate, every touched doc (in its state as of the
   writes committed before the read launched) and every served doc is
   scored in float64, and the read's top-k list is the float64 order,
   ties to the lower doc id (the arena's tie rule: docs fill slots in doc
   id order).

The isolation predicates are checked apart, on every row of every
finished read (`isolation`), from the regenerated metadata lanes.

The same scan at a lower precision stands in for the system as the
control (`answer_lists`): it must come out not correct.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.corpus import Corpus

ANY_TENANT = -2
CANDIDATES = 64
SIGNALS = ("dense", "lex", "wsum")


@dataclasses.dataclass
class Read:
    """One sampled read: what it asked, and what it was served."""
    q: np.ndarray                 # (D,) f32 query row as sent
    tenant: int
    acl_bits: int
    cat_mask: int
    min_ts: int
    terms: tuple | None
    mode: str                     # "dense" | "wsum" | "rrf"
    writes_before: int            # writes committed before its launch
    slots: np.ndarray             # (k,) served slots, -1 padding
    scores: np.ndarray            # (k,) served scores


@dataclasses.dataclass(frozen=True)
class Lexical:
    k1: float = 1.2
    b: float = 0.75
    rrf_c: float = 60.0
    max_query_terms: int = 16


def query_terms(terms, vocab: int, cap: int) -> tuple:
    """match() semantics: ids in the vocabulary, first occurrence kept,
    at most ``cap``."""
    out: list[int] = []
    for t in terms or ():
        if 0 <= int(t) < vocab and int(t) not in out:
            out.append(int(t))
    return tuple(out[:cap])


def to_bf16(x):
    """Round f32 values to bf16 and keep them in f32. `reduce_precision`
    rounds on every backend; a cast to bf16 and back may be elided by the
    compiler (it is on TPU)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def similarity(q, emb, precision: str):
    """(S, D) x (n, D) -> (S, n) f32 dot products. ``"highest"`` is exact
    f32 (`Precision.HIGHEST`); ``"high"`` is the three-pass bf16 product
    that `Precision.HIGH` stands for, spelled out so that it rounds the
    same on every backend: each operand splits into a bf16 head and a bf16
    tail, and the tail x tail term is dropped (the pieces are exact in f32,
    so the three exact-f32 products add up what three bf16 passes do)."""
    if precision not in ("highest", "high"):
        raise ValueError(f"unknown precision {precision!r}")

    def dot(a, b):
        return jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    if precision == "highest":
        return dot(q, emb)
    qh, eh = to_bf16(q), to_bf16(emb)
    ql, el = to_bf16(q - qh), to_bf16(emb - eh)
    return dot(qh, el) + dot(ql, eh) + dot(qh, eh)


@functools.partial(jax.jit, static_argnames=("kc", "precision", "has_lex",
                                             "lex_bf16"))
def _scan_chunk(q, preds, weights, cols, dead, avgdl, k1, b, *, kc,
                precision, has_lex, lex_bf16):
    """Top-``kc`` rows of one chunk per read and signal. q (S, D) unit
    rows; preds (S, 4) [tenant, min_ts, cat_mask, acl_bits] as int32 bit
    patterns; weights (S, V) per-read idf of its match() terms."""
    dense = similarity(q, cols["emb"], precision)
    tenant, ts = cols["tenant"][None, :], cols["updated_at"][None, :]
    cat = cols["category"][None, :].astype(jnp.uint32)
    acl = cols["acl"][None, :]
    p = jax.lax.bitcast_convert_type(preds, jnp.uint32)
    keep = (tenant >= 0) & ~dead[None, :]
    keep &= (preds[:, 0:1] == ANY_TENANT) | (tenant == preds[:, 0:1])
    keep &= ts >= preds[:, 1:2]
    keep &= ((p[:, 2:3] >> cat) & 1) != 0
    keep &= (acl & p[:, 3:4]) != 0
    sigs = [dense]
    if has_lex:
        tfs = cols["tfs"].astype(jnp.float32)
        dl = tfs.sum(axis=1, keepdims=True)
        ln = tfs * (k1 + 1.0) / (tfs + k1 * (1.0 - b + b * dl
                                             / jnp.maximum(avgdl, 1.0)))
        if lex_bf16:
            ln = to_bf16(ln)
        terms = cols["terms"]
        lex = jnp.zeros_like(dense)
        for t in range(terms.shape[1]):
            lane = terms[:, t]
            w = jnp.take(weights, jnp.maximum(lane, 0), axis=1)
            lex = lex + jnp.where(lane[None, :] >= 0, w * ln[None, :, t], 0.0)
        sigs += [lex, dense + lex]
    out = []
    for s in sigs:
        v, i = jax.lax.top_k(jnp.where(keep, s, -jnp.inf), kc)
        out.append((v, i))
    return out


class Reference:
    """Answers sampled reads of one run (see module docstring)."""

    def __init__(self, corpus: Corpus, k: int, lexical: Lexical | None,
                 writes: tuple):
        self.corpus = corpus
        self.shape = corpus.shape
        self.k = k
        self.lex = lexical
        self.w_doc, self.w_emb, self.w_ts = (np.asarray(w) for w in writes)
        self.touched = np.unique(self.w_doc)
        if lexical is not None:
            self.idf, self.avgdl = self._bm25_stats()

    # -- corpus-wide BM25 statistics ---------------------------------------
    def _bm25_stats(self):
        v = self.shape.vocab_size
        df = np.zeros(v, np.int64)
        n_docs = total = 0
        for c in range(self.shape.n_chunks):
            cols = self.corpus.chunk(c)
            t = cols["terms"]
            df += np.asarray(jnp.bincount(jnp.where(t >= 0, t, v).ravel(),
                                          length=v + 1))[:v]
            n_docs += int(jnp.sum(jnp.any(t >= 0, axis=1)))
            total += int(jnp.sum(jnp.where(t >= 0, cols["tfs"], 0)))
        idf = np.maximum(np.log1p((n_docs - df + 0.5) / (df + 0.5)), 0.0)
        return idf, total / max(n_docs, 1)

    # -- step 1: the device scan -------------------------------------------
    def _pred_rows(self, reads):
        return np.asarray([[r.tenant, r.min_ts, r.cat_mask, r.acl_bits]
                           for r in reads], np.int64).astype(np.uint32) \
                 .view(np.int32)

    def _weights(self, reads):
        v = self.shape.vocab_size if self.lex is not None else 1
        w = np.zeros((len(reads), v), np.float32)
        if self.lex is None:
            return w
        for i, r in enumerate(reads):
            for t in query_terms(r.terms, v, self.lex.max_query_terms):
                w[i, t] = np.float32(self.idf[t])
        return w

    def scan(self, reads, *, precision: str = "highest",
             lex_bf16: bool = False, kc: int = CANDIDATES,
             exclude_touched: bool = True):
        """Per read, per signal: (f32 scores, doc ids) of the best ``kc``
        rows over the whole corpus, best first, ties to the lower id."""
        q = np.stack([r.q for r in reads]).astype(np.float64)
        q = (q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
             ).astype(np.float32)
        preds = jnp.asarray(self._pred_rows(reads))
        weights = jnp.asarray(self._weights(reads))
        has_lex = self.lex is not None
        lx = self.lex or Lexical()
        n_sig = 3 if has_lex else 1
        best = [[] for _ in range(n_sig)]
        for c in range(self.shape.n_chunks):
            lo, hi = self.shape.chunk_span(c)
            dead = np.zeros(hi - lo, bool)
            if exclude_touched:
                t = self.touched[(self.touched >= lo) & (self.touched < hi)]
                dead[t - lo] = True
            out = _scan_chunk(jnp.asarray(q), preds, weights,
                              self.corpus.chunk(c), jnp.asarray(dead),
                              jnp.float32(self.avgdl if has_lex else 1.0),
                              jnp.float32(lx.k1), jnp.float32(lx.b),
                              kc=min(kc, hi - lo), precision=precision,
                              has_lex=has_lex, lex_bf16=lex_bf16)
            for j, (v, i) in enumerate(out):
                best[j].append((np.asarray(v), np.asarray(i) + lo))
        lists = []
        for j in range(n_sig):
            v = np.concatenate([b[0] for b in best[j]], axis=1)
            i = np.concatenate([b[1] for b in best[j]], axis=1)
            order = np.lexsort((i, -v), axis=1)[:, :kc]
            lists.append((np.take_along_axis(v, order, 1),
                          np.take_along_axis(i, order, 1)))
        return lists

    def answer_lists(self, reads, **scan_kw):
        """Top-k answers computed by the reference scan itself, as the
        system would serve them: (slots (S, k), scores (S, k)); the control
        passes a lower precision."""
        lists = self.scan(reads, kc=self.k, exclude_touched=False, **scan_kw)
        slots = np.full((len(reads), self.k), -1, np.int64)
        scores = np.full((len(reads), self.k), -np.inf, np.float64)
        for i, r in enumerate(reads):
            if r.mode == "rrf":
                (ds, di), (ls, li) = lists[0], lists[1]
                d_ok, l_ok = np.isfinite(ds[i]), np.isfinite(ls[i])
                slots[i] = rrf_fuse(di[i], li[i], self.k, self.lex.rrf_c,
                                    d_ok, l_ok)
                continue
            v, ids = lists[2 if r.mode == "wsum" else 0]
            ok = np.isfinite(v[i])
            slots[i] = np.where(ok, ids[i], -1)
            scores[i] = np.where(ok, v[i], -np.inf)
        return slots, scores

    # -- step 2: float64 on the host ---------------------------------------
    def _state(self, ids, writes_before: int, rows: dict) -> dict:
        """Columns of ``ids`` as of the first ``writes_before`` writes."""
        out = {k: v.copy() for k, v in rows.items()}
        for w in range(min(writes_before, len(self.w_doc))):
            hit = ids == self.w_doc[w]
            out["emb"][hit] = self.w_emb[w]
            out["updated_at"][hit] = self.w_ts[w]
        return out

    def _mask(self, r: Read, st: dict) -> np.ndarray:
        ok = st["tenant"] >= 0
        if r.tenant != ANY_TENANT:
            ok &= st["tenant"] == r.tenant
        ok &= st["updated_at"] >= r.min_ts
        ok &= ((np.uint64(r.cat_mask) >> st["category"].astype(np.uint64))
               & np.uint64(1)) != 0
        ok &= (st["acl"].astype(np.uint64) & np.uint64(r.acl_bits)) != 0
        return ok

    def _signals(self, r: Read, st: dict) -> dict:
        q = r.q.astype(np.float64)
        q /= max(np.linalg.norm(q), 1e-12)
        e = st["emb"].astype(np.float64)
        dense = e @ q / np.maximum(np.linalg.norm(e, axis=1), 1e-12)
        sig = {"dense": dense}
        if self.lex is not None:
            lx = self.lex
            tf = st["tfs"].astype(np.float64)
            dl = tf.sum(axis=1, keepdims=True)
            ln = tf * (lx.k1 + 1.0) / (tf + lx.k1 * (
                1.0 - lx.b + lx.b * dl / max(self.avgdl, 1.0)))
            lex = np.zeros(len(dense))
            for t in query_terms(r.terms, self.shape.vocab_size,
                                 lx.max_query_terms):
                lex += self.idf[t] * np.where(st["terms"] == t, ln, 0.0).sum(1)
            sig["lex"] = lex
            sig["wsum"] = dense + lex
        return sig

    def judge(self, reads, lists, served: tuple, tol: float) -> dict:
        """Compare served answers (slots (S, k), scores (S, k)) with the
        reference. ``tol`` is the relative score difference under which two
        ranks count as undetermined (an rrf list built on them is excused).
        Returns the numbers compared and counts."""
        k = self.k
        slots, scores = served
        n_docs = self.shape.n_docs
        ids_needed = set(self.touched.tolist())
        for i in range(len(reads)):
            for sig_v, sig_i in lists:
                ids_needed.update(sig_i[i][np.isfinite(sig_v[i])].tolist())
            ids_needed.update(int(s) for s in slots[i] if 0 <= s < n_docs)
        ids_all = np.asarray(sorted(ids_needed), np.int64)
        names = ("emb", "tenant", "category", "updated_at", "acl")
        if self.lex is not None:
            names += ("terms", "tfs")
        rows = self.corpus.rows(ids_all, names=names)
        pos = {int(d): j for j, d in enumerate(ids_all)}
        out = dict(rows=0, score_gap=0.0, rrf_mismatch=0, rrf_tie_excused=0,
                   ref_undecided=0)
        for i, r in enumerate(reads):
            sig_names = (("dense", "lex") if r.mode == "rrf" else
                         ("wsum",) if r.mode == "wsum" else ("dense",))
            cand = set(self.touched.tolist())
            decided = True
            for name in sig_names:
                v, ids = lists[SIGNALS.index(name)]
                fin = np.isfinite(v[i])
                cand.update(ids[i][fin].tolist())
                if fin.all():
                    floor = float(v[i][-1])       # best score left outside
                    decided &= self._complete(r, name, floor, ids[i], rows,
                                              pos)
            served_ids = np.asarray(slots[i], np.int64)
            bad = (served_ids >= n_docs) | (served_ids < -1)
            served_ok = served_ids[(served_ids >= 0) & ~bad]
            cand.update(served_ok.tolist())
            c_ids = np.asarray(sorted(cand), np.int64)
            st = self._state(c_ids, r.writes_before,
                             {n: rows[n][[pos[int(d)] for d in c_ids]]
                              for n in names})
            keep = self._mask(r, st)
            sig = self._signals(r, st)
            where = {int(d): j for j, d in enumerate(c_ids)}
            out["rows"] += 1
            if not decided:
                out["ref_undecided"] += 1
                continue
            if r.mode == "rrf":
                per = [self._ranked(sig[n], keep, c_ids) for n in sig_names]
                want = rrf_fuse(per[0][1][:k], per[1][1][:k], k,
                                self.lex.rrf_c,
                                np.isfinite(per[0][0][:k]),
                                np.isfinite(per[1][0][:k]))
                if np.array_equal(want, served_ids):
                    continue
                if any(ranks_tied(s, k, tol) for s, _ in per):
                    out["rrf_tie_excused"] += 1
                else:
                    out["rrf_mismatch"] += 1
                continue
            s_ref, i_ref = self._ranked(sig[sig_names[0]], keep, c_ids)
            for j in range(k):
                best = s_ref[j] if j < len(s_ref) else -np.inf
                d = int(served_ids[j])
                if d < 0 or d >= n_docs:
                    gap = np.inf if np.isfinite(best) else 0.0
                else:
                    got = sig[sig_names[0]][where[d]]
                    gap = max(abs(float(scores[i][j]) - got)
                              / max(1.0, abs(got)),
                              (best - got) / max(1.0, abs(best)))
                out["score_gap"] = max(out["score_gap"], float(gap))
        return out

    def _complete(self, r, name, floor, ids, rows, pos) -> bool:
        """Whether the kc candidates surely hold the read's top-k: the k-th
        best candidate, scored in float64, lies clear of the best score
        left outside (or both are the exact 0 of no lexical match)."""
        st = self._state(ids, 0, {n: rows[n][[pos[int(d)] for d in ids]]
                                  for n in rows})
        s = np.where(self._mask(r, st), self._signals(r, st)[name], -np.inf)
        kth = np.sort(s)[::-1][self.k - 1]
        return bool(kth - floor > 1e-4 * max(1.0, abs(kth))
                    or (kth == 0.0 and floor == 0.0))

    def _ranked(self, s, keep, ids):
        s = np.where(keep, s, -np.inf)
        order = np.lexsort((ids, -s))
        return s[order], np.where(np.isfinite(s[order]), ids[order], -1)

    def isolation(self, preds, slots, writes_before) -> int:
        """Served rows outside their read's predicate, over every row of
        every read given: ``preds`` (R, 4) [tenant, min_ts, cat_mask,
        acl_bits], ``slots`` (R, k) served doc ids (-1 padding),
        ``writes_before`` (R,) writes committed before each read launched
        (an update moves ``updated_at``, so recency is judged on the state
        the read saw). An id outside the corpus is a violation."""
        preds = np.asarray(preds, np.int64)
        slots = np.asarray(slots, np.int64)
        bad = (slots >= self.shape.n_docs) | (slots < -1)
        live = (slots >= 0) & ~bad
        ids = np.unique(slots[live])
        if len(ids) == 0:
            return int(bad.sum())
        cols = self.corpus.rows(ids, names=("tenant", "category",
                                            "updated_at", "acl"))
        at = np.searchsorted(ids, np.where(live, slots, ids[0]))
        tenant, cat, acl = (cols[n][at].astype(np.int64)
                            for n in ("tenant", "category", "acl"))
        ts = cols["updated_at"][at].astype(np.int64)
        wb = np.asarray(writes_before, np.int64)
        for r, j in zip(*np.nonzero(live & np.isin(slots, self.touched))):
            hit = np.nonzero(self.w_doc[:wb[r]] == slots[r, j])[0]
            if len(hit):
                ts[r, j] = self.w_ts[hit[-1]]
        p = preds[:, :, None]
        ok = tenant >= 0
        ok &= (p[:, 0] == ANY_TENANT) | (tenant == p[:, 0])
        ok &= ts >= p[:, 1]
        ok &= ((p[:, 2] >> cat) & 1) != 0
        ok &= (acl & p[:, 3]) != 0
        return int(bad.sum() + (live & ~ok).sum())

    def readback_gap(self, stored_emb, stored_ts) -> float:
        """Largest 1 - cos(stored row, last written row) over the written
        docs; a row whose timestamp is not the written one reads 1."""
        gap = 0.0
        for j, d in enumerate(self.touched):
            last = np.nonzero(self.w_doc == d)[0][-1]
            if int(stored_ts[j]) != int(self.w_ts[last]):
                return 1.0
            a = stored_emb[j].astype(np.float64)
            w = self.w_emb[last].astype(np.float64)
            cos = a @ w / max(np.linalg.norm(a) * np.linalg.norm(w), 1e-300)
            gap = max(gap, 1.0 - cos)
        return gap


def ranks_tied(s: np.ndarray, k: int, tol: float) -> bool:
    """Whether the ranks of a reference list are undetermined at the given
    relative precision: a near-tie at the k-th place, or two listed scores
    within ``tol`` of each other without being equal."""
    fin = s[np.isfinite(s)]
    if len(fin) > k and fin[k - 1] - fin[k] <= tol * max(1.0, abs(fin[k - 1])):
        return True
    gaps = fin[:k][:-1] - fin[:k][1:]
    return bool(len(fin) > 1 and ((gaps > 0) & (gaps <= tol * max(
        1.0, abs(float(fin[0]))))).any())


def rrf_fuse(d_ids, l_ids, k, c, d_valid, l_valid) -> np.ndarray:
    """Reciprocal-rank fusion of two ranked id lists: score = sum over the
    lists holding a doc of 1/(c + rank), in float32; a doc in both keeps
    its dense position; ties go to the earlier position in [dense | lex]."""
    cand = []
    for r, d in enumerate(d_ids):
        if d_valid[r]:
            s = 1.0 / (c + r + 1)
            both = np.nonzero((l_ids == d) & l_valid)[0]
            if len(both):
                s += 1.0 / (c + both[0] + 1)
            cand.append((np.float32(s), len(cand), d))
    for r, d in enumerate(l_ids):
        if l_valid[r] and not (d_valid & (d_ids == d)).any():
            cand.append((np.float32(1.0 / (c + r + 1)), k + r, d))
    cand.sort(key=lambda x: (-x[0], x[1]))
    out = np.full(k, -1, np.int64)
    for i, (_, _, d) in enumerate(cand[:k]):
        out[i] = d
    return out
