"""Synthetic corpus + query workload matching the paper's benchmark setup:
50,000 documents, 128-dim embeddings, 20 tenant namespaces, 5 content
categories, timestamps uniform over the past 180 days (Section 6.1).

Embeddings are drawn from a topic mixture on the unit sphere: each document
is a unit topic direction plus isotropic noise, then re-normalized, and
queries are drawn from the SAME generative process (a query embeds near some
topic, like a real user question does). Real embedding corpora are strongly
clustered — that structure is what makes any ANN index (the paper's HNSW,
our IVF) sub-linear at high recall. A purely isotropic Gaussian corpus is
the known degenerate case where nearest neighbors are statistically
indistinguishable from random rows and NO index can prune, so it would
benchmark the hardware, not the system.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.store import DocBatch

DAY_S = 86_400


@dataclasses.dataclass(frozen=True)
class CorpusConfig:
    n_docs: int = 50_000
    dim: int = 128
    n_tenants: int = 20
    n_categories: int = 5
    n_acl_groups: int = 8
    days_span: int = 180
    seed: int = 0
    # topic mixture: unit topic direction + noise, re-normalized. The
    # per-coordinate sigma is scaled so the noise VECTOR norm (~sigma *
    # sqrt(dim)) stays comparable across dims; at the default dim=128 the
    # noise norm is ~0.8 of the topic norm — clustered, not degenerate.
    n_topics: int = 64
    topic_sigma: float = 0.07
    # synthetic vocabulary for the lexical lanes: per-doc terms mix a
    # topic-correlated block (each topic prefers its own slice of the
    # common-term range — text about a topic reuses that topic's words)
    # with a Zipfian background over all common terms, plus RARE entity
    # terms (ids in the top `n_entity_terms` of the vocab, a handful of
    # docs each) — the "exact error code / ticket id" tokens where dense
    # recall collapses and hybrid retrieval earns its keep. Drawn from a
    # rng stream derived from (seed, salt), so adding the vocabulary left
    # every pre-existing column (embeddings, tenants, ...) byte-identical.
    vocab_size: int = 2048
    doc_terms: int = 16            # T lanes per doc (LexicalConfig.doc_terms)
    topic_term_lanes: int = 4      # lanes drawn from the doc's topic block
    zipf_alpha: float = 1.1        # background term popularity decay
    n_entity_terms: int = 256      # rare-id tail of the vocab
    entity_frac: float = 0.05      # docs carrying one entity term

    @property
    def now_ts(self) -> int:
        return self.days_span * DAY_S

    @property
    def n_common_terms(self) -> int:
        return self.vocab_size - self.n_entity_terms


def topic_basis(cfg: CorpusConfig) -> np.ndarray:
    """The corpus's unit topic directions, (n_topics, dim). Derived from
    cfg.seed alone so make_corpus and make_queries share one mixture."""
    rng = np.random.default_rng(np.random.SeedSequence([cfg.seed, 0x70B1C5]))
    t = rng.standard_normal((cfg.n_topics, cfg.dim)).astype(np.float32)
    return t / np.maximum(np.linalg.norm(t, axis=1, keepdims=True), 1e-12)


def _topic_points(cfg: CorpusConfig, rng: np.random.Generator, n: int,
                  with_topics: bool = False):
    topics = topic_basis(cfg)
    tid = rng.integers(0, cfg.n_topics, n)
    x = topics[tid] + cfg.topic_sigma * rng.standard_normal(
        (n, cfg.dim)).astype(np.float32)
    x = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    return (x, tid) if with_topics else x


def _doc_lexical(cfg: CorpusConfig, tid: np.ndarray,
                 rng: np.random.Generator):
    """Per-doc (terms, tfs) lanes, (n, T) int32: topic-correlated lanes +
    Zipfian background + a rare entity term on `entity_frac` of docs
    (entity ids correlated with topic, so dense retrieval helps but cannot
    pinpoint — the keyword-anchored regime the hybrid engine targets)."""
    n = len(tid)
    t_lanes = cfg.doc_terms
    v_common = cfg.n_common_terms
    # topic-correlated lanes: each topic owns a contiguous common-term block
    block = max(v_common // cfg.n_topics, 1)
    n_topic = min(cfg.topic_term_lanes, t_lanes)
    base = (tid[:, None] * block) % v_common
    terms = np.empty((n, t_lanes), np.int64)
    terms[:, :n_topic] = (base + rng.integers(
        0, block, (n, n_topic))) % v_common
    # Zipfian background over the whole common range
    ranks = np.arange(1, v_common + 1, dtype=np.float64)
    p = ranks ** -cfg.zipf_alpha
    p /= p.sum()
    terms[:, n_topic:] = rng.choice(v_common, size=(n, t_lanes - n_topic),
                                    p=p)
    # rare entity terms: last lane, entity id drawn from the doc's topic's
    # entity slice — df per entity stays in the single digits at bench scale
    if cfg.n_entity_terms and cfg.entity_frac > 0:
        has_ent = rng.random(n) < cfg.entity_frac
        e_block = max(cfg.n_entity_terms // cfg.n_topics, 1)
        ent = (v_common + (tid * e_block
                           + rng.integers(0, e_block, n))
               % cfg.n_entity_terms)
        terms[has_ent, t_lanes - 1] = ent[has_ent]
    tfs = rng.integers(1, 4, (n, t_lanes))
    return terms.astype(np.int32), tfs.astype(np.int32)


def make_corpus(cfg: CorpusConfig) -> DocBatch:
    rng = np.random.default_rng(cfg.seed)
    emb, tid = _topic_points(cfg, rng, cfg.n_docs, with_topics=True)
    tenant = rng.integers(0, cfg.n_tenants, cfg.n_docs, dtype=np.int32)
    category = rng.integers(0, cfg.n_categories, cfg.n_docs, dtype=np.int32)
    updated_at = rng.integers(0, cfg.days_span * DAY_S, cfg.n_docs, dtype=np.int64).astype(np.int32)
    # each doc permits 1..3 random ACL groups
    acl = np.zeros(cfg.n_docs, dtype=np.uint32)
    for _ in range(3):
        bit = rng.integers(0, cfg.n_acl_groups, cfg.n_docs)
        on = rng.random(cfg.n_docs) < 0.6
        acl |= (np.uint32(1) << bit.astype(np.uint32)) * on.astype(np.uint32)
    acl |= np.uint32(1) << rng.integers(0, cfg.n_acl_groups, cfg.n_docs).astype(np.uint32)
    doc_id = np.arange(cfg.n_docs, dtype=np.int32)
    # lexical lanes from a DERIVED stream: every pre-vocabulary column stays
    # byte-identical to earlier corpus versions (seeded tests, bench files)
    rng_lex = np.random.default_rng(np.random.SeedSequence([cfg.seed, 0x7E45]))
    terms, tfs = _doc_lexical(cfg, tid, rng_lex)
    return DocBatch(emb=jnp.asarray(emb), tenant=jnp.asarray(tenant),
                    category=jnp.asarray(category), updated_at=jnp.asarray(updated_at),
                    acl=jnp.asarray(acl), doc_id=jnp.asarray(doc_id),
                    terms=jnp.asarray(terms), tfs=jnp.asarray(tfs))


def stream_corpus(cfg: CorpusConfig, chunk_rows: int = 65_536):
    """Chunked corpus generator for bench-scale (million-row) ingest.

    Yields `DocBatch` chunks of at most ``chunk_rows`` docs with globally
    unique, monotonically increasing doc_ids, drawn from the same topic
    mixture as `make_corpus` (the shared `topic_basis` stream). Host memory
    stays O(chunk_rows x dim) instead of O(n_docs x dim), and each chunk
    draws from its OWN derived rng stream — SeedSequence([seed, salt,
    chunk_index]) — so chunk c is reproducible without generating chunks
    0..c-1 (a resumable ingest can seek). The draw ORDER differs from
    `make_corpus`, so the same cfg yields a statistically identical but not
    byte-identical corpus; only `make_corpus` carries the seeded-bytes
    contract the small fixed-seed tests rely on.

    >>> cfg = CorpusConfig(n_docs=100, dim=8, vocab_size=512)
    >>> chunks = list(stream_corpus(cfg, chunk_rows=64))
    >>> [int(c.emb.shape[0]) for c in chunks]
    [64, 36]
    >>> int(chunks[1].doc_id[0])      # ids continue across chunks
    64
    """
    for chunk in range(-(-cfg.n_docs // chunk_rows)):
        yield corpus_chunk(cfg, chunk, chunk_rows)


def corpus_chunk(cfg: CorpusConfig, chunk: int,
                 chunk_rows: int = 65_536) -> DocBatch:
    """Chunk ``chunk`` of `stream_corpus` as a device `DocBatch`."""
    return DocBatch(**{name: jnp.asarray(col) for name, col
                       in chunk_columns(cfg, chunk, chunk_rows).items()})


def chunk_columns(cfg: CorpusConfig, chunk: int,
                  chunk_rows: int = 65_536) -> dict[str, np.ndarray]:
    """Host (numpy) columns of chunk ``chunk`` of `stream_corpus`, keyed by
    `DocBatch` field. Each chunk draws from its own derived rng stream, so
    chunks may be generated out of order or on several threads and still
    be byte-identical."""
    start = chunk * chunk_rows
    n = min(chunk_rows, cfg.n_docs - start)
    rng = np.random.default_rng(
        np.random.SeedSequence([cfg.seed, 0x57E4A, chunk]))
    emb, tid = _topic_points(cfg, rng, n, with_topics=True)
    tenant = rng.integers(0, cfg.n_tenants, n, dtype=np.int32)
    category = rng.integers(0, cfg.n_categories, n, dtype=np.int32)
    updated_at = rng.integers(0, cfg.days_span * DAY_S, n,
                              dtype=np.int64).astype(np.int32)
    acl = np.zeros(n, dtype=np.uint32)
    for _ in range(3):
        bit = rng.integers(0, cfg.n_acl_groups, n)
        on = rng.random(n) < 0.6
        acl |= (np.uint32(1) << bit.astype(np.uint32)) * on.astype(np.uint32)
    acl |= np.uint32(1) << rng.integers(
        0, cfg.n_acl_groups, n).astype(np.uint32)
    doc_id = np.arange(start, start + n, dtype=np.int32)
    rng_lex = np.random.default_rng(
        np.random.SeedSequence([cfg.seed, 0x7E45, chunk]))
    terms, tfs = _doc_lexical(cfg, tid, rng_lex)
    return dict(emb=emb, tenant=tenant, category=category,
                updated_at=updated_at, acl=acl, doc_id=doc_id, terms=terms,
                tfs=tfs)


def make_queries(cfg: CorpusConfig, n_queries: int, batch: int = 1, seed: int = 1) -> jax.Array:
    rng = np.random.default_rng(seed)
    q = _topic_points(cfg, rng, n_queries * batch)
    return jnp.asarray(q.reshape(n_queries, batch, cfg.dim))


def make_keyword_queries(cfg: CorpusConfig, corpus: DocBatch,
                         n_queries: int, *, seed: int = 2,
                         query_sigma: float = 0.12,
                         max_df: int = 24):
    """Keyword-anchored query workload: each query targets the docs carrying
    one RARE entity term ("the exact error code"), with an embedding drawn
    near a relevant doc but noisier than the corpus noise — the regime where
    dense-only recall collapses and the paper's composed-query thesis needs
    a lexical signal INSIDE the same layer.

    Returns (q (n, dim) f32, match_terms list[tuple[int]], relevant
    list[np.ndarray of doc_ids]). Ground truth is exact by construction:
    the relevant set for a query is every doc whose lanes contain its
    anchor term.
    """
    rng = np.random.default_rng(seed)
    terms = np.asarray(corpus.terms)
    doc_id = np.asarray(corpus.doc_id)
    ent_lo = cfg.n_common_terms
    is_ent = terms >= ent_lo
    df = np.bincount(terms[is_ent].ravel(), minlength=cfg.vocab_size)
    eligible = np.nonzero((df[ent_lo:] >= 1) & (df[ent_lo:] <= max_df))[0] + ent_lo
    if len(eligible) == 0:
        raise ValueError("corpus has no rare entity terms — raise "
                         "entity_frac or n_docs")
    qs, match_terms, relevant = [], [], []
    for _ in range(n_queries):
        e = int(eligible[rng.integers(0, len(eligible))])
        rel_rows = np.nonzero((terms == e).any(axis=1))[0]
        anchor = int(rel_rows[rng.integers(0, len(rel_rows))])
        v = (np.asarray(corpus.emb)[anchor]
             + query_sigma * rng.standard_normal(cfg.dim).astype(np.float32))
        qs.append(v / max(np.linalg.norm(v), 1e-12))
        match_terms.append((e,))
        relevant.append(doc_id[rel_rows])
    return np.asarray(qs, np.float32), match_terms, relevant
