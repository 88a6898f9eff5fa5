"""Seeded corpus generator that runs on the device.

A `jax.random` rendering of the paper's corpus shapes (topic-mixture
embeddings on the unit sphere, tenants, categories, ACL groups, timestamps
over a span of days, and fixed-width lexical lanes with a Zipfian
background and rare entity terms). The same seed gives the same corpus on
any run; chunk ``c`` is drawn from its own key, so a chunk can be made
again without making the others. The harness ingests these chunks, the
traffic generator anchors queries on them, and the reference makes them
again to score against: nothing here comes from the system under test.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

DAY_S = 86_400


@dataclasses.dataclass(frozen=True)
class CorpusShape:
    """What one deployment's corpus looks like (the ``corpus`` group of a
    configuration file, plus its lexical lane shape)."""
    n_docs: int
    dim: int
    n_tenants: int = 20
    n_categories: int = 5
    n_acl_groups: int = 8
    days_span: int = 180
    n_topics: int = 64
    topic_sigma: float = 0.07
    chunk_rows: int = 250_000
    lexical: bool = False
    vocab_size: int = 2048
    doc_terms: int = 16
    topic_term_lanes: int = 4
    zipf_alpha: float = 1.1
    n_entity_terms: int = 256
    entity_frac: float = 0.05

    @property
    def now_ts(self) -> int:
        return self.days_span * DAY_S

    @property
    def n_common_terms(self) -> int:
        return self.vocab_size - self.n_entity_terms

    @property
    def n_chunks(self) -> int:
        return -(-self.n_docs // self.chunk_rows)

    def chunk_span(self, c: int) -> tuple[int, int]:
        lo = c * self.chunk_rows
        return lo, min(lo + self.chunk_rows, self.n_docs)


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed below 2**64 (``--seed`` may exceed
    32 bits): the low word seeds the key, the high word is folded in."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def stream_key(seed: int, stream: int) -> jax.Array:
    """Independent key per purpose: 1 topics, 2 corpus chunks, 3 query
    rows, 4 write embeddings."""
    return jax.random.fold_in(base_key(seed), stream)


@functools.partial(jax.jit, static_argnames=("n_topics", "dim"))
def _topics(key, n_topics: int, dim: int):
    t = jax.random.normal(key, (n_topics, dim), jnp.float32)
    return t / jnp.maximum(jnp.linalg.norm(t, axis=1, keepdims=True), 1e-12)


def topic_points(key, topics, n: int, sigma: float):
    """(n, D) unit rows near random topics, and each row's topic id."""
    k_t, k_n = jax.random.split(key)
    tid = jax.random.randint(k_t, (n,), 0, topics.shape[0])
    x = topics[tid] + sigma * jax.random.normal(
        k_n, (n, topics.shape[1]), jnp.float32)
    return x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True),
                           1e-12), tid


def _lexical(key, tid, shape: CorpusShape):
    """(n, T) term ids and term frequencies. Lanes 0..topic_term_lanes-1
    come from the doc's topic block of common terms, the rest from a
    Zipfian background over all common terms, and the last lane of
    `entity_frac` of the docs holds a rare entity term of the doc's topic.
    A term counts once per doc: a repeat lane is left empty (-1, tf 0)."""
    n = tid.shape[0]
    t_lanes = shape.doc_terms
    v_common = shape.n_common_terms
    block = max(v_common // shape.n_topics, 1)
    n_topic = min(shape.topic_term_lanes, t_lanes)
    k_top, k_bg, k_has, k_ent, k_tf = jax.random.split(key, 5)
    base = (tid[:, None] * block) % v_common
    topic = (base + jax.random.randint(k_top, (n, n_topic), 0, block)) % v_common
    ranks = jnp.arange(1, v_common + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -shape.zipf_alpha)
    cdf = cdf / cdf[-1]
    u = jax.random.uniform(k_bg, (n, t_lanes - n_topic))
    background = jnp.minimum(jnp.searchsorted(cdf, u), v_common - 1)
    terms = jnp.concatenate([topic, background], axis=1).astype(jnp.int32)
    if shape.n_entity_terms and shape.entity_frac > 0:
        has = jax.random.uniform(k_has, (n,)) < shape.entity_frac
        e_block = max(shape.n_entity_terms // shape.n_topics, 1)
        ent = v_common + (tid * e_block + jax.random.randint(
            k_ent, (n,), 0, e_block)) % shape.n_entity_terms
        terms = terms.at[:, -1].set(jnp.where(has, ent, terms[:, -1]))
    tfs = jax.random.randint(k_tf, (n, t_lanes), 1, 4, jnp.int32)
    cols_t, cols_f = [terms[:, :1]], [tfs[:, :1]]
    for j in range(1, t_lanes):
        dup = (terms[:, :j] == terms[:, j:j + 1]).any(axis=1, keepdims=True)
        cols_t.append(jnp.where(dup, -1, terms[:, j:j + 1]))
        cols_f.append(jnp.where(dup, 0, tfs[:, j:j + 1]))
    return jnp.concatenate(cols_t, 1), jnp.concatenate(cols_f, 1)


@functools.partial(jax.jit, static_argnames=("shape", "n"))
def _chunk(key, topics, start, shape: CorpusShape, n: int) -> dict:
    k_pts, k_ten, k_cat, k_ts, k_acl, k_lex = jax.random.split(key, 6)
    emb, tid = topic_points(k_pts, topics, n, shape.topic_sigma)
    g = shape.n_acl_groups
    ka = jax.random.split(k_acl, 3)
    bits = jax.random.randint(ka[0], (3, n), 0, g).astype(jnp.uint32)
    on = (jax.random.uniform(ka[1], (3, n)) < 0.6).astype(jnp.uint32)
    acl = (jnp.uint32(1) << jax.random.randint(ka[2], (n,), 0, g)
           .astype(jnp.uint32))
    for j in range(3):
        acl = acl | ((jnp.uint32(1) << bits[j]) * on[j])
    cols = dict(
        emb=emb,
        tenant=jax.random.randint(k_ten, (n,), 0, shape.n_tenants, jnp.int32),
        category=jax.random.randint(k_cat, (n,), 0, shape.n_categories,
                                    jnp.int32),
        updated_at=jax.random.randint(k_ts, (n,), 0, shape.now_ts, jnp.int32),
        acl=acl,
        doc_id=start + jnp.arange(n, dtype=jnp.int32),
        topic=tid.astype(jnp.int32))
    if shape.lexical:
        cols["terms"], cols["tfs"] = _lexical(k_lex, tid, shape)
    return cols


class Corpus:
    """The corpus of one seed: `chunk(c)` makes chunk ``c`` on the device
    (dict of columns: emb, tenant, category, updated_at, acl, doc_id,
    topic and, with lexical lanes, terms and tfs)."""

    def __init__(self, shape: CorpusShape, seed: int):
        self.shape = shape
        self.seed = seed
        self.topics = _topics(stream_key(seed, 1), shape.n_topics, shape.dim)

    def chunk(self, c: int) -> dict:
        lo, hi = self.shape.chunk_span(c)
        key = jax.random.fold_in(stream_key(self.seed, 2), c)
        return _chunk(key, self.topics, jnp.int32(lo), self.shape, hi - lo)

    def points(self, stream: int, n: int) -> np.ndarray:
        """(n, D) f32 host rows from the corpus's own topic mixture
        (corpus-shaped queries and re-embeddings)."""
        emb, _ = _points(stream_key(self.seed, stream), self.topics, n,
                         self.shape.topic_sigma)
        return np.asarray(emb)

    def rows(self, doc_ids, names=("emb",)) -> dict:
        """Host copies of the named columns at ``doc_ids``, made again
        chunk by chunk (the gather's index list is padded to a power of
        two, so a few programs serve every call)."""
        ids = np.asarray(doc_ids, np.int64)
        out: dict = {}
        for c in np.unique(ids // self.shape.chunk_rows):
            lo, _ = self.shape.chunk_span(int(c))
            sel = np.nonzero(ids // self.shape.chunk_rows == c)[0]
            local = np.zeros(1 << max(len(sel) - 1, 0).bit_length(), np.int32)
            local[:len(sel)] = ids[sel] - lo
            cols = _take(self.chunk(int(c)), jnp.asarray(local), tuple(names))
            for name in names:
                part = np.asarray(cols[name])[:len(sel)]
                if name not in out:
                    out[name] = np.zeros((len(ids),) + part.shape[1:],
                                         part.dtype)
                out[name][sel] = part
        return out


@functools.partial(jax.jit, static_argnames=("names",))
def _take(cols: dict, idx, names: tuple) -> dict:
    return {n: cols[n][idx] for n in names}


@functools.partial(jax.jit, static_argnames=("n", "sigma"))
def _points(key, topics, n: int, sigma: float):
    return topic_points(key, topics, n, sigma)
