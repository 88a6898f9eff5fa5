"""The one traffic generator: a mix file of parameters in, a seeded
schedule of reads and writes out.

A mix (``bench/traffic/<name>.json``) fixes the arrival process and rate,
the query levels and their shares, the tenant skew, the query kind
(corpus-shaped rows, or keyword queries anchored on a rare entity term)
and the write share. Every seed gets the same number of operations, the
same count of each level, tenant, term count, fusion mode and write; the
seed decides their order, the arrival instants, the query rows and which
docs are written. So runs with different seeds do the same amount of
work, in another order.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.corpus import DAY_S, Corpus

ALL_BITS = 0xFFFFFFFF
ANY_TENANT = -2


@dataclasses.dataclass(frozen=True)
class Level:
    """One query level: who asks and which clauses the query carries."""
    name: str
    share: float
    scope: str = "tenant"              # "tenant" session or "admin" session
    acl_bits: int = ALL_BITS
    categories: tuple | None = None
    newer_than_days: int | None = None

    def min_ts(self, now_ts: int) -> int:
        return (0 if self.newer_than_days is None
                else now_ts - self.newer_than_days * DAY_S)


@dataclasses.dataclass
class Schedule:
    """Operations in due order. ``t`` is seconds after the window opens;
    ``kind`` 0 = read, 1 = write; ``index`` is the op's row in the read or
    write arrays below."""
    t: np.ndarray
    kind: np.ndarray
    index: np.ndarray
    levels: list
    k: int
    # reads
    level: np.ndarray                  # index into `levels`
    tenant: np.ndarray                 # session tenant (ANY_TENANT for admin)
    q: np.ndarray                      # (n_reads, D) f32, as sent
    terms: list | None                 # match() term ids per read
    mode: np.ndarray                   # "dense" | "wsum" | "rrf"
    # writes: row 0 is the set-up write that warms the commit program
    w_doc: np.ndarray
    w_emb: np.ndarray
    w_ts: np.ndarray

    @property
    def n_reads(self) -> int:
        return len(self.level)

    @property
    def n_writes(self) -> int:
        return len(self.w_doc) - 1

    def read_spec(self, r: int, now_ts: int) -> dict:
        """What read ``r`` asks, stated without the builder."""
        lv = self.levels[self.level[r]]
        cats = lv.categories
        return dict(tenant=int(self.tenant[r]),
                    acl_bits=lv.acl_bits if lv.scope == "tenant" else ALL_BITS,
                    cat_mask=(ALL_BITS if cats is None
                              else sum(1 << c for c in cats)),
                    min_ts=lv.min_ts(now_ts),
                    terms=None if self.terms is None else self.terms[r],
                    mode=str(self.mode[r]))


def exact_counts(shares, n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` items over ``shares``."""
    p = np.asarray(shares, np.float64)
    p = p / p.sum()
    raw = p * n
    out = np.floor(raw).astype(np.int64)
    rest = n - int(out.sum())
    out[np.argsort(-(raw - out), kind="stable")[:rest]] += 1
    return out


def _spread(rng, counts) -> np.ndarray:
    """A shuffled sequence holding value i exactly counts[i] times."""
    return rng.permutation(np.repeat(np.arange(len(counts)), counts))


def zipf_shares(n: int, s: float) -> np.ndarray:
    return np.arange(1, n + 1, dtype=np.float64) ** -s


def load_levels(mix: dict) -> list:
    return [Level(name=lv["name"], share=float(lv["share"]),
                  scope=lv.get("scope", "tenant"),
                  acl_bits=int(lv.get("acl_bits", ALL_BITS)),
                  categories=(tuple(lv["categories"])
                              if lv.get("categories") else None),
                  newer_than_days=lv.get("newer_than_days"))
            for lv in mix["levels"]]


def make_schedule(mix: dict, corpus: Corpus, seed: int, seconds: float, *,
                  meta: dict | None = None,
                  rate: float | None = None) -> Schedule:
    """The seeded schedule of one run. ``meta`` holds host columns of the
    corpus (tenant, topic, terms' last lane) that keyword queries anchor
    on; ``rate`` overrides the mix's rate (the knee sweep)."""
    shape = corpus.shape
    rng = np.random.default_rng([seed, 0x7AFF1C])
    rate = float(mix["arrival"]["rate_per_s"] if rate is None else rate)
    if mix["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']['process']}")
    n_ops = max(int(round(rate * seconds)), 1)
    # a Poisson process that puts n_ops arrivals in the window places them
    # as sorted uniform instants
    t = np.sort(rng.uniform(0.0, seconds, n_ops))
    w_share = float(mix.get("writes", {}).get("share", 0.0))
    n_writes = int(round(w_share * n_ops))
    kind = np.zeros(n_ops, np.int8)
    kind[rng.permutation(n_ops)[:n_writes]] = 1
    index = np.zeros(n_ops, np.int64)
    index[kind == 0] = np.arange(n_ops - n_writes)
    index[kind == 1] = 1 + np.arange(n_writes)
    n_reads = n_ops - n_writes

    levels = load_levels(mix)
    level = _spread(rng, exact_counts([lv.share for lv in levels], n_reads))
    # tenant sessions: Zipf over the tenants, the hottest one drawn
    scoped = np.asarray([levels[i].scope == "tenant" for i in level], bool)
    t_perm = rng.permutation(shape.n_tenants)
    tenant = np.full(n_reads, ANY_TENANT, np.int64)
    tenant[scoped] = t_perm[_spread(rng, exact_counts(
        zipf_shares(shape.n_tenants, float(mix["tenants"]["zipf_s"])),
        int(scoped.sum())))]

    qcfg = mix["queries"]
    terms = None
    mode = np.full(n_reads, "dense", object)
    if qcfg["kind"] == "corpus":
        q = corpus.points(3, n_reads)
    elif qcfg["kind"] == "keyword":
        # (fusion mode, term count) pairs in fixed counts: together they
        # decide how many scans a batch splits into
        fusion = qcfg["fusion"]
        names = sorted(fusion)
        weights = np.asarray(qcfg["terms"]["weights"], np.float64)
        joint = np.outer([fusion[m] for m in names], weights / weights.sum())
        pair = _spread(rng, exact_counts(joint.ravel(), n_reads))
        mode = np.asarray(names, object)[pair // len(weights)]
        lengths = 1 + pair % len(weights)
        q, terms = _keyword_queries(qcfg, corpus, meta, tenant, lengths, rng)
    else:
        raise ValueError(f"unknown query kind {qcfg['kind']!r}")

    wcfg = mix.get("writes", {})
    if n_writes and wcfg.get("op") != "update":
        raise ValueError(f"unknown write op {wcfg.get('op')!r}")
    perm = rng.permutation(shape.n_docs)
    cdf = np.cumsum(zipf_shares(shape.n_docs, float(wcfg.get("zipf_s", 1.0))))
    ranks = np.searchsorted(cdf, rng.uniform(0, cdf[-1], n_writes + 1))
    w_doc = perm[np.minimum(ranks, shape.n_docs - 1)]
    w_emb = corpus.points(4, n_writes + 1)
    w_ts = np.full(n_writes + 1, shape.now_ts, np.int64)
    return Schedule(t=t, kind=kind, index=index, levels=levels,
                    k=int(mix["limit_k"]), level=level, tenant=tenant, q=q,
                    terms=terms, mode=mode, w_doc=w_doc, w_emb=w_emb,
                    w_ts=w_ts)


def _keyword_queries(qcfg: dict, corpus: Corpus, meta: dict, tenant,
                     lengths, rng):
    """Per read: an anchor doc of the session's tenant that carries a rare
    entity term; the query row is the anchor's embedding plus noise; the
    terms are the entity term and, up to the read's length, distinct
    common terms from the anchor's topic block."""
    shape = corpus.shape
    n = len(tenant)
    v_common = shape.n_common_terms
    ent = meta["last_term"]
    has_ent = ent >= v_common
    anchors = np.zeros(n, np.int64)
    for t in np.unique(tenant):
        pool = np.nonzero(has_ent & (meta["tenant"] == t))[0]
        if len(pool) == 0:
            raise ValueError(f"tenant {t} holds no doc with an entity term")
        rows = np.nonzero(tenant == t)[0]
        anchors[rows] = pool[rng.integers(0, len(pool), len(rows))]
    emb = corpus.rows(anchors, names=("emb",))["emb"].astype(np.float32)
    noise = rng.standard_normal(emb.shape).astype(np.float32)
    q = emb + float(qcfg["query_sigma"]) * noise
    q /= np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    block = max(v_common // shape.n_topics, 1)
    terms = []
    for r in range(n):
        base = (int(meta["topic"][anchors[r]]) * block) % v_common
        extra = rng.choice(block, size=min(lengths[r] - 1, block),
                           replace=False)
        terms.append((int(ent[anchors[r]]),)
                     + tuple(int((base + e) % v_common) for e in extra))
    return q.astype(np.float32), terms
