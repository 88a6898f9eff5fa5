"""The traffic generator is a function of its seed: the same seed gives the
same schedule, another seed the same amounts of work in another order."""
import collections

import numpy as np

from bench import harness
from bench.corpus import Corpus
from bench.traffic import make_schedule


def _schedule(cell, seed):
    shape = harness.corpus_shape(cell.config)
    corpus = Corpus(shape, seed)
    meta = None
    if shape.lexical:
        cols = [corpus.chunk(c) for c in range(shape.n_chunks)]
        meta = {"tenant": np.concatenate([np.asarray(c["tenant"])
                                          for c in cols]),
                "topic": np.concatenate([np.asarray(c["topic"])
                                         for c in cols]),
                "last_term": np.concatenate([np.asarray(c["terms"][:, -1])
                                             for c in cols])}
    return make_schedule(cell.mix, corpus, seed, 2.0, meta=meta)


def _work(s):
    terms = (collections.Counter(len(t) for t in s.terms)
             if s.terms is not None else None)
    return (len(s.t), s.n_reads, s.n_writes,
            collections.Counter(s.level.tolist()),
            sorted(collections.Counter(s.tenant.tolist()).values()),
            collections.Counter(s.mode.tolist()), terms)


def test_same_seed_same_schedule(tiny):
    for name in ("dense768.ycsb-b", "hybrid.keyword"):
        cell = tiny(name)
        seed = 2**31 + 12345          # wider than 32 signed bits
        a, b = _schedule(cell, seed), _schedule(cell, seed)
        for f in ("t", "kind", "index", "level", "tenant", "q", "w_doc",
                  "w_emb", "w_ts"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
        assert a.terms == b.terms and list(a.mode) == list(b.mode)


def test_other_seed_same_work_other_order(tiny):
    for name in ("dense768.ycsb-b", "hybrid.keyword"):
        cell = tiny(name)
        a, b = _schedule(cell, 5), _schedule(cell, 6)
        assert _work(a) == _work(b)
        assert not np.array_equal(a.t, b.t)
        assert not np.array_equal(a.q, b.q)
