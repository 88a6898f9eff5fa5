"""The isolation check holds every served row to its read's predicate, on
the state the read saw, whether or not the read is in the sample."""
import numpy as np

from bench import harness
from bench.corpus import Corpus
from bench.reference import ANY_TENANT, Reference
from bench.traffic import ALL_BITS


def _reference(tiny, writes=((), (), ())):
    cell = tiny("dense768.levels")
    corpus = Corpus(harness.corpus_shape(cell.config), 31)
    return Reference(corpus, 10, None, writes), corpus


def _meta(corpus, ids):
    return corpus.rows(np.asarray(ids), names=("tenant", "updated_at",
                                                "category", "acl"))


def test_foreign_tenant_row_is_a_violation(tiny):
    ref, corpus = _reference(tiny)
    ids = np.arange(400)
    m = _meta(corpus, ids)
    t = int(m["tenant"][0])
    own = ids[m["tenant"] == t][:3]
    other = ids[m["tenant"] != t][:1]
    slots = np.full((2, 10), -1)
    slots[:, :3] = own
    slots[1, 3] = other[0]
    preds = np.asarray([[t, 0, ALL_BITS, ALL_BITS]] * 2)
    assert ref.isolation(preds, slots, np.zeros(2)) == 1
    preds[:, 0] = ANY_TENANT
    assert ref.isolation(preds, slots, np.zeros(2)) == 0
    slots[0, 9] = corpus.shape.n_docs      # no such doc
    assert ref.isolation(preds, slots, np.zeros(2)) == 1


def test_recency_is_judged_on_the_state_the_read_saw(tiny):
    probe, corpus = _reference(tiny)
    m = _meta(corpus, np.arange(40))
    doc = int(np.argmin(m["updated_at"]))
    old_ts = int(m["updated_at"][doc])
    now = corpus.shape.now_ts
    ref, _ = _reference(tiny, ([doc], np.zeros((1, 128), np.float32), [now]))
    slots = np.full((2, 10), -1)
    slots[:, 0] = doc
    preds = np.asarray([[ANY_TENANT, old_ts + 1, ALL_BITS, ALL_BITS]] * 2)
    # before the update the doc is too old; after it, it is fresh
    assert ref.isolation(preds, slots, np.asarray([0, 1])) == 1
    assert probe.isolation(preds, slots, np.asarray([1, 1])) == 2
