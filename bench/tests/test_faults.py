"""A run whose timed path is broken underneath comes out not correct: an
answer altered where it is produced, half of each batch left unanswered,
and a write that leaves the state unchanged. (The one-chip cells have no
exchange between chips to leave out.)"""
import numpy as np

from bench import harness
from repro.api import executor
from repro.core import transactions


def _run(cell):
    return harness.run(cell, 21, 1.0, False, t_proc0=0.0,
                       say=lambda *_: None)


def test_sound_run_is_correct(tiny):
    assert _run(tiny("dense768.ycsb-b"))["correct"] is True


def test_altered_answer_is_caught(tiny, monkeypatch):
    finish = executor.finish_plans

    def altered(pending):
        scores, slots, tiers = finish(pending)
        slots = slots.copy()
        slots[:, 0] = np.where(slots[:, 0] >= 0, slots[:, 0] ^ 1, -1)
        return scores, slots, tiers

    from repro.api import ragdb
    monkeypatch.setattr(ragdb, "finish_plans", altered)
    line = _run(tiny("dense768.levels"))
    assert line["correct"] is False
    assert (line["checks"]["score_gap"]["value"]
            > line["checks"]["score_gap"]["limit"]
            or line["checks"]["isolation_violations"]["value"] > 0)


def test_unchanged_state_is_caught(tiny, monkeypatch):
    update = transactions.TransactionLog.update

    def lost(self, doc_ids, new_emb, updated_at):
        old = self._store
        update(self, doc_ids, new_emb, updated_at)
        self._store = old              # the commit returns the old state

    monkeypatch.setattr(transactions.TransactionLog, "update", lost)
    line = _run(tiny("dense768.ycsb-b"))
    assert line["correct"] is False
    assert line["checks"]["readback_gap"]["value"] > (
        line["checks"]["readback_gap"]["limit"])


def test_half_the_batch_left_out_is_caught(tiny, monkeypatch):
    """The scan answers only the first half of each batch's rows; the rest
    come back empty."""
    finish = executor.finish_plans

    def halved(pending):
        scores, slots, tiers = finish(pending)
        half = (len(slots) + 1) // 2
        slots, scores = slots.copy(), scores.copy()
        slots[half:] = -1
        scores[half:] = np.finfo(np.float32).min
        return scores, slots, tiers

    from repro.api import ragdb
    monkeypatch.setattr(ragdb, "finish_plans", halved)
    line = _run(tiny("dense768.levels", rate=120.0))
    assert line["correct"] is False
