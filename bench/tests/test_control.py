"""The control (the reference scan in the system's place, one precision
step down) comes out not correct by the cell's own limits, and the
system comes out correct, at a size a CPU test run holds."""
from bench.control import readings


def _say(*_):
    pass


def test_control_fails_dense(tiny):
    cell = tiny("dense768.ycsb-b")
    lim = cell.config["check"]["limits"]
    r = readings(cell, 2**32 + 11, 1.0, True, say=_say)
    assert r["program"]["score_gap"] <= lim["score_gap"]
    assert r["control"]["score_gap"] > lim["score_gap"]
    assert r["program_readback_gap"] <= lim["readback_gap"]
    assert r["control_readback_gap"] > lim["readback_gap"]


def test_control_fails_hybrid(tiny):
    cell = tiny("hybrid.keyword")
    lim = cell.config["check"]["limits"]
    r = readings(cell, 2**32 + 12, 1.0, True, say=_say)
    assert r["program"]["score_gap"] <= lim["score_gap"]
    assert r["control"]["score_gap"] > lim["score_gap"]
