"""Arena-scan kernel (`kernels/arena_scan`): share of the tiles the merge
gate let into a running list, in percent: sum of ``tiles_merged`` over sum
of ``tiles`` (tiles x lists x query blocks a scan streamed) of the
window's ``device_sync`` spans, one per dispatch unit. A program that
writes no tally (one whose kernel merges every tile) reads None."""


def read(run):
    seen = {}
    for tr in run.spans:
        for s in tr.spans:
            if (s.name == "device_sync" and s.t1 is not None
                    and "tiles" in s.ann):
                seen[(s.t0, s.t1)] = s.ann
    tiles = sum(int(a["tiles"]) for a in seen.values())
    if not tiles:
        return None
    return 100.0 * sum(int(a["tiles_merged"]) for a in seen.values()) / tiles
