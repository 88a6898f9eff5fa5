"""Share of its HBM roofline that the hybrid arena scan (wsum and rrf,
`kernels/arena_scan` with its lexical stage) reaches over the window, from
the device trace: a pass reads emb, meta and the T term-id and T weight
lanes once (`_scan_cost.pass_bytes`), so a launch of two 8-row passes
reads as half."""
from bench.metrics._scan_cost import roofline_pct


def read(run):
    return roofline_pct(run, "hybrid")
