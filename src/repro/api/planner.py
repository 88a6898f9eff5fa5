"""The planner: LogicalPlan -> PhysicalPlan.

Compilation is deterministic and fully reported by ``explain()``.

Engine selection — cost-based when measurements exist, threshold fallback
otherwise. One clause overrides the contest: a match() clause compiles to
the "hybrid" engine unconditionally (and its absence makes "hybrid"
unreachable) — only that engine scores the lexical signal, so routing a
match() query anywhere else would silently change what the query MEANS,
and the planner refuses rather than drop a clause:
  * with a `CostModel` loaded into `PlannerConfig` (fitted from
    ``results/bench_latency.json`` by ``benchmarks/bench_latency.py``), the
    planner estimates per-query latency for every *available* engine (ref
    always; pallas on a TPU backend; sharded with a device mesh; ivf when
    the RagDB carries a built index) and picks the cheapest — the reason
    string carries every estimate, so the choice is auditable;
  * without measurements (or when a candidate engine has no curve) the old
    static rules apply, first match wins:
      1. the builder's explicit `.using(engine)` hint;
      2. "ivf"      if the RagDB carries an index and the arena is at least
         `ivf_min_rows` (pruned scan: p50 stops scaling with corpus size);
      3. "sharded"  if the RagDB was built with a device mesh and the hot
         arena is at least `shard_min_rows`;
      4. "pallas"   on a TPU backend once the arena crosses `pallas_min_rows`
         (the fused filtered_topk kernel amortizes its launch there);
      5. "ref"      otherwise (pure-jnp reference; the only engine on CPU).

  Selectivity guard: a pruned scan scores at most nprobe clusters' rows, so
  a highly selective predicate (tenant / category / ACL clause) can
  under-fill the k-list even when qualifying rows exist elsewhere in the
  arena. Those plans fall back to an exact engine and the reason string
  says so — completeness beats speed, the same priority order as tier
  routing.

Tier routing — the paper's §7.3 invariant, previously buried inside
`TieredRouter.query`:
  * multi-constraint queries that only need the hot window are answered by
    the hot unified tier alone ("hot") — warm rows are older than the hot
    floor by placement, so the probe could not contribute;
  * everything else additionally probes the warm similarity tier and merges
    ("hot+warm") — unless the warm tier is empty, in which case probing it
    could only return padding. The route is a completeness rule, not a
    heuristic, so the cost model only *annotates* it (estimated warm-probe
    cost in the reason string); it never overrides it.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

import jax
import numpy as np

from repro.api.plan import (ALL_BITS, ANY_TENANT, LogicalPlan, PhysicalPlan,
                            bucket_rows)
from repro.kernels.arena_scan.ops import default_blk_b
from repro.kernels.hybrid_score.hybrid_score import hybrid_spec

#: default location bench_latency writes its measurements to (cwd-relative,
#: i.e. resolved from the repo root where benchmarks are run).
DEFAULT_MEASUREMENTS = os.path.join("results", "bench_latency.json")


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Measured per-engine latency curves: ``engine -> ((n_rows, p50_ms), ...)``.

    Curves are stored as tuples (hashable, so a `PlannerConfig` stays frozen)
    and interpolated log-log: retrieval cost is near power-law in arena rows,
    so interpolating in log space is exact for linear scans and close for
    everything else. Outside the measured range the end segment's slope is
    extrapolated; a single-point curve extrapolates linearly in ``n_rows``
    (a masked scan's cost is row-proportional).

    >>> cm = CostModel(curves=(("ref", ((1000, 1.0), (4000, 4.0))),))
    >>> round(cm.estimate_ms("ref", 2000), 3)
    2.0
    >>> round(cm.estimate_ms("ref", 8000), 3)
    8.0
    >>> cm.estimate_ms("pallas", 2000) is None
    True
    """
    curves: tuple[tuple[str, tuple[tuple[int, float], ...]], ...] = ()
    warm_probe_ms: float | None = None

    def curve(self, engine: str) -> tuple[tuple[int, float], ...] | None:
        """The measured (n_rows, p50_ms) points for ``engine``, or None."""
        for name, pts in self.curves:
            if name == engine:
                return pts
        return None

    def estimate_ms(self, engine: str, n_rows: int) -> float | None:
        """Estimated p50 latency (ms) of one query on ``engine`` at
        ``n_rows`` arena rows; None when the engine has no curve."""
        pts = self.curve(engine)
        if not pts:
            return None
        pts = sorted(pts)
        n = max(int(n_rows), 1)
        if len(pts) == 1:
            n0, t0 = pts[0]
            return t0 * n / max(n0, 1)
        xs = [math.log(max(p[0], 1)) for p in pts]
        ys = [math.log(max(p[1], 1e-9)) for p in pts]
        x = math.log(n)
        # clamp to the end segments for extrapolation
        j = 1
        while j < len(xs) - 1 and x > xs[j]:
            j += 1
        x0, x1, y0, y1 = xs[j - 1], xs[j], ys[j - 1], ys[j]
        slope = (y1 - y0) / (x1 - x0) if x1 != x0 else 0.0
        return math.exp(y0 + slope * (x - x0))

    def calibrated(self, table) -> "CostModel":
        """A copy whose curves are rescaled by the measured/predicted ratio
        a live `obs.CalibrationTable` observed per engine — the calibration
        audit closed back into pricing (the ROADMAP's "learned, self-tuning
        planner" first step: bench-time curves drift; the ratio is exactly
        the drift). Engines the table never saw (or saw only unpriced)
        keep their bench-time curves; a None/empty table is identity.

        >>> from repro.obs import CalibrationTable
        >>> cm = CostModel(curves=(("ref", ((1000, 1.0), (4000, 4.0))),))
        >>> t = CalibrationTable()
        >>> t.record_unit(engine="ref", n_rows=1000, groups=1, k=8, rows=1,
        ...               predicted_ms=1.0, launch_ms=0.5, sync_ms=1.5,
        ...               rows_scanned=1000)
        >>> round(cm.calibrated(t).estimate_ms("ref", 2000), 3)  # x2 drift
        4.0
        >>> cm.calibrated(None) is cm
        True
        """
        if table is None or not getattr(table, "recorded", 0):
            return self
        per_engine = table.per_engine()
        curves = []
        for eng, pts in self.curves:
            ratio = (per_engine.get(eng) or {}).get("ratio")
            if ratio is None or ratio <= 0.0:
                curves.append((eng, pts))
            else:
                curves.append((eng, tuple((n, ms * ratio)
                                          for n, ms in pts)))
        return dataclasses.replace(self, curves=tuple(curves))

    @classmethod
    def from_bench(cls, path: str | None = None) -> "CostModel | None":
        """Load the ``cost_model`` section bench_latency saves; None when the
        file or section is missing (the planner then falls back to the
        static thresholds)."""
        path = path or DEFAULT_MEASUREMENTS
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            return None
        section = payload.get("cost_model")
        if not section or not section.get("engines"):
            return None
        curves = tuple(
            (eng, tuple((int(n), float(ms)) for n, ms in pts))
            for eng, pts in sorted(section["engines"].items()) if pts)
        if not curves:
            return None
        warm = section.get("warm_probe_ms")
        return cls(curves=curves,
                   warm_probe_ms=float(warm) if warm is not None else None)


@dataclasses.dataclass(frozen=True)
class PlannerConfig:
    """Planner knobs. ``cost_model`` (when loaded) makes engine selection
    cost-based; the row thresholds are the fallback rules.

    >>> PlannerConfig().cost_model is None
    True
    """
    pallas_min_rows: int = 1 << 15    # fused-kernel launch amortization point
    shard_min_rows: int = 1 << 20     # below this a single device wins
    ivf_min_rows: int = 1 << 12       # below this the exact scan is trivial
    ivf_nprobe: int | None = None     # probe depth; None = the index default
    fuse_min_groups: int = 2          # grouped-scan fusion floor: batches with
                                      # at least this many exact-engine groups
                                      # sharing a fuse key scan once (a huge
                                      # value disables fusion)
    paged_min_rows: int | None = None  # paged-regime threshold: arenas at or
                                       # above this row count stream through
                                       # the paged arena scan (page tiles DMA'd
                                       # from HBM, double-buffered) instead of
                                       # the VMEM-resident tiling. None (the
                                       # default) keeps every scan resident.
                                       # Bit-identical either way — this is a
                                       # memory-traffic knob, not a semantics
                                       # knob.
    page_rows: int = 2048             # rows per page tile in the paged regime:
                                      # at D=768 f32 the emb double buffer is
                                      # 2*2048*768*4 B = 12 MiB, inside the
                                      # 16 MiB scoped VMEM a TPU v5e kernel
                                      # gets (4096 is refused there); a
                                      # multiple of 128 (lane-major pages)
    cost_model: CostModel | None = None
    # serving-path hints (consumed by serving.scheduler + degrade_plan):
    deadline_ms: float | None = None  # per-query latency SLO; compile_plan
                                      # annotates plans whose estimate busts
                                      # it, the scheduler degrades them
    degrade_min_nprobe: int = 1       # nprobe floor for the ivf rung

    @classmethod
    def with_measured_costs(cls, path: str | None = None,
                            **kwargs) -> "PlannerConfig":
        """A config with `CostModel.from_bench(path)` loaded (None-safe:
        missing measurements leave the static-threshold behavior)."""
        return cls(cost_model=CostModel.from_bench(path), **kwargs)


@dataclasses.dataclass(frozen=True)
class FusedGroup:
    """One hot-tier dispatch unit after batch-level fusion: either several
    predicate groups answered by ONE fused grouped scan (``fused=True``) or
    a single group on its own engine. ``plans`` holds one representative
    `PhysicalPlan` per member predicate group, in batch order; ``reason`` is
    the auditable fusion decision (mirrors the engine/route reason strings)."""
    plans: tuple
    fused: bool
    reason: str


def fuse_batch(plans, *, cfg: PlannerConfig = PlannerConfig(),
               rows=None) -> list[FusedGroup]:
    """Batch-level fusion rule: collapse exact-engine predicate groups that
    share a `fuse_key` (same k, engine, tier route) into one grouped scan.

    ``plans`` is one representative `PhysicalPlan` per DISTINCT predicate
    group in the batch (executor.execute_plans dedups by group_key first);
    ``rows`` the query rows of each group, aligned to ``plans`` (None =
    each plan's own rows). Groups whose engine scans per-group candidate
    sets (ivf) or owns a collective (sharded) stay on their engines; exact
    groups fuse when at least ``cfg.fuse_min_groups`` of them share a fuse
    key — the arena then streams once for all of them instead of once per
    group (`rows_scanned` G*N -> N, G compiled programs -> 1).

    Hybrid groups of one score mix share a fuse key whatever their
    query-term bucket. The groups of one bucket fuse as one launch, and
    launches of different buckets join while the joined launch makes
    fewer passes (`_lex_passes`) than the launches it replaces: a joined
    pass runs at its largest bucket, so it is cheaper than two passes,
    never than one. A launch left with fewer than ``fuse_min_groups``
    groups runs each on its own.

    With a cost model loaded the decision is priced from the engine's
    measured curve: a fused scan costs ~one scan at ``n_rows`` where the
    loop costs G of them, and the reason string carries both estimates.

    >>> from repro.api.plan import LogicalPlan, PhysicalPlan
    >>> mk = lambda t: PhysicalPlan(
    ...     logical=LogicalPlan(tenant=t, k=5),
    ...     pred=LogicalPlan(tenant=t, k=5).predicate(), engine="ref",
    ...     engine_reason="", route="hot", route_reason="", n_rows=1024)
    >>> units = fuse_batch([mk(0), mk(1), mk(2)])
    >>> len(units), units[0].fused, len(units[0].plans)
    (1, True, 3)
    >>> [u.fused for u in fuse_batch([mk(0)])]
    [False]
    """
    if rows is None:
        rows = [1 if p.logical.q is None else len(np.atleast_2d(p.logical.q))
                for p in plans]
    order: list[tuple] = []                    # first-occurrence unit order
    buckets: dict[tuple, list[int]] = {}
    for i, p in enumerate(plans):
        key = ("fuse", p.fuse_key) if p.fusable else ("solo", id(p))
        if key not in buckets:
            buckets[key] = []
            order.append(key)
        buckets[key].append(i)
    units: list[FusedGroup] = []
    for key in order:
        group = [plans[i] for i in buckets[key]]
        gsz = len(group)
        if key[0] == "solo":
            (p,) = group
            units.append(FusedGroup((p,), False,
                                    f"{p.engine} engine runs per group"))
            continue
        if gsz < cfg.fuse_min_groups:
            for p in group:
                units.append(FusedGroup(
                    (p,), False,
                    f"{gsz} group(s) share fuse key {p.fuse_key!r} "
                    f"< fuse_min_groups={cfg.fuse_min_groups}"))
            continue
        for members in _join_buckets(plans, rows, buckets[key]):
            launch = [plans[i] for i in members]
            n = len(launch)
            if n < cfg.fuse_min_groups:
                for p in launch:
                    units.append(FusedGroup(
                        (p,), False,
                        f"{n} group(s) in its launch < fuse_min_groups="
                        f"{cfg.fuse_min_groups}: joining another query-term "
                        f"bucket saves no pass"))
                continue
            k, engine, route, _mix, _page, _shards, _plc = launch[0].fuse_key
            n_rows = launch[0].n_rows
            est = (cfg.cost_model.estimate_ms(engine, n_rows)
                   if cfg.cost_model is not None else None)
            if est is not None:
                reason = (f"cost model: one fused scan ~{est:.2f}ms replaces "
                          f"{n} looped scans ~{n * est:.2f}ms at {n_rows} "
                          f"rows")
            else:
                reason = (f"{n} exact groups share (k={k}, engine={engine!r}, "
                          f"route={route!r}): one scan replaces {n}")
            qts = sorted({p.lex[1] for p in launch if p.lex is not None})
            if len(qts) > 1:
                reason += f"; joins query-term buckets {qts} at qt {qts[-1]}"
            units.append(FusedGroup(tuple(launch), True, reason))
    return units


def _lex_passes(rows: int, mode: str) -> int:
    """Arena streams a hybrid launch of ``rows`` query rows makes: its pow2
    row bucket over the lexical scan's query-row block."""
    bucket = bucket_rows(rows)
    return -(-bucket // default_blk_b(bucket, hybrid_spec(mode)))


def _join_buckets(plans, rows, idxs) -> list[list[int]]:
    """Split one fuse key's groups (``idxs`` into ``plans``) into launches,
    each a list of member indices in batch order: the groups of one
    query-term bucket stay together, and each bucket joins the first
    launch it saves a pass against. Dense groups carry no bucket: one
    launch."""
    parts: dict = {}
    for i in idxs:
        lex = plans[i].lex
        parts.setdefault(None if lex is None else lex[1], []).append(i)
    if len(parts) == 1:
        return [list(idxs)]
    mode = plans[idxs[0]].lex[0]
    launches: list[list] = []                  # [members, rows]
    for members in parts.values():
        r = sum(rows[i] for i in members)
        for launch in launches:
            joined = _lex_passes(launch[1] + r, mode)
            if joined < _lex_passes(launch[1], mode) + _lex_passes(r, mode):
                launch[0] += members
                launch[1] += r
                break
        else:
            launches.append([list(members), r])
    return [sorted(members) for members, _ in launches]


def _candidate_engines(has_mesh: bool, has_index: bool = False) -> list[str]:
    """Engines the current rig can actually run (ref always; pallas needs a
    TPU backend; sharded needs a mesh-built RagDB; ivf needs a built
    index)."""
    cands = ["ref"]
    if jax.default_backend() == "tpu":
        cands.append("pallas")
    if has_mesh:
        cands.append("sharded")
    if has_index:
        cands.append("ivf")
    return cands


def ivf_blocked_reason(logical: LogicalPlan) -> str | None:
    """Why the planner must not route this plan through the pruned scan, or
    None when ivf is admissible. The pruned scan only scores nprobe
    clusters' rows, so a selective predicate can under-fill the k-list even
    though qualifying rows exist outside the probed clusters — exactness
    requires the exact engines there. The check runs on the LOWERED
    predicate, so a no-op clause (e.g. in_categories(range(32)), which
    lowers to the pass-all mask) doesn't forfeit the pruned scan. Recency
    alone is admissible: the hot arena covers the bound by tier placement,
    and a tight bound that still under-fills is completed by the executor's
    exact-rescan net (see executor._dispatch)."""
    pred = logical.predicate()
    if pred.tenant != ANY_TENANT:
        return "selective predicate (tenant clause) could under-fill the pruned scan"
    if pred.cat_mask != ALL_BITS:
        return "selective predicate (category clause) could under-fill the pruned scan"
    if pred.acl_bits != ALL_BITS:
        return "selective predicate (ACL clause) could under-fill the pruned scan"
    return None


def choose_engine(logical: LogicalPlan, *, n_rows: int,
                  cfg: PlannerConfig = PlannerConfig(),
                  has_mesh: bool = False,
                  has_index: bool = False,
                  has_lex: bool = False) -> tuple[str, str]:
    """Pick the execution engine and an auditable reason string.

    A match() clause short-circuits to "hybrid" (the only engine that
    scores the lexical signal; anything else would silently drop the
    clause). Otherwise an explicit ``.using()`` hint wins; then the cost
    model (if every candidate engine has a measured curve); then the static
    thresholds. The selectivity guard removes "ivf" from the candidates for
    constrained plans (see `ivf_blocked_reason`) — the reason string
    records the skip.

    >>> eng, why = choose_engine(LogicalPlan(k=5), n_rows=512)
    >>> eng
    'ref'
    >>> cm = CostModel(curves=(("ref", ((1 << 10, 1.0), (1 << 20, 1000.0))),
    ...                        ("sharded", ((1 << 10, 8.0), (1 << 20, 80.0)))))
    >>> cfg = PlannerConfig(cost_model=cm)
    >>> choose_engine(LogicalPlan(k=5), n_rows=1 << 20, cfg=cfg,
    ...               has_mesh=True)[0]
    'sharded'
    >>> choose_engine(LogicalPlan(k=5), n_rows=1 << 10, cfg=cfg,
    ...               has_mesh=True)[0]
    'ref'
    >>> choose_engine(LogicalPlan(k=5), n_rows=1 << 16, has_index=True)[0]
    'ivf'
    >>> eng, why = choose_engine(LogicalPlan(tenant=3, k=5), n_rows=1 << 16,
    ...                          has_index=True)
    >>> eng, "ivf skipped" in why
    ('ref', True)
    >>> choose_engine(LogicalPlan(match_terms=(3, 7), k=5), n_rows=512,
    ...               has_lex=True)[0]
    'hybrid'
    """
    # a match() clause is a CORRECTNESS requirement, not a speed choice:
    # only the hybrid engine scores the lexical signal, so every other
    # engine would silently drop the clause — the planner refuses instead
    if logical.match_terms is not None:
        if not has_lex:
            raise ValueError("match() requires a lexical arena — construct "
                             "the RagDB with lexical_cfg")
        if logical.engine not in (None, "hybrid"):
            raise ValueError(
                f"a match() query must run on the hybrid engine, "
                f"not .using({logical.engine!r}) — drop the hint or the "
                f"match() clause")
        reason = "match() clause — fused dense+BM25 one-pass scan"
        cm = cfg.cost_model
        est = cm.estimate_ms("hybrid", n_rows) if cm is not None else None
        if est is not None:
            reason += f" (cost model: ~{est:.2f}ms)"
        return "hybrid", reason
    if logical.engine == "hybrid":
        raise ValueError("engine='hybrid' requires a match() clause — "
                         "there is no lexical signal to fuse")
    if (logical.fusion, logical.w_dense, logical.w_lex) != ("wsum", 1.0, 1.0):
        raise ValueError("fuse() requires a match() clause — without one "
                         "there is no lexical signal to mix, and silently "
                         "ignoring the knobs would misreport the ranking")
    if logical.engine is not None:
        return logical.engine, "caller hint (.using())"
    cands = _candidate_engines(has_mesh, has_index)
    note = ""
    if "ivf" in cands:
        blocked = ivf_blocked_reason(logical)
        if blocked is not None:
            cands.remove("ivf")
            note = f"; ivf skipped: {blocked}"
    cm = cfg.cost_model
    if cm is not None:
        ests = {e: cm.estimate_ms(e, n_rows) for e in cands}
        if all(v is not None for v in ests.values()):
            best = min(ests, key=lambda e: ests[e])
            detail = ", ".join(f"{e} ~{ests[e]:.2f}ms" for e in cands)
            return best, f"cost model: {detail}{note}"
    if "ivf" in cands and n_rows >= cfg.ivf_min_rows:
        return "ivf", f"index present and {n_rows} rows >= {cfg.ivf_min_rows}"
    if has_mesh and n_rows >= cfg.shard_min_rows:
        return "sharded", (f"mesh present and {n_rows} rows >= "
                           f"{cfg.shard_min_rows}{note}")
    if jax.default_backend() == "tpu" and n_rows >= cfg.pallas_min_rows:
        return "pallas", (f"tpu backend and {n_rows} rows >= "
                          f"{cfg.pallas_min_rows}{note}")
    return "ref", f"{jax.default_backend()} backend, {n_rows} rows{note}"


def choose_route(logical: LogicalPlan, *, hot_window_s: int, now_ts: int,
                 warm_rows: int,
                 cost_model: CostModel | None = None,
                 warm_lex: bool = False) -> tuple[str, str]:
    """Tier routing (paper §7.3). Semantics-driven — the warm probe runs
    exactly when it could contribute rows; the cost model only annotates the
    reason with the probe's measured price. A match() query can only spill
    warm when the warm tier carries lexical lanes (``warm_lex``) — probing
    a lanes-less warm store would score its rows dense-only, silently
    changing the clause's meaning mid-merge.

    >>> choose_route(LogicalPlan(tenant=1, min_ts=950, k=3),
    ...              hot_window_s=100, now_ts=1000, warm_rows=10)[0]
    'hot'
    >>> choose_route(LogicalPlan(k=3), hot_window_s=100, now_ts=1000,
    ...              warm_rows=10)[0]
    'hot+warm'
    >>> choose_route(LogicalPlan(k=3), hot_window_s=100, now_ts=1000,
    ...              warm_rows=0)
    ('hot', 'warm tier empty')
    >>> choose_route(LogicalPlan(k=3, match_terms=(5,)), hot_window_s=100,
    ...              now_ts=1000, warm_rows=10)
    ('hot', 'warm tier has no lexical lanes — hybrid stays hot')
    """
    if warm_rows == 0:
        return "hot", "warm tier empty"
    if logical.match_terms is not None and not warm_lex:
        return "hot", "warm tier has no lexical lanes — hybrid stays hot"
    recent_only = logical.min_ts >= now_ts - hot_window_s
    if logical.constrained and recent_only:
        return "hot", "constrained query within the hot window"
    reason = "long-tail similarity spills to the warm tier"
    if cost_model is not None and cost_model.warm_probe_ms is not None:
        reason += f" (+~{cost_model.warm_probe_ms:.2f}ms measured warm probe)"
    return "hot+warm", reason


def compile_plan(logical: LogicalPlan, *, n_rows: int, hot_window_s: int,
                 now_ts: int, warm_rows: int,
                 cfg: PlannerConfig = PlannerConfig(),
                 has_mesh: bool = False, mesh_shards: int = 0,
                 placement: str | None = None, index=None,
                 lex=None, warm_lex: bool = False) -> PhysicalPlan:
    """Compile WHAT (LogicalPlan) into HOW (PhysicalPlan): engine + route +
    the predicate-group batching key, with the cost estimate attached so
    ``explain()`` can render it. ``index`` is the RagDB's `IVFIndex` (or
    None): its presence adds "ivf" to the candidate engines, and ivf plans
    carry nprobe + the candidate-row estimate for explain(). ``lex`` is the
    hot tier's `LexicalArena` (or None): its presence admits match()
    clauses, which compile to the "hybrid" engine with the score-mix
    identity (fusion mode, query-term-count bucket, weights) stamped into
    the group key; ``warm_lex`` says whether the warm tier carries lanes
    (hybrid plans only spill warm when it does). ``mesh_shards`` /
    ``placement`` describe the RagDB's mesh (shard count S and row
    placement kind): sharded plans carry both — S shapes the compiled
    merge (S·k gathered candidates) and a "tenant" placement lets
    explain() show which shards the scan will actually touch."""
    engine, engine_reason = choose_engine(logical, n_rows=n_rows, cfg=cfg,
                                          has_mesh=has_mesh,
                                          has_index=index is not None,
                                          has_lex=lex is not None)
    route, route_reason = choose_route(logical, hot_window_s=hot_window_s,
                                       now_ts=now_ts, warm_rows=warm_rows,
                                       cost_model=cfg.cost_model,
                                       warm_lex=warm_lex)
    est = (cfg.cost_model.estimate_ms(engine, n_rows)
           if cfg.cost_model is not None else None)
    if (cfg.deadline_ms is not None and est is not None
            and est > cfg.deadline_ms):
        engine_reason += (f"; est busts deadline hint {cfg.deadline_ms:g}ms "
                          "— degradable under load")
    page_rows = None
    if (cfg.paged_min_rows is not None and n_rows >= cfg.paged_min_rows
            and engine in ("ref", "pallas", "hybrid")):
        # Paged regime: the full-arena engines stream the arena in page
        # tiles instead of holding tiles VMEM-resident. ivf scans per-group
        # candidate sets (already small) and sharded pages per shard —
        # neither takes the knob.
        page_rows = cfg.page_rows
        engine_reason += (f"; paged regime (n_rows >= {cfg.paged_min_rows}, "
                          f"{page_rows} rows/page)")
    nprobe = ivf_est = lex_key = None
    if engine == "hybrid":
        qt_bucket = bucket_rows(len(logical.match_terms))
        # rrf ranks ignore the weights — normalize them out of the identity
        # so rrf groups differing only in unused weights still fuse
        if logical.fusion == "wsum":
            lex_key = ("wsum", qt_bucket, float(logical.w_dense),
                       float(logical.w_lex))
        else:
            lex_key = ("rrf", qt_bucket, 1.0, 1.0)
    if engine == "ivf":
        if index is None:
            raise ValueError("engine='ivf' requires a built index — "
                             "call RagDB.build_index() first")
        nprobe = cfg.ivf_nprobe or index.cfg.nprobe
        q_rows = 1 if logical.q is None else len(np.atleast_2d(logical.q))
        ivf_est = (index.n_clusters, index.cluster_cap,
                   index.candidate_rows(nprobe, rows=q_rows))
    shards = plc = None
    if engine == "sharded":
        if not has_mesh or mesh_shards < 1:
            raise ValueError("engine='sharded' requires a mesh-built RagDB")
        shards = mesh_shards
        plc = placement or "hash"
    return PhysicalPlan(logical=logical, pred=logical.predicate(),
                        engine=engine, engine_reason=engine_reason,
                        route=route, route_reason=route_reason, n_rows=n_rows,
                        est_cost_ms=est,
                        cost_source=("measured" if est is not None
                                     else "static-thresholds"),
                        nprobe=nprobe, ivf_est=ivf_est, lex=lex_key,
                        page_rows=page_rows, shards=shards, placement=plc)


# ---------------------------------------------------------------------------
# deadline-aware plan degradation (the serving scheduler's ladder)
# ---------------------------------------------------------------------------

def degrade_plan(plan: PhysicalPlan, *, n_rows: int, hot_window_s: int,
                 now_ts: int, warm_rows: int,
                 cfg: PlannerConfig = PlannerConfig(),
                 has_mesh: bool = False, mesh_shards: int = 0,
                 placement: str | None = None, index=None,
                 lex=None, warm_lex: bool = False) -> PhysicalPlan | None:
    """One rung DOWN the degradation ladder, or None when it is exhausted.

    Every rung produces a plan that is still a real, standalone-compilable
    plan — executing the degraded plan through the scheduler is bit-identical
    to compiling and running it directly (tests/test_scheduler.py asserts
    this). What degrades is the QUERY CONTRACT (probe depth, score signal),
    never the isolation clauses: tenant/ACL/recency predicates ride through
    every rung untouched, so a degraded response can narrow recall but can
    never widen visibility. The rungs, in order of preference:

      1. ivf nprobe shrink — halve the probe depth (floor
         ``cfg.degrade_min_nprobe``): recall narrows, the scan shrinks
         proportionally, predicate exactness is untouched. Degraded probes
         also WAIVE the executor's completeness rescan — an under-filled
         k-list is the degraded answer, not a trigger for a full-arena
         exact scan (with the rescan in play, every rung below the default
         depth would cost MORE than the undegraded plan);
      2. hybrid -> dense — drop the lexical signal and recompile as a pure
         dense plan on the cheapest available engine (the one rung that
         changes what the query RANKS ON, which is why it is recorded in
         `explain()` and `ExecStats` rather than applied silently);
      3. ivf -> exact — at the nprobe floor, switch to the cheapest exact
         engine when the cost model prices it under the floored probe
         (starved/rescan-prone predicates make the probe a pure tax there).

    Exhausted (None) means the scheduler's only remaining lever is a
    cache-stale serve within the declared staleness bound (RagDB.execute's
    ``stale_within_s``) — that rung lives in the cache, not in the plan.

    >>> from repro.api.plan import LogicalPlan
    >>> lp = LogicalPlan(k=5)
    >>> p = compile_plan(lp, n_rows=1 << 10, hot_window_s=10, now_ts=0,
    ...                  warm_rows=0)
    >>> degrade_plan(p, n_rows=1 << 10, hot_window_s=10, now_ts=0,
    ...              warm_rows=0) is None          # ref plan: nothing to shed
    True
    """
    kw = dict(n_rows=n_rows, hot_window_s=hot_window_s, now_ts=now_ts,
              warm_rows=warm_rows, cfg=cfg, has_mesh=has_mesh,
              mesh_shards=mesh_shards, placement=placement, index=index,
              lex=lex, warm_lex=warm_lex)
    if plan.engine == "ivf" and plan.nprobe is not None:
        floor = max(int(cfg.degrade_min_nprobe), 1)
        if plan.nprobe > floor:
            new_nprobe = max(plan.nprobe // 2, floor)
            ivf_est, est = plan.ivf_est, plan.est_cost_ms
            if index is not None:
                q_rows = (1 if plan.logical.q is None
                          else len(np.atleast_2d(plan.logical.q)))
                cand = index.candidate_rows(new_nprobe, rows=q_rows)
                ivf_est = (index.n_clusters, index.cluster_cap, cand)
                if est is not None and plan.ivf_est and plan.ivf_est[2]:
                    # the measured curve prices the DEFAULT probe depth; a
                    # shallower probe scans proportionally fewer candidates
                    est = est * cand / plan.ivf_est[2]
            return dataclasses.replace(
                plan, nprobe=new_nprobe, ivf_est=ivf_est, est_cost_ms=est,
                degraded=plan.degraded + (
                    f"nprobe {plan.nprobe}->{new_nprobe}",))
        # at the floor: switch to the cheapest exact engine only when the
        # cost model actually prices it under the floored probe
        cm = cfg.cost_model
        if cm is not None:
            exacts = [e for e in _candidate_engines(has_mesh)
                      if e in ("ref", "pallas")]
            ests = {e: cm.estimate_ms(e, n_rows) for e in exacts}
            ests = {e: v for e, v in ests.items() if v is not None}
            floor_est = plan.est_cost_ms
            if ests and floor_est is not None:
                best = min(ests, key=lambda e: ests[e])
                if ests[best] < floor_est:
                    fresh = compile_plan(dataclasses.replace(
                        plan.logical, engine=best), **kw)
                    return dataclasses.replace(
                        fresh, degraded=plan.degraded + (f"ivf->{best}",))
        return None
    if plan.engine == "hybrid":
        dense = dataclasses.replace(plan.logical, match_terms=None,
                                    fusion="wsum", w_dense=1.0, w_lex=1.0,
                                    engine=None)
        fresh = compile_plan(dense, **kw)
        return dataclasses.replace(
            fresh, degraded=plan.degraded + ("hybrid->dense",))
    return None
