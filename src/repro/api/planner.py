"""Batched query-plan engine for the HIGGS sketch.

The legacy surface executed every query independently: one boundary search
per call, then one device dispatch per tree level — so a 64-path compound
workload with a shared time range paid 64x the planning and 64x the device
round-trips.  The planner restores the paper's locality argument at the
batch level:

1. Lower the batch: Edge/Path/Subgraph queries become slices of one
   concatenated (src, dst) edge batch per distinct ``[ts, te]`` range
   (a *time-range class*); VertexQuery batches group by (range, direction).
2. Plan once per range class: ``boundary_search`` runs once per distinct
   range, and its (plan, filtered) decomposition is memoized across
   ``query()`` calls until the next insertion mutates the tree.
3. Probe once per (level, range class): one pool gather + one probe kernel
   launch covers every query coordinate in the class, then per-query
   results are scattered back and reduced (sum for Path/Subgraph).

``QueryStats.device_dispatches`` counts the launches, making the
<= 1-per-(level, range-class) contract checkable by tests.

Windowed sketches change nothing structurally here: plans carry stable
*global* node ids (``_LevelPool.gather`` translates them to physical
window slots), coarse-segment roots arrive as ordinary plan entries at
the segment-root level, and every eviction/coarsening bumps
``structure_version`` so memoized plans over reclaimed nodes can never
be replayed.
"""
from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.queries import (EDGE_LOWERED, QueryBatch, QueryResult,
                               QueryStats, VertexQuery)
from repro.core import cmatrix
from repro.core.cmatrix import NodeState
from repro.core.cmatrix import pow2_pad as _pow2_pad
from repro.runtime.trace import spanned

if TYPE_CHECKING:  # avoid a circular import; higgs imports this module
    from repro.core.higgs import HiggsSketch


def _pad_q(a, q: int) -> np.ndarray:
    """Zero-pad a (q,)-shaped query-coordinate array to its pow2 bucket.

    Probes are pure reads, so the padded lanes compute garbage that the
    caller slices away; what matters is that a serving workload with
    variable coalesced batch sizes reuses O(log q) compile keys instead
    of one per distinct q (higgsxla rule X2).  Only the leading (query)
    axis pads — row-coordinate arrays are (q, r)."""
    a = np.asarray(a)
    qp = _pow2_pad(q)
    if qp == q:
        return a
    return np.pad(a, [(0, qp - q)] + [(0, 0)] * (a.ndim - 1))


# ---------------------------------------------------------------------------
# fused probe launches
# ---------------------------------------------------------------------------
#
# One jitted launch per (level, time-range class): the pool-row take,
# level-coordinate derivation and probe reduce fuse over the resident
# slabs from ``_LevelPool.device_view()``.  Only the probed row indices,
# the plan's leaf coordinates and the two time scalars cross to the
# device per launch; the slabs themselves upload at most once per
# mutation epoch (device storage: never).  ``params``/``level`` are
# static (HiggsParams is frozen), so the cache keys by (slab shape, pad,
# level, match_time) exactly as the higgsxla corpus declares.

@functools.partial(jax.jit,
                   static_argnames=("level", "params", "match_time"))
def _edge_probe_fused(slabs: NodeState, idx, mask, f1s, bs, f1d, bd,
                      ts, te, *, level: int, params, match_time: bool):
    nodes = NodeState(*(jnp.take(f, idx, axis=0) for f in slabs))
    fs_l, rows = cmatrix.coords_at_level(f1s, bs, level, params)
    fd_l, cols = cmatrix.coords_at_level(f1d, bd, level, params)
    return cmatrix.probe_edge(nodes, mask, fs_l, fd_l, rows, cols,
                              ts, te, match_time=match_time)


@functools.partial(jax.jit,
                   static_argnames=("level", "params", "direction",
                                    "match_time"))
def _vertex_probe_fused(slabs: NodeState, idx, mask, f1, base, ts, te, *,
                        level: int, params, direction: str,
                        match_time: bool):
    nodes = NodeState(*(jnp.take(f, idx, axis=0) for f in slabs))
    f_l, rows = cmatrix.coords_at_level(f1, base, level, params)
    return cmatrix.probe_vertex(nodes, mask, f_l, rows, ts, te,
                                direction=direction,
                                match_time=match_time)


class QueryPlanner:
    """Executes typed query batches against one :class:`HiggsSketch`."""

    # memoized plans are tiny, but a read-only phase serving arbitrarily
    # many distinct ranges must not grow memory without bound
    MAX_CACHED_PLANS = 1024

    def __init__(self, sketch: "HiggsSketch"):
        self.sketch = sketch
        self.lifetime = QueryStats()       # accumulated across executions
        self._plan_cache: dict[tuple[int, int], tuple[dict, list]] = {}
        self._cache_version = -1
        # True while the cache dict is shared with another planner
        # (warm cross-epoch adoption); any mutation first rebinds to a
        # private shallow copy — plan *values* are immutable and stay
        # shared either way
        self._cache_shared = False

    # ------------------------------------------------------------------
    # planning
    # ------------------------------------------------------------------

    def plan(self, ts: int, te: int, stats: QueryStats):
        """Memoized boundary search; invalidated when the tree mutates.

        Eviction is LRU: a hit re-inserts the plan at the back of the
        (insertion-ordered) dict, so steady-state serving of a few hot
        ranges keeps them resident no matter how many cold ranges churn
        through — evicting the oldest-*inserted* plan used to drop the
        hottest entry first.
        """
        version = self.sketch.structure_version
        if version != self._cache_version:
            # rebind, never clear in place: the old dict may be shared
            # with epoch replicas pinned at the previous version
            self._plan_cache = {}
            self._cache_shared = False
            self._cache_version = version
        key = (int(ts), int(te))
        cached = self._plan_cache.get(key)
        self._own_cache()
        if cached is None:
            cached = self.sketch.boundary_search(ts, te)
            if len(self._plan_cache) >= self.MAX_CACHED_PLANS:
                self._plan_cache.pop(next(iter(self._plan_cache)))
            stats.boundary_searches += 1
            stats.plan_cache_misses += 1
        else:
            stats.plan_cache_hits += 1
            self._plan_cache.pop(key)
        self._plan_cache[key] = cached
        return cached

    def _own_cache(self) -> None:
        """Copy-on-write un-share: a shallow dict copy (the plan values
        themselves are never copied) before the first mutation after a
        warm adoption."""
        if self._cache_shared:
            self._plan_cache = dict(self._plan_cache)
            self._cache_shared = False

    def adopt_cache(self, donor: "QueryPlanner", *,
                    copy: bool = False) -> None:
        """Warm cross-epoch plan reuse: adopt the donor's memoized plans.

        Plans are pure functions of the tree structure, so a replica
        whose frozen ``structure_version`` matches the version the
        donor's cache was built against can adopt it wholesale — the
        first answer on a fresh epoch pin then costs zero boundary
        searches.  A stale donor cache (the writer mutated since it last
        planned) or an empty one is ignored.

        Default is zero-copy: both planners share the dict and flip to
        copy-on-write, so neither side's later mutations (LRU reorder,
        inserts, ``invalidate``) can reach the other.  ``copy=True``
        (the deep-pin path) takes a private shallow copy up front.
        """
        if donor._cache_version != self.sketch.structure_version \
                or not donor._plan_cache:
            return
        if copy:
            self._plan_cache = dict(donor._plan_cache)
            self._cache_shared = False
        else:
            donor._cache_shared = True
            self._plan_cache = donor._plan_cache
            self._cache_shared = True
        self._cache_version = donor._cache_version

    def invalidate(self) -> None:
        """Drop every memoized plan and re-seed the cache epoch from the
        sketch's current ``structure_version``.  Called after a snapshot
        restore: the version counter alone cannot be trusted across
        restores (a different tree can legitimately carry the same
        count), so restoring must invalidate explicitly.

        Copy-on-invalidate: the cache is *rebound* to a fresh dict, not
        cleared in place, so invalidating a pinned epoch replica can
        never empty a cache it shares with the live writer."""
        self._plan_cache = {}
        self._cache_shared = False
        self._cache_version = self.sketch.structure_version

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def execute(self, queries: QueryBatch) -> QueryResult:
        stats = QueryStats(n_queries=len(queries))
        values: list = [None] * len(queries)

        # lower: group by time-range class (and direction for vertices)
        edge_groups: dict[tuple[int, int], list] = {}
        vertex_groups: dict[tuple[int, int, str], list] = {}
        for qi, q in enumerate(queries):
            if isinstance(q, EDGE_LOWERED):
                src, dst = q.edge_arrays()
                edge_groups.setdefault((q.ts, q.te), []).append(
                    (qi, src, dst))
            elif isinstance(q, VertexQuery):
                vertex_groups.setdefault((q.ts, q.te, q.direction),
                                         []).append((qi, q.v))
            else:
                raise TypeError(
                    f"unsupported query type: {type(q).__name__}")

        for (ts, te), jobs in edge_groups.items():
            src = np.concatenate([s for _, s, _ in jobs])
            dst = np.concatenate([d for _, _, d in jobs])
            out = self._edge_batch(src, dst, ts, te, stats)
            off = 0
            for qi, s, _ in jobs:
                values[qi] = queries[qi].reduce(out[off:off + len(s)])
                off += len(s)

        for (ts, te, direction), jobs in vertex_groups.items():
            v = np.concatenate([x for _, x in jobs])
            out = self._vertex_batch(v, ts, te, direction, stats)
            off = 0
            for qi, x in jobs:
                values[qi] = queries[qi].reduce(out[off:off + len(x)])
                off += len(x)

        self.lifetime.merge(stats)
        return QueryResult(values, stats,
                           epoch=int(self.sketch.structure_version))

    # ------------------------------------------------------------------
    # batched probes: one gather + one kernel launch per (level, class)
    # ------------------------------------------------------------------

    def _edge_batch(self, src, dst, ts, te, stats: QueryStats) -> np.ndarray:
        sk = self.sketch
        out = np.zeros((len(src),), np.float64)
        if len(src) == 0:
            return out
        f1s, bs = sk._query_coords(src, "s")
        f1d, bd = sk._query_coords(dst, "d")
        plan, filtered = self.plan(ts, te, stats)
        for level, ids in sorted(plan.items()):
            out += self._probe_level_edge(level, np.asarray(ids), f1s, bs,
                                          f1d, bd, ts, te, False, stats)
            out += self._ob_edge(level, ids, f1s, bs, f1d, bd, ts, te,
                                 False, stats)
        if filtered:
            out += self._probe_level_edge(1, np.asarray(filtered), f1s, bs,
                                          f1d, bd, ts, te, True, stats)
            out += self._ob_edge(1, filtered, f1s, bs, f1d, bd, ts, te,
                                 True, stats)
        return out

    def _vertex_batch(self, v, ts, te, direction,
                      stats: QueryStats) -> np.ndarray:
        sk = self.sketch
        out = np.zeros((len(v),), np.float64)
        if len(v) == 0:
            return out
        side = "s" if direction == "out" else "d"
        f1, base = sk._query_coords(v, side)
        plan, filtered = self.plan(ts, te, stats)
        for level, ids in sorted(plan.items()):
            out += self._probe_level_vertex(level, np.asarray(ids), f1, base,
                                            ts, te, direction, False, stats)
            out += self._ob_vertex(level, ids, f1, base, ts, te, direction,
                                   False, stats)
        if filtered:
            out += self._probe_level_vertex(1, np.asarray(filtered), f1,
                                            base, ts, te, direction, True,
                                            stats)
            out += self._ob_vertex(1, filtered, f1, base, ts, te, direction,
                                   True, stats)
        return out

    # -- device probes ---------------------------------------------------

    @spanned("higgs.probe")
    def _probe_level_edge(self, level, ids, f1s, bs, f1d, bd, ts, te,
                          filter_time, stats: QueryStats):
        sk = self.sketch
        if len(ids) == 0 or level > len(sk.pools) or \
                sk.pools[level - 1].n == 0:
            return 0.0
        p = sk.params
        r = p.r if p.use_mmb else 1
        q = len(np.asarray(f1s))
        stats.device_dispatches += 1
        stats.buckets_probed += len(ids) * r * r * q
        pool = sk.pools[level - 1]
        idx, mask = pool.gather_ids(ids, _pow2_pad(len(ids)))
        res = _edge_probe_fused(pool.device_view(), idx, mask,
                                jnp.asarray(_pad_q(f1s, q), jnp.uint32),
                                jnp.asarray(_pad_q(bs, q), jnp.uint32),
                                jnp.asarray(_pad_q(f1d, q), jnp.uint32),
                                jnp.asarray(_pad_q(bd, q), jnp.uint32),
                                np.uint32(ts), np.uint32(te),
                                level=level, params=p,
                                match_time=filter_time)
        return np.asarray(res, np.float64)[:q]

    @spanned("higgs.probe")
    def _probe_level_vertex(self, level, ids, f1, base, ts, te, direction,
                            filter_time, stats: QueryStats):
        sk = self.sketch
        if len(ids) == 0 or level > len(sk.pools) or \
                sk.pools[level - 1].n == 0:
            return 0.0
        p = sk.params
        r = p.r if p.use_mmb else 1
        q = len(np.asarray(f1))
        stats.device_dispatches += 1
        stats.buckets_probed += len(ids) * r * p.d(level) * q
        pool = sk.pools[level - 1]
        idx, mask = pool.gather_ids(ids, _pow2_pad(len(ids)))
        res = _vertex_probe_fused(pool.device_view(), idx, mask,
                                  jnp.asarray(_pad_q(f1, q), jnp.uint32),
                                  jnp.asarray(_pad_q(base, q),
                                              jnp.uint32),
                                  np.uint32(ts), np.uint32(te),
                                  level=level, params=p,
                                  direction=direction,
                                  match_time=filter_time)
        return np.asarray(res, np.float64)[:q]

    # -- host-side overflow-block probes ---------------------------------
    # (also composed by repro.shard.planner.ShardedQueryPlanner, whose
    # stacked fan-in path pairs each shard's plan with these OB scans)

    @spanned("higgs.ob_scan")
    def _ob_edge(self, level, ids, f1s, bs, f1d, bd, ts, te, filter_time,
                 stats: QueryStats):
        ob = self.sketch.ob
        f1s, bs = np.asarray(f1s), np.asarray(bs)
        f1d, bd = np.asarray(f1d), np.asarray(bd)
        out = np.zeros((len(f1s),), np.float64)
        for nid in ids:
            rec = ob.get(level, int(nid))
            if not rec:
                continue
            stats.ob_probes += 1
            tok = np.ones(len(rec["w"]), bool) if not filter_time else \
                (rec["t"] >= ts) & (rec["t"] <= te)
            m = (rec["f1s"][None, :] == f1s[:, None]) & \
                (rec["f1d"][None, :] == f1d[:, None]) & \
                (rec["bs"][None, :] == bs[:, None]) & \
                (rec["bd"][None, :] == bd[:, None]) & tok[None, :]
            out += (m * rec["w"][None, :]).sum(axis=1)
        return out

    @spanned("higgs.ob_scan")
    def _ob_vertex(self, level, ids, f1, base, ts, te, direction,
                   filter_time, stats: QueryStats):
        ob = self.sketch.ob
        f1, base = np.asarray(f1), np.asarray(base)
        fk, bk = ("f1s", "bs") if direction == "out" else ("f1d", "bd")
        out = np.zeros((len(f1),), np.float64)
        for nid in ids:
            rec = ob.get(level, int(nid))
            if not rec:
                continue
            stats.ob_probes += 1
            tok = np.ones(len(rec["w"]), bool) if not filter_time else \
                (rec["t"] >= ts) & (rec["t"] <= te)
            m = (rec[fk][None, :] == f1[:, None]) & \
                (rec[bk][None, :] == base[:, None]) & tok[None, :]
            out += (m * rec["w"][None, :]).sum(axis=1)
        return out


# ---------------------------------------------------------------------------
# higgsxla shape corpus: the production probe launches
# ---------------------------------------------------------------------------
#
# ``_probe_level_edge``/``_probe_level_vertex`` dispatch ONE jitted
# launch (`_edge_probe_fused`/`_vertex_probe_fused`): pool-row take +
# coordinate derivation + probe reduce fused over the resident slabs.
# Per launch only the row indices, mask, plan coordinates and np.uint32
# time scalars cross to the device — the slab operand stays resident
# (``_LevelPool.device_view`` re-uploads host-storage pools at most once
# per mutation epoch; that barrier is inventoried separately as
# ``planner.pool_sync``).  ``jit_in_production=True``: the former eager
# X1 findings are retired by this fusion, not re-baselined.

def xla_entry_points():
    import jax
    import jax.numpy as jnp

    from repro.analysis.xla.registry import EntryPoint, TraceCase
    from repro.core.cmatrix import NodeState
    from repro.core.params import HiggsParams

    p = HiggsParams()
    b = p.b
    u32, i32, f32 = jnp.uint32, jnp.int32, jnp.float32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    def slabs(cap, d):
        shp = (cap, d, d, b)
        return NodeState(sds(shp, u32), sds(shp, u32), sds(shp, f32),
                         sds(shp, u32), sds(shp, u32))

    def edge_args(cap, m, q, d):
        return (slabs(cap, d), sds((m,), i32), sds((m,), jnp.bool_),
                sds((q,), u32), sds((q,), u32), sds((q,), u32),
                sds((q,), u32), sds((), u32), sds((), u32))

    def build_edge():
        d1, d2 = p.d1, p.d(2)
        cases = [
            # two pow2 gather buckets at level 1 + one level-2 shape:
            # three declared compile keys for the plan-level launches
            TraceCase("L1_m8_q16", edge_args(64, 8, 16, d1),
                      {"level": 1, "params": p, "match_time": False}),
            TraceCase("L1_m16_q16", edge_args(64, 16, 16, d1),
                      {"level": 1, "params": p, "match_time": False}),
            TraceCase("L2_m8_q16", edge_args(16, 8, 16, d2),
                      {"level": 2, "params": p, "match_time": False}),
            # the filtered re-probe at level 1 (distinct static arg)
            TraceCase("L1_m8_q16_filtered", edge_args(64, 8, 16, d1),
                      {"level": 1, "params": p, "match_time": True}),
        ]
        return _edge_probe_fused, ("level", "params", "match_time"), cases

    def build_vertex():
        d1 = p.d1
        args = (slabs(64, d1), sds((8,), i32), sds((8,), jnp.bool_),
                sds((16,), u32), sds((16,), u32), sds((), u32),
                sds((), u32))
        cases = [
            TraceCase("L1_m8_q16_out", args,
                      {"level": 1, "params": p, "direction": "out",
                       "match_time": False}),
            TraceCase("L1_m8_q16_in", args,
                      {"level": 1, "params": p, "direction": "in",
                       "match_time": False}),
        ]
        return (_vertex_probe_fused,
                ("level", "params", "direction", "match_time"), cases)

    def build_pool_sync():
        # the per-mutation-epoch device_view upload of a host-storage
        # level-1 pool (cap=64 is the steady smoke-workload bucket):
        # the one h2d barrier a query burst pays between drains.  Under
        # device storage this transfer does not exist at all.
        def pool_sync(fp_s, fp_d, w, t, idx):
            return (fp_s, fp_d, w, t, idx)

        args = tuple(slabs(64, p.d1))
        return (jax.jit(pool_sync), (),
                [TraceCase("L1_cap64", args, {})])

    return [
        EntryPoint("planner.edge_probe", build_edge,
                   host_args=(1, 2, 3, 4, 5, 6, 7, 8),
                   fetch_output=True,
                   jit_in_production=True, expected_compile_keys=4),
        EntryPoint("planner.vertex_probe", build_vertex,
                   host_args=(1, 2, 3, 4, 5, 6), fetch_output=True,
                   jit_in_production=True, expected_compile_keys=2),
        EntryPoint("planner.pool_sync", build_pool_sync,
                   host_args=(0, 1, 2, 3, 4), fetch_output=False,
                   jit_in_production=True, expected_compile_keys=1),
    ]
