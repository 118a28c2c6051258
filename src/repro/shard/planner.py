"""Shard-aware query planning: route, fan out, merge.

Routing rules (see :mod:`repro.shard.partition`):

* Edge-lowered queries (``EdgeQuery``/``PathQuery``/``SubgraphQuery``)
  route **per edge** by ``shard_of(src)`` — each edge lives in exactly
  one shard, so the per-edge routing matrix is one-hot and the routed
  sum is a scatter in disguise.  The probe itself is *stacked*: per
  (level, time-range class) every owning shard's nodes are gathered
  once and probed with one
  :func:`repro.kernels.ops.edge_probe_stacked` launch.
* ``VertexQuery(direction="out")`` routes by ``shard_of(v)`` the same
  way.
* ``VertexQuery(direction="in")`` fans out: in-edges of a vertex are
  spread across shards, so the answer is the **sum** of per-shard
  answers over the shards in the vertex's :class:`DstShardMap` bitmask.
  The fan-in probe is *stacked*: per (level, time-range class), every
  contributing shard's node pool is gathered once and probed with one
  :func:`repro.kernels.ops.vertex_probe_stacked` launch — one device
  dispatch for all shards, mirroring the single-sketch planner's
  one-dispatch-per-(level, class) contract at the fleet level.

``QueryStats`` accounting: per-shard executions are folded in with
:meth:`QueryStats.absorb` (work counters sum across the fleet while
``n_queries`` stays the *caller's* batch size — sub-batches are an
implementation detail), and every shard that did any work sets its bit
in ``shard_mask``, so merging two fleet results composes associatively:
the union never double-counts a shard both executions probed.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import numpy as np

from repro.api.planner import _pad_q
from repro.api.queries import (EDGE_LOWERED, QueryBatch, QueryResult,
                               QueryStats, VertexQuery)
from repro.core import cmatrix
from repro.core.cmatrix import pow2_pad as _pow2_pad
from repro.shard.partition import shard_of

if TYPE_CHECKING:  # summary imports this module
    from repro.shard.summary import ShardedHiggs


class ShardedQueryPlanner:
    """Executes typed query batches against a :class:`ShardedHiggs`.

    Stateless beyond lifetime accounting: plan memoization lives in each
    shard's own :class:`~repro.api.planner.QueryPlanner`, which also
    keeps restore-time invalidation per shard (``load_state`` on a shard
    reseeds its cache exactly like the unsharded path).
    """

    def __init__(self, summary: "ShardedHiggs"):
        self.summary = summary
        self.lifetime = QueryStats()

    def execute(self, queries: QueryBatch) -> QueryResult:
        sm = self.summary
        S = sm.n_shards
        stats = QueryStats(n_queries=len(queries))
        values: list = [None] * len(queries)

        sub: list[list] = [[] for _ in range(S)]     # per-shard sub-batch
        recs: list[list] = [[] for _ in range(S)]    # (qi, scatter idx)
        acc: dict[int, np.ndarray] = {}              # qi -> per-item values
        fanin: dict[tuple[int, int], list] = {}      # (ts, te) -> [(qi, v)]
        fanin_e: dict[tuple[int, int], list] = {}    # (ts,te)->[(qi,src,dst)]

        for qi, q in enumerate(queries):
            if isinstance(q, EDGE_LOWERED):
                src, dst = q.edge_arrays()
                if len(src) == 0:
                    values[qi] = q.reduce(np.zeros((0,), np.float64))
                    continue
                fanin_e.setdefault((q.ts, q.te), []).append((qi, src, dst))
            elif isinstance(q, VertexQuery):
                if q.direction == "out":
                    acc[qi] = np.zeros((len(q.v),), np.float64)
                    sids = shard_of(q.v, S, sm.params.seed)
                    for s in np.unique(sids):
                        idx = np.nonzero(sids == s)[0]
                        sub[s].append(VertexQuery(q.v[idx], q.ts, q.te,
                                                  "out"))
                        recs[s].append((qi, idx))
                else:
                    fanin.setdefault((q.ts, q.te), []).append((qi, q.v))
            else:
                raise TypeError(
                    f"unsupported query type: {type(q).__name__}")

        touched = np.zeros((S,), bool)
        for s in range(S):
            if not sub[s]:
                continue
            touched[s] = True
            res = sm.shards[s].query(sub[s])
            stats.absorb(res.stats)
            for (qi, idx), val in zip(recs[s], res.values):
                acc[qi][idx] = np.asarray(val, np.float64)

        for (ts, te), jobs in fanin_e.items():
            src = np.concatenate([s_ for _, s_, _ in jobs])
            dst = np.concatenate([d_ for _, _, d_ in jobs])
            out, used = self._fanin_edge(src, dst, ts, te, stats)
            touched |= used
            off = 0
            for qi, s_, _ in jobs:
                acc[qi] = out[off:off + len(s_)]
                off += len(s_)

        for (ts, te), jobs in fanin.items():
            vs = np.concatenate([v for _, v in jobs])
            out, used = self._fanin_vertex(vs, ts, te, stats)
            touched |= used
            off = 0
            for qi, v in jobs:
                acc[qi] = out[off:off + len(v)]
                off += len(v)

        for qi, q in enumerate(queries):
            if values[qi] is None:
                values[qi] = q.reduce(acc[qi])

        for s in np.nonzero(touched)[0]:
            stats.shard_mask |= 1 << int(s)
        self.lifetime.merge(stats)
        return QueryResult(values, stats,
                           epoch=int(sm.structure_version))

    # ------------------------------------------------------------------
    # stacked fan-in probe for edge-lowered queries
    # ------------------------------------------------------------------

    def _fanin_edge(self, src: np.ndarray, dst: np.ndarray, ts: int,
                    te: int, stats: QueryStats):
        """(q,) edge answers over the owning shards, plus the (S,) mask
        of shards that contributed any probe.

        Each edge lives in exactly one shard (``shard_of(src)``), so the
        routing matrix is one-hot per column and the routed sum
        degenerates to the legacy per-shard scatter — bit-identically:
        each query's probe reduces over its own padded node axis alone,
        and the cross-shard combine adds exact zeros.  What changes is
        the launch shape: per (level, range class) the fleet issues ONE
        :func:`repro.kernels.ops.edge_probe_stacked` dispatch instead of
        one per shard, the same contract `_fanin_vertex` already keeps.
        """
        sm = self.summary
        S = sm.n_shards
        q = len(src)
        sids = shard_of(src, S, sm.params.seed)
        route = np.zeros((S, q), bool)
        route[sids, np.arange(q)] = True
        shard_ids = [s for s in range(S) if route[s].any()]
        out = np.zeros((q,), np.float64)
        used = np.zeros((S,), bool)
        if not shard_ids:
            return out, used
        used[shard_ids] = True
        # identical params across shards => identical query coordinates
        f1s, bs = sm.shards[0]._query_coords(src, "s")
        f1d, bd = sm.shards[0]._query_coords(dst, "d")

        plans = {s: sm.shards[s].planner.plan(ts, te, stats)
                 for s in shard_ids}
        levels = sorted({lvl for plan, _ in plans.values() for lvl in plan})
        for level in levels:
            per_shard = [(s, np.asarray(plans[s][0][level]))
                         for s in shard_ids if level in plans[s][0]]
            out += self._probe_level_stacked_edge(
                per_shard, route, level, f1s, bs, f1d, bd, ts, te, False,
                stats)
            for s, ids in per_shard:
                ob = sm.shards[s].planner._ob_edge(
                    level, ids, f1s, bs, f1d, bd, ts, te, False, stats)
                out += ob * route[s]
        filt = [(s, np.asarray(plans[s][1])) for s in shard_ids
                if plans[s][1]]
        if filt:
            out += self._probe_level_stacked_edge(
                filt, route, 1, f1s, bs, f1d, bd, ts, te, True, stats)
            for s, ids in filt:
                ob = sm.shards[s].planner._ob_edge(
                    1, ids, f1s, bs, f1d, bd, ts, te, True, stats)
                out += ob * route[s]
        return out, used

    def _probe_level_stacked_edge(self, per_shard, route, level, f1s, bs,
                                  f1d, bd, ts, te, filter_time,
                                  stats: QueryStats):
        """One stacked edge-probe launch over every contributing shard's
        nodes at one (level, range class); routed (q,) float64 sum."""
        from repro.kernels import ops
        sm = self.summary
        live, nodes, mask = self._stack_pools(per_shard, level)
        q = len(np.asarray(f1s))
        if not live:
            return np.zeros((q,), np.float64)
        p = sm.params
        r = p.r if p.use_mmb else 1
        fs_l, rows = cmatrix.coords_at_level(f1s, bs, level, p)
        fd_l, cols = cmatrix.coords_at_level(f1d, bd, level, p)
        # pad the query axis to a pow2 bucket so variable coalesced batch
        # sizes (serving) reuse a bounded set of compile keys; padded
        # lanes are sliced away, accounting stays on the true q
        fs_l, rows, fd_l, cols = (
            _pad_q(a, q) for a in (fs_l, rows, fd_l, cols))
        stats.device_dispatches += 1
        stats.buckets_probed += sum(len(ids) for _, ids in live) \
            * r * r * q
        res = sm.run_stacked(ops.edge_probe_stacked, nodes, mask, fs_l,
                             fd_l, rows, cols, np.uint32(ts),
                             np.uint32(te), match_time=filter_time)
        part = np.asarray(res, np.float64)[:len(live), :q]
        sel = np.stack([route[s] for s, _ in live])  # (k, q)
        return (part * sel).sum(axis=0)

    def _stack_pools(self, per_shard, level):
        """(live list, stacked NodeState, stacked mask) for the shards
        that have probe-able nodes at ``level`` — the shared gather half
        of both stacked fan-in paths."""
        import jax.numpy as jnp
        sm = self.summary
        live = [(s, ids) for s, ids in per_shard
                if len(ids) and level <= len(sm.shards[s].pools)
                and sm.shards[s].pools[level - 1].n > 0]
        if not live:
            return live, None, None
        pad = _pow2_pad(max(len(ids) for _, ids in live))
        gathered = [sm.shards[s].pools[level - 1].gather(ids, pad)
                    for s, ids in live]
        if sm.mesh is not None:
            # pad the shard axis to whole devices with masked-off rows
            # (sliced away by the callers): every launch spans the mesh
            n_pad = -len(gathered) % sm.mesh.devices.size
            gathered += [(gathered[0][0],
                          jnp.zeros_like(gathered[0][1]))] * n_pad
        nodes = type(gathered[0][0])(
            *(jnp.stack([getattr(g[0], name) for g in gathered])
              for name in type(gathered[0][0])._fields))
        mask = jnp.stack([g[1] for g in gathered])
        nodes, mask = sm.place_stacked(nodes, mask)
        return live, nodes, mask

    # ------------------------------------------------------------------
    # stacked fan-in probe for ``in`` direction vertex queries
    # ------------------------------------------------------------------

    def _fanin_vertex(self, vs: np.ndarray, ts: int, te: int,
                      stats: QueryStats):
        """(q,) summed answers over the routed shards, plus the (S,) mask
        of shards that contributed any probe."""
        sm = self.summary
        route = sm.dst_map.routing_matrix(vs)        # (S, q) bool
        shard_ids = [s for s in range(sm.n_shards) if route[s].any()]
        out = np.zeros((len(vs),), np.float64)
        used = np.zeros((sm.n_shards,), bool)
        if not shard_ids:
            return out, used
        used[shard_ids] = True
        # identical params across shards => identical query coordinates
        f1, base = sm.shards[0]._query_coords(vs, "d")

        plans = {s: sm.shards[s].planner.plan(ts, te, stats)
                 for s in shard_ids}
        levels = sorted({lvl for plan, _ in plans.values() for lvl in plan})
        for level in levels:
            per_shard = [(s, np.asarray(plans[s][0][level]))
                         for s in shard_ids if level in plans[s][0]]
            out += self._probe_level_stacked(per_shard, route, level, f1,
                                             base, ts, te, False, stats)
            for s, ids in per_shard:
                ob = sm.shards[s].planner._ob_vertex(
                    level, ids, f1, base, ts, te, "in", False, stats)
                out += ob * route[s]
        filt = [(s, np.asarray(plans[s][1])) for s in shard_ids
                if plans[s][1]]
        if filt:
            out += self._probe_level_stacked(filt, route, 1, f1, base,
                                             ts, te, True, stats)
            for s, ids in filt:
                ob = sm.shards[s].planner._ob_vertex(
                    1, ids, f1, base, ts, te, "in", True, stats)
                out += ob * route[s]
        return out, used

    def _probe_level_stacked(self, per_shard, route, level, f1, base,
                             ts, te, filter_time, stats: QueryStats):
        """One stacked launch over every contributing shard's nodes at
        one (level, range class); returns the routed (q,) float64 sum."""
        from repro.kernels import ops
        sm = self.summary
        live, nodes, mask = self._stack_pools(per_shard, level)
        q = len(np.asarray(f1))
        if not live:
            return np.zeros((q,), np.float64)
        p = sm.params
        r = p.r if p.use_mmb else 1
        f_l, rows = cmatrix.coords_at_level(f1, base, level, p)
        f_l, rows = _pad_q(f_l, q), _pad_q(rows, q)
        stats.device_dispatches += 1
        stats.buckets_probed += sum(len(ids) for _, ids in live) \
            * r * p.d(level) * q
        res = sm.run_stacked(ops.vertex_probe_stacked, nodes, mask, f_l,
                             rows, np.uint32(ts), np.uint32(te),
                             direction="in", match_time=filter_time)
        part = np.asarray(res, np.float64)[:len(live), :q]
        sel = np.stack([route[s] for s, _ in live])  # (k, q)
        return (part * sel).sum(axis=0)
