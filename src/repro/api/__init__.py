"""Unified query API for graph-stream summaries.

* :mod:`repro.api.queries` — typed, batched query descriptions
  (``EdgeQuery``/``VertexQuery``/``PathQuery``/``SubgraphQuery``) and the
  ``QueryResult``/``QueryStats`` return types, and the ingest path's
  ``IngestStats`` counters.
* :mod:`repro.api.protocol` — the formal ``GraphSummary`` protocol plus the
  pointwise/batched adapter mixins.
* :mod:`repro.api.planner` — the batched query-plan engine for HIGGS.
* :mod:`repro.api.handle` — ``SummaryHandle``, the session façade
  ``make_summary``/``restore_summary`` return (query/save/restore/
  snapshot_epoch/serve).
* :mod:`repro.api.registry` — ``make_summary(name, **kw)``.
"""
from repro.api.handle import SummaryHandle
from repro.api.planner import QueryPlanner
from repro.api.protocol import (GraphSummary, LegacyQueryMixin,
                                PointwiseQueryMixin, SnapshotMixin)
from repro.api.queries import (EdgeQuery, IngestStats, PathQuery, Query,
                               QueryBatch, QueryResult, QueryStats,
                               SubgraphQuery, VertexQuery)
from repro.api.registry import (available_summaries, build_summary,
                                make_summary, register, restore_summary)

__all__ = [
    "EdgeQuery", "VertexQuery", "PathQuery", "SubgraphQuery",
    "Query", "QueryBatch", "QueryResult", "QueryStats", "IngestStats",
    "GraphSummary", "LegacyQueryMixin", "PointwiseQueryMixin",
    "SnapshotMixin", "QueryPlanner", "SummaryHandle",
    "make_summary", "build_summary", "register", "available_summaries",
    "restore_summary",
]
