"""Paper Fig. 12 + 13: path queries (1-7 hops) and subgraph queries —
AAE/ARE and latency, temporal range fixed (paper uses 1e5).

Each workload is timed two ways so the perf trajectory tracks the batched
query-plan engine against the legacy surface:

* ``path/...`` / ``subgraph/...`` — legacy per-call loop (one
  ``path_query``/``subgraph_query`` call per compound query; for HIGGS
  each call plans and probes on its own).
* ``path-batched/...`` / ``subgraph-batched/...`` — the whole workload as
  one typed batch through ``GraphSummary.query()``; HIGGS's planner runs
  one boundary search for the shared range and one device probe per
  (level, range-class) for the entire batch.
"""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.api import PathQuery, SubgraphQuery
from repro.stream.generator import lkml_like_stream


def run(n_edges: int = 80_000, n_queries: int = 64, seed: int = 0):
    stream = lkml_like_stream(n_edges=n_edges, seed=seed)
    src, dst, w, t = stream
    t_max = int(t[-1])
    l_bits = max(int(np.ceil(np.log2(t_max + 1))), 1)
    sketches = common.build_all(stream, l_bits)
    ora = common.build_oracle(stream)
    rng = np.random.default_rng(seed + 3)
    lq = min(10 ** 5, t_max)
    ts, te = common.rand_ranges(rng, t_max, lq, 1)[0]

    # paths from real edges chained through shared vertices
    for hops in (1, 3, 5, 7):
        paths = []
        for _ in range(n_queries):
            i = rng.integers(0, n_edges)
            path = [int(src[i]), int(dst[i])]
            for _ in range(hops - 1):
                path.append(int(dst[rng.integers(0, n_edges)]))
            paths.append(path)
        batch = [PathQuery(p, ts, te) for p in paths]
        true = [ora.path_query(p, ts, te) for p in paths]
        for name, (sk, _) in sketches.items():
            def run_paths(s=sk):
                return [s.path_query(p, ts, te) for p in paths]
            est, us_legacy = common.time_queries(run_paths, repeat=1)
            aae, are = common.aae_are(np.asarray(est), np.asarray(true))
            common.emit(f"path/{name}/hops={hops}", us_legacy / n_queries,
                        f"AAE={aae:.4g};ARE={are:.4g}")

            res, us_batched = common.time_queries(
                lambda s=sk: s.query(batch), repeat=1)
            np.testing.assert_allclose(np.asarray(res.values),
                                       np.asarray(est), rtol=1e-9)
            common.emit(f"path-batched/{name}/hops={hops}",
                        us_batched / n_queries,
                        f"speedup={us_legacy / max(us_batched, 1e-9):.2f}x;"
                        f"dispatches={res.stats.device_dispatches}")

    for size in (10, 40, 70):
        graphs = []
        for _ in range(max(n_queries // 4, 8)):
            idx = rng.integers(0, n_edges, size)
            graphs.append([(int(src[i]), int(dst[i])) for i in idx])
        batch = [SubgraphQuery(g, ts, te) for g in graphs]
        true = [ora.subgraph_query(g, ts, te) for g in graphs]
        for name, (sk, _) in sketches.items():
            def run_graphs(s=sk):
                return [s.subgraph_query(g, ts, te) for g in graphs]
            est, us_legacy = common.time_queries(run_graphs, repeat=1)
            aae, are = common.aae_are(np.asarray(est), np.asarray(true))
            common.emit(f"subgraph/{name}/size={size}",
                        us_legacy / len(graphs),
                        f"AAE={aae:.4g};ARE={are:.4g}")

            res, us_batched = common.time_queries(
                lambda s=sk: s.query(batch), repeat=1)
            np.testing.assert_allclose(np.asarray(res.values),
                                       np.asarray(est), rtol=1e-9)
            common.emit(f"subgraph-batched/{name}/size={size}",
                        us_batched / len(graphs),
                        f"speedup={us_legacy / max(us_batched, 1e-9):.2f}x;"
                        f"dispatches={res.stats.device_dispatches}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
