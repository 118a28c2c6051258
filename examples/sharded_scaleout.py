"""Sharded scale-out: partition-parallel ingestion and fan-out queries.

Builds the same balanced many-tenant stream into a single HIGGS sketch
and a 4-shard ``ShardedHiggs`` fleet, compares ingestion wall-clock,
then answers one mixed query batch on the fleet and shows the merged
``QueryStats`` (including fan-out breadth) and a crash-consistent
snapshot/restore of the whole fleet.

    PYTHONPATH=src python examples/sharded_scaleout.py
"""
import os
import tempfile
import time

import numpy as np

from repro.api import (EdgeQuery, PathQuery, SubgraphQuery, VertexQuery,
                       make_summary, restore_summary)
from repro.stream.generator import balanced_stream
from repro.stream.pipeline import StreamPipeline


def main():
    src, dst, w, t = balanced_stream(n_edges=60_000, seed=5)
    t_max = int(t[-1])
    print(f"stream: {len(src)} edges, ~{src.max() + 1} vertices "
          f"(balanced many-tenant shape), {os.cpu_count()} cores")

    results = {}
    for name, kw in (("higgs", {}), ("higgs-sharded", {"shards": 4})):
        sk = make_summary(name, d1=16, F1=19, **kw)
        t0 = time.perf_counter()
        StreamPipeline(src, dst, w, t, batch=32768).feed(sk)
        dt = time.perf_counter() - t0
        results[name] = (sk, dt)
        print(f"  {name:14s} ingest {dt:6.2f}s "
              f"({len(src) / dt:,.0f} edges/s)")
    fleet, dt_sharded = results["higgs-sharded"]
    print(f"shard speedup: {results['higgs'][1] / dt_sharded:.2f}x "
          f"(mode={fleet._mode}, {fleet.n_shards} shards, "
          f"{fleet.n_leaves} leaves total)")
    # per-batch shard-load telemetry: source partitioning is hostage to
    # per-source skew (the PR 4 Lkml hot-sender caveat) — a fleet that
    # routes > 50% of a batch to one shard warns once at ingest time
    print(fleet.partition_stats.summary())

    # the first stream edges carry the earliest timestamps, so a range
    # anchored at 0 makes the queried edges actually present
    ts, te = 0, t_max // 2
    batch = [
        EdgeQuery(src[:5], dst[:5], ts, te),
        VertexQuery(src[:3], ts, te, "out"),
        VertexQuery(dst[:3], ts, te, "in"),     # fans out via DstShardMap
        PathQuery([int(src[0]), int(dst[0]), int(dst[1])], ts, te),
        SubgraphQuery([(int(src[i]), int(dst[i])) for i in range(8)],
                      ts, te),
    ]
    res = fleet.query(batch)
    single = results["higgs"][0].query(batch)
    for i, q in enumerate(batch):
        a = np.asarray(res.values[i]).ravel()
        b = np.asarray(single.values[i]).ravel()
        print(f"  {type(q).__name__:14s} fleet={np.round(a, 1)} "
              f"single={np.round(b, 1)}")
    s = res.stats
    print(f"fleet stats: {s.n_queries} queries, "
          f"{s.shards_touched}/{fleet.n_shards} shards touched, "
          f"{s.device_dispatches} device dispatches, "
          f"{s.buckets_probed} buckets probed")

    # the whole fleet snapshots as ONE manifest (nested per-shard states)
    with tempfile.TemporaryDirectory() as ckpt:
        fleet.save(ckpt, step=0)
        again = restore_summary(ckpt)
        same = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(again.query(batch).values, res.values))
        print(f"snapshot -> restore_summary round trip: "
              f"{'bit-identical answers' if same else 'MISMATCH'}")
    fleet.close()


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
