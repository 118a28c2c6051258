"""Quickstart: summarize a graph stream with HIGGS, answer a mixed batch
of typed temporal-range queries in one call (compared against the exact
oracle), then serve the same summary to concurrent callers with
epoch-consistent, coalesced reads.

    PYTHONPATH=src python examples/quickstart.py
"""
import asyncio

from repro.api import (EdgeQuery, PathQuery, SubgraphQuery, VertexQuery,
                       make_summary)
from repro.stream.generator import lkml_like_stream
from repro.stream.pipeline import StreamPipeline


def build(src, dst, w, t):
    # a communication-network-shaped stream (Lkml twin): 50k replies
    print(f"stream: {len(src)} edges, {src.max() + 1} vertices, "
          f"time span {t[-1]}")

    # make_summary returns a SummaryHandle: query/save/restore/
    # snapshot_epoch/serve is the whole session surface.  Any registered
    # summary builds the same way; try "horae" or "pgss"
    pipe = StreamPipeline(src, dst, w, t)
    sketch = pipe.feed_summary("higgs", d1=16, F1=19, b=3, r=4)
    oracle = StreamPipeline(src, dst, w, t).feed_summary("oracle")
    print(f"HIGGS: {len(sketch.leaf_starts)} leaves, "
          f"{sketch.n_levels} levels, "
          f"{sketch.space_bytes() / 1e6:.2f} MB, "
          f"leaf utilization {sketch.utilization():.2f}")
    return sketch, oracle


def typed_batch_demo(sketch, oracle, src, dst, t):
    ts, te = int(t[len(t) // 4]), int(t[len(t) // 2])
    print(f"\nTRQ range [{ts}, {te}]:")

    # one typed batch carrying every TRQ primitive; the planner runs
    # boundary search once and one device probe per (level, range class)
    batch = [
        EdgeQuery(src[:5], dst[:5], ts, te),
        VertexQuery(src[:3], ts, te, "out"),
        PathQuery([int(src[0]), int(dst[0]), int(dst[1])], ts, te),
        SubgraphQuery([(int(src[i]), int(dst[i])) for i in range(8)],
                      ts, te),
    ]
    est = sketch.query(batch)
    true = oracle.query(batch)

    edges_est, verts_est, path_est, sub_est = est.values
    edges_true, verts_true, path_true, sub_true = true.values
    for i in range(5):
        print(f"  edge {src[i]}->{dst[i]}: HIGGS={edges_est[i]:.0f} "
              f"exact={edges_true[i]:.0f}")
    for i in range(3):
        print(f"  vertex {src[i]} (out): HIGGS={verts_est[i]:.0f} "
              f"exact={verts_true[i]:.0f}")
    print(f"  path (3 vertices): HIGGS={path_est:.0f} exact={path_true:.0f}")
    print(f"  subgraph (8 edges): HIGGS={sub_est:.0f} exact={sub_true:.0f}")

    s = est.stats
    print(f"\nplanner stats: {s.n_queries} queries, "
          f"{s.boundary_searches} boundary search(es), "
          f"{s.plan_cache_hits} plan-cache hit(s), "
          f"{s.device_dispatches} device dispatches, "
          f"{s.buckets_probed} buckets probed "
          f"(served from epoch {est.epoch})")
    return batch


async def serve_demo(sketch, batch):
    """Eight concurrent callers against one service session: the readers
    coalesce all of them into ONE planner execution per round — one
    probe launch per (level, range class) for the whole fleet — served
    from an immutable read epoch."""
    async with sketch.serve(readers=2) as svc:
        results = await asyncio.gather(*[svc.submit(batch)
                                         for _ in range(8)])
    res = results[0]
    print(f"\nserving: 8 callers coalesced into "
          f"{svc.stats.rounds} round(s) "
          f"(factor {res.stats.coalesced}), epoch {res.epoch}, "
          f"{res.stats.device_dispatches} dispatches for everyone "
          f"combined")


def epoch_demo(sketch, src, dst, w, t):
    """A pinned read epoch answers identically forever, even while the
    live summary keeps ingesting."""
    span = int(t[-1])
    probe = [EdgeQuery(src[:5], dst[:5], 0, 2 * span + 1)]
    epoch = sketch.snapshot_epoch()
    before = epoch.query(probe).values[0]
    # a second day of identical traffic arrives (timestamps shifted past
    # the first day: streams are non-decreasing in t)
    sketch.insert(src, dst, w, t + span + 1)
    sketch.flush()
    after = epoch.query(probe).values[0]
    assert (before == after).all()
    live = sketch.query(probe).values[0]
    print(f"epoch {epoch.epoch} pinned: {before.tolist()} before and "
          f"after a second day of traffic (the live summary now "
          f"answers {live.tolist()})")


def main():
    src, dst, w, t = lkml_like_stream(n_edges=50_000, seed=7)
    sketch, oracle = build(src, dst, w, t)
    batch = typed_batch_demo(sketch, oracle, src, dst, t)
    asyncio.run(serve_demo(sketch, batch))
    epoch_demo(sketch, src, dst, w, t)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
