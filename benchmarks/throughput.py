"""Paper Fig. 16-18: insertion throughput, insertion latency, and
deletion throughput (deletion = negative-weight insertion).

Also reports the HIGGS serial-vs-batched ingestion comparison (PR 2):
the legacy one-launch-per-leaf reference path against the batched
multi-leaf engine, fed in leaf-aligned batches; and the sharded
scale-out comparison (PR 4): ``ShardedHiggs`` partition-parallel
ingestion at ``--shards S`` against the S=1 degenerate case, on the
balanced many-tenant stream (source-partition parallelism measures the
engine, not the workload's skew — see ``balanced_stream``).  All
variants are warmed with one pass first so the numbers are
steady-state ingestion, not XLA compile or worker-fork time.

``--smoke`` runs scaled-down versions of both comparisons and fails
loudly on regression — the CI gate for the ingestion path.  With
``--json PATH`` it writes the machine-readable result file CI compares
against ``benchmarks/baselines/BENCH_baseline.json`` (see
``benchmarks.compare_bench``) and uploads as a build artifact.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from benchmarks import common
from benchmarks.common import record, write_json
from repro.stream.generator import balanced_stream, lkml_like_stream

# the metric store lives in benchmarks.common (shared with space.py);
# METRICS is re-exported for older tooling that poked it here
METRICS = common.METRICS


def _feed(sk, stream, batch: int) -> float:
    src, dst, w, t = stream
    n = len(src)
    t0 = time.perf_counter()
    for s in range(0, n, batch):
        sk.insert(src[s:s + batch], dst[s:s + batch], w[s:s + batch],
                  t[s:s + batch])
    sk.flush()
    return time.perf_counter() - t0


def serial_vs_batched(stream, repeat: int = 1):
    """Steady-state ingestion seconds for the serial reference path and
    the batched engine; returns (serial_s, batched_s, sketches)."""
    from repro.core.higgs import HiggsSketch
    from repro.core.params import HiggsParams

    n = len(stream[0])
    params = {
        # both flags explicit: the comparison must not drift with the
        # HIGGS_BATCHED_INGEST env default the CI matrix flips
        "serial": HiggsParams(d1=16, F1=19, batched_ingest=False,
                              insert_backend="host"),
        "batched": HiggsParams(d1=16, F1=19, batched_ingest=True),
    }
    secs, sketches = {}, {}
    for tag, p in params.items():
        batch = max(p.chunk_size, 8192 // p.chunk_size * p.chunk_size)
        _feed(HiggsSketch(p), stream, batch)        # warm all shapes
        best = float("inf")
        for _ in range(repeat):
            sk = HiggsSketch(p)
            best = min(best, _feed(sk, stream, batch))
        secs[tag] = best
        sketches[tag] = sk
        common.emit(f"throughput/ingest/higgs_{tag}", best / n * 1e6,
                    f"edges_per_s={n / best:.0f}")
    common.emit("throughput/ingest/batched_speedup",
                secs["serial"] / secs["batched"],
                f"serial_s={secs['serial']:.2f};"
                f"batched_s={secs['batched']:.2f}")
    return secs["serial"], secs["batched"], sketches


def sharded_scaleout(stream, shards: int, repeat: int = 3):
    """Steady-state ingestion seconds for ``ShardedHiggs`` at S=shards
    vs the S=1 degenerate case; returns (s1_s, sharded_s, summaries).

    Both variants feed the identical leaf-aligned batches; the sharded
    instance is primed with one empty insert before the clock starts so
    worker-fork time (a per-process constant, not a per-edge cost) stays
    out of the steady-state number.  Repeats are *interleaved* (s1, sS,
    s1, sS, ...) and each side keeps its best, so machine-load drift
    during the measurement cannot systematically favor one variant.
    """
    from repro.core.params import HiggsParams
    from repro.shard import ShardedHiggs

    n = len(stream[0])
    p = common.DEFAULT_KW["HIGGS"]
    chunk = HiggsParams(**p).chunk_size
    batch = max(chunk, 32768 // chunk * chunk)
    variants = (("s1", 1), (f"s{shards}", shards))

    def build(S):
        sk = ShardedHiggs(shards=S, **p)
        sk.insert(*(np.zeros(0, a.dtype) for a in stream))      # prime
        return sk

    secs = {tag: float("inf") for tag, _ in variants}
    out = {}
    for tag, S in variants:
        _feed(build(S), stream, batch)             # warm all shapes
    for _ in range(repeat):
        for tag, S in variants:
            sk = build(S)
            secs[tag] = min(secs[tag], _feed(sk, stream, batch))
            out[tag] = sk            # runs are bit-identical; keep last
    for tag, _ in variants:
        common.emit(f"throughput/ingest/higgs_sharded_{tag}",
                    secs[tag] / n * 1e6,
                    f"edges_per_s={n / secs[tag]:.0f}")
    speedup = secs["s1"] / secs[f"s{shards}"]
    common.emit("throughput/ingest/shard_speedup", speedup,
                f"s1={secs['s1']:.2f}s;s{shards}="
                f"{secs[f's{shards}']:.2f}s;mode={out[f's{shards}']._mode}")
    return secs["s1"], secs[f"s{shards}"], out


def run(n_edges: int = 100_000, seed: int = 0, shards: int = 4):
    stream = lkml_like_stream(n_edges=n_edges, seed=seed)
    src, dst, w, t = stream
    t_max = int(t[-1])
    l_bits = max(int(np.ceil(np.log2(t_max + 1))), 1)

    serial_vs_batched(stream)
    if shards > 1:
        sharded_scaleout(balanced_stream(n_edges=n_edges, seed=seed),
                         shards)

    sketches = common.build_all(stream, l_bits)
    for name, (sk, ins_s) in sketches.items():
        eps = n_edges / ins_s
        common.emit(f"throughput/insert/{name}", ins_s / n_edges * 1e6,
                    f"edges_per_s={eps:.0f}")

    # deletion: remove the first half of the stream
    half = n_edges // 2
    for name, (sk, _) in sketches.items():
        t0 = time.perf_counter()
        sk.insert(src[:half], dst[:half], -w[:half], t[:half])
        sk.flush()
        dt = time.perf_counter() - t0
        common.emit(f"throughput/delete/{name}", dt / half * 1e6,
                    f"edges_per_s={half / dt:.0f}")


def _assert_sketches_identical(a, b, tag: str) -> None:
    """Bit-identity: leaf keys, every pool level, and the overflow store."""
    from repro.core.cmatrix import NodeState

    assert np.array_equal(a.leaf_starts, b.leaf_starts), \
        f"{tag}: leaf start keys diverged"
    assert np.array_equal(a.leaf_ends, b.leaf_ends), \
        f"{tag}: leaf end keys diverged"
    for lvl, (pa, pb) in enumerate(zip(a.pools, b.pools)):
        assert pa.n == pb.n, f"{tag}: level {lvl + 1} node count diverged"
        # raw physical-slab comparison is the point here (bit-identity of
        # both sketches' storage) — exempted via higgslint-baseline.json
        for name in NodeState._fields:
            assert np.array_equal(pa.arrs[name][:pa.n],
                                  pb.arrs[name][:pb.n]), \
                f"{tag}: level {lvl + 1} {name} diverged"
    da, db = a.ob.data, b.ob.data
    assert set(da) == set(db), f"{tag}: overflow keys diverged"
    for key in da:
        for f in da[key]:
            assert np.array_equal(da[key][f], db[key][f]), \
                f"{tag}: overflow {key}/{f} diverged"


def smoke(n_edges: int = 30_000, seed: int = 0, min_speedup: float = 1.5,
          shards: int = 4, json_path: str | None = None):
    """CI gate: batched must stay >= min_speedup x serial AND produce the
    bit-identical sketch; with shards > 1, partition-parallel ingestion
    must beat the S=1 degenerate case (>= 2x on hosts with >= 4 cores,
    no-loss on smaller hosts, where the parallel ceiling is below 2x by
    hardware).  Deterministic structure counters (leaves, space) are
    recorded alongside the wall-clock ratios for the baseline compare.
    """
    # metrics are recorded before any assert and the JSON lands in a
    # finally block: the uploaded artifact must exist precisely when a
    # gate trips, or CI regressions come with no diagnostics attached
    try:
        stream = lkml_like_stream(n_edges=n_edges, seed=seed)
        serial_s, batched_s, sk = serial_vs_batched(stream)
        speedup = serial_s / batched_s
        record("ingest/batched_speedup", speedup, "floor")
        record("structure/n_leaves", len(sk["batched"].leaf_starts),
               "exact")
        record("structure/space_bytes", sk["batched"].space_bytes(),
               "exact")
        _assert_sketches_identical(sk["serial"], sk["batched"], "smoke")
        assert speedup >= min_speedup, (
            f"smoke: batched ingestion regressed to {speedup:.2f}x "
            f"serial (floor {min_speedup}x)")
        print(f"smoke OK: batched={speedup:.2f}x serial, "
              f"sketches identical")
        # cost ratio of the fused device aggregation vs the retired
        # gather->numpy->append dataflow; records its own floor metric
        from benchmarks.roofline import fused_aggregate_speedup
        fused_aggregate_speedup(n_edges=n_edges, seed=seed)
        if shards > 1:
            shard_smoke(n_edges=2 * n_edges, shards=shards)
    finally:
        if json_path:
            write_json(json_path)


def shard_smoke(n_edges: int, shards: int, seed: int = 0):
    """The scale-out leg of the smoke gate (balanced stream)."""
    stream = balanced_stream(n_edges=n_edges, seed=seed)
    s1_s, sharded_s, out = sharded_scaleout(stream, shards)
    speedup = s1_s / sharded_s
    fleet = out[f"s{shards}"]
    assert fleet.n_items == n_edges, "sharded smoke: items lost"
    assert out["s1"].n_items == n_edges, "sharded smoke: items lost (S=1)"
    record("ingest/shard_speedup", speedup, "floor")
    record("ingest/edges_per_s_sharded", n_edges / sharded_s, "info")
    record("structure/sharded_n_leaves", fleet.n_leaves, "exact")
    record("structure/sharded_space_bytes", fleet.space_bytes(), "exact")
    cores = os.cpu_count() or 1
    # >= 4 cores is the acceptance bar; below that the hardware cannot
    # reach 2x, so the gate only rejects sharding that LOSES throughput.
    # HIGGS_MIN_SHARD_SPEEDUP overrides the floor so a contended CI
    # runner can be recalibrated without a code change.
    env_floor = os.environ.get("HIGGS_MIN_SHARD_SPEEDUP")
    floor = float(env_floor) if env_floor else (2.0 if cores >= 4
                                                else 0.75)
    assert speedup >= floor, (
        f"sharded smoke: {shards}-shard ingestion at {speedup:.2f}x "
        f"S=1 (floor {floor}x on {cores} cores, mode={fleet._mode}; "
        f"override with HIGGS_MIN_SHARD_SPEEDUP)")
    out["s1"].close()
    fleet.close()
    print(f"sharded smoke OK: {shards} shards = {speedup:.2f}x S=1 "
          f"({cores} cores, floor {floor}x)")


def retention_smoke(n_edges: int = 60_000, seed: int = 0,
                    n_windows: int = 10, json_path: str | None = None):
    """CI gate for the bounded-memory temporal lifecycle.

    Streams ~``n_windows`` retention horizons of data through a
    ``retention=window`` sketch and asserts:

    * **bounded** — resident ``space_bytes`` at every later window
      boundary never exceeds ``1.2 x`` the two-window footprint (the
      derived budget; dropping *below* it is bounded-memory working,
      never a failure);
    * **plateau** — the last five window boundaries stay within ±20% of
      their own median: steady state is flat, not still trending;
    * **correctness** — after the full stream, every in-window
      edge/vertex/path/subgraph answer is bit-identical to a fresh
      sketch built from the retained suffix alone;
    * **budget policy** — a ``retention=budget`` sketch configured with
      that same derived budget never exceeds it at any checkpoint.

    Deterministic structure counters (retained segments, evictions,
    steady-state bytes) are recorded for the baseline compare.
    """
    from repro.api import EdgeQuery, PathQuery, SubgraphQuery, VertexQuery
    from repro.core.higgs import HiggsSketch
    from repro.core.params import HiggsParams, RetentionPolicy

    try:
        rng = np.random.default_rng(seed)
        t_max = n_windows * 10_000
        src = rng.integers(0, 5_000, n_edges).astype(np.uint32)
        dst = rng.integers(0, 5_000, n_edges).astype(np.uint32)
        w = rng.integers(1, 16, n_edges).astype(np.float32)
        t = np.sort(rng.integers(0, t_max, n_edges).astype(np.uint32))
        horizon = t_max // n_windows
        kw = dict(d1=8, F1=19, segment_levels=1)
        sk = HiggsSketch(HiggsParams(
            retention=RetentionPolicy.window(horizon), **kw))

        per_window = n_edges // n_windows
        series = []
        for wi in range(n_windows):
            s = slice(wi * per_window,
                      n_edges if wi == n_windows - 1 else
                      (wi + 1) * per_window)
            sk.insert(src[s], dst[s], w[s], t[s])
            series.append(sk.space_bytes())
        sk.flush()

        ref = series[1]                      # footprint after 2 windows
        budget = 1.2 * ref
        for wi, sb in enumerate(series[1:], start=2):
            assert sb <= budget, (
                f"retention smoke: space at window {wi} = {sb:.0f}B "
                f"exceeds 1.2x the 2-window footprint {ref:.0f}B")
        tail = series[-5:]
        mid = float(np.median(tail))
        for wi, sb in enumerate(tail, start=n_windows - len(tail) + 1):
            assert abs(sb - mid) <= 0.2 * mid, (
                f"retention smoke: steady state not flat — window {wi} "
                f"= {sb:.0f}B vs tail median {mid:.0f}B")
        print(f"retention smoke: space bounded by {budget:.0f}B and "
              f"flat at {mid:.0f}B +/- 20% over the last {len(tail)} "
              f"of {n_windows} windows")

        # in-window answers == fresh sketch over the retained suffix
        drop = sk.segments.items_dropped
        fresh = HiggsSketch(HiggsParams(
            retention=RetentionPolicy.window(horizon), **kw))
        fresh.insert(src[drop:], dst[drop:], w[drop:], t[drop:])
        fresh.flush()
        ts0 = int(t[-1]) - horizon
        queries = [
            EdgeQuery(src[-256:], dst[-256:], ts0, int(t[-1])),
            VertexQuery(src[-64:], ts0, int(t[-1]), "out"),
            VertexQuery(dst[-64:], ts0 + horizon // 3, int(t[-1]), "in"),
            PathQuery([int(src[-1]), int(dst[-1]), int(dst[-2])],
                      ts0, int(t[-1])),
            SubgraphQuery([(int(src[-i]), int(dst[-i]))
                           for i in range(1, 9)], ts0, int(t[-1])),
        ]
        va = sk.query(queries).values
        vb = fresh.query(queries).values
        for i, (x, y) in enumerate(zip(va, vb)):
            assert np.array_equal(np.asarray(x), np.asarray(y)), (
                f"retention smoke: in-window query {i} diverged from "
                f"the fresh retained-suffix sketch")
        print("retention smoke: in-window answers bit-identical to "
              "fresh retained-suffix sketch")

        # budget policy: never exceeds the configured cap
        bk = HiggsSketch(HiggsParams(
            retention=RetentionPolicy.budget(budget), **kw))
        for wi in range(n_windows):
            s = slice(wi * per_window,
                      n_edges if wi == n_windows - 1 else
                      (wi + 1) * per_window)
            bk.insert(src[s], dst[s], w[s], t[s])
            assert bk.space_bytes() <= budget, (
                f"retention smoke: budget sketch at "
                f"{bk.space_bytes():.0f}B exceeds {budget:.0f}B")
        bk.flush()
        assert bk.space_bytes() <= budget
        rs = sk.retention_stats()
        record("retention/steady_state_bytes", series[-1], "exact")
        record("retention/segments_retained", rs["segments_retained"],
               "exact")
        record("retention/segments_evicted", rs["segments_evicted"],
               "exact")
        record("retention/budget_space_bytes", bk.space_bytes(), "exact")
        record("retention/budget_segments_coarse",
               bk.retention_stats()["segments_coarse"], "exact")
        print(f"retention smoke OK: steady state {series[-1]:.0f}B, "
              f"{rs['segments_evicted']} segments evicted, budget sketch "
              f"{bk.space_bytes():.0f}B <= {budget:.0f}B "
              f"({bk.retention_stats()['segments_coarse']} coarse)")
    finally:
        if json_path:
            write_json(json_path)


def resume_smoke(n_edges: int = 30_000, seed: int = 0,
                 kill_at: int | None = None):
    """CI gate for crash-consistent persistence: ingest with periodic
    atomic sketch+cursor snapshots, kill at a random batch, resume into a
    FRESH pipeline + sketch, and assert the final sketch is bit-identical
    (pools, overflow store, leaf intervals, batched query answers) to an
    uninterrupted reference run over the same stream."""
    import tempfile

    from repro.api import EdgeQuery, VertexQuery
    from repro.core.higgs import HiggsSketch
    from repro.core.params import HiggsParams
    from repro.stream.pipeline import StreamPipeline

    stream = lkml_like_stream(n_edges=n_edges, seed=seed)
    p = HiggsParams(d1=16, F1=19)
    batch = 4096
    # run_resumable feeds leaf-aligned batches; count those, not the
    # nominal ones, or the kill point may land past the end of the run
    aligned = max(p.chunk_size, batch // p.chunk_size * p.chunk_size)
    n_batches = -(-n_edges // aligned)
    assert n_batches >= 2, \
        f"resume smoke needs >= 2 batches to kill mid-stream " \
        f"(n_edges={n_edges}, aligned batch={aligned})"
    if kill_at is None:
        # deliberately unseeded: the resume smoke WANTS a fresh kill
        # point per run (the chosen batch is printed for reproduction)
        kill_at = int(np.random.default_rng().integers(1, n_batches))  # higgslint: disable=R1
    print(f"resume smoke: killing after batch {kill_at}/{n_batches}")

    ref = HiggsSketch(p)
    StreamPipeline(*stream, batch=batch).feed(ref)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        pipe = StreamPipeline(*stream, batch=batch)
        sk = HiggsSketch(p)
        n_calls = [0]

        def stop():
            n_calls[0] += 1
            return n_calls[0] >= kill_at

        pipe.run_resumable(sk, ckpt_dir, every=2, should_stop=stop)
        assert pipe.cursor < len(pipe), \
            "resume smoke: run completed before the kill fired"

        pipe2 = StreamPipeline(*stream, batch=batch)
        sk2 = HiggsSketch(p)
        pipe2.run_resumable(sk2, ckpt_dir, every=2, keep=3)
        assert pipe2.cursor == len(pipe2), "resume smoke: did not finish"

    _assert_sketches_identical(ref, sk2, "resume smoke")
    src, dst, _, t = stream
    t_max = int(t[-1])
    queries = [EdgeQuery(src[:256], dst[:256], t_max // 4, 3 * t_max // 4),
               EdgeQuery(src[:64], dst[:64], 0, t_max),
               VertexQuery(src[:64], t_max // 8, t_max, "out"),
               VertexQuery(dst[:64], 0, t_max // 2, "in")]
    va = ref.query(queries).values
    vb = sk2.query(queries).values
    for i, (x, y) in enumerate(zip(va, vb)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"resume smoke: query {i} answers diverged"
    print(f"resume smoke OK: kill at batch {kill_at}/{n_batches}, "
          f"resumed sketch bit-identical to uninterrupted run")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny ingestion regression gate (CI)")
    ap.add_argument("--resume", action="store_true",
                    help="kill-and-resume persistence gate (CI); with "
                         "--smoke runs only the resume gate")
    ap.add_argument("--kill-at", type=int, default=0,
                    help="deterministic kill batch for --resume "
                         "(default: random)")
    ap.add_argument("--retention", type=str, default="",
                    help="with --smoke: run the bounded-memory lifecycle "
                         "gate instead (currently 'window')")
    ap.add_argument("--shards", type=int, default=4,
                    help="shard count for the scale-out comparison "
                         "(0/1 skips it)")
    ap.add_argument("--json", type=str, default="",
                    help="write machine-readable smoke results here "
                         "(the CI perf-gate artifact)")
    ap.add_argument("--n-edges", type=int, default=0)
    args = ap.parse_args()
    if args.retention and (args.resume or not args.smoke):
        ap.error("--retention is a --smoke gate; run "
                 "`--smoke --retention window`")
    if args.resume:
        resume_smoke(n_edges=args.n_edges or 30_000,
                     kill_at=args.kill_at or None)
    elif args.smoke and args.retention:
        if args.retention != "window":
            ap.error("--retention currently supports only 'window'")
        retention_smoke(n_edges=args.n_edges or 60_000,
                        json_path=args.json or None)
    elif args.smoke:
        smoke(n_edges=args.n_edges or 30_000, shards=args.shards,
              json_path=args.json or None)
    else:
        run(n_edges=args.n_edges or 100_000, shards=args.shards)
