"""Concurrent serving benchmark: coalesced vs per-caller sequential QPS.

Simulates N concurrent callers (default 8) submitting typed query
batches against one HIGGS summary and compares:

* **sequential** — each caller's batch executed as its own
  ``summary.query()`` call, the pre-serving baseline: every caller pays
  its own plan lookup and its own probe launch per (level, range class);
* **coalesced** — the same traffic through :class:`SummaryService`:
  callers racing through ``asyncio.gather`` are merged into one planner
  execution per round, so the fleet pays ONE probe launch per (level,
  range class) for all callers together.

Reported metrics: closed-loop QPS for both modes and their ratio (the
``>= 2x at 8 callers`` acceptance gate), open-loop QPS (every request
enqueued up front — the maximum-coalescing regime), per-submit p50/p99
latency, and the per-round device-dispatch counters that make the
coalescing contract checkable as exact structure metrics.

``--smoke`` scales down, asserts the speedup gate in-process
(``HIGGS_MIN_COALESCE_SPEEDUP`` overrides the 2.0 floor for noisy
hosts), re-verifies live-epoch bit-identity while a writer drains, and
with ``--json`` writes the machine-readable metrics CI gates through
``benchmarks/compare_bench.py`` against
``benchmarks/baselines/BENCH_serving_baseline.json``.
"""
from __future__ import annotations

import argparse
import asyncio
import os
import time

import numpy as np

from benchmarks import common
from benchmarks.common import record, write_json
from repro.api import EdgeQuery, VertexQuery, make_summary
from repro.core.params import HiggsParams
from repro.serve import SummaryService
from repro.stream.generator import balanced_stream
from repro.stream.pipeline import StreamPipeline

PARAMS = HiggsParams(d1=16, F1=19)


def caller_batches(stream, t_max, callers: int, q: int):
    """One typed batch per caller, all sharing one time-range class (the
    regime coalescing is built for: one boundary search, one launch per
    level for the whole fleet)."""
    src, dst, _, _ = stream
    out = []
    for c in range(callers):
        lo = (c * q) % (len(src) - q)
        out.append([EdgeQuery(src[lo:lo + q], dst[lo:lo + q], 0, t_max),
                    VertexQuery(src[lo:lo + q // 2], 0, t_max, "out")])
    return out


def run_sequential(sk, batches, rounds: int) -> tuple[float, int]:
    """Per-caller sequential execution; returns (seconds, dispatches per
    round)."""
    for b in batches:                      # warm every shape
        sk.query(b)
    t0 = time.perf_counter()
    for _ in range(rounds):
        for b in batches:
            sk.query(b)
    secs = time.perf_counter() - t0
    per_round = sum(sk.query(b).stats.device_dispatches for b in batches)
    return secs, per_round


def run_coalesced(sk, batches, rounds: int):
    """Closed-loop service execution: every caller waits for its answer
    before submitting the next round.  Returns (seconds, per-submit
    latencies, dispatches per round, realized coalesce factor)."""

    async def main():
        async with SummaryService(sk, readers=2) as svc:
            async def caller(batch):
                lat = []
                for _ in range(rounds):
                    t0 = time.perf_counter()
                    res = await svc.submit(batch)
                    lat.append(time.perf_counter() - t0)
                return lat, res
            await asyncio.gather(*[svc.submit(b) for b in batches])  # warm
            t0 = time.perf_counter()
            outs = await asyncio.gather(*[caller(b) for b in batches])
            secs = time.perf_counter() - t0
            return svc, secs, outs

    svc, secs, outs = asyncio.run(main())
    lats = np.concatenate([lat for lat, _ in outs])
    per_round = outs[0][1].stats.device_dispatches
    factor = svc.stats.coalesced_jobs / max(svc.stats.rounds, 1)
    return secs, lats, per_round, factor


def run_open_loop(sk, batches, rounds: int) -> float:
    """Open-loop: every request of every round enqueued up front."""

    async def main():
        async with SummaryService(sk, readers=2) as svc:
            await asyncio.gather(*[svc.submit(b) for b in batches])
            t0 = time.perf_counter()
            await asyncio.gather(*[svc.submit(b)
                                   for _ in range(rounds)
                                   for b in batches])
            return time.perf_counter() - t0

    return asyncio.run(main())


def verify_live_epoch_consistency(stream, batches) -> None:
    """Bit-identity under a live writer: every answer served while the
    writer drains must equal a fresh quiesced summary fed exactly the
    pinned stream prefix."""

    async def main():
        sk = make_summary("higgs", params=PARAMS)
        pipe = StreamPipeline(*stream, batch=2048)
        observed = []
        async with SummaryService(sk, readers=2) as svc:
            svc.attach_stream(pipe)
            while not svc._writer_task.done():
                observed.append(await svc.submit(batches[0]))
            observed.append(await svc.submit(batches[0]))
            return svc, observed

    svc, observed = asyncio.run(main())
    for res in observed:
        pin = svc.epoch_log[res.epoch]
        ref = make_summary("higgs", params=PARAMS)
        if pin["cursor"]:
            ref.insert(*(a[:pin["cursor"]] for a in stream))
        if pin["flushed"]:
            ref.flush()
        want = ref.query(batches[0])
        for got, exp in zip(res.values, want.values):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    print(f"serving/live_epoch: {len(observed)} answers over "
          f"{len(svc.epoch_log)} epochs bit-identical to quiesced refs")


def measure_epoch_plan_cache_hit_rate(sk, batches, pins: int = 10):
    """Warm cross-epoch plan reuse: the fraction of plan lookups the
    *first* answer of each of ``pins`` fresh epoch pins serves from the
    adopted writer cache.  Fresh pins are the honest probe — a single
    long-lived epoch amortizes its own early misses and would score
    high even without adoption; here every pin starts a new replica
    whose only warmth is what ``_pin_replica`` handed over."""
    for b in batches:                       # memoize the writer's plans
        sk.query(b)
    hits = misses = 0
    for i in range(pins):
        ep = sk.snapshot_epoch()
        st = ep.query(batches[i % len(batches)]).stats
        hits += st.plan_cache_hits
        misses += st.plan_cache_misses
    return hits / max(hits + misses, 1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down run with in-process gates (CI)")
    ap.add_argument("--callers", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=0,
                    help="closed-loop rounds per caller (0 = auto)")
    ap.add_argument("--edges", type=int, default=0,
                    help="stream size (0 = auto)")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write machine-readable metrics here")
    args = ap.parse_args(argv)

    n = args.edges or (60_000 if args.smoke else 200_000)
    rounds = args.rounds or (30 if args.smoke else 100)
    t_max = 5000
    stream = balanced_stream(n, n_vertices=4000, t_max=t_max, seed=7)
    batches = caller_batches(stream, t_max, args.callers, q=8)

    sk = make_summary("higgs", params=PARAMS)
    half = n // 2
    sk.insert(*(a[:half] for a in stream))
    sk.flush()

    seq_s, seq_disp = run_sequential(sk, batches, rounds)
    coal_s, lats, coal_disp, factor = run_coalesced(sk, batches, rounds)
    run_open_loop(sk, batches, rounds)     # warm the deep-queue shapes
    open_s = run_open_loop(sk, batches, rounds)

    total = args.callers * rounds
    seq_qps, coal_qps = total / seq_s, total / coal_s
    ratio = coal_qps / seq_qps
    common.emit("serving/sequential_qps", seq_qps)
    common.emit("serving/coalesced_qps", coal_qps)
    common.emit("serving/openloop_qps", total / open_s)
    common.emit("serving/qps_ratio", ratio,
                f"seq_disp_per_round={seq_disp};"
                f"coal_disp_per_round={coal_disp};"
                f"coalesce_factor={factor:.1f}")
    common.emit("serving/p50_ms", float(np.percentile(lats, 50)) * 1e3)
    common.emit("serving/p99_ms", float(np.percentile(lats, 99)) * 1e3)
    hit_rate = measure_epoch_plan_cache_hit_rate(sk, batches)
    common.emit("serving/epoch_plan_cache_hit_rate", hit_rate)

    record("serving/epoch_plan_cache_hit_rate", hit_rate, kind="floor")
    record("serving/coalesce_qps_ratio", ratio, kind="floor")
    record("serving/sequential_dispatches_per_round", seq_disp,
           kind="exact")
    record("serving/coalesced_dispatches_per_round", coal_disp,
           kind="exact")
    record("serving/coalesce_factor", factor, kind="exact")
    record("serving/sequential_qps", seq_qps)
    record("serving/coalesced_qps", coal_qps)
    record("serving/openloop_qps", total / open_s)
    record("serving/p50_ms", float(np.percentile(lats, 50)) * 1e3)
    record("serving/p99_ms", float(np.percentile(lats, 99)) * 1e3)

    if args.smoke:
        verify_live_epoch_consistency(stream, batches)
        record("serving/live_epoch_bit_identical", 1.0, kind="exact")
        floor = float(os.environ.get("HIGGS_MIN_COALESCE_SPEEDUP", "2.0"))
        assert factor >= args.callers, (
            f"coalescing broke: realized factor {factor:.1f} < "
            f"{args.callers} gathered callers per round")
        assert coal_disp < seq_disp, (
            f"coalesced round dispatches ({coal_disp}) not below the "
            f"sequential round's ({seq_disp})")
        assert ratio >= floor, (
            f"coalesced serving only {ratio:.2f}x the per-caller "
            f"sequential QPS at {args.callers} callers (floor {floor}x; "
            f"override with HIGGS_MIN_COALESCE_SPEEDUP)")
        assert hit_rate >= 0.9, (
            f"warm cross-epoch plan reuse broke: fresh pins answered "
            f"with plan-cache hit rate {hit_rate:.2f} (floor 0.9) — "
            f"epoch replicas are re-deriving plans the writer already "
            f"memoized")
        print(f"serving smoke OK: {ratio:.2f}x QPS at {args.callers} "
              f"callers (floor {floor}x), dispatches/round "
              f"{seq_disp} -> {coal_disp}, epoch plan-cache hit rate "
              f"{hit_rate:.2f}")

    if args.json_out:
        write_json(args.json_out)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
