"""Paper Fig. 19: space overhead across datasets (paper bit-layout
accounting for HIGGS; array footprint for baselines) — plus the
bounded-memory evidence the retention lifecycle claims: a resident-bytes
**time series** per summary as the stream plays, and a
``steady_state_bytes`` metric in the BENCH JSON (``--json``), so
"memory plateaus under retention" is measured, not asserted.
"""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks import common
from benchmarks.common import record, write_json
from repro.stream.generator import (lkml_like_stream, power_law_stream,
                                    wiki_talk_like_stream)

# time-series sampling: resident bytes recorded after each of N_POINTS
# equal stream slices
N_POINTS = 20


def resident_series(name: str, sk, stream, n_points: int = N_POINTS):
    """Feed ``stream`` in ``n_points`` slices, recording ``space_bytes``
    after each; returns the series (bytes, one per sample point)."""
    src, dst, w, t = stream
    n = len(src)
    series = []
    for i in range(n_points):
        s = slice(i * n // n_points, (i + 1) * n // n_points)
        sk.insert(src[s], dst[s], w[s], t[s])
        sb = sk.space_bytes()
        series.append(sb)
        common.emit(f"space/series/{name}/{i}", 0.0,
                    f"items={s.stop};bytes={sb:.0f}")
    sk.flush()
    return series


def steady_state_bytes(series: list[float]) -> float:
    """Median of the last quarter of the series — where a bounded
    summary has plateaued and an unbounded one is still climbing."""
    tail = series[-max(1, len(series) // 4):]
    return float(np.median(tail))


def lifecycle_comparison(seed: int = 0, n_edges: int = 80_000):
    """Unbounded vs window vs budget HIGGS on one long stream: emits the
    three time series and records ``steady_state_bytes`` (exact) plus
    the unbounded/windowed ratio (info) into the BENCH JSON."""
    from repro.core.higgs import HiggsSketch
    from repro.core.params import HiggsParams, RetentionPolicy

    rng = np.random.default_rng(seed)
    t_max = 100_000
    stream = (rng.integers(0, 5_000, n_edges).astype(np.uint32),
              rng.integers(0, 5_000, n_edges).astype(np.uint32),
              rng.integers(1, 16, n_edges).astype(np.float32),
              np.sort(rng.integers(0, t_max, n_edges).astype(np.uint32)))
    kw = dict(d1=8, F1=19, segment_levels=1)
    variants = {
        "HIGGS": HiggsParams(**kw),
        "HIGGS-window": HiggsParams(
            retention=RetentionPolicy.window(t_max // 10), **kw),
    }
    series = {}
    for name, params in variants.items():
        series[name] = resident_series(name, HiggsSketch(params), stream)
    # budget = the windowed steady state, demonstrating coarsening holds
    # the same footprint while keeping old ranges answerable
    budget = steady_state_bytes(series["HIGGS-window"])
    series["HIGGS-budget"] = resident_series(
        "HIGGS-budget",
        HiggsSketch(HiggsParams(retention=RetentionPolicy.budget(budget),
                                **kw)),
        stream)
    for name, ser in series.items():
        ss = steady_state_bytes(ser)
        record(f"space/steady_state_bytes/{name}", ss, "exact")
        common.emit(f"space/steady_state/{name}", 0.0, f"bytes={ss:.0f}")
    record("space/unbounded_over_window",
           steady_state_bytes(series["HIGGS"]) / budget, "info")
    return series


def run(seed: int = 0, json_path: str | None = None):
    try:
        datasets = {
            "lkml": lkml_like_stream(n_edges=100_000, seed=seed),
            "wiki-talk": wiki_talk_like_stream(n_edges=120_000, seed=seed),
            "powerlaw": power_law_stream(n_edges=100_000, seed=seed),
        }
        for ds_name, stream in datasets.items():
            t_max = int(stream[3][-1])
            l_bits = max(int(np.ceil(np.log2(t_max + 1))), 1)
            sketches = common.build_all(stream, l_bits)
            base = None
            for name, (sk, _) in sketches.items():
                mb = sk.space_bytes() / 1e6
                if name == "HIGGS":
                    base = mb
                    extra = f"utilization={sk.utilization():.3f}"
                else:
                    extra = f"vs_HIGGS={mb / base:.2f}x" if base else ""
                common.emit(f"space/{ds_name}/{name}", 0.0,
                            f"MB={mb:.2f};{extra}")
        lifecycle_comparison(seed=seed)
    finally:
        if json_path:
            write_json(json_path)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", type=str, default="",
                    help="write machine-readable space metrics here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(seed=args.seed, json_path=args.json or None)
