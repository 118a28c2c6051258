"""Paper Fig. 14 + 15: robustness to stream irregularity — vertex-query
accuracy, latency, space, and update throughput under varied skewness
(power-law exponent 1.5-3.0) and arrival variance (600-1600)."""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.stream.generator import power_law_stream, variance_stream


def _eval(tag, stream, n_queries=128, seed=1):
    src, dst, w, t = stream
    t_max = int(t[-1])
    l_bits = max(int(np.ceil(np.log2(t_max + 1))), 1)
    sketches = common.build_all(
        stream, l_bits, include=("HIGGS", "Horae", "PGSS"))
    ora = common.build_oracle(stream)
    rng = np.random.default_rng(seed)
    lq = max(t_max // 8, 1)
    ts, te = common.rand_ranges(rng, t_max, lq, 1)[0]
    qv = src[rng.integers(0, len(src), n_queries)].astype(np.uint32)
    for name, (sk, ins_s) in sketches.items():
        est, us = common.time_queries(
            lambda s=sk: s.vertex_query(qv, ts, te, "out"))
        true = ora.vertex_query(qv, ts, te, "out")
        aae, _ = common.aae_are(np.asarray(est), true)
        common.emit(
            f"irregularity/{tag}/{name}", us / n_queries,
            f"AAE={aae:.4g};MB={sk.space_bytes() / 1e6:.1f};"
            f"ins_eps={len(src) / ins_s:.0f}")


def run(n_edges: int = 60_000, seed: int = 0):
    for skew in (1.5, 2.0, 2.5, 3.0):
        stream = power_law_stream(n_edges=n_edges, n_vertices=10_000,
                                  skew=skew, seed=seed)
        _eval(f"skew={skew}", stream)
    for var in (600, 1100, 1600):
        stream = variance_stream(n_edges=n_edges, n_vertices=10_000,
                                 variance=var, seed=seed)
        _eval(f"var={var}", stream)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
