"""Paper Fig. 10 (g-i) + Fig. 11: query latency vs L_q, plus the
hardware-independent buckets-probed counter."""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.stream.generator import lkml_like_stream


def run(n_edges: int = 120_000, n_queries: int = 256, seed: int = 0):
    stream = lkml_like_stream(n_edges=n_edges, seed=seed)
    src, dst, w, t = stream
    t_max = int(t[-1])
    l_bits = max(int(np.ceil(np.log2(t_max + 1))), 1)
    sketches = common.build_all(stream, l_bits)
    rng = np.random.default_rng(seed + 2)

    for lq_exp in (3, 5, 7):
        lq = min(10 ** lq_exp, t_max)
        ts, te = common.rand_ranges(rng, t_max, lq, 1)[0]
        qi = rng.integers(0, n_edges, n_queries)
        qs, qd = src[qi].astype(np.uint32), dst[qi].astype(np.uint32)
        for name, (sk, _) in sketches.items():
            sk.probe_counter = getattr(sk, "probe_counter", 0)
            p0 = sk.probe_counter if hasattr(sk, "probe_counter") else 0
            _, us = common.time_queries(
                lambda: sk.edge_query(qs, qd, ts, te))
            probes = (getattr(sk, "probe_counter", 0) - p0) // 4
            common.emit(f"latency/edge/{name}/Lq=1e{lq_exp}",
                        us / n_queries,
                        f"probes_per_query={probes / max(n_queries, 1):.0f}")
        qv = qs[: n_queries // 4]
        for name, (sk, _) in sketches.items():
            _, us = common.time_queries(
                lambda: sk.vertex_query(qv, ts, te, "out"))
            common.emit(f"latency/vertex/{name}/Lq=1e{lq_exp}",
                        us / len(qv), "")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
