"""Paper Fig. 10 (a-f) + Fig. 11: edge/vertex query AAE & ARE vs the
query-range length L_q, across all competitors."""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.stream.generator import lkml_like_stream


def run(n_edges: int = 120_000, n_queries: int = 400, seed: int = 0):
    stream = lkml_like_stream(n_edges=n_edges, seed=seed)
    src, dst, w, t = stream
    t_max = int(t[-1])
    l_bits = max(int(np.ceil(np.log2(t_max + 1))), 1)
    sketches = common.build_all(stream, l_bits)
    ora = common.build_oracle(stream)
    rng = np.random.default_rng(seed + 1)
    n_v = int(src.max()) + 1

    for lq_exp in (3, 5, 7):               # L_q = t_max >> (21 - ...)
        lq = min(10 ** lq_exp, t_max)
        ranges = common.rand_ranges(rng, t_max, lq, 4)
        # half existing edges, half random pairs (paper queries both)
        qi = rng.integers(0, n_edges, n_queries // 2)
        qs = np.concatenate([src[qi],
                             rng.integers(0, n_v, n_queries // 2)])
        qd = np.concatenate([dst[qi],
                             rng.integers(0, n_v, n_queries // 2)])
        qs_u = qs.astype(np.uint32)
        qd_u = qd.astype(np.uint32)
        for name, (sk, _) in sketches.items():
            est = np.concatenate([sk.edge_query(qs_u, qd_u, a, b)
                                  for a, b in ranges])
            true = np.concatenate([ora.edge_query(qs_u, qd_u, a, b)
                                   for a, b in ranges])
            aae, are = common.aae_are(est, true)
            common.emit(f"accuracy/edge/{name}/Lq=1e{lq_exp}", 0.0,
                        f"AAE={aae:.4g};ARE={are:.4g}")
        qv = qs_u[:n_queries // 4]
        for name, (sk, _) in sketches.items():
            est = np.concatenate([sk.vertex_query(qv, a, b, "out")
                                  for a, b in ranges])
            true = np.concatenate([ora.vertex_query(qv, a, b, "out")
                                   for a, b in ranges])
            aae, are = common.aae_are(est, true)
            common.emit(f"accuracy/vertex/{name}/Lq=1e{lq_exp}", 0.0,
                        f"AAE={aae:.4g};ARE={are:.4g}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
