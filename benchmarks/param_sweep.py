"""Paper Fig. 21: leaf matrix size d1 vs space overhead and query
latency."""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.core.higgs import HiggsSketch
from repro.core.params import HiggsParams
from repro.stream.generator import lkml_like_stream


def run(n_edges: int = 60_000, seed: int = 0):
    stream = lkml_like_stream(n_edges=n_edges, seed=seed)
    src, dst, w, t = stream
    t_max = int(t[-1])
    rng = np.random.default_rng(seed + 9)
    qs = src[rng.integers(0, n_edges, 256)].astype(np.uint32)
    qd = dst[rng.integers(0, n_edges, 256)].astype(np.uint32)
    lq = max(t_max // 16, 1)
    ts, te = common.rand_ranges(rng, t_max, lq, 1)[0]
    for d1 in (8, 16, 32):
        sk = HiggsSketch(HiggsParams(d1=d1, F1=19))
        sk.insert(*stream)
        sk.flush()
        _, us = common.time_queries(lambda: sk.edge_query(qs, qd, ts, te))
        common.emit(f"param/d1={d1}", us / len(qs),
                    f"MB={sk.space_bytes() / 1e6:.2f};"
                    f"levels={sk.n_levels};leaves={len(sk.leaf_starts)}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
