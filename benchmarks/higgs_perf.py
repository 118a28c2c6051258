"""§HIGGS-perf: hypothesis-driven iterations on the paper-core hot path
(measurable on this hardware; Pallas kernels are structural-only here).

H-A  duplicate premerge: merging identical (s,d,t) items inside a chunk
     before placement should cut entry pressure (higher utilization,
     fewer OB spills) on duplicate-heavy streams at ~zero cost.
H-B  query batching: the probe path is dispatch-bound at q=1; batching
     queries through one jitted probe amortizes dispatch ~linearly up to
     VMEM-tile limits.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core.higgs import HiggsSketch
from repro.core.params import HiggsParams
from repro.stream.generator import lkml_like_stream


def run(n_edges: int = 40_000, seed: int = 0):
    # --- H-A: premerge (duplicate-heavy stream: each edge repeated 4x
    # back-to-back with identical timestamps — reply bursts)
    src, dst, w, t = lkml_like_stream(n_edges=n_edges, seed=seed)
    idx = np.repeat(np.arange(n_edges // 4), 4)
    src2, dst2, t2 = src[idx], dst[idx], t[idx]
    w2 = np.ones(len(idx), np.float32)
    import repro.core.cmatrix as cm
    orig = (cm._premerge, cm._premerge_pre, cm._premerge_host)
    # warm the FULL pipeline once (all aggregation levels compile here);
    # per-variant we only clear the chunk-insert caches
    warm = HiggsSketch(HiggsParams(d1=16, F1=19))
    warm.insert(src2, dst2, w2, t2)
    warm.flush()

    def _clear():
        cm.insert_chunk._clear_cache()
        cm.insert_chunks_pre._clear_cache()

    for tag, enabled in (("premerge_on", True), ("premerge_off", False)):
        if enabled:
            cm._premerge, cm._premerge_pre, cm._premerge_host = orig
        else:
            cm._premerge = lambda hs, hd, tt, ww, vv: (ww, vv)
            cm._premerge_pre = lambda ww, vv, o, s: (ww, vv)
            cm._premerge_host = lambda ww, vv, o, s: (ww, vv)
        _clear()
        warm2 = HiggsSketch(HiggsParams(d1=16, F1=19))
        warm2.insert(src2[:8192], dst2[:8192], w2[:8192], t2[:8192])
        sk = HiggsSketch(HiggsParams(d1=16, F1=19))
        t0 = time.perf_counter()
        sk.insert(src2, dst2, w2, t2)
        sk.flush()
        dt = time.perf_counter() - t0
        common.emit(f"higgs_perf/{tag}", dt / len(idx) * 1e6,
                    f"utilization={sk.utilization():.3f};"
                    f"ob_entries={sk.ob.total_entries()};"
                    f"leaves={len(sk.leaf_starts)}")
    cm._premerge, cm._premerge_pre, cm._premerge_host = orig
    _clear()

    # --- H-B: query batching
    sk = HiggsSketch(HiggsParams(d1=16, F1=19))
    sk.insert(src, dst, w, t)
    sk.flush()
    t_max = int(t[-1])
    rng = np.random.default_rng(seed + 1)
    qs = src[rng.integers(0, n_edges, 256)].astype(np.uint32)
    qd = dst[rng.integers(0, n_edges, 256)].astype(np.uint32)
    ts, te = 0, t_max // 2
    for q in (1, 16, 256):
        def batched():
            for i in range(0, 256, q):
                sk.edge_query(qs[i:i + q], qd[i:i + q], ts, te)
        _, us = common.time_queries(batched, repeat=1)
        common.emit(f"higgs_perf/query_batch_q={q}", us / 256, "")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
