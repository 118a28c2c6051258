"""Paper Fig. 20: optimization ablations.

* MMB (multiple mapping buckets): leaf utilization, overflow spill count,
  and space with r=4 vs r=1;
* OB (overflow blocks): accuracy on fine ranges of a bursty stream with
  and without OB (without, spills open duplicate-key leaves — the error
  the paper's OB prevents);
* vectorized chunk insertion (the paper's parallelization analogue on
  TPU, DESIGN.md §3) vs the faithful sequential reference.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import cmatrix, hashing
from repro.core.higgs import HiggsSketch
from repro.core.oracle import ExactOracle
from repro.core.params import HiggsParams
from repro.kernels import ref as kref
from repro.stream.generator import power_law_stream, variance_stream


def run(n_edges: int = 50_000, seed: int = 0):
    # --- MMB ------------------------------------------------------------
    stream = power_law_stream(n_edges=n_edges, n_vertices=5_000, seed=seed)
    for r, tag in ((4, "MMB_on"), (1, "MMB_off")):
        sk = HiggsSketch(HiggsParams(d1=16, F1=19, r=r, use_mmb=(r > 1)))
        t0 = time.perf_counter()
        sk.insert(*stream)
        sk.flush()
        dt = time.perf_counter() - t0
        common.emit(
            f"ablation/{tag}", dt / n_edges * 1e6,
            f"utilization={sk.utilization():.3f};"
            f"ob_entries={sk.ob.total_entries()};"
            f"MB={sk.space_bytes() / 1e6:.2f}")

    # --- OB (bursty timestamps stress the leaf keys) ---------------------
    burst = variance_stream(n_edges=n_edges, n_vertices=3_000,
                            variance=1600, t_slots=128, seed=seed)
    ora = ExactOracle()
    ora.insert(*burst)
    rng = np.random.default_rng(seed + 7)
    qs = burst[0][rng.integers(0, n_edges, 256)].astype(np.uint32)
    qd = burst[1][rng.integers(0, n_edges, 256)].astype(np.uint32)
    for use_ob, tag in ((True, "OB_on"), (False, "OB_off")):
        sk = HiggsSketch(HiggsParams(d1=16, F1=19, use_ob=use_ob))
        sk.insert(*burst)
        sk.flush()
        errs = []
        for a, b in [(3, 9), (40, 47), (100, 110)]:
            est = sk.edge_query(qs, qd, a, b)
            true = ora.edge_query(qs, qd, a, b)
            errs.append(np.abs(est - true).mean())
        common.emit(f"ablation/{tag}", 0.0,
                    f"AAE_fine_ranges={np.mean(errs):.4g}")

    # --- vectorized vs sequential insertion ------------------------------
    p = HiggsParams(d1=16, F1=19)
    n = p.chunk_size
    rng = np.random.default_rng(seed)
    hs = hashing.np_mix32(rng.integers(0, 5_000, n).astype(np.uint32),
                          p.seed)
    hd = hashing.np_mix32(rng.integers(0, 5_000, n).astype(np.uint32),
                          p.seed ^ 0x5BD1E995)
    w = np.ones(n, np.float32)
    t = np.sort(rng.integers(0, 1000, n).astype(np.uint32))
    valid = np.ones(n, bool)
    import jax.numpy as jnp
    args = (jnp.asarray(hs), jnp.asarray(hd), jnp.asarray(w),
            jnp.asarray(t), jnp.asarray(valid))

    def vec():
        node = cmatrix.make_node(p.d1, p.b)
        out = cmatrix.insert_chunk(node, *args, p)
        out[0].fp_s.block_until_ready()
        return out

    vec()                                    # compile
    t0 = time.perf_counter()
    for _ in range(5):
        vec()
    vec_us = (time.perf_counter() - t0) / 5 * 1e6

    fs = hs & np.uint32(p.fp_mask)
    fd = hd & np.uint32(p.fp_mask)
    rows = np.asarray(cmatrix.chain_from_base((hs >> p.F1) % p.d1, p.r,
                                              p.d1))
    cols = np.asarray(cmatrix.chain_from_base((hd >> p.F1) % p.d1, p.r,
                                              p.d1))
    t0 = time.perf_counter()
    kref.seq_insert_ref(cmatrix.make_node(p.d1, p.b), fs, fd, rows, cols,
                        w, t, valid, b=p.b, r=p.r)
    seq_us = (time.perf_counter() - t0) * 1e6
    common.emit("ablation/parallel_chunked", vec_us / n,
                f"sequential_us_per_edge={seq_us / n:.2f};"
                f"speedup={seq_us / vec_us:.1f}x")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
