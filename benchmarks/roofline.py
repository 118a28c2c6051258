"""Roofline table (deliverable g): reads experiments/dryrun/*.json and
prints, per (arch x shape), the three roofline terms, the dominant
bottleneck, and the MODEL_FLOPS / HLO_FLOPs usefulness ratio.

Hardware model (TPU v5e-like): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI x 4 links.

``--smoke`` is the ingest-roofline CI gate: it measures the batched
drain's speedup over the serial reference on a small stream and asserts
it clears the **committed** ``ingest/batched_speedup`` floor from
``benchmarks/baselines/BENCH_baseline.json`` (with the same 25% noise
tolerance the compare_bench gate uses).  Raising that committed floor is
how a perf PR burns its win into CI — the gate then fails any later
change that gives the win back.
"""
from __future__ import annotations

import glob
import json
import os
import time

from benchmarks import common

PEAK = 197e12
HBM = 819e9
ICI = 50e9 * 4


def load_records(out_dir: str = "experiments/dryrun", mesh: str = "pod"):
    recs = []
    for path in sorted(glob.glob(os.path.join(out_dir, f"*_{mesh}.json"))):
        with open(path) as fh:
            recs.append(json.load(fh))
    return recs


def summarize(rec: dict) -> dict | None:
    if rec.get("status") == "skipped_na":
        return {"arch": rec["arch"], "shape": rec["shape"],
                "skip": True}
    if rec.get("status") != "compiled":
        return None
    n_dev = rec["n_devices"]
    flops = rec["hlo_flops"]
    nbytes = rec["hlo_bytes_accessed"]
    coll = sum(rec.get("collectives", {}).values())
    terms = {"compute_s": flops / PEAK, "memory_s": nbytes / HBM,
             "collective_s": coll / ICI}
    dom = max(terms, key=terms.get)
    total = max(terms.values())
    model_flops_dev = rec["analytic_flops"] / n_dev
    return {
        "arch": rec["arch"], "shape": rec["shape"], "skip": False,
        **terms, "dominant": dom.replace("_s", ""),
        # while loops the HLO scan could not bound: their bodies are
        # costed ONCE, so every term above is a lower bound then
        "unknown_trips": rec.get("unknown_trip_counts", 0),
        "useful_ratio": model_flops_dev / max(flops, 1),
        "roofline_frac": (model_flops_dev / PEAK) / max(total, 1e-12),
        "mem_bytes_per_dev": rec.get("memory", {}).get(
            "temp_size_in_bytes", 0) +
        rec.get("memory", {}).get("argument_size_in_bytes", 0),
        "microbatches": rec.get("microbatches", 1),
    }


def run(out_dir: str = "experiments/dryrun"):
    recs = load_records(out_dir)
    if not recs:
        common.emit("roofline/NO_DRYRUN_RECORDS", 0.0,
                    "run repro.launch.sweep first")
        return
    for rec in recs:
        s = summarize(rec)
        if s is None:
            common.emit(f"roofline/{rec['arch']}/{rec['shape']}", 0.0,
                        "FAILED")
            continue
        if s["skip"]:
            common.emit(f"roofline/{s['arch']}/{s['shape']}", 0.0,
                        "skipped_na(long-context full attention)")
            continue
        extra = (f";UNKNOWN_TRIPS={s['unknown_trips']}(terms are lower "
                 f"bounds)" if s["unknown_trips"] else "")
        common.emit(
            f"roofline/{s['arch']}/{s['shape']}", 0.0,
            f"compute_s={s['compute_s']:.4g};memory_s={s['memory_s']:.4g};"
            f"collective_s={s['collective_s']:.4g};dom={s['dominant']};"
            f"useful={s['useful_ratio']:.2f};"
            f"roofline_frac={s['roofline_frac']:.3f};"
            f"hbm_GB={s['mem_bytes_per_dev'] / 1e9:.1f}{extra}")


def committed_floor(metric: str = "ingest/batched_speedup") -> float:
    path = os.path.join(os.path.dirname(__file__), "baselines",
                        "BENCH_baseline.json")
    with open(path) as fh:
        base = json.load(fh)
    entry = base["metrics"][metric]
    assert entry["kind"] == "floor", metric
    return float(entry["value"])


def fused_aggregate_speedup(n_edges: int = 20_000, seed: int = 0,
                            repeat: int = 5) -> float:
    """Measured speedup of the fused device-resident aggregation cascade
    over the retired dataflow (``gather_block`` d2h -> numpy twin ->
    ``append_batch`` h2d) on the *same* device-storage child pool.

    Builds one device sketch, then re-aggregates its ready leaf block
    into fresh parent pools both ways (the fused step does not donate
    the child slabs, so the workload is reusable across repeats)."""
    import jax
    import numpy as np

    from repro.core import cmatrix
    from repro.core.cmatrix import EMPTY
    from repro.core.higgs import HiggsSketch
    from repro.core.params import HiggsParams
    from repro.core.pool import _LevelPool
    from repro.kernels.pipeline import DrainPipeline
    from repro.stream.generator import lkml_like_stream

    p = HiggsParams(d1=16, F1=19, insert_backend="pallas",
                    batched_ingest=True, interpret=True)
    sk = HiggsSketch(p)
    sk.insert(*lkml_like_stream(n_edges=n_edges, seed=seed))
    sk.flush()
    assert sk._storage == "device"
    theta = p.theta
    child = sk.pools[0]
    m = (child.n - child.base) // theta
    assert m >= 2, "stream too small to form an aggregation block"
    u0 = child.base // theta
    ob = sk._gather_child_obs_stacked(1, u0, m)
    pipe = DrainPipeline(p)

    def run_fused():
        parent = _LevelPool(p.d(2), p.b, storage="device")
        t0 = time.perf_counter()
        pipe.aggregate(child, parent, 1, u0, m, ob)
        jax.block_until_ready(parent.device_slabs()["w"])
        return time.perf_counter() - t0

    def run_reference():
        # the retired device dataflow, verbatim: bulk d2h child fetch,
        # host coordinate recovery + placement twin, h2d parent append
        parent = _LevelPool(p.d(2), p.b, storage="device")
        t0 = time.perf_counter()
        blk = child.gather_block(u0 * theta, m * theta)
        d, per = child.d, theta * child.d * child.d * child.b
        e_fs = np.asarray(blk["fp_s"]).reshape(m, per)
        e_fd = np.asarray(blk["fp_d"]).reshape(m, per)
        e_w = np.asarray(blk["w"]).reshape(m, per)
        e_idx = np.asarray(blk["idx"]).reshape(m, per)
        grid = np.broadcast_to(
            np.arange(d, dtype=np.uint32)[:, None, None], (d, d, child.b))
        e_row = np.broadcast_to(
            np.broadcast_to(grid[None], (theta,) + grid.shape)
            .reshape(1, per), (m, per))
        e_col = np.broadcast_to(
            np.broadcast_to(grid.transpose(1, 0, 2)[None],
                            (theta,) + grid.shape).reshape(1, per),
            (m, per))
        e_valid = e_fs != EMPTY
        f1s, base_s = cmatrix.host_recover_leaf_coords(
            e_row, e_fs, e_idx, 1, p, "s")
        f1d, base_d = cmatrix.host_recover_leaf_coords(
            e_col, e_fd, e_idx, 1, p, "d")
        w_all = e_w.astype(np.float32)
        if ob is not None:
            f1s = np.concatenate([f1s, ob["f1s"]], axis=1)
            f1d = np.concatenate([f1d, ob["f1d"]], axis=1)
            base_s = np.concatenate([base_s, ob["bs"]], axis=1)
            base_d = np.concatenate([base_d, ob["bd"]], axis=1)
            w_all = np.concatenate([w_all, ob["w"]], axis=1)
            e_valid = np.concatenate([e_valid, ob["valid"]], axis=1)
        fp_s_p, rows_p = cmatrix.host_coords_at_level(f1s, base_s, 2, p)
        fp_d_p, cols_p = cmatrix.host_coords_at_level(f1d, base_d, 2, p)
        rows_p = np.where(e_valid[..., None], rows_p, np.uint32(0))
        cols_p = np.where(e_valid[..., None], cols_p, np.uint32(0))
        r = p.r if p.use_mmb else 1
        orders = cmatrix.host_round_orders(rows_p, cols_p, p.d(2), r)
        state4, wmat, _ = cmatrix.aggregate_children_host(
            fp_s_p, fp_d_p, rows_p, cols_p, w_all, e_valid, orders, p, 1)
        s4 = np.asarray(state4)
        parent.append_batch(
            {"fp_s": s4[:, 0], "fp_d": s4[:, 1], "t": s4[:, 2],
             "idx": s4[:, 3], "w": np.asarray(wmat)}, m)
        jax.block_until_ready(parent.device_slabs()["w"])
        return time.perf_counter() - t0

    run_fused()                            # compile + warm both paths
    run_reference()
    fused_s = min(run_fused() for _ in range(repeat))
    ref_s = min(run_reference() for _ in range(repeat))
    speedup = ref_s / fused_s
    common.emit("roofline/aggregate/fused_speedup", speedup,
                f"m={m};ref_s={ref_s:.4f};fused_s={fused_s:.4f}")
    common.record("aggregate/fused_speedup", speedup, "floor")
    return speedup


def smoke(n_edges: int = 30_000, seed: int = 0,
          tolerance: float = 0.25) -> None:
    """CI gate: measured batched-ingest speedup and fused-aggregation
    speedup vs their committed floors."""
    from benchmarks import throughput

    floor = committed_floor()
    stream = throughput.lkml_like_stream(n_edges=n_edges, seed=seed)
    serial_s, batched_s, _ = throughput.serial_vs_batched(stream)
    speedup = serial_s / batched_s
    gate = floor * (1.0 - tolerance)
    common.emit("roofline/ingest/batched_speedup", speedup,
                f"committed_floor={floor};gate={gate:.2f}")
    assert speedup >= gate, (
        f"roofline smoke: batched ingest speedup {speedup:.2f}x fell "
        f"below the committed floor {floor}x (gate {gate:.2f}x with "
        f"{tolerance:.0%} noise tolerance)")
    agg_floor = committed_floor("aggregate/fused_speedup")
    agg = fused_aggregate_speedup(n_edges=max(n_edges // 2, 10_000),
                                  seed=seed)
    agg_gate = agg_floor * (1.0 - tolerance)
    assert agg >= agg_gate, (
        f"roofline smoke: fused aggregation speedup {agg:.2f}x fell "
        f"below the committed floor {agg_floor}x (gate {agg_gate:.2f}x "
        f"with {tolerance:.0%} noise tolerance)")
    print(f"roofline smoke OK: batched={speedup:.2f}x serial "
          f"(committed floor {floor}x); fused aggregate={agg:.2f}x "
          f"retired dataflow (committed floor {agg_floor}x)")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="ingest speedup gate vs the committed "
                         "BENCH_baseline floor")
    ap.add_argument("--edges", type=int, default=30_000)
    args = ap.parse_args()
    if args.smoke:
        smoke(n_edges=args.edges)
    else:
        run()
