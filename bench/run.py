#!/usr/bin/env python3
"""HIGGS benchmark: one cell of ``BENCHMARK.json`` on the chips at hand.

    python bench/run.py --workload lkml.ingest --seed 7 --seconds 30 --trace 0

A cell names a configuration (``bench/configs/<config>.json``: the
stream's shape, the summary's parameters, the prefill) and a traffic mix
(``bench/traffic/<traffic>.json``: the writer and the check's queries).
A run

1. starts JAX with the persistent compile cache in the checkout
   (``repro.compile_cache``) and exits non-zero, printing no result,
   without a TPU or with fewer chips than the cell asks for;
2. makes the stream from ``--seed`` and builds the summary through
   ``make_summary``, then, as set-up, prefills it and compiles the
   eviction slides the window will run (``warm_evictions``);
3. measures ``--seconds`` seconds of the writer (``drive.py``);
4. asks the final state the traffic mix's check batches, frees the
   summary and holds every answer against the plain reference
   (``check.py``);
5. prints a set-up line, then the result as the last line of standard
   output: the end-to-end metrics with ``--trace 0``, the per-layer
   metrics (``bench/metrics/<metric>.py``) with ``--trace 1``, which also
   traces a few steady seconds of the window.

``--rehearsal`` runs the same flow on the CPU at the configuration's
``rehearsal`` sizes (Pallas interpreted); it prints the would-be result
after ``rehearsal:``, never as a result line.  ``--control`` runs the
program with the configuration's ``control`` overrides (the control of
the correctness check: it must come out not correct).
"""
from __future__ import annotations

import argparse
import asyncio
import copy
import gc
import json
import logging
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

T_IMPORT = time.perf_counter()


def _since_process_start() -> float:
    """Seconds since this process started, at this call."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = float(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


PROC_START = time.perf_counter() - _since_process_start()

sys.path.insert(0, str(Path(__file__).resolve().parent))
import loader  # noqa: E402

sys.path.insert(0, str(loader.ROOT / "src"))

now = time.perf_counter


def log(msg: str, err: bool = False) -> None:
    print(msg, file=sys.stderr if err else sys.stdout, flush=True)


class CompileClock(logging.Handler):
    """Seconds and count of XLA compiles and persistent-cache loads, from
    ``jax.monitoring``; from JAX's compile log, the programs lowered and
    those the persistent cache held (a lowering the cache did not hold
    compiled), and the names lowered while ``names`` is a list."""

    def __init__(self):
        super().__init__(logging.WARNING)
        import jax
        self.seconds = 0.0
        self.names: list | None = None
        self.lowered = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.lowered += 1
            if self.names is not None:
                self.names.append(msg.split()[1])
        elif msg.startswith("Persistent compilation cache hit for "):
            self.hits += 1

    def compiled(self) -> int:
        """Programs compiled so far, not loaded from the cache."""
        return self.lowered - self.hits


def pct(x, q: float) -> float | None:
    return float(np.percentile(x, q)) if len(x) else None


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def sketch_params(cfg: dict, control: bool) -> dict:
    """The summary's parameters; with ``control``, the configuration's
    ``control["sketch"]`` overrides, which break one guarantee it
    states."""
    kw = dict(cfg["sketch"])
    if control:
        kw.update(cfg["control"]["sketch"])
    kw["retention"] = {k: v for k, v in cfg["retention"].items()
                       if k in ("kind", "t_horizon", "max_bytes")}
    return kw


def rehearsal_config(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(cfg.pop("rehearsal", {}))
    return cfg


def block(summary) -> None:
    import jax
    for pool in summary.pools:
        if pool.storage_kind == "device" and pool.n:
            jax.block_until_ready(pool.device_slabs())


def to_queries(plain: list) -> list:
    from repro.api.queries import (EdgeQuery, PathQuery, SubgraphQuery,
                                   VertexQuery)
    out = []
    for kind, ids, ts, te in plain:
        if kind == "edge":
            out.append(EdgeQuery(ids[0], ids[1], ts, te))
        elif kind in ("out", "in"):
            out.append(VertexQuery(ids, ts, te, kind))
        elif kind == "path":
            out.append(PathQuery(np.append(ids[0], ids[1][-1:]), ts, te))
        else:
            out.append(SubgraphQuery(np.stack(ids, axis=1), ts, te))
    return out


def eviction_counts(cfg: dict, t: np.ndarray, cuts, first: int) -> dict:
    """Per level, the node counts at which evictions slide that level
    during the inserts ``cuts[first:]`` (items inserted after each call),
    replayed from leaf closing and retention (``reference.LeafScan``): a
    level holds the closed leaves over ``theta ** (level - 1)``, less
    the nodes of the segments evicted before."""
    from reference import LeafScan
    levels = int(cfg["sketch"]["segment_levels"])
    theta = int(cfg["sketch"]["theta"])
    scan = LeafScan(t, cfg["sketch"], cfg["retention"])
    out: dict = {lvl: set() for lvl in range(1, levels + 2)}
    for i, c in enumerate(cuts):
        e0 = scan.n_evicted
        scan.advance(c)
        if i < first:
            continue
        n_leaves = len(scan.leaf_ends)
        for e in range(e0, scan.n_evicted):
            for lvl in out:
                out[lvl].add(n_leaves // theta ** (lvl - 1)
                             - e * theta ** (levels + 1 - lvl))
    return out


def warm_evictions(summary, cfg, counts: dict) -> int:
    """Compile the eviction slides the window will run.  Under device
    pools a slide is eager jnp slicing whose programs depend on how many
    nodes a level holds when a segment leaves; run the program's own
    slide on a scratch copy of each level for ``counts``.  The range of
    counts is widened by a node and rounded out to whole segments, so
    that seeds, whose ranges differ by a few nodes, warm the same counts
    and find them in the cache.  Returns the counts warmed."""
    import jax

    from repro.core.pool import DevicePoolStorage
    levels = int(cfg["sketch"]["segment_levels"])
    theta = int(cfg["sketch"]["theta"])
    warmed = 0
    for lvl, pool in enumerate(summary.pools[:levels + 1], start=1):
        if pool.storage_kind != "device" or not counts.get(lvl):
            continue
        k = theta ** (levels - lvl + 1)         # nodes a segment holds here
        lo = (min(counts[lvl]) - 1) // k * k
        hi = -(-(max(counts[lvl]) + 1) // k) * k
        st = DevicePoolStorage(pool.d, pool.b)
        for n in range(lo, hi + 1):
            if k <= n <= pool.cap:
                st.slabs, st.cap = dict(pool.device_slabs()), pool.cap
                st.slide(n, k)
                warmed += 1
                jax.block_until_ready(list(st.slabs.values()))
    return warmed


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU run, Pallas interpreted; no result line")
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's control; must come out "
                         "not correct")
    ap.add_argument("--dump", default=None,
                    help="directory for the reduced trace")
    args = ap.parse_args(argv)

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, args.workload)
    cfg = loader.load_config(cell["config"])
    mix = loader.load_traffic(cell["traffic"])
    if args.rehearsal:
        cfg = rehearsal_config(cfg)
    if mix["writer"]["mode"] != "closed":
        log(f"bench: traffic {cell['traffic']!r}: only a closed-loop writer "
            "is supported", err=True)
        return 1

    from repro import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearsal:
        log(f"bench: JAX found no TPU (platform {dev.platform!r})", err=True)
        return 1
    if len(devices) < int(cell["chips"]):
        log(f"bench: {cell['name']} needs {cell['chips']} chips, JAX found "
            f"{len(devices)}", err=True)
        return 1
    t_jax = now()
    clock = CompileClock()

    import streams
    import traffic
    from check import LIMITS, compare
    from drive import Log, Phase, insert, run_phase
    from reference import Reference

    from repro.api import make_summary
    from repro.serve.service import SummaryService

    # -- stream -------------------------------------------------------------
    batch = int(mix["writer"]["batch"])
    prefill = int(cfg["prefill_edges"])
    limit = (prefill + int(float(cfg["max_ingest_eps"]) * args.seconds)
             + 2 * batch)
    arrays = streams.EdgeStream(cfg["stream"], args.seed).arrays(limit)
    t_first = int(arrays[3][0])
    span = (int(cfg["retention"]["t_horizon"])
            if cfg["retention"]["kind"] == "window" else None)
    t_stream = now()

    # -- summary and prefill ------------------------------------------------
    summary = make_summary("higgs", **sketch_params(cfg, args.control))
    resolved = {"backend": summary._backend, "storage": summary._storage}
    if not args.rehearsal and resolved != cfg["expect_on_tpu"]:
        log(f"bench: resolved {resolved}, configuration states "
            f"{cfg['expect_on_tpu']}", err=True)
        return 1
    lg = Log()
    pb = int(cfg.get("prefill_batch", batch))
    starts = range(0, prefill, pb)
    cuts = [min(c + pb, prefill) for c in starts]
    for c0, c1 in zip(starts, cuts):
        insert(summary, arrays, c0, c1, lg)
    block(summary)
    t_prefill = now()
    n_slides = 0
    if cfg["retention"]["kind"] != "none":
        window_cuts = list(range(prefill + batch, limit + 1, batch))
        counts = eviction_counts(cfg, arrays[3], cuts + window_cuts,
                                 len(cuts))
        n_slides = warm_evictions(summary, cfg, counts)
    compile_setup = clock.seconds
    compiled_setup = clock.compiled()
    t_warm = now()

    # -- the window ---------------------------------------------------------
    trace_dir = str(loader.ROOT / ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    t_open = now()
    ph = Phase(t_stop=t_open + args.seconds, cursor=prefill,
               batch=batch)
    if args.trace:
        trace_s = min(4.0, args.seconds / 2)
        a = t_open + (args.seconds - trace_s) / 2
        ph.trace, ph.trace_dir = (a, a + trace_s), trace_dir
    c_win0 = clock.seconds
    clock.names = []
    i_ins = len(lg.inserts)
    cursor = run_phase(summary, arrays, ph, lg, limit)
    block(summary)
    lowered, clock.names = clock.names, None
    compile_window = clock.seconds - c_win0
    compiled_window = clock.compiled() - compiled_setup
    window = lg.inserts[i_ins:]
    t_close = t_open + args.seconds
    done = [c for (_, b, c, _) in window if b <= t_close]
    n_in = (done[-1] if done else prefill) - prefill
    ins_s = np.asarray([b - a for (a, b, _, _) in window])
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    space = float(summary.space_bytes())
    levels = int(summary.n_levels)

    # -- the final state, asked the traffic mix's check batches -------------
    plain = traffic.check_batches(arrays, cursor, t_first, span,
                                  mix["check"], cfg["sketch"], args.seed)
    asked = []
    t_check0 = now()

    async def ask():
        async with SummaryService(summary, readers=1) as svc:
            for p in plain:
                sub_v = int(summary.structure_version)
                asked.append((p, await svc.submit(to_queries(p)), sub_v))
    asyncio.run(ask())
    t_check = now() - t_check0
    del summary
    gc.collect()

    # -- correctness -----------------------------------------------------------
    t_ref0 = now()
    ref = Reference(tuple(a[:cursor] for a in arrays), cfg["sketch"],
                    cfg["retention"])
    verdict, _ = compare(ref, [(c, v) for _, _, c, v in lg.inserts], asked)
    t_ref = now() - t_ref0

    # -- metrics ---------------------------------------------------------------
    e2e = {"ingest_eps": n_in / args.seconds, "setup_s": t_open - PROC_START}
    log("[setup] " + json.dumps({
        "cache_dir": cache_dir, "proc_to_jax_s": t_jax - PROC_START,
        "stream_s": t_stream - t_jax, "prefill_s": t_prefill - t_stream,
        "warmup_s": t_warm - t_prefill, "compile_s_setup": compile_setup,
        "eviction_counts_warmed": n_slides,
        "compiled_setup": compiled_setup,
        "compile_s_window": compile_window,
        "compiled_window": compiled_window,
        "lowered_in_window": sorted(set(lowered)),
        "insert_ms_p50": pct(ins_s * 1e3, 50),
        "insert_ms_max": float(ins_s.max() * 1e3) if len(ins_s) else None,
        "window_overrun_ms": ((window[-1][1] - t_close) * 1e3 if window
                              else None),
        "peak_bytes_in_use": peak, "bytes_limit": stats.get("bytes_limit"),
        "space_bytes": space, "levels": levels, "items": cursor,
        "prefill_items": prefill, "window_items": n_in,
        "check_ask_s": t_check, "reference_s": t_ref,
        "control": args.control, **resolved}))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(verdict["correct"]),
           "attempted": verdict["attempted"], "failed": verdict["failed"]}
    if args.trace:
        reduced = read_trace(trace_dir, args.dump)
        ctx = {"trace": reduced, "backend": resolved["backend"],
               "counters": {"edges_traced": lg.edges_traced}}
        metrics = {}
        for m in loader.metrics_of(bench, cell["name"], "per_layer"):
            v = loader.load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in loader.metrics_of(bench, cell["name"],
                                              "end_to_end")}
    out["metrics"] = metrics
    out["device"] = device
    checks = {k: {"value": v, "limit": LIMITS[k]}
              for k, v in verdict["numbers"].items()}
    checks["values_compared"] = {"value": verdict["values_compared"],
                                 "limit": "more than 0"}
    out["checks"] = checks
    if verdict["values_compared"] == 0:
        out["correct"] = False
    for k, v in checks.items():
        log(f"check {k} = {v['value']} (limit {v['limit']})", err=True)
    log(("rehearsal: " if args.rehearsal else "") + json.dumps(out))
    return 0


def read_trace(trace_dir: str, dump: str | None):
    import devtrace as tr
    try:
        events = tr.load(trace_dir)
    except FileNotFoundError:
        return None
    if dump:
        os.makedirs(dump, exist_ok=True)
        tr.save(events, os.path.join(dump, "trace_events.json.gz"))
        with open(os.path.join(dump, "trace_structure.json"), "w") as fh:
            json.dump(tr.describe(trace_dir), fh, indent=1)
    return tr.reduce(events)


if __name__ == "__main__":
    sys.exit(main())
