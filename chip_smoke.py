#!/usr/bin/env python3
"""End-to-end bring-up check of HIGGS on a TPU.

    python chip_smoke.py                # one chip: phases `default`, `device`
    python chip_smoke.py --chips 4      # four chips: phase `sharded` only

Every phase drives the user-facing path: a summary from
``make_summary``, fed a ``wiki_talk_like_stream`` (generated from
``--seed``; ``--edges``, see ``DEFAULT_EDGES``) through
``StreamPipeline``, then served by ``SummaryService`` to 8 concurrent
callers.  Each caller submits one typed batch — edge, vertex out, vertex
in, path and subgraph queries, each over the latest 1%, the latest half
and the whole of the stream's time span.

* ``default``: ``make_summary("higgs")`` with ``auto`` parameters.  Its
  answers must equal, bit for bit, those of the host numpy twin
  (``insert_backend="host", pool_storage="host"``), which a CPU-only
  child process builds from the same stream while the chip works.
* ``device``: ``insert_backend="pallas", pool_storage="device"``,
  compared bit for bit with ``insert_backend="pallas",
  pool_storage="host"`` (the storage-seam contract).
* ``sharded`` (``--chips 4``): ``higgs-sharded`` with 4 shards and
  ``parallel="shard_map"`` over a 4-device mesh.  Every stacked probe's
  operands must span the 4 devices, and the answers must equal, bit for
  bit, those of the same fleet state under ``parallel="none"``.

Every answer is also held against ``ExactOracle`` (built in the child from
the edges the queries touch): a summary may overestimate, never
underestimate.  The script exits non-zero, and prints no result, when JAX
finds no TPU, when a phase resolves another backend, storage or Pallas
mode than it asked for, on any mismatch, or when any phase raises.  Its
last line is the JSON result ``{"ok": true, "device": {...}}``.

``--cpu-rehearsal`` runs the same flow on the CPU at a tiny size with
Pallas interpreted; it checks paths and answers, never reports a result,
and its numbers are not device numbers.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import multiprocessing as mp
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

WIKI_TALK_EDGES = 7_833_140    # SNAP wiki-talk-temporal temporal edges
LKML_REPLY_EDGES = 1_096_440   # the smallest paper graph: the floor of any cut
# Cut from WIKI_TALK_EDGES to the floor, for every phase alike: on one
# v5e chip the one-chip phases ingest at ~130 s per million edges of
# host time, so the full stream would need ~20 minutes cold, past this
# script's time budget; the four-chip phase costs four chips per second.
DEFAULT_EDGES = LKML_REPLY_EDGES
N_CALLERS = 8
KERNEL_LEAVES, KERNEL_CHUNK = 8, 1024   # the kernel check's one drain
_START = time.perf_counter()


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# stream and queries
# ---------------------------------------------------------------------------

def make_stream(n_edges: int, seed: int):
    from repro.stream.generator import wiki_talk_like_stream
    return wiki_talk_like_stream(n_edges=n_edges, seed=seed)


def make_queries(stream, seed: int) -> list[list]:
    """One typed batch per caller, drawn from edges of the stream."""
    import numpy as np

    from repro.api.queries import (EdgeQuery, PathQuery, SubgraphQuery,
                                   VertexQuery)
    src, dst, _, t = stream
    rng = np.random.default_rng(seed + 1)
    t0, t1 = int(t[0]), int(t[-1])
    span = t1 - t0
    ranges = [(t1 - span // 100, t1), (t1 - span // 2, t1), (t0, t1)]
    # first edge out of each source, to walk paths along real edges
    srcs, first = np.unique(src, return_index=True)
    batches = []
    for _ in range(N_CALLERS):
        e = rng.integers(0, len(src), 16)
        v_out = src[rng.integers(0, len(src), 16)]
        v_in = dst[rng.integers(0, len(src), 16)]
        path = [src[e[0]], dst[e[0]]]
        for _ in range(3):
            k = np.searchsorted(srcs, path[-1])
            if k == len(srcs) or srcs[k] != path[-1]:
                break
            path.append(dst[first[k]])
        sub = np.stack([src[e[8:]], dst[e[8:]]], axis=1)
        batch = []
        for ts, te in ranges:
            batch += [EdgeQuery(src[e], dst[e], ts, te),
                      VertexQuery(v_out, ts, te, "out"),
                      VertexQuery(v_in, ts, te, "in"),
                      PathQuery(np.asarray(path), ts, te),
                      SubgraphQuery(sub, ts, te)]
        batches.append(batch)
    return batches


def touched_edges(stream, batches):
    """The stream edges any query can see: an exact answer to every query
    needs only the edges whose source or destination it names."""
    import numpy as np

    from repro.api.queries import VertexQuery
    vs = []
    for batch in batches:
        for q in batch:
            if isinstance(q, VertexQuery):
                vs.append(q.v)
            else:
                vs += list(q.edge_arrays())
    vs = np.unique(np.concatenate(vs))
    src, dst = stream[0], stream[1]
    keep = np.isin(src, vs) | np.isin(dst, vs)
    return tuple(a[keep] for a in stream)


# ---------------------------------------------------------------------------
# references, built on the CPU in a child process while the chip works
# ---------------------------------------------------------------------------

def cpu_references(n_edges: int, seed: int, batches, with_twin: bool):
    """Exact-oracle answers and, with ``with_twin``, the host numpy twin's
    answers to every caller's batch, computed on the CPU."""
    import jax
    jax.config.update("jax_platforms", "cpu")     # never touch the chip
    from repro.api import make_summary
    from repro.core.oracle import ExactOracle
    from repro.stream.pipeline import StreamPipeline

    stream = make_stream(n_edges, seed)
    out = {}
    oracle = ExactOracle()
    oracle.insert(*touched_edges(stream, batches))
    out["oracle"] = [oracle.query(b).values for b in batches]
    if with_twin:
        twin = make_summary("higgs", insert_backend="host",
                            pool_storage="host")
        t = time.perf_counter()
        StreamPipeline(*stream).feed(twin)
        out["twin_ingest_s"] = time.perf_counter() - t
        out["twin"] = [twin.query(b).values for b in batches]
    return out


def _child(queue, *args) -> None:
    try:
        queue.put(("ok", cpu_references(*args)))
    except BaseException:
        queue.put(("error", traceback.format_exc()))
        raise


class CpuReferences:
    """:func:`cpu_references` in a spawned child process, so the
    references build on the host's other cores while the chip works."""

    def __init__(self, *args):
        ctx = mp.get_context("spawn")
        self._queue = ctx.Queue()
        self._proc = ctx.Process(target=_child, args=(self._queue, *args),
                                 daemon=True)
        self._result = None

    def __enter__(self) -> "CpuReferences":
        self._proc.start()
        return self

    def __exit__(self, *exc) -> None:
        if self._proc.is_alive():
            self._proc.terminate()
        self._proc.join()

    def result(self) -> dict:
        if self._result is None:
            status, payload = self._queue.get()   # drain before join
            self._proc.join()
            if status != "ok":
                raise RuntimeError(f"CPU reference child failed:\n{payload}")
            self._result = payload
        return self._result


# ---------------------------------------------------------------------------
# one phase
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds the XLA backend spends compiling (all phases so far)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def block(summary) -> None:
    """Wait for every device-resident pool slab of a summary."""
    import jax
    sketches = getattr(summary, "shards", None) or [summary]
    for sk in sketches:
        for pool in sk.pools:
            if pool.storage_kind == "device":
                jax.block_until_ready(pool.device_slabs())


def ingest(summary, stream, clock: CompileClock) -> dict:
    from repro.stream.pipeline import StreamPipeline
    c0, t = clock.seconds, time.perf_counter()
    StreamPipeline(*stream).feed(summary)
    block(summary)
    return {"ingest_s": time.perf_counter() - t,
            "compile_s": clock.seconds - c0}


async def _serve(summary, batches):
    from repro.serve.service import SummaryService
    async with SummaryService(summary, readers=2) as svc:
        results = await asyncio.gather(*(svc.submit(b) for b in batches))
    return [r.values for r in results], svc.stats


def serve(summary, batches):
    """Answer every caller's batch through one concurrent session."""
    return asyncio.run(_serve(summary, batches))


def mismatches(got, want) -> int:
    import numpy as np
    return sum(not np.array_equal(np.asarray(a), np.asarray(b))
               for gb, wb in zip(got, want) for a, b in zip(gb, wb))


def below_oracle(got, oracle) -> int:
    import numpy as np
    return sum(bool(np.any(np.asarray(a) < np.asarray(b)))
               for gb, ob in zip(got, oracle) for a, b in zip(gb, ob))


def resolved(summary) -> dict:
    """What each shard (or the one sketch) resolved ``auto`` to."""
    from repro.kernels.leaf_insert import default_interpret
    got = set()
    for sk in getattr(summary, "shards", None) or [summary]:
        interpret = sk.params.interpret
        got.add((sk._backend, sk._storage, default_interpret()
                 if interpret is None else interpret))
    if len(got) != 1:
        raise SystemExit(f"shards resolved differently: {sorted(got)}")
    return dict(zip(("backend", "storage", "interpret"), got.pop()))


def describe(name: str, summary, facts: dict, expect: dict) -> None:
    """Print the phase's facts; exit if it resolved what it did not ask
    for."""
    import jax
    dev = jax.devices()[0]
    got = resolved(summary)
    stats = dev.memory_stats() or {}
    leaves = getattr(summary, "n_leaves", None)
    if leaves is None:
        leaves = len(summary.leaf_starts)
    line = {"phase": name, **got, "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "edges": int(summary.n_items), "leaves": int(leaves),
            "levels": int(summary.n_levels),
            "space_bytes": float(summary.space_bytes()),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"), **facts,
            "elapsed_s": time.perf_counter() - _START}
    log(f"[{name}] " + json.dumps(line))
    for key, want in expect.items():
        if got[key] != want:
            raise SystemExit(f"[{name}] resolved {key}={got[key]!r}, "
                             f"asked for {want!r}")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--edges", type=int, default=None,
                    help=f"stream length (default {DEFAULT_EDGES}; "
                         f"never below {LKML_REPLY_EDGES} on the chip)")
    ap.add_argument("--seed", type=int, default=4)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny CPU run with Pallas interpreted; never "
                         "reports a result")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal

    from repro import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX found {len(devices)}", file=sys.stderr)
        return 1
    n_edges = args.edges or (20_000 if rehearsal else DEFAULT_EDGES)
    if n_edges < LKML_REPLY_EDGES and not rehearsal:
        print(f"chip_smoke: --edges below the {LKML_REPLY_EDGES}-edge floor",
              file=sys.stderr)
        return 1
    log(f"chip_smoke: {n_edges} edges (seed {args.seed}), "
        f"compile cache {cache_dir}"
        + (f"; cut from {WIKI_TALK_EDGES}" if n_edges < WIKI_TALK_EDGES
           else ""))
    interpret = rehearsal    # what auto must resolve: compiled on the chip

    stream = make_stream(n_edges, args.seed)
    batches = make_queries(stream, args.seed)
    n_queries = sum(len(b) for b in batches)
    clock = CompileClock()

    with CpuReferences(n_edges, args.seed, batches, args.chips == 1) as refs:
        if args.chips == 4:
            bad = phase_sharded(stream, batches, clock, n_queries, refs,
                                interpret)
        else:
            bad = phase_default(stream, batches, clock, n_queries, refs,
                                interpret)
            bad += phase_device(stream, batches, clock, n_queries, refs,
                                interpret)
    if bad:
        print(f"chip_smoke: {bad} mismatch(es)", file=sys.stderr)
        return 1
    if rehearsal:
        log("chip_smoke: CPU rehearsal passed (not a chip result)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


def auto_expect(interpret: bool) -> dict:
    """What ``auto`` parameters must resolve to on this platform."""
    import jax
    return {"backend": "vector" if jax.default_backend() == "tpu"
            else "host", "storage": "host", "interpret": interpret}


def phase_default(stream, batches, clock, n_queries, refs, interpret) -> int:
    from repro.api import make_summary
    sk = make_summary("higgs")
    facts = ingest(sk, stream, clock)
    got, stats = serve(sk, batches)
    ref = refs.result()
    bad = mismatches(got, ref["twin"]) + below_oracle(got, ref["oracle"])
    describe("default", sk, {**facts, "twin_ingest_s": ref["twin_ingest_s"],
                             "queries": n_queries,
                             "answered": stats.queries_served,
                             "mismatches": bad}, auto_expect(interpret))
    return bad


def kernel_check(stream, interpret: bool) -> dict:
    """Real stream edges through the Pallas kernels, held against their
    host references: one drain of ``KERNEL_LEAVES`` leaves of
    ``KERNEL_CHUNK`` items through ``leaf_insert_batched`` (bit for bit
    against ``seq_insert_ref``, Algorithm 1 in numpy), then edge and
    vertex probes of the leaves it built (against ``cmatrix``'s probes,
    to ``tests/test_kernels.py``'s tolerance).  The storage comparison
    cannot see a kernel fault: both of its sides run the same kernel."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import cmatrix, hashing
    from repro.core.cmatrix import NodeState
    from repro.core.params import HiggsParams
    from repro.kernels import ops, ref

    p = HiggsParams()
    L, n, r, q = KERNEL_LEAVES, KERNEL_CHUNK, p.r, 16
    src, dst, w, t = (np.asarray(a) for a in stream)
    # leaf i holds the n stream edges after edge i*n, live for its first
    # lengths[i]: a quarter of a leaf up to more than a leaf holds, so
    # both the merge and the spill paths run
    lengths = np.linspace(n // 4, n, L).astype(np.int64)
    take = np.arange(L)[:, None] * n + np.arange(n)[None, :]
    valid = np.arange(n)[None, :] < lengths[:, None]
    hs = jnp.asarray(hashing.np_mix32(src[take].astype(np.uint32), p.seed))
    hd = jnp.asarray(hashing.np_mix32(dst[take].astype(np.uint32),
                                      p.seed ^ 0x5BD1E995))
    fs = np.asarray(hashing.fingerprint(hs, p.F1))
    fd = np.asarray(hashing.fingerprint(hd, p.F1))
    rows = np.asarray(cmatrix.chain_from_base(
        hashing.address(hs, p.F1, p.d1), r, p.d1))
    cols = np.asarray(cmatrix.chain_from_base(
        hashing.address(hd, p.F1, p.d1), r, p.d1))
    wt = w[take].astype(np.float32)
    tt = t[take].astype(np.uint32)
    fresh = cmatrix.make_nodes(L, p.d1, p.b)
    got, got_spill = ops.leaf_insert_batched(
        fresh, fs, fd, rows, cols, wt, tt, valid, r=r, interpret=interpret)
    got = NodeState(*(np.asarray(a) for a in got))
    got_spill = np.asarray(got_spill).astype(bool) & valid
    insert_bad = 0
    for i in range(L):
        want, want_spill = ref.seq_insert_ref(
            NodeState(*(np.asarray(a[i]) for a in fresh)), fs[i], fd[i],
            rows[i], cols[i], wt[i], tt[i], valid[i], b=p.b, r=r)
        insert_bad += sum(
            not np.array_equal(g[i].view(np.uint32), x.view(np.uint32))
            for g, x in zip(got, want))
        insert_bad += not np.array_equal(got_spill[i], want_spill)

    # probes: q inserted edges (one leaf masked off), over the whole
    # block's time span and over its later half
    nodes = NodeState(*(jnp.asarray(a) for a in got))
    mask = jnp.asarray(np.arange(L) != 1)
    e = np.random.default_rng(0).integers(0, n // 2, (q,))
    li = np.arange(q) % L
    qs = [jnp.asarray(a[li, e]) for a in (fs, fd, rows, cols)]
    probe_bad = 0
    for ts, te in ((tt.min(), tt.max()), (np.median(tt), tt.max())):
        ts, te = jnp.uint32(ts), jnp.uint32(te)
        for match_time in (False, True):
            pairs = [(ops.edge_probe(nodes, mask, *qs, ts, te,
                                     match_time=match_time,
                                     interpret=interpret),
                      ref.edge_probe_ref(nodes, mask, *qs, ts, te,
                                         match_time))]
            for direction, fv, rv in (("out", qs[0], qs[2]),
                                      ("in", qs[1], qs[3])):
                pairs.append((
                    ops.vertex_probe(nodes, mask, fv, rv, ts, te,
                                     direction=direction,
                                     match_time=match_time,
                                     interpret=interpret),
                    ref.vertex_probe_ref(nodes, mask, fv, rv, ts, te,
                                         direction, match_time)))
            probe_bad += sum(
                not np.allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
                for a, b in pairs)
    return {"leaves": L, "chunk": n,
            "spilled": int(got_spill.sum()),
            "insert_mismatches": int(insert_bad),
            "probe_mismatches": int(probe_bad),
            "elapsed_s": time.perf_counter() - _START}


def phase_device(stream, batches, clock, n_queries, refs, interpret) -> int:
    from repro.api import make_summary
    check = kernel_check(stream, interpret)
    log("[kernel-check] " + json.dumps(check))
    kernel_bad = check["insert_mismatches"] + check["probe_mismatches"]
    expect = {"backend": "pallas", "interpret": interpret}
    sk = make_summary("higgs", insert_backend="pallas",
                      pool_storage="device")
    facts = ingest(sk, stream, clock)
    got, stats = serve(sk, batches)
    describe("device", sk, {**facts, "queries": n_queries,
                            "answered": stats.queries_served},
             {**expect, "storage": "device"})
    ref_sk = make_summary("higgs", insert_backend="pallas",
                          pool_storage="host")
    ref_facts = ingest(ref_sk, stream, clock)
    want = [ref_sk.query(b).values for b in batches]
    bad = mismatches(got, want) + below_oracle(got, refs.result()["oracle"])
    describe("device-reference", ref_sk, {**ref_facts, "mismatches": bad},
             {**expect, "storage": "host"})
    return bad + kernel_bad


def phase_sharded(stream, batches, clock, n_queries, refs, interpret) -> int:
    from repro.api import make_summary
    from repro.shard.summary import ShardedHiggs
    fleet = make_summary("higgs-sharded", shards=4, parallel="shard_map")
    facts = ingest(fleet, stream, clock)
    # record the devices of every stacked probe operand
    spans = []
    run_stacked = ShardedHiggs.run_stacked

    def recording(self, fn, nodes, mask, *a, **kw):
        spans.append(len(nodes.fp_s.sharding.device_set))
        return run_stacked(self, fn, nodes, mask, *a, **kw)

    ShardedHiggs.run_stacked = recording
    try:
        got, stats = serve(fleet, batches)
    finally:
        ShardedHiggs.run_stacked = run_stacked
    off_mesh = sum(n != 4 for n in spans)
    # the same fleet state, probed without the mesh dispatch
    arrays, meta = fleet.state_dict()
    plain = make_summary("higgs-sharded", shards=4, parallel="none")
    plain.load_state(arrays, dict(meta, config=dict(meta["config"],
                                                    parallel="none")))
    want = [plain.query(b).values for b in batches]
    bad = (mismatches(got, want) + below_oracle(got, refs.result()["oracle"])
           + off_mesh + (0 if spans else 1))
    describe("sharded", fleet, {**facts, "queries": n_queries,
                                "answered": stats.queries_served,
                                "stacked_launches": len(spans),
                                "launches_off_mesh": off_mesh,
                                "mismatches": bad},
             auto_expect(interpret))
    return bad


if __name__ == "__main__":
    sys.exit(main())
