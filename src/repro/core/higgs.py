"""HIGGS: the item-based, bottom-up hierarchical graph-stream summary.

Host/device split (DESIGN.md §3): tree metadata (leaf start/end timestamps,
per-level node counts, overflow blocks) lives on the host; the compressed
matrices live on device as per-level stacked pools.  Insertion is chunked —
each chunk of ``params.chunk_size`` stream items becomes one leaf, with
equal-timestamp runs never split across leaves (this subsumes the paper's
Overflow Block trigger; a run longer than a chunk spills into the leaf's OB,
exactly the OB's role in the paper).  Aggregation (paper Alg. 2) fires
bottom-up whenever theta nodes of a level complete.
"""
from __future__ import annotations

import dataclasses
import os

import jax.numpy as jnp
import numpy as np

from repro.analysis.sanitize import maybe_check as _sanitize_check
from repro.api.planner import QueryPlanner
from repro.api.protocol import LegacyQueryMixin
from repro.api.queries import IngestStats, QueryBatch, QueryResult
from repro.core import cmatrix, hashing
from repro.core.cmatrix import EMPTY, NodeState
from repro.core.cmatrix import pow2_pad as _pow2_pad
from repro.core.params import HiggsParams
from repro.core.pool import _LevelPool
from repro.core.segments import SegmentStore
from repro.runtime.trace import fetch as _fetch
from repro.runtime.trace import span as _span
from repro.runtime.trace import spanned as _spanned


class _LeafIndex:
    """Leaf [start, end] timestamp keys (the B+-tree key strip) with
    amortized-doubling storage — ``np.append`` per closed leaf made
    metadata growth O(n^2) over the stream."""

    def __init__(self):
        self.n = 0
        self._starts = np.zeros((16,), np.uint64)
        self._ends = np.zeros((16,), np.uint64)

    def _reserve(self, need: int) -> None:
        if need <= len(self._starts):
            return
        cap = len(self._starts)
        while cap < need:
            cap *= 2
        starts = np.zeros((cap,), np.uint64)
        ends = np.zeros((cap,), np.uint64)
        starts[: self.n] = self._starts[: self.n]
        ends[: self.n] = self._ends[: self.n]
        self._starts, self._ends = starts, ends

    def append(self, ts0: int, ts1: int) -> None:
        self._reserve(self.n + 1)
        self._starts[self.n] = np.uint64(ts0)
        self._ends[self.n] = np.uint64(ts1)
        self.n += 1

    def extend(self, ts0s: np.ndarray, ts1s: np.ndarray) -> None:
        m = len(ts0s)
        self._reserve(self.n + m)
        self._starts[self.n:self.n + m] = ts0s
        self._ends[self.n:self.n + m] = ts1s
        self.n += m

    def drop_prefix(self, k: int) -> None:
        """Drop the ``k`` oldest interval keys (evicted or coarsened
        leaves); the retained keys slide to the front in place."""
        if k <= 0:
            return
        if k > self.n:
            raise ValueError(f"cannot drop {k} of {self.n} leaf keys")
        self._starts[: self.n - k] = self._starts[k: self.n].copy()
        self._ends[: self.n - k] = self._ends[k: self.n].copy()
        self.n -= k

    def load(self, starts: np.ndarray, ends: np.ndarray) -> None:
        """Overwrite with snapshot keys (fresh doubling storage)."""
        self.n = 0
        self._starts = np.zeros((16,), np.uint64)
        self._ends = np.zeros((16,), np.uint64)
        self.extend(np.asarray(starts, np.uint64),
                    np.asarray(ends, np.uint64))

    @property
    def starts(self) -> np.ndarray:
        return self._starts[: self.n]

    @property
    def ends(self) -> np.ndarray:
        return self._ends[: self.n]

    def pin_view(self) -> "_LeafIndex":
        """Zero-copy clone sharing the key arrays; safe while the writer
        only appends past ``n`` (reserve copies-on-grow) — the lifecycle
        ``drop_prefix`` slide is excluded by the pin fast-path gate."""
        clone = _LeafIndex.__new__(_LeafIndex)
        clone.n = self.n
        clone._starts = self._starts
        clone._ends = self._ends
        return clone


class _OverflowStore:
    """Host-side overflow blocks: canonical entries per (level, node).

    Columns grow by amortized doubling (like :class:`_LeafIndex`) — the
    previous ``np.concatenate`` per add made a hot key's growth O(n^2)
    over the stream."""

    FIELDS = ("f1s", "f1d", "bs", "bd", "w", "t")

    def __init__(self):
        self._cols: dict[tuple[int, int], dict[str, np.ndarray]] = {}
        self._len: dict[tuple[int, int], int] = {}

    @staticmethod
    def _dtype(field: str):
        return np.float64 if field == "w" else np.uint32

    def add(self, level: int, node: int, **cols) -> None:
        n = len(cols["w"])
        if n == 0:
            return
        key = (level, node)
        store = self._cols.get(key)
        if store is None:
            store = {k: np.zeros((max(16, n),), self._dtype(k))
                     for k in self.FIELDS}
            self._cols[key] = store
            self._len[key] = 0
        m = self._len[key]
        cap = len(store["w"])
        if m + n > cap:
            new_cap = max(2 * cap, m + n)
            for k in self.FIELDS:
                buf = np.zeros((new_cap,), self._dtype(k))
                buf[:m] = store[k][:m]
                store[k] = buf
        for k in self.FIELDS:
            store[k][m:m + n] = np.asarray(cols.get(k, np.zeros(n)),
                                           self._dtype(k))
        self._len[key] = m + n

    def get(self, level: int, node: int):
        key = (level, node)
        if key not in self._cols:
            return None
        m = self._len[key]
        return {k: v[:m] for k, v in self._cols[key].items()}

    def drop(self, level: int, node: int) -> int:
        """Discard the entries of one (level, node) key — segment
        eviction pruning; returns the number of entries freed."""
        key = (level, node)
        freed = self._len.pop(key, 0)
        self._cols.pop(key, None)
        return freed

    @property
    def data(self) -> dict:
        """Trimmed {(level, node): columns} view (accounting/tests)."""
        return {key: self.get(*key) for key in self._cols}

    def total_entries(self) -> int:
        return sum(self._len.values())

    def load(self, records: dict) -> None:
        """Overwrite with snapshot records {(level, node): columns};
        column capacities re-amortize from the trimmed lengths."""
        self._cols.clear()
        self._len.clear()
        for (level, node), cols in records.items():
            self.add(level, node, **cols)

    def pin_view(self) -> "_OverflowStore":
        """Clone sharing the column buffers through copied key dicts.

        Writer appends either write in place past the pinned length
        (invisible — :meth:`get` slices to the pin's own ``_len``) or
        double capacity, which rebinds buffers in the *writer's* inner
        dict; the pin's copied dicts keep the old buffers.  ``drop`` is
        lifecycle-only and excluded by the pin fast-path gate."""
        clone = _OverflowStore()
        clone._cols = {key: dict(cols) for key, cols in self._cols.items()}
        clone._len = dict(self._len)
        return clone


class HiggsSketch(LegacyQueryMixin):
    """The full HIGGS structure behind the ``GraphSummary`` protocol.

    The batched surface is :meth:`query` (a typed query batch executed by
    the :class:`~repro.api.planner.QueryPlanner`); the legacy per-method
    API (``edge_query``/``vertex_query``/``path_query``/``subgraph_query``)
    comes from :class:`LegacyQueryMixin` as thin shims over :meth:`query`.
    """

    name = "HIGGS"
    snapshot_kind = "higgs"
    # rebuilt from params / restored via the probe_counter property —
    # intentionally not serialized (higgslint R3); _pinned marks an
    # epoch replica (a restored sketch is always writable again);
    # ingest_stats is telemetry and starts afresh
    _SNAPSHOT_DERIVED = ("_probe_base", "_chunk_pad", "_backend",
                         "_storage", "_pipeline", "_pinned",
                         "ingest_stats")

    def __init__(self, params: HiggsParams = HiggsParams()):
        self.params = params
        self._backend = self._resolve_backend(params)
        self._storage = self._resolve_storage(params, self._backend)
        self._pipeline = None     # lazy fused-drain pipeline (pallas+device)
        self.ingest_stats = IngestStats()          # ingest-path counters
        self.pools: list[_LevelPool] = [
            _LevelPool(params.d1, params.b, storage=self._storage,
                       stats=self.ingest_stats)]   # level 1 (leaves)
        self._leaves = _LeafIndex()
        self.ob = _OverflowStore()
        self._buf: list[np.ndarray] = []           # pending raw items
        self._buf_len = 0
        self.n_items = 0
        self.segments = SegmentStore(params)       # temporal lifecycle
        self._t_last = 0                           # newest closed-leaf end
        self._version = 0                          # bumped on tree mutation
        self._probe_base = 0                       # legacy counter offset
        self.planner = QueryPlanner(self)
        self._chunk_pad = _pow2_pad(params.chunk_size, lo=64)
        self._pinned = False                       # epoch replicas only

    @staticmethod
    def _resolve_backend(params: HiggsParams) -> str:
        backend = params.insert_backend
        if backend != "auto":
            return backend
        env = os.environ.get("HIGGS_INSERT_BACKEND", "").strip().lower()
        if env in ("host", "vector", "pallas"):
            if env == "pallas" and not (params.use_ob and
                                        params.batched_ingest):
                # the same refusal as an explicit insert_backend="pallas"
                raise ValueError(
                    "HIGGS_INSERT_BACKEND=pallas requires use_ob and "
                    "batched_ingest (spills must go to overflow blocks, "
                    "not recursive leaves)")
            return env
        import jax
        return "vector" if jax.default_backend() == "tpu" else "host"

    @staticmethod
    def _resolve_storage(params: HiggsParams, backend: str) -> str:
        if params.pool_storage != "auto":
            return params.pool_storage
        # device residency pays off when the drain runs on device; the
        # host/vector placement engines keep the zero-copy numpy pools
        return "device" if backend == "pallas" else "host"

    @property
    def leaf_starts(self) -> np.ndarray:
        return self._leaves.starts

    @property
    def leaf_ends(self) -> np.ndarray:
        return self._leaves.ends

    @property
    def structure_version(self) -> int:
        """Monotone counter of tree mutations; the planner's memoized
        boundary-search plans are valid for a single version."""
        return self._version

    @property
    def probe_counter(self) -> int:
        """Legacy view of buckets probed; canonical accounting now lives
        in per-execution :class:`~repro.api.queries.QueryStats`."""
        return self._probe_base + self.planner.lifetime.buckets_probed

    @probe_counter.setter
    def probe_counter(self, value: int) -> None:
        self._probe_base = value - self.planner.lifetime.buckets_probed

    # ------------------------------------------------------------------
    # batched queries (GraphSummary surface)
    # ------------------------------------------------------------------

    def query(self, queries: QueryBatch) -> QueryResult:
        """Execute a typed query batch: one boundary search per distinct
        time range, one device probe per (level, range class)."""
        return self.planner.execute(queries)

    # ------------------------------------------------------------------
    # read epochs (concurrent serving surface)
    # ------------------------------------------------------------------

    def snapshot_epoch(self):
        """Pin an immutable :class:`~repro.serve.epoch.ReadEpoch` of the
        current (drained) state: queries against it are bit-identical to
        quiescing the sketch at this ``structure_version``, no matter
        what the writer drains afterwards."""
        from repro.serve.epoch import ReadEpoch
        return ReadEpoch.pin(self)

    def epoch_info(self) -> dict:
        """Position metadata stamped onto a pinned epoch."""
        return {
            "n_items": int(self.n_items),
            "n_leaves": int(self._leaves.n),
            "t_last": int(self._t_last),
            "segments": self.segments.epoch_stamp(),
        }

    @_spanned("higgs.pin")
    def _pin_replica(self) -> "HiggsSketch":
        """Read-only replica frozen at the current ``structure_version``.

        Fast path (host pool storage, dormant lifecycle): share the
        writer's slabs zero-copy behind pinned counts — every writer
        mutation is then either append-past-``n`` (invisible through the
        pinned counts) or copy-on-grow (rebinds the writer's arrays,
        leaving the pin untouched).  Device storage (whose fused drain
        donates slab buffers) and live retention policies (whose
        lifecycle slides retained rows in place) deep-copy through the
        snapshot codec instead — same bits, independent storage.

        The pending raw-item buffer is deliberately not carried: items
        that have not closed a leaf are invisible to queries on the live
        sketch too, so the replica answers exactly like the writer would
        if it were quiesced right now.

        Either way the replica's planner adopts the writer's memoized
        plan cache when it is warm at this ``structure_version`` (plans
        are pure functions of the tree structure): zero-copy with
        copy-on-write on the fast path, a dict copy on the deep path —
        a fresh epoch pin is then O(1) to its first answer.
        """
        if self._storage == "host" and not self.segments.active:
            rep = object.__new__(type(self))
            rep.params = self.params
            rep._backend = self._backend
            rep._storage = self._storage
            rep._pipeline = None
            rep.ingest_stats = IngestStats()
            rep.pools = [pool.pin_view() for pool in self.pools]
            rep._leaves = self._leaves.pin_view()
            rep.ob = self.ob.pin_view()
            rep._buf = []
            rep._buf_len = 0
            rep.n_items = self.n_items
            rep.segments = SegmentStore(self.params)
            rep.segments.load(self.segments.meta())
            rep._t_last = self._t_last
            rep._version = self._version
            rep._probe_base = 0
            rep.planner = QueryPlanner(rep)
            rep.planner.adopt_cache(self.planner)
            rep._chunk_pad = self._chunk_pad
        else:
            arrays, meta = self.state_dict()
            rep = type(self)(self.params)
            rep.load_state(arrays, meta)
            rep.planner.adopt_cache(self.planner, copy=True)
        rep._pinned = True
        return rep

    # ------------------------------------------------------------------
    # persistence (GraphSummary snapshot surface)
    # ------------------------------------------------------------------

    def state_dict(self):
        """Full sketch state as flat host arrays + JSON-able metadata.

        Everything the stream ever contributed is captured: every level
        pool (trimmed to its node count, capacities recorded), the leaf
        interval index, the overflow-store columns, the *pending* raw-item
        buffer (a mid-stream snapshot must not lose items that have not
        formed a leaf yet), plus ``structure_version`` and the params.

        This is the **snapshot barrier** for device-resident pools: the
        ``pool.arrs`` host view materializes the device slabs exactly
        here (epoch-cached — repeated snapshots of an unchanged pool
        reuse the fetch), so steady-state ingest never pays pool d2h and
        kill-and-resume stays bit-identical across storage backends.
        """
        arrays: dict[str, np.ndarray] = {
            "leaf_starts": self._leaves.starts,
            "leaf_ends": self._leaves.ends,
            "buf": (np.concatenate(self._buf, axis=1) if self._buf
                    else np.zeros((4, 0), np.uint32)),
        }
        pools_meta = []
        for lvl, pool in enumerate(self.pools, start=1):
            pools_meta.append({"n": int(pool.n), "cap": int(pool.cap),
                               "d": int(pool.d), "b": int(pool.b),
                               "base": int(pool.base)})
            # snapshots serialize the physical slabs verbatim (base is
            # saved alongside) — no id translation wanted here
            src = (pool.arrs  # higgslint: disable=R2
                   if pool.arrs is not None
                   else cmatrix.empty_node_arrays(0, pool.d, pool.b))
            for name in NodeState._fields:
                arrays[f"pool{lvl}/{name}"] = src[name][:pool.n]
        ob_keys = []
        for (level, node), cols in self.ob.data.items():
            ob_keys.append([int(level), int(node)])
            for field, col in cols.items():
                arrays[f"ob/{level}.{node}/{field}"] = col
        meta = {
            "config": dataclasses.asdict(self.params),
            "n_items": int(self.n_items),
            "buf_len": int(self._buf_len),
            "version": int(self._version),
            "probe_counter": int(self.probe_counter),
            "pools": pools_meta,
            "ob_keys": ob_keys,
            "t_last": int(self._t_last),
            "segments": self.segments.meta(),
        }
        return arrays, meta

    def load_state(self, arrays: dict, meta: dict) -> None:
        """Exact inverse of :meth:`state_dict`: reconfigure from the saved
        params and overwrite all state, leaving a sketch bit-identical to
        the saved one (pools, OB, intervals, pending buffer and therefore
        all query answers and all future-insert behavior).  The planner is
        rebuilt and its plan cache re-seeded from the restored
        ``structure_version`` — stale plans must never survive a restore.
        """
        self.__init__(HiggsParams(**meta["config"]))
        for lvl, pm in enumerate(meta["pools"], start=1):
            if lvl > len(self.pools):
                self.pools.append(_LevelPool(int(pm["d"]), int(pm["b"]),
                                             storage=self._storage,
                                             stats=self.ingest_stats))
            self.pools[lvl - 1].load(
                {name: arrays[f"pool{lvl}/{name}"]
                 for name in NodeState._fields},
                int(pm["n"]), cap=int(pm["cap"]),
                base=int(pm.get("base", 0)))
        self._leaves.load(arrays["leaf_starts"], arrays["leaf_ends"])
        self.ob.load({(int(lvl), int(node)):
                      {f: arrays[f"ob/{lvl}.{node}/{f}"]
                       for f in _OverflowStore.FIELDS}
                      for lvl, node in meta["ob_keys"]})
        buf = np.ascontiguousarray(arrays["buf"], np.uint32)
        self._buf = [buf] if buf.shape[1] else []
        self._buf_len = int(meta["buf_len"])
        self.n_items = int(meta["n_items"])
        self._t_last = int(meta.get("t_last", 0))
        self.segments.load(meta.get("segments"))
        self._version = int(meta["version"])
        self.planner.invalidate()
        self.probe_counter = int(meta["probe_counter"])

    # ------------------------------------------------------------------
    # insertion
    # ------------------------------------------------------------------

    @_spanned("higgs.insert")
    def insert(self, src, dst, w, t) -> None:
        """Insert a batch of stream items (arrival order, t non-decreasing).

        src/dst: uint32 vertex ids; w: weights (negative = deletion);
        t: uint32 timestamps.
        """
        if self._pinned:
            raise RuntimeError(
                "epoch-pinned replica is read-only; insert into the "
                "live summary it was pinned from")
        batch = np.stack([
            np.asarray(src, np.uint32), np.asarray(dst, np.uint32),
            np.asarray(w, np.float32).view(np.uint32),
            np.asarray(t, np.uint32)], axis=0)
        self._buf.append(batch)
        self._buf_len += batch.shape[1]
        self.n_items += batch.shape[1]
        self.ingest_stats.inserts += 1
        self._drain(final=False)

    def flush(self) -> None:
        """Close the current partial leaf (end of stream / snapshot)."""
        if self._pinned:
            raise RuntimeError(
                "epoch-pinned replica is read-only; flush the live "
                "summary it was pinned from")
        self._drain(final=True)
        if self.segments.active:
            self._lifecycle()          # idempotent; a no-op drain must
            #                            still settle expired segments
        _sanitize_check(self)

    def _drain(self, final: bool) -> None:
        """Split the pending buffer into every complete leaf at once.

        Chunk boundaries are a deterministic function of the buffered item
        sequence alone (never of how ``insert`` batched it), so the span
        scan (:meth:`_split_pending`) is equivalent to the legacy
        one-leaf-per-iteration loop; closing then happens for all spans in
        one batched launch (or serially per span on the reference path).
        """
        cs = self.params.chunk_size
        if self._buf_len < cs and not (final and self._buf_len > 0):
            return
        with _span("higgs.drain"):
            with _span("higgs.drain.split"):
                buf, spans = self._split_pending(final)
            if not spans:
                return
            self.ingest_stats.drains += 1
            # the OB ablation re-opens spill leaves recursively, which
            # must interleave with leaf order — only the serial path can
            # do that
            if self.params.batched_ingest and self.params.use_ob:
                self._close_leaves_batched(buf, spans)
            else:
                for s, e in spans:
                    self._close_leaf(buf[:, s:e])
            if self.segments.active:
                self._lifecycle()
            _sanitize_check(self)

    def _split_pending(self, final: bool):
        """Concatenate the pending buffer and cut it into leaf spans;
        the items past the last span stay pending.  Returns
        ``(buf, spans)``."""
        cs = self.params.chunk_size
        buf = np.concatenate(self._buf, axis=1) if len(self._buf) > 1 \
            else self._buf[0]
        ts_col = buf[3]
        n = buf.shape[1]
        spans: list[tuple[int, int]] = []
        pos = 0
        while n - pos >= cs or (final and n - pos > 0):
            rem = n - pos
            take = min(cs, rem)
            if take < rem and ts_col[pos + take] == ts_col[pos + take - 1]:
                # never split a run of equal timestamps across leaves
                boundary_t = ts_col[pos + take - 1]
                tail = ts_col[pos:]
                run_end = int(np.searchsorted(tail, boundary_t, "right"))
                run_start = int(np.searchsorted(tail, boundary_t, "left"))
                # a run longer than a chunk becomes an oversize leaf whose
                # excess lands in the overflow block (the paper's OB case)
                take = run_end if run_start == 0 else run_start
                if take <= 0:
                    # provably unreachable on a non-decreasing buffer
                    # (the boundary run always has positive extent);
                    # bisecting an out-of-order buffer can return 0,
                    # which previously spun this loop forever
                    raise ValueError(
                        "non-monotonic timestamps in the pending "
                        "buffer: stream items must arrive with "
                        "non-decreasing t")
            if not final and take == rem:
                # cannot prove the trailing timestamp run has ended — wait
                break
            spans.append((pos, pos + take))
            pos += take
        if pos:
            rest = buf[:, pos:]
            self._buf = [rest] if rest.shape[1] else []
            self._buf_len = int(rest.shape[1])
        else:
            self._buf = [buf]          # keep concatenated for the next call
        return buf, spans

    def _close_leaf(self, chunk: np.ndarray) -> None:
        p = self.params
        hs = hashing.np_mix32(chunk[0], p.seed)
        hd = hashing.np_mix32(chunk[1], p.seed ^ 0x5BD1E995)
        self._close_leaf_hashed(hs, hd, chunk[2].view(np.float32),
                                chunk[3].astype(np.uint32))

    def _close_leaf_hashed(self, hs, hd, w, t) -> None:
        p = self.params
        n = len(hs)
        pad = _pow2_pad(n, lo=64)

        def padded(x, dt):
            out = np.zeros((pad,), dt)
            out[:n] = x
            return jnp.asarray(out)

        valid = np.zeros((pad,), bool)
        valid[:n] = True
        node = cmatrix.make_node(p.d1, p.b)
        node, spill, n_spill = cmatrix.insert_chunk(
            node, padded(hs, np.uint32), padded(hd, np.uint32),
            padded(w, np.float32), padded(t, np.uint32),
            jnp.asarray(valid), p)
        leaf_id = self.pools[0].base + self.pools[0].append(node)
        self.ingest_stats.leaves_closed += 1
        self._leaves.append(int(t[0]), int(t[-1]))
        self._t_last = max(self._t_last, int(t[-1]))
        k = int(n_spill)
        # item accounting: OB spill stays with this leaf; the ablation's
        # recursive spill re-counts its items in the leaf it opens
        self.segments.on_leaves([n if p.use_ob else n - k])
        self._version += 1

        if k:
            self.ingest_stats.spill_items += k
            s_hs = np.asarray(spill["hs"][:k])
            s_hd = np.asarray(spill["hd"][:k])
            if p.use_ob:
                self.ob.add(1, leaf_id,
                            f1s=s_hs & p.fp_mask, f1d=s_hd & p.fp_mask,
                            bs=(s_hs >> p.F1) % p.d1,
                            bd=(s_hd >> p.F1) % p.d1,
                            w=np.asarray(spill["w"][:k], np.float64),
                            t=np.asarray(spill["t"][:k]))
            else:
                # ABLATION (paper Sec. IV-C): without overflow blocks the
                # spill opens a NEW leaf whose key may duplicate an
                # existing timestamp — boundary search then misattributes
                # fine-grained ranges (the error OB exists to prevent)
                self._close_leaf_hashed(
                    s_hs, s_hd, np.asarray(spill["w"][:k], np.float32),
                    np.asarray(spill["t"][:k], np.uint32))
        self._maybe_aggregate()

    # ------------------------------------------------------------------
    # batched multi-leaf closing
    # ------------------------------------------------------------------

    def _close_leaves_batched(self, buf: np.ndarray,
                              spans: list[tuple[int, int]]) -> None:
        """Close every drained span at once: one vectorized hash pass over
        the drained region, one batched placement pass (numpy phases,
        vmapped ``insert_chunks_pre``, or the grid-over-leaves Pallas
        kernel, per the resolved backend), one spill scatter into the
        overflow store, then the cascade."""
        p = self.params
        nl = len(spans)
        s0, s_end = spans[0][0], spans[-1][1]
        if self._backend == "pallas" and self._storage == "device":
            # fused path: raw items stage once, hashing/placement/append
            # all happen on device against the persistent pool slabs
            self._close_leaves_fused(buf, spans)
            return
        hs_full = hashing.np_mix32(buf[0, s0:s_end], p.seed)
        hd_full = hashing.np_mix32(buf[1, s0:s_end], p.seed ^ 0x5BD1E995)
        w_full = np.ascontiguousarray(buf[2, s0:s_end]).view(np.float32)
        t_full = buf[3, s0:s_end]

        max_len = max(e - s for s, e in spans)
        pad = max(self._chunk_pad, _pow2_pad(max_len, lo=64))
        # the jitted backends pow2-pad the leaf axis too (all-invalid
        # rows, discarded below) so varying drain sizes don't trigger a
        # recompile per distinct leaf count; the host engine has no
        # compile cache and takes the exact count
        lead = nl if self._backend == "host" else _pow2_pad(nl, lo=1)
        hs = np.zeros((lead, pad), np.uint32)
        hd = np.zeros((lead, pad), np.uint32)
        w = np.zeros((lead, pad), np.float32)
        t = np.zeros((lead, pad), np.uint32)
        valid = np.zeros((lead, pad), bool)
        for i, (s, e) in enumerate(spans):
            m = e - s
            hs[i, :m] = hs_full[s - s0:e - s0]
            hd[i, :m] = hd_full[s - s0:e - s0]
            w[i, :m] = w_full[s - s0:e - s0]
            t[i, :m] = t_full[s - s0:e - s0]
            valid[i, :m] = True

        if self._backend == "pallas":
            host, spill_mask, w_sp = self._insert_leaves_pallas(
                hs, hd, w, t, valid)
        else:
            fs, fd, rows, cols = cmatrix.host_leaf_coords(hs, hd, p)
            pm_order, pm_same = cmatrix.host_premerge_meta(hs, hd, t, valid)
            r = p.r if p.use_mmb else 1
            orders = cmatrix.host_round_orders(rows, cols, p.d1, r)
            if self._backend == "host":
                state4, wmat, spill, w_merged = cmatrix.insert_chunks_host(
                    fs, fd, rows, cols, w, t, valid, pm_order, pm_same,
                    orders, p)
            else:
                state4, wmat, spill, w_merged = cmatrix.insert_chunks_pre(
                    jnp.asarray(fs), jnp.asarray(fd), jnp.asarray(rows),
                    jnp.asarray(cols), jnp.asarray(w), jnp.asarray(t),
                    jnp.asarray(valid), jnp.asarray(pm_order),
                    jnp.asarray(pm_same), jnp.asarray(orders), p)
            s4 = np.asarray(state4)
            host = {"fp_s": s4[:, 0], "fp_d": s4[:, 1], "t": s4[:, 2],
                    "idx": s4[:, 3], "w": np.asarray(wmat)}
            spill_mask = np.asarray(spill)
            w_sp = np.asarray(w_merged)

        base = self.pools[0].base + self.pools[0].append_batch(host, nl)
        self.ingest_stats.leaves_closed += nl
        starts = t_full[[s - s0 for s, _ in spans]]
        ends = t_full[[e - 1 - s0 for _, e in spans]]
        self._leaves.extend(starts, ends)
        self._t_last = max(self._t_last, int(ends[-1]))
        self.segments.on_leaves([e - s for s, e in spans])
        self._version += nl

        if spill_mask.any():
            self.ingest_stats.spill_items += int(spill_mask.sum())
            with _span("higgs.drain.spill"):
                for i in range(nl):
                    idxs = np.nonzero(spill_mask[i])[0]
                    if not len(idxs):
                        continue
                    s_hs = hs[i, idxs]
                    s_hd = hd[i, idxs]
                    self.ob.add(1, base + i,
                                f1s=s_hs & p.fp_mask, f1d=s_hd & p.fp_mask,
                                bs=(s_hs >> p.F1) % p.d1,
                                bd=(s_hd >> p.F1) % p.d1,
                                w=w_sp[i, idxs].astype(np.float64),
                                t=t[i, idxs])
        self._maybe_aggregate()

    def _insert_leaves_pallas(self, hs, hd, w, t, valid):
        """Alg.-1-faithful backend: one Pallas launch, grid over leaves.

        Sequential per-edge placement inside each leaf (no premerge), so
        results differ from the vector backend by design — this is the
        paper-faithful mode, compiled on TPU / interpreted elsewhere per
        ``params.interpret``."""
        from repro.kernels import ops
        p = self.params
        r = p.r if p.use_mmb else 1
        hs_j, hd_j = jnp.asarray(hs), jnp.asarray(hd)
        fs = hashing.fingerprint(hs_j, p.F1)
        fd = hashing.fingerprint(hd_j, p.F1)
        rows = cmatrix.chain_from_base(
            hashing.address(hs_j, p.F1, p.d1), r, p.d1)
        cols = cmatrix.chain_from_base(
            hashing.address(hd_j, p.F1, p.d1), r, p.d1)
        nodes = cmatrix.make_nodes(hs.shape[0], p.d1, p.b)
        nodes, spill_mask = ops.leaf_insert_batched(
            nodes, fs, fd, rows, cols, jnp.asarray(w), jnp.asarray(t),
            jnp.asarray(valid), r=r, interpret=p.interpret)
        host = {name: np.asarray(getattr(nodes, name))
                for name in NodeState._fields}
        mask = np.asarray(spill_mask).astype(bool) & valid
        return host, mask, w          # no premerge: spill weights are raw

    def _close_leaves_fused(self, buf: np.ndarray,
                            spans: list[tuple[int, int]]) -> None:
        """Device-resident drain (pallas backend + device pool storage).

        Raw spans stage into the pinned double buffer and one fused
        launch hashes, places and appends them into the donated level-1
        slabs (`kernels/pipeline.py`).  Bit-identical to
        :meth:`_insert_leaves_pallas` + ``append_batch``: same kernel,
        same operand bits (the device ``mix32`` twin is exact), same
        append order.  Only the spill mask returns to host; spilled hash
        values are recomputed here from the staged raw items.
        """
        p = self.params
        nl = len(spans)
        max_len = max(e - s for s, e in spans)
        pad = max(self._chunk_pad, _pow2_pad(max_len, lo=64))
        lead = _pow2_pad(nl, lo=1)
        if self._pipeline is None:
            from repro.kernels.pipeline import DrainPipeline
            self._pipeline = DrainPipeline(p, self.ingest_stats)
        pool = self.pools[0]
        base_slot, spill_mask, stage = self._pipeline.ingest(
            pool, buf, spans, lead, pad)
        base = pool.base + base_slot
        self.ingest_stats.leaves_closed += nl
        starts = buf[3, [s for s, _ in spans]]
        ends = buf[3, [e - 1 for _, e in spans]]
        self._leaves.extend(starts, ends)
        self._t_last = max(self._t_last, int(ends[-1]))
        self.segments.on_leaves([e - s for s, e in spans])
        self._version += nl

        if spill_mask.any():
            self.ingest_stats.spill_items += int(spill_mask.sum())
            with _span("higgs.drain.spill"):
                for i in range(nl):
                    idxs = np.nonzero(spill_mask[i])[0]
                    if not len(idxs):
                        continue
                    s_hs = hashing.np_mix32(stage[0, i, idxs], p.seed)
                    s_hd = hashing.np_mix32(stage[1, i, idxs],
                                            p.seed ^ 0x5BD1E995)
                    self.ob.add(1, base + i,
                                f1s=s_hs & p.fp_mask, f1d=s_hd & p.fp_mask,
                                bs=(s_hs >> p.F1) % p.d1,
                                bd=(s_hd >> p.F1) % p.d1,
                                w=stage[2, i, idxs].view(np.float32)
                                .astype(np.float64),
                                t=stage[3, i, idxs])
        self._maybe_aggregate()

    # ------------------------------------------------------------------
    # aggregation cascade
    # ------------------------------------------------------------------

    def _maybe_aggregate(self) -> None:
        p = self.params
        cap = self.segments.level_cap
        level = 1
        while True:
            if level + 1 > p.max_levels:
                return                              # fingerprints exhausted
            if cap is not None and level + 1 > cap:
                return          # hierarchy stops at the segment roots so
                #                 every sealed segment stays a complete,
                #                 independently evictable subtree
            pool = self.pools[level - 1]
            parent_n = self.pools[level].total if level < len(self.pools) \
                else 0
            n_ready = pool.total // p.theta - parent_n
            if n_ready <= 0:
                return
            if level >= len(self.pools):
                # the leaf closings that triggered this cascade already
                # bumped _version this drain
                self.pools.append(  # higgslint: disable=R5
                    _LevelPool(p.d(level + 1), p.b, storage=self._storage,
                               stats=self.ingest_stats))
            with _span("higgs.cascade"):
                if p.batched_ingest:
                    self._build_parents_batched(level, parent_n, n_ready)
                else:
                    self._build_parents_serial(level)
            level += 1

    def _build_parents_serial(self, level: int) -> None:
        """Reference path: one ``aggregate_children`` launch per parent."""
        p = self.params
        pool = self.pools[level - 1]
        while self.pools[level - 1].total - self.pools[level].total \
                * p.theta >= p.theta:
            u = self.pools[level].total             # global parent id
            child_ids = np.arange(u * p.theta, (u + 1) * p.theta)
            children, _ = pool.gather(child_ids, p.theta)
            ob_cols = self._gather_child_obs(level, child_ids)
            parent, spill, n_spill = cmatrix.aggregate_children(
                children, *ob_cols, p, level)
            # covered by the leaf-closing bump earlier in this drain
            self.pools[level].append(parent)  # higgslint: disable=R5
            k = int(n_spill)
            if k:
                self.ingest_stats.spill_items += k
                self.ob.add(level + 1, u,
                            f1s=np.asarray(spill["f1s"][:k]),
                            f1d=np.asarray(spill["f1d"][:k]),
                            bs=np.asarray(spill["base_s"][:k]),
                            bd=np.asarray(spill["base_d"][:k]),
                            w=np.asarray(spill["w"][:k], np.float64),
                            t=np.zeros((k,), np.uint32))

    def _build_parents_batched(self, level: int, u0: int, m: int) -> None:
        """Build all ``m`` ready parents at a level in one batched step.

        Device pool storage dispatches to the fused device cascade
        (:meth:`_build_parents_fused`): child blocks are reduced into
        the donated parent slabs without any ``gather_block`` host
        fetch.  Host storage stays the bit-reference: child entries are
        gathered as plain views, leaf coordinates recovered and
        parent-level probe chains + per-round sort orders computed in
        numpy, and ``aggregate_children_host`` does sort-free placement
        on the host."""
        if self._storage == "device":
            self._build_parents_fused(level, u0, m)
            return
        p = self.params
        theta = p.theta
        pool = self.pools[level - 1]
        # bulk child gather through the pool API: one contiguous block
        # fetch (a bounded d2h barrier under device storage, plain
        # views under host storage); gather_block translates global
        # parent-child ids to window-physical slots internally
        blk = pool.gather_block(u0 * theta, m * theta)
        d = pool.d
        per = theta * d * d * pool.b

        e_fs = blk["fp_s"].reshape(m, per)
        e_fd = blk["fp_d"].reshape(m, per)
        e_w = blk["w"].reshape(m, per)
        e_idx = blk["idx"].reshape(m, per)
        grid = np.broadcast_to(
            np.arange(d, dtype=np.uint32)[:, None, None],
            (d, d, pool.b))
        e_row = np.broadcast_to(grid[None], (theta,) + grid.shape)\
            .reshape(1, per)
        e_col = np.broadcast_to(grid.transpose(1, 0, 2)[None],
                                (theta,) + grid.shape).reshape(1, per)
        e_row = np.broadcast_to(e_row, (m, per))
        e_col = np.broadcast_to(e_col, (m, per))
        e_valid = e_fs != EMPTY

        f1s, base_s = cmatrix.host_recover_leaf_coords(
            e_row, e_fs, e_idx, level, p, "s")
        f1d, base_d = cmatrix.host_recover_leaf_coords(
            e_col, e_fd, e_idx, level, p, "d")
        w_all = e_w.astype(np.float32)

        with _span("higgs.cascade.ob"):
            ob = self._gather_child_obs_stacked(level, u0, m)
        if ob is not None:
            f1s = np.concatenate([f1s, ob["f1s"]], axis=1)
            f1d = np.concatenate([f1d, ob["f1d"]], axis=1)
            base_s = np.concatenate([base_s, ob["bs"]], axis=1)
            base_d = np.concatenate([base_d, ob["bd"]], axis=1)
            w_all = np.concatenate([w_all, ob["w"]], axis=1)
            e_valid = np.concatenate([e_valid, ob["valid"]], axis=1)

        plevel = level + 1
        fp_s_p, rows_p = cmatrix.host_coords_at_level(f1s, base_s, plevel, p)
        fp_d_p, cols_p = cmatrix.host_coords_at_level(f1d, base_d, plevel, p)
        # EMPTY entries recover garbage coordinates; zero them so host
        # indexing stays in bounds (they are never active — the device
        # path relied on XLA's gather clamping for the same items)
        rows_p = np.where(e_valid[..., None], rows_p, np.uint32(0))
        cols_p = np.where(e_valid[..., None], cols_p, np.uint32(0))
        r = p.r if p.use_mmb else 1
        orders = cmatrix.host_round_orders(rows_p, cols_p, p.d(plevel), r)

        # one numpy twin for every host-storage backend: on CPU the
        # placement twin outruns the XLA scatter path, and the former
        # vector-backend aggregate_children_pre launch survives only
        # inside the fused device step (kernels.aggregate_fused)
        state4, wmat, spill = cmatrix.aggregate_children_host(
            fp_s_p, fp_d_p, rows_p, cols_p, w_all, e_valid, orders,
            p, level)
        s4 = np.asarray(state4)
        host = {"fp_s": s4[:, 0], "fp_d": s4[:, 1], "t": s4[:, 2],
                "idx": s4[:, 3], "w": np.asarray(wmat)}
        # covered by the leaf-closing bump earlier in this drain
        self.pools[level].append_batch(host, m)  # higgslint: disable=R5
        spill_h = np.asarray(spill)
        if not spill_h.any():
            return
        self.ingest_stats.spill_items += int(spill_h.sum())
        with _span("higgs.drain.spill"):
            for i in range(m):
                idxs = np.nonzero(spill_h[i])[0]
                if len(idxs):
                    self.ob.add(level + 1, u0 + i,
                                f1s=f1s[i, idxs], f1d=f1d[i, idxs],
                                bs=base_s[i, idxs], bd=base_d[i, idxs],
                                w=w_all[i, idxs].astype(np.float64),
                                t=np.zeros((len(idxs),), np.uint32))

    def _build_parents_fused(self, level: int, u0: int, m: int) -> None:
        """Device-resident aggregation cascade step (device pool storage).

        One fused launch (`kernels/pipeline.py::_aggregate_step`) takes
        the ready theta-child block sliced off the child pool's live slabs,
        recovers leaf coordinates, computes round orders and places all
        ``m`` parents, which are appended to the *donated* parent slabs —
        the child block never crosses to host (``_maybe_aggregate`` chains
        one such launch per ready level per drain).  Only the small
        spill mask is fetched; the canonical spill columns stay lazy
        device arrays and materialize only when the mask is non-empty.
        Bit-identical to the host-storage reference path above.
        """
        from repro.kernels.pipeline import DrainPipeline, pack_ob
        pool = self.pools[level - 1]
        with _span("higgs.cascade.ob"):
            ob_pack = pack_ob(self._gather_child_obs_stacked(level, u0, m),
                              m)
        if self._pipeline is None:
            self._pipeline = DrainPipeline(self.params, self.ingest_stats)
        # covered by the leaf-closing version bump earlier in this drain
        spill_h, coords = self._pipeline.aggregate(  # higgslint: disable=R5
            pool, self.pools[level], level, u0, m, ob_pack)
        if not spill_h.any():
            return
        self.ingest_stats.spill_items += int(spill_h.sum())
        with _span("higgs.drain.spill"):
            f1s, f1d, base_s, base_d, w_all = (
                a[:m] for a in _fetch(tuple(coords), self.ingest_stats))
            for i in range(m):
                idxs = np.nonzero(spill_h[i])[0]
                if len(idxs):
                    self.ob.add(level + 1, u0 + i,
                                f1s=f1s[i, idxs], f1d=f1d[i, idxs],
                                bs=base_s[i, idxs], bd=base_d[i, idxs],
                                w=w_all[i, idxs].astype(np.float64),
                                t=np.zeros((len(idxs),), np.uint32))

    def _gather_child_obs_stacked(self, level: int, u0: int, m: int):
        """Overflow columns for ``m`` theta-blocks of children as stacked
        (m, ob_pad) host arrays; ``None`` when no child has OB entries."""
        theta = self.params.theta
        recs = [self.ob.get(level, c)
                for c in range(u0 * theta, (u0 + m) * theta)]
        totals = [sum(len(r["w"]) for r in recs[i * theta:(i + 1) * theta]
                      if r) for i in range(m)]
        if not any(totals):
            return None
        pad = _pow2_pad(max(totals), lo=16)
        out = {k: np.zeros((m, pad), np.uint32)
               for k in ("f1s", "f1d", "bs", "bd")}
        out["w"] = np.zeros((m, pad), np.float32)
        out["valid"] = np.zeros((m, pad), bool)
        for i in range(m):
            off = 0
            for rec in recs[i * theta:(i + 1) * theta]:
                if not rec:
                    continue
                n = len(rec["w"])
                for k in ("f1s", "f1d", "bs", "bd"):
                    out[k][i, off:off + n] = rec[k]
                out["w"][i, off:off + n] = rec["w"]
                out["valid"][i, off:off + n] = True
                off += n
        return out

    def _gather_child_obs(self, level: int, child_ids: np.ndarray):
        recs = [self.ob.get(level, int(c)) for c in child_ids]
        total = sum(len(r["w"]) for r in recs if r)
        if total == 0:
            return (None, None, None, None, None, None)
        pad = _pow2_pad(total, lo=16)
        cols = {k: np.zeros((pad,), np.uint32) for k in ("f1s", "f1d",
                                                         "bs", "bd")}
        wcol = np.zeros((pad,), np.float32)
        vcol = np.zeros((pad,), bool)
        off = 0
        for r in recs:
            if not r:
                continue
            m = len(r["w"])
            for k in ("f1s", "f1d", "bs", "bd"):
                cols[k][off:off + m] = r[k]
            wcol[off:off + m] = r["w"]
            vcol[off:off + m] = True
            off += m
        return (jnp.asarray(cols["f1s"]), jnp.asarray(cols["f1d"]),
                jnp.asarray(cols["bs"]), jnp.asarray(cols["bd"]),
                jnp.asarray(wcol), jnp.asarray(vcol))

    # ------------------------------------------------------------------
    # temporal lifecycle: sealing, eviction, coarsening compaction
    # ------------------------------------------------------------------

    @_spanned("higgs.lifecycle")
    def _lifecycle(self) -> None:
        """Seal completed segments, then enforce the retention policy.

        Runs after every drain (and on flush).  Everything here is a
        deterministic function of the closed-leaf sequence alone — never
        of insert batching — so per-shard eviction stays bit-identical
        to an independently built sketch over the same sub-stream.
        """
        st = self.segments
        while st.can_seal():
            i0 = st.n_sealed * st.seg_leaves - st.fine_base_leaf
            st.seal(int(self._leaves.starts[i0]),
                    int(self._leaves.ends[i0 + st.seg_leaves - 1]))
        pol = self.params.retention
        if pol.kind == "window":
            expire = self._t_last - pol.t_horizon
            while st.records and st.records[0].t_end < expire:
                self._evict_front()
        elif pol.kind == "budget":
            while self.space_bytes() > pol.max_bytes:
                if st.n_coarse < len(st.records):
                    self._coarsen_oldest_fine()
                elif st.records:
                    self._evict_front()     # every old segment is already
                    #                         coarse: drop roots, oldest
                    #                         first
                else:
                    break                   # only the active region is
                    #                         left — the budget's floor

    def _drop_segment_levels(self, lo_level: int, hi_level: int) -> None:
        """Reclaim one segment's nodes (and overflow keys) at levels
        ``lo_level..hi_level`` — always the oldest retained prefix at
        each level, which is what keeps pool slots contiguous."""
        st = self.segments
        # _evict_front/_coarsen_oldest_fine (the only callers) bump
        # _version once per reclaimed segment
        for lvl in range(lo_level, hi_level + 1):
            pool = self.pools[lvl - 1]
            cnt = st.nodes_per_segment(lvl)
            for node in range(pool.base, pool.base + cnt):
                self.ob.drop(lvl, node)  # higgslint: disable=R5
            pool.drop_prefix(cnt)  # higgslint: disable=R5

    @_spanned("higgs.evict")
    def _evict_front(self) -> None:
        """Evict the oldest retained segment wholesale: its slabs at
        every resident level, its overflow keys, and (for fine
        segments) its slice of the leaf-interval index."""
        st = self.segments
        seg = st.records.pop(0)
        if seg.coarse:
            self._drop_segment_levels(st.root_level, st.root_level)
            st.items_coarsened -= seg.n_items
        else:
            self._drop_segment_levels(1, st.root_level)
            self._leaves.drop_prefix(st.seg_leaves)
        st.n_evicted += 1
        st.items_evicted += seg.n_items
        self._version += 1                 # invalidate memoized plans

    @_spanned("higgs.evict")
    def _coarsen_oldest_fine(self) -> None:
        """Collapse the oldest fine segment into its retained root: drop
        its leaves and mid-level ancestors (plus their overflow keys and
        interval keys), keep the level-(L+1) root and the root's
        overflow entries.  The segment's time range stays answerable at
        segment resolution via :meth:`boundary_search`."""
        st = self.segments
        seg = st.records[st.n_coarse]
        self._drop_segment_levels(1, st.levels)
        self._leaves.drop_prefix(st.seg_leaves)
        seg.coarse = True
        st.items_coarsened += seg.n_items
        self._version += 1

    def retention_stats(self) -> dict:
        """Lifecycle telemetry (also surfaced by the stream pipeline's
        retention hook and the space benchmark)."""
        st = self.segments
        return {
            "policy": self.params.retention.kind,
            "segments_retained": len(st.records),
            "segments_coarse": st.n_coarse,
            "segments_evicted": st.n_evicted,
            "items_evicted": int(st.items_evicted),
            "items_coarsened": int(st.items_coarsened),
            "base_leaf": int(st.fine_base_leaf),
            "space_bytes": float(self.space_bytes()),
        }

    # ------------------------------------------------------------------
    # boundary search (paper Alg. 3) — canonical theta-ary decomposition
    # ------------------------------------------------------------------

    @_spanned("higgs.plan")
    def boundary_search(self, ts: int, te: int):
        """Decompose [ts, te] into (plan, filtered_leaves):

        plan: dict level -> list of global node ids queried *without*
        time filter; filtered_leaves: global leaf ids queried *with* the
        [ts, te] filter.

        The search runs over the retained window: ``base`` (the global
        id of the first leaf still resident at leaf resolution) offsets
        every emitted id, and alignment is checked on global positions —
        eviction is theta^L-aligned, so for every level the cascade can
        still build (the cap is L+1 when a policy is live) the window-
        relative grouping matches a fresh sketch built on the retained
        suffix, which is what keeps in-window answers bit-identical.
        Ranges overlapping *coarsened* segments are additionally covered
        by those segments' retained root nodes: the whole root joins the
        plan unfiltered, so a partially overlapping range is answered at
        segment resolution — an overestimate, preserving HIGGS's
        one-sided error.
        """
        if te < ts:
            return {}, []
        plan: dict[int, list[int]] = {}
        seg = self.segments
        base = seg.fine_base_leaf
        if seg.active:
            roots = seg.coarse_roots_overlapping(ts, te)
            if roots:
                plan[seg.root_level] = roots
        starts, ends = self.leaf_starts, self.leaf_ends
        n1 = len(starts)
        if n1 == 0:
            return plan, []
        li = int(np.searchsorted(starts, np.uint64(max(ts, 0)),
                                 "right")) - 1
        li = max(li, 0)
        ri = int(np.searchsorted(starts, np.uint64(max(te, 0)),
                                 "right")) - 1
        if ri < 0 or (li == ri and int(ends[li]) < ts):
            return plan, []                         # range between leaves
        # boundary leaves fully inside the range join the interior cover;
        # partially covered ones are queried with the exact time filter
        lo, hi = li, ri
        filtered = []
        if not (ts <= int(starts[li]) and te >= int(ends[li])):
            filtered.append(base + li)
            lo = li + 1
        if ri >= lo and not te >= int(ends[ri]):
            if ri != li:
                filtered.append(base + ri)
            hi = ri - 1
        theta = self.params.theta
        pos = lo
        while pos <= hi:
            lvl = 0
            blk = 1
            # largest aligned, existing block starting at pos (global
            # alignment == window alignment for all buildable levels)
            while ((base + pos) % (blk * theta) == 0
                   and pos + blk * theta - 1 <= hi
                   and lvl + 2 <= len(self.pools)
                   and ((base + pos) // (blk * theta))
                   < self.pools[lvl + 1].total):
                blk *= theta
                lvl += 1
            plan.setdefault(lvl + 1, []).append((base + pos) // blk)
            pos += blk
        return plan, filtered

    # ------------------------------------------------------------------
    # query-coordinate hashing (shared with the planner)
    # ------------------------------------------------------------------

    def _query_coords(self, vid: np.ndarray, side: str):
        p = self.params
        seed = p.seed if side == "s" else p.seed ^ 0x5BD1E995
        h = hashing.np_mix32(np.asarray(vid, np.uint32), seed)
        f1 = h & p.fp_mask
        base = (h >> p.F1) % p.d1
        return jnp.asarray(f1), jnp.asarray(base)

    # ------------------------------------------------------------------
    # accounting
    # ------------------------------------------------------------------

    def space_bytes(self) -> float:
        """Space per the paper's bit layout (Sec. V-A), not numpy overhead."""
        p = self.params
        total_bits = 0.0
        for level, pool in enumerate(self.pools, start=1):
            ent = p.leaf_entry_bits() if level == 1 else \
                p.node_entry_bits(level)
            total_bits += pool.n * p.d(level) ** 2 * p.b * ent
        for (level, _), rec in self.ob.data.items():
            ent = p.leaf_entry_bits() if level == 1 else \
                p.node_entry_bits(level)
            total_bits += len(rec["w"]) * ent
        total_bits += 64 * len(self.leaf_starts)    # B-tree keys
        # segment-record metadata (0.0 while the lifecycle is dormant,
        # keeping the legacy accounting — and the CI exact baselines —
        # bit-for-bit unchanged)
        return total_bits / 8.0 + self.segments.space_bytes()

    def utilization(self) -> float:
        """Fraction of leaf-matrix entries occupied (paper Eq. 7)."""
        pool = self.pools[0]
        if pool.n == 0:
            return 0.0
        # occupancy is slot-local; ids never enter the computation
        fp = pool.arrs["fp_s"][: pool.n]  # higgslint: disable=R2
        return float((fp != EMPTY).mean())

    @property
    def n_levels(self) -> int:
        return len([p_ for p_ in self.pools if p_.n > 0])
