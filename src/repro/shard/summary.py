"""``ShardedHiggs``: S independent HIGGS forests behind one summary.

Scale-out by partition: every stream edge is routed to exactly one
per-shard :class:`~repro.core.higgs.HiggsSketch` by a salted hash of its
source vertex, so each shard is a *complete, independent* HIGGS summary
of its sub-stream — per-shard state is bit-identical to a single sketch
built over that partition alone (the testable contract), shards never
synchronize during ingestion, and the fleet answers queries through the
shard-aware planner (:mod:`repro.shard.planner`).

Ingestion partitions each incoming batch in one host pass
(:func:`repro.shard.partition.partition_batch`) and drives all shards'
batched drains in parallel.  The execution mode resolves per host:

* ``"process"`` (the CPU default) — forked worker processes via
  :class:`~repro.shard.engine.ShardProcessEngine`.  Workers own the
  authoritative shard state between read barriers; any read
  (query / snapshot / accounting) first collects worker snapshots into
  the local shard replicas (``_sync``), so callers always observe the
  exact current state, pending buffers included.
* ``"threads"`` — a thread pool; only useful when the per-shard drain
  releases the GIL (the jitted ``"vector"``/``"pallas"`` backends, i.e.
  real accelerators).  On a multi-device host the stacked probe path
  additionally places pools across a 1-D device mesh
  (:func:`repro.launch.mesh.make_shard_mesh`).
* ``"none"`` — sequential; also the S=1 degenerate case, which is
  bit-identical to an unsharded ``HiggsSketch`` end to end.
* ``"shard_map"`` (explicit only, never auto-resolved) — sequential
  ingest, but stacked fan-in probes dispatch through
  :func:`repro.compat.shard_map` over a 1-D ``("shard",)`` device mesh:
  the leading shard axis is split across devices and query operands are
  replicated, so each device probes only its resident pool slice.  On
  single-device hosts a degenerate 1-device mesh keeps the code path
  live (and bit-identical to ``"none"``).

The full ``GraphSummary`` protocol is implemented, so
``make_summary("higgs-sharded", shards=4, ...)`` drops into the
registry, benchmarks, stream pipeline, and persistence layers
unchanged; ``state_dict``/``load_state`` nest per-shard manifests so
``StreamPipeline.run_resumable`` and ``repro.api.restore_summary``
work without modification.

Temporal lifecycle: a shared :class:`~repro.core.params.RetentionPolicy`
(``retention=...``) propagates to every shard — worker processes
included — and each shard enforces it on its own sub-stream.  Because
eviction/coarsening is a deterministic function of the closed-leaf
sequence alone, per-shard state under retention stays bit-identical to
an independently built single sketch over the same partition, which is
the same contract the ingestion engine already guarantees.  Per-batch
shard load is tracked in :class:`~repro.shard.partition.PartitionStats`
(``.partition_stats``) with a one-time hot-shard warning.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from repro.analysis.sanitize import maybe_check as _sanitize_check
from repro.api.protocol import LegacyQueryMixin
from repro.api.queries import QueryBatch, QueryResult
from repro.core.higgs import HiggsSketch
from repro.core.params import HiggsParams
from repro.shard.engine import ShardProcessEngine, fork_available
from repro.shard.partition import (DstShardMap, PartitionStats,
                                   partition_batch)
from repro.shard.planner import ShardedQueryPlanner

_PARALLEL_MODES = ("auto", "process", "threads", "none", "shard_map")


class ShardedHiggs(LegacyQueryMixin):
    """Hash-partitioned fleet of ``HiggsSketch`` shards.

    ``shards``: partition count S (1..64); ``parallel``: ``"auto"``
    (process fan-out on multi-core CPU hosts, threads for accelerator
    backends, sequential otherwise), ``"process"``, ``"threads"``, or
    ``"none"``.  Remaining kwargs are :class:`HiggsParams` fields shared
    by every shard (or pass ``params=``).
    """

    name = "HIGGS-sharded"
    snapshot_kind = "higgs-sharded"
    # host/runtime wiring rebuilt in __init__ plus unsaved telemetry
    # (partition_stats) — intentionally not serialized (higgslint R3);
    # _pinned marks an epoch replica (restored fleets are writable)
    _SNAPSHOT_DERIVED = ("partition_stats", "planner", "mesh", "_mode",
                         "_pool", "_pinned")

    def __init__(self, shards: int = 4, parallel: str = "auto",
                 params: HiggsParams | None = None, **kw):
        if parallel not in _PARALLEL_MODES:
            raise ValueError(f"parallel must be one of {_PARALLEL_MODES}, "
                             f"got {parallel!r}")
        if params is None:
            params = HiggsParams(**kw)
        elif kw:
            raise TypeError("pass either params= or HiggsParams fields, "
                            "not both")
        self.params = params
        self.n_shards = int(shards)
        self.parallel = parallel
        # identical params (and seed) per shard: shard routing is already
        # decorrelated by the partition salt, and shared params are what
        # make query coordinates computable once for the whole fleet
        self._shards = [HiggsSketch(params) for _ in range(self.n_shards)]
        self.dst_map = DstShardMap(self.n_shards, params.seed)
        self.partition_stats = PartitionStats(n_shards=self.n_shards)
        self.planner = ShardedQueryPlanner(self)
        self.mesh = None
        if parallel == "shard_map":
            from repro.launch.mesh import (make_shard_mesh,
                                           make_single_shard_mesh)
            self.mesh = (make_shard_mesh(self.n_shards)
                         or make_single_shard_mesh())
        elif self.n_shards > 1:
            from repro.launch.mesh import make_shard_mesh
            self.mesh = make_shard_mesh(self.n_shards)
        self._mode = self._resolve_parallel()
        self._engine: Optional[ShardProcessEngine] = None
        self._stale = False                # workers ahead of local state
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pinned = False               # epoch replicas only

    # ------------------------------------------------------------------
    # parallel drive
    # ------------------------------------------------------------------

    def _resolve_parallel(self) -> str:
        mode = self.parallel
        cores = os.cpu_count() or 1
        # fork safety: a worker may only ever run the numpy-only drain.
        # The host backend with the batched engine + overflow blocks is
        # that path; the legacy per-leaf closer (batched_ingest=False)
        # and the OB-ablation spill recursion (use_ob=False) both launch
        # jitted jax computations, which must not run post-fork.
        p = self.params
        forkable = (self._shards[0]._backend == "host"
                    and self._shards[0]._storage == "host"
                    and p.batched_ingest and p.use_ob)
        if mode == "shard_map":
            # explicit opt-in only: ingest is sequential, probes go
            # through the mesh dispatch (see run_stacked)
            return mode
        if mode == "auto":
            if self.n_shards == 1 or cores == 1:
                return "none"
            if forkable and fork_available():
                return "process"
            if self._shards[0]._backend != "host":
                # jitted backends release the GIL during XLA execution
                return "threads"
            return "none"
        if mode == "process":
            if not forkable:
                raise ValueError(
                    "parallel='process' needs the jax-free drain: "
                    "insert_backend='host' (or 'auto' on CPU) with "
                    "batched_ingest=True and use_ob=True")
            if not fork_available():
                return "threads"
        return mode

    def _get_engine(self) -> ShardProcessEngine:
        if self._engine is None:
            seed = None
            if self.n_items > 0:           # resume: re-seed workers
                seed = {i: sh.state_dict()
                        for i, sh in enumerate(self._shards)}
            self._engine = ShardProcessEngine(self.n_shards, self.params,
                                              seed_states=seed)
        return self._engine

    def _sync(self) -> None:
        """Read barrier for process mode: pull every worker's snapshot
        into the local shard replicas so reads observe the exact current
        state (pending buffers included)."""
        if self._engine is None or not self._stale:
            return
        for i, state in self._engine.collect().items():
            self._shards[i].load_state(*state)
        self._stale = False
        for sh in self._shards:
            _sanitize_check(sh)

    @property
    def shards(self) -> list[HiggsSketch]:
        """The per-shard sketches, synced first: while the process
        engine is ahead of the local replicas, direct shard reads would
        otherwise observe stale state."""
        self._sync()
        return self._shards

    def close(self) -> None:
        """Shut down worker processes (after syncing their state) and
        the thread pool.  Safe to call more than once; reads keep
        working afterwards and the next insert restarts the engine."""
        if self._engine is not None:
            self._sync()
            self._engine.close()
            self._engine = None
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _map_shards(self, fn, jobs) -> None:
        """Run ``fn(shard, *args)`` over jobs, on the thread pool in
        ``"threads"`` mode (shards are disjoint state, so plain fan-out
        is safe) and sequentially otherwise."""
        if self._mode != "threads" or len(jobs) <= 1:
            for shard, *args in jobs:
                fn(shard, *args)
            return
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=min(self.n_shards, os.cpu_count() or 1),
                thread_name_prefix="higgs-shard")
        futs = [self._pool.submit(fn, shard, *args)
                for shard, *args in jobs]
        for f in futs:
            f.result()                 # surface the first worker error

    def place_stacked(self, nodes, mask):
        """Device placement for a stacked (k, ...) probe batch: shard the
        leading axis across the device mesh when one is available; hosts
        without a mesh keep the batch where it is."""
        if self.mesh is None:
            return nodes, mask
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        k, ndev = nodes.fp_s.shape[0], self.mesh.devices.size
        if k % ndev:
            raise ValueError(
                f"stacked probe batch of {k} shards does not split over "
                f"the {ndev}-device mesh (the planner pads it to whole "
                f"devices)")
        spec = NamedSharding(self.mesh, PartitionSpec("shard"))
        return (jax.device_put(nodes, spec), jax.device_put(mask, spec))

    def run_stacked(self, fn, nodes, mask, *args, **static):
        """Launch a stacked (k, ...) probe ``fn(nodes, mask, *args)``.

        Normal modes call the jitted wrapper directly (XLA partitions a
        mesh-placed batch on its own).  ``"shard_map"`` mode makes the
        partitioning explicit: the leading shard axis splits across the
        1-D ``("shard",)`` mesh, query operands replicate, and each
        device vmaps only its resident pool slice — arithmetic is
        per-shard-independent, so the stacked (k, q) output is
        bit-identical to the plain launch."""
        if self._mode != "shard_map":
            return fn(nodes, mask, *args, **static)
        import functools

        from jax.sharding import PartitionSpec

        from repro import compat
        shard, rep = PartitionSpec("shard"), PartitionSpec()
        mapped = compat.shard_map(
            functools.partial(fn, **static), mesh=self.mesh,
            in_specs=(shard, shard) + (rep,) * len(args),
            out_specs=shard)
        return mapped(nodes, mask, *args)

    # ------------------------------------------------------------------
    # GraphSummary surface
    # ------------------------------------------------------------------

    def insert(self, src, dst, w, t) -> None:
        """Partition the batch by source vertex in one host pass, update
        the destination routing map, and drive every shard's batched
        drain through the resolved parallel mode."""
        if self._pinned:
            raise RuntimeError(
                "epoch-pinned replica is read-only; insert into the "
                "live summary it was pinned from")
        sids, parts = partition_batch(src, dst, w, t, self.n_shards,
                                      self.params.seed)
        self.partition_stats.record(
            np.bincount(sids, minlength=self.n_shards))
        self.dst_map.update(np.asarray(dst, np.uint32), sids)
        if self._mode == "process":
            self._get_engine().insert(
                {s: parts[s] for s in range(self.n_shards)
                 if len(parts[s][0])})
            self._stale = True
            return
        jobs = [(self._shards[s], parts[s]) for s in range(self.n_shards)
                if len(parts[s][0])]
        self._map_shards(lambda sh, part: sh.insert(*part), jobs)

    def flush(self) -> None:
        if self._pinned:
            raise RuntimeError(
                "epoch-pinned replica is read-only; flush the live "
                "summary it was pinned from")
        if self._mode == "process" and self._engine is not None:
            # workers close their pending leaves; pulling their (now
            # larger) state stays lazy — a flush with no read after it
            # must not pay O(total sketch state) pipe serialization
            self._engine.flush()
            self._stale = True
            return
        self._map_shards(lambda sh: sh.flush(),
                         [(sh,) for sh in self._shards])

    def query(self, queries: QueryBatch) -> QueryResult:
        self._sync()
        return self.planner.execute(queries)

    # ------------------------------------------------------------------
    # read epochs (concurrent serving surface)
    # ------------------------------------------------------------------

    def snapshot_epoch(self):
        """Pin an immutable :class:`~repro.serve.epoch.ReadEpoch` of the
        fleet: per-shard pinned replicas plus a frozen copy of the
        destination routing map, so the coalesced batch fans through the
        stacked probe path against one consistent fleet state."""
        from repro.serve.epoch import ReadEpoch
        return ReadEpoch.pin(self)

    def epoch_info(self) -> dict:
        """Position metadata stamped onto a pinned epoch."""
        self._sync()
        return {
            "n_items": int(self.n_items),
            "n_leaves": int(self.n_leaves),
            "shards": [sh.epoch_info() for sh in self._shards],
        }

    def _pin_replica(self) -> "ShardedHiggs":
        """Read-only fleet replica at the current ``structure_version``:
        per-shard pins (zero-copy where each shard's storage allows it)
        plus a frozen routing-map copy.  Process-mode workers are synced
        first, so the pin observes the exact current fleet state.

        Warm plan reuse composes per shard: each shard pin adopts its
        writer shard's memoized plan cache (the fleet-level
        :class:`ShardedQueryPlanner` is stateless), so a fresh fleet
        epoch answers its first batch without any boundary searches
        when the writers' caches are warm."""
        self._sync()
        rep = object.__new__(type(self))
        rep.params = self.params
        rep.n_shards = self.n_shards
        rep.parallel = self.parallel
        rep._shards = [sh._pin_replica() for sh in self._shards]
        rep.dst_map = self.dst_map.pin_view()
        rep.partition_stats = PartitionStats(n_shards=self.n_shards)
        rep.planner = ShardedQueryPlanner(rep)
        rep.mesh = self.mesh
        # replicas never ingest; keep the explicit mesh-dispatch probe
        # path, drop the ingest-only parallel modes
        rep._mode = "shard_map" if self._mode == "shard_map" else "none"
        rep._engine = None
        rep._stale = False
        rep._pool = None
        rep._pinned = True
        return rep

    def space_bytes(self) -> float:
        """Fleet total: per-shard sketches plus the secondary
        destination routing map (4-byte key + 8-byte bitmask each)."""
        self._sync()
        return sum(sh.space_bytes() for sh in self.shards) \
            + self.dst_map.space_bytes()

    # ------------------------------------------------------------------
    # aggregate views
    # ------------------------------------------------------------------

    @property
    def n_items(self) -> int:
        self._sync()
        return sum(sh.n_items for sh in self.shards)

    @property
    def structure_version(self) -> int:
        self._sync()
        return sum(sh.structure_version for sh in self.shards)

    @property
    def n_leaves(self) -> int:
        self._sync()
        return sum(len(sh.leaf_starts) for sh in self.shards)

    @property
    def n_levels(self) -> int:
        self._sync()
        return max((sh.n_levels for sh in self.shards), default=0)

    def utilization(self) -> float:
        self._sync()
        ns = [sh.pools[0].n for sh in self.shards]
        if sum(ns) == 0:
            return 0.0
        return float(sum(sh.utilization() * n
                         for sh, n in zip(self.shards, ns)) / sum(ns))

    def retention_stats(self) -> dict:
        """Fleet lifecycle telemetry: per-shard counters summed (each
        shard enforces the shared :class:`RetentionPolicy` on its own
        sub-stream, bit-deterministically), plus the fleet space total.

        In process mode this is deliberately *not* a full read barrier:
        workers answer a counters-only ``stats`` command (a few ints per
        shard), so the pipeline's per-batch ``on_retention`` hook never
        serializes the whole fleet state just to chart a plateau."""
        if self._engine is not None and self._stale:
            per = list(self._engine.stats().values())
            space = sum(p["space_bytes"] for p in per) \
                + self.dst_map.space_bytes()
        else:
            per = [sh.retention_stats() for sh in self._shards]
            space = self.space_bytes()
        out = {"policy": self.params.retention.kind,
               "space_bytes": float(space)}
        for key in ("segments_retained", "segments_coarse",
                    "segments_evicted", "items_evicted", "items_coarsened"):
            out[key] = sum(p[key] for p in per)
        return out

    # ------------------------------------------------------------------
    # persistence: nested per-shard manifests
    # ------------------------------------------------------------------

    def state_dict(self):
        """Per-shard states nested under ``shard<i>/`` key prefixes plus
        the destination routing map; ``meta["config"]`` holds the
        constructor kwargs so ``restore_summary`` rebuilds the fleet."""
        self._sync()
        arrays: dict[str, np.ndarray] = {}
        shard_metas = []
        for i, sh in enumerate(self.shards):
            a, m = sh.state_dict()
            for key, val in a.items():
                arrays[f"shard{i}/{key}"] = val
            shard_metas.append(m)
        arrays.update(self.dst_map.state_arrays())
        meta = {
            "config": {"shards": self.n_shards, "parallel": self.parallel,
                       **dataclasses.asdict(self.params)},
            "shards": shard_metas,
        }
        return arrays, meta

    def load_state(self, arrays: dict, meta: dict) -> None:
        if getattr(self, "_engine", None) is not None:
            # restored state supersedes the workers'; drop them so the
            # next insert re-seeds a fresh engine from the local shards
            self._engine.close()
            self._engine = None
            self._stale = False
        cfg = dict(meta["config"])
        shards = int(cfg.pop("shards"))
        parallel = cfg.pop("parallel", "auto")
        self.__init__(shards=shards, parallel=parallel,
                      params=HiggsParams(**cfg))
        if len(meta["shards"]) != self.n_shards:
            raise ValueError(
                f"snapshot holds {len(meta['shards'])} shards, "
                f"expected {self.n_shards}")
        for i, (sh, m) in enumerate(zip(self.shards, meta["shards"])):
            prefix = f"shard{i}/"
            sub = {k[len(prefix):]: v for k, v in arrays.items()
                   if k.startswith(prefix)}
            sh.load_state(sub, m)
        self.dst_map.load(arrays["dstmap/keys"], arrays["dstmap/masks"])
