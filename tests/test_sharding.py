"""Sharded multi-sketch scale-out (PR 4): partition stability, per-shard
bit-equality against independently built single sketches, fan-out query
merge vs the oracle and the plain sketch, the S=1 degenerate identity,
stacked probe kernels, process-engine equivalence and error surfacing,
and the sharded kill-and-resume round trip."""
import numpy as np
import pytest

from repro.api import (EdgeQuery, PathQuery, SubgraphQuery, VertexQuery,
                       make_summary, restore_summary)
from repro.core.cmatrix import NodeState
from repro.core.higgs import HiggsSketch
from repro.core.oracle import ExactOracle
from repro.core.params import HiggsParams
from repro.shard import (DstShardMap, ShardedHiggs, partition_batch,
                         shard_of)
from repro.shard.engine import fork_available
from repro.stream.pipeline import StreamPipeline

# batched_ingest pinned: sharding is orthogonal to the drain engine, and
# these streams are sized for the batched path (the CI matrix's legacy
# leg would otherwise pay hundreds of per-leaf launches per test); the
# legacy composition is covered once, explicitly, below
PARAMS_SMALL = dict(d1=4, F1=14, b=2, r=2, batched_ingest=True)

needs_fork = pytest.mark.skipif(not fork_available(),
                                reason="no fork start method")


def make_stream(n, nv, t_max, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, n).astype(np.uint32)
    dst = rng.integers(0, nv, n).astype(np.uint32)
    w = rng.integers(1, 10, n).astype(np.float32)
    t = np.sort(rng.integers(0, t_max, n).astype(np.uint32))
    return src, dst, w, t


def query_batch(stream, t_max):
    src, dst = stream[0], stream[1]
    return [
        EdgeQuery(src[:40], dst[:40], t_max // 4, 3 * t_max // 4),
        EdgeQuery(src[:10], dst[:10], 0, t_max),
        VertexQuery(src[:20], 0, t_max, "out"),
        VertexQuery(dst[:20], t_max // 8, t_max, "in"),
        PathQuery([int(src[0]), int(dst[0]), int(dst[1])], 0, t_max),
        SubgraphQuery([(int(src[2]), int(dst[2])),
                       (int(src[3]), int(dst[3]))], 1, t_max - 1),
    ]


def assert_shard_equal(a: HiggsSketch, b: HiggsSketch, tag=""):
    np.testing.assert_array_equal(a.leaf_starts, b.leaf_starts, err_msg=tag)
    np.testing.assert_array_equal(a.leaf_ends, b.leaf_ends, err_msg=tag)
    assert len(a.pools) == len(b.pools), tag
    for lvl, (pa, pb) in enumerate(zip(a.pools, b.pools)):
        assert pa.n == pb.n, (tag, lvl)
        for name in NodeState._fields:
            assert np.array_equal(pa.arrs[name][:pa.n],
                                  pb.arrs[name][:pb.n]), (tag, lvl, name)
    da, db = a.ob.data, b.ob.data
    assert set(da) == set(db), tag
    for key in da:
        for f in da[key]:
            assert np.array_equal(da[key][f], db[key][f]), (tag, key, f)


class TestPartition:
    def test_partition_stable_and_complete(self):
        stream = make_stream(3000, 50, 900, 0)
        seed = HiggsParams().seed
        sids, parts = partition_batch(*stream, 4, seed)
        assert sum(len(p[0]) for p in parts) == 3000
        for s, part in enumerate(parts):
            # stability: the sub-stream is the masked original, in order
            mask = sids == s
            for got, orig in zip(part, stream):
                np.testing.assert_array_equal(got, orig[mask])
            # routing is a pure function of src
            np.testing.assert_array_equal(shard_of(part[0], 4, seed),
                                          np.full(len(part[0]), s))

    def test_single_shard_short_circuit(self):
        stream = make_stream(100, 20, 50, 1)
        sids, parts = partition_batch(*stream, 1, 7)
        assert (sids == 0).all() and len(parts) == 1
        for got, orig in zip(parts[0], stream):
            np.testing.assert_array_equal(got, orig)

    def test_dst_map_routing_and_fallback(self):
        m = DstShardMap(4, seed=3)
        m.update(np.array([5, 5, 9], np.uint32),
                 np.array([1, 3, 0], np.uint32))
        assert m.shards_for(5) == [1, 3]
        assert m.shards_for(9) == [0]
        # never-seen vertex falls back to its own hash shard
        assert m.shards_for(1234) == [int(shard_of([1234], 4, 3)[0])]
        rm = m.routing_matrix(np.array([5, 9], np.uint32))
        assert rm.shape == (4, 2)
        assert rm[:, 0].tolist() == [False, True, False, True]

    def test_process_mode_requires_jax_free_drain(self):
        # the legacy per-leaf closer and the OB ablation run jitted jax
        # code, which must never execute in a forked worker
        with pytest.raises(ValueError, match="jax-free drain"):
            ShardedHiggs(shards=2, parallel="process", d1=4, F1=14,
                         b=2, r=2, batched_ingest=False,
                         insert_backend="host")
        with pytest.raises(ValueError, match="jax-free drain"):
            ShardedHiggs(shards=2, parallel="process", d1=4, F1=14,
                         b=2, r=2, batched_ingest=True, use_ob=False,
                         insert_backend="host")

    def test_dst_map_bounds(self):
        with pytest.raises(ValueError):
            DstShardMap(0, seed=0)
        with pytest.raises(ValueError):
            DstShardMap(65, seed=0)


class TestPerShardBitEquality:
    """Acceptance: shard i's sketch is bit-identical to a single
    HiggsSketch independently built over shard i's partition."""

    @pytest.mark.parametrize("parallel", ["none", "threads"])
    def test_matches_independent_build(self, parallel):
        stream = make_stream(4000, 64, 1500, 2)
        p = HiggsParams(**PARAMS_SMALL)
        sh = ShardedHiggs(shards=4, parallel=parallel, params=p)
        StreamPipeline(*stream, batch=600).feed(sh)
        _, parts = partition_batch(*stream, 4, p.seed)
        for i, part in enumerate(parts):
            ref = HiggsSketch(p)
            # feed in the same pipeline batching the fleet used: leaf
            # boundaries depend only on the item sequence, so any
            # batching works — use one shot for independence
            ref.insert(*part)
            ref.flush()
            assert_shard_equal(ref, sh.shards[i], f"shard {i}")

    # a 40-vertex stream over 2 shards legitimately skews past 50%;
    # the telemetry warning has its own tests in test_retention.py
    @pytest.mark.filterwarnings("ignore:shard skew:RuntimeWarning")
    def test_legacy_ingest_engine_composes(self):
        """Sharding over the serial per-leaf reference drain produces
        the same per-shard sketches (tiny stream: the reference path
        pays one launch per leaf)."""
        stream = make_stream(600, 40, 400, 9)
        p = HiggsParams(d1=4, F1=14, b=2, r=2, batched_ingest=False,
                        insert_backend="host")
        sh = ShardedHiggs(shards=2, parallel="none", params=p)
        sh.insert(*stream)
        sh.flush()
        _, parts = partition_batch(*stream, 2, p.seed)
        for i, part in enumerate(parts):
            ref = HiggsSketch(p)
            ref.insert(*part)
            ref.flush()
            assert_shard_equal(ref, sh.shards[i], f"legacy shard {i}")

    @needs_fork
    def test_process_engine_bit_identical(self):
        stream = make_stream(4000, 64, 1500, 2)
        # pinned: forked workers need the jax-free host drain even when
        # the CI matrix exports HIGGS_INSERT_BACKEND=pallas
        p = HiggsParams(insert_backend="host", **PARAMS_SMALL)
        seq = ShardedHiggs(shards=3, parallel="none", params=p)
        par = ShardedHiggs(shards=3, parallel="process", params=p)
        for sk in (seq, par):
            StreamPipeline(*stream, batch=600).feed(sk)
        assert par._mode == "process"
        for i in range(3):
            assert_shard_equal(seq.shards[i], par.shards[i], f"shard {i}")
        par.close()

    @needs_fork
    @pytest.mark.filterwarnings("ignore:shard skew:RuntimeWarning")
    def test_process_engine_mid_stream_reads(self):
        """A read between inserts syncs worker state exactly (pending
        buffers included) and ingestion continues in the workers."""
        stream = make_stream(3000, 50, 900, 4)
        p = HiggsParams(insert_backend="host", **PARAMS_SMALL)
        seq = ShardedHiggs(shards=2, parallel="none", params=p)
        par = ShardedHiggs(shards=2, parallel="process", params=p)
        half = 1500
        for sk in (seq, par):
            sk.insert(*(a[:half] for a in stream))
        assert par.n_items == seq.n_items == half      # mid-stream sync
        for sk in (seq, par):
            sk.insert(*(a[half:] for a in stream))
            sk.flush()
        for i in range(2):
            assert_shard_equal(seq.shards[i], par.shards[i], f"shard {i}")
        par.close()

    @needs_fork
    def test_worker_error_surfaces_at_barrier(self):
        from repro.shard.engine import ShardProcessEngine
        eng = ShardProcessEngine(2, HiggsParams(**PARAMS_SMALL))
        # mismatched column lengths blow up inside the worker's insert;
        # the engine must report it at the next barrier, not drop it
        eng.insert({0: (np.uint32([1, 2]), np.uint32([3]),
                        np.float32([1.0]), np.uint32([0]))})
        with pytest.raises(RuntimeError, match="shard worker failed"):
            eng.flush()
        eng.close()


class TestFanoutMerge:
    def setup_method(self):
        self.t_max = 1200
        self.stream = make_stream(5000, 48, self.t_max, 3)
        self.sh = ShardedHiggs(shards=4, parallel="none", **PARAMS_SMALL)
        self.sh.insert(*self.stream)
        self.sh.flush()

    def test_one_sided_vs_oracle(self):
        """Sharding preserves the sketch's one-sided overestimate."""
        ora = ExactOracle()
        ora.insert(*self.stream)
        batch = query_batch(self.stream, self.t_max)
        est = self.sh.query(batch).values
        true = ora.query(batch).values
        for i, (a, b) in enumerate(zip(est, true)):
            a = np.asarray(a, np.float64)
            b = np.asarray(b, np.float64)
            assert (a >= b - 1e-4).all(), i

    def test_merge_equals_manual_shard_sum(self):
        """The planner's merge is exactly scatter (edges, out-vertices)
        plus routed sum (in-vertices) over per-shard answers."""
        src, dst = self.stream[0][:64], self.stream[1][:64]
        got = self.sh.query(
            [EdgeQuery(src, dst, 100, 1000)]).values[0]
        sids = shard_of(src, 4, self.sh.params.seed)
        want = np.zeros(64)
        for s in range(4):
            idx = np.nonzero(sids == s)[0]
            if len(idx):
                want[idx] = self.sh.shards[s].query(
                    [EdgeQuery(src[idx], dst[idx], 100, 1000)]).values[0]
        np.testing.assert_array_equal(got, want)

        vs = self.stream[1][:32]
        got_in = self.sh.query(
            [VertexQuery(vs, 0, self.t_max, "in")]).values[0]
        want_in = np.zeros(32)
        for qi, v in enumerate(vs):
            for s in self.sh.dst_map.shards_for(int(v)):
                want_in[qi] += self.sh.shards[s].query(
                    [VertexQuery([v], 0, self.t_max, "in")]).values[0][0]
        np.testing.assert_allclose(got_in, want_in, rtol=0, atol=1e-6)

    def test_stats_accounting(self):
        batch = query_batch(self.stream, self.t_max)
        res = self.sh.query(batch)
        s = res.stats
        assert s.n_queries == len(batch)
        assert 1 <= s.shards_touched <= 4
        assert s.buckets_probed > 0
        assert s.device_dispatches > 0

    def test_in_queries_touch_only_routed_shards(self):
        # a vertex never seen as destination routes to its fallback
        # shard only — the fan-in must not probe the whole fleet
        unseen = np.uint32([4_000_000])
        res = self.sh.query([VertexQuery(unseen, 0, self.t_max, "in")])
        assert res.stats.shards_touched == 1


class TestDegenerateS1:
    def test_identical_to_plain_higgs(self):
        t_max = 1000
        stream = make_stream(4000, 60, t_max, 5)
        p = HiggsParams(**PARAMS_SMALL)
        plain = HiggsSketch(p)
        sh = ShardedHiggs(shards=1, params=p)
        for sk in (plain, sh):
            StreamPipeline(*stream, batch=700).feed(sk)
        assert_shard_equal(plain, sh.shards[0], "S=1 state")
        batch = query_batch(stream, t_max)
        va = plain.query(batch).values
        vb = sh.query(batch).values
        for i, (a, b) in enumerate(zip(va, vb)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), i
        assert sh.space_bytes() > plain.space_bytes()  # + routing map


class TestStackedProbes:
    """The stacked-shard kernel entry points match a per-shard loop."""

    def _stacked_inputs(self):
        import jax.numpy as jnp
        from repro.core.cmatrix import pow2_pad
        t_max = 800
        stream = make_stream(3000, 40, t_max, 6)
        sh = ShardedHiggs(shards=3, parallel="none", **PARAMS_SMALL)
        sh.insert(*stream)
        sh.flush()
        n_pad = pow2_pad(max(sh.shards[s].pools[0].n for s in range(3)))
        ids = [np.arange(sh.shards[s].pools[0].n) for s in range(3)]
        gathered = [sh.shards[s].pools[0].gather(ids[s], n_pad)
                    for s in range(3)]
        nodes = NodeState(*(jnp.stack([getattr(g[0], f) for g in gathered])
                            for f in NodeState._fields))
        mask = jnp.stack([g[1] for g in gathered])
        return sh, stream, t_max, gathered, nodes, mask

    def test_vertex_probe_stacked(self):
        from repro.core import cmatrix
        from repro.kernels import ops
        sh, stream, t_max, gathered, nodes, mask = self._stacked_inputs()
        f1, base = sh.shards[0]._query_coords(stream[0][:16], "s")
        f_l, rows = cmatrix.coords_at_level(f1, base, 1, sh.params)
        got = np.asarray(ops.vertex_probe_stacked(
            nodes, mask, f_l, rows, np.uint32(0), np.uint32(t_max),
            direction="out", match_time=True))
        for s, (n_s, m_s) in enumerate(gathered):
            want = np.asarray(cmatrix.probe_vertex(
                n_s, m_s, f_l, rows, np.uint32(0), np.uint32(t_max),
                direction="out", match_time=True))
            np.testing.assert_array_equal(got[s], want)

    def test_edge_probe_stacked(self):
        from repro.core import cmatrix
        from repro.kernels import ops
        sh, stream, t_max, gathered, nodes, mask = self._stacked_inputs()
        f1s, bs = sh.shards[0]._query_coords(stream[0][:16], "s")
        f1d, bd = sh.shards[0]._query_coords(stream[1][:16], "d")
        fs_l, rows = cmatrix.coords_at_level(f1s, bs, 1, sh.params)
        fd_l, cols = cmatrix.coords_at_level(f1d, bd, 1, sh.params)
        got = np.asarray(ops.edge_probe_stacked(
            nodes, mask, fs_l, fd_l, rows, cols, np.uint32(0),
            np.uint32(t_max), match_time=False))
        for s, (n_s, m_s) in enumerate(gathered):
            want = np.asarray(cmatrix.probe_edge(
                n_s, m_s, fs_l, fd_l, rows, cols, np.uint32(0),
                np.uint32(t_max), match_time=False))
            np.testing.assert_array_equal(got[s], want)


class TestShardMapMode:
    """``parallel="shard_map"``: stacked probes dispatched through an
    explicit ``shard_map`` over the 1-D shard mesh stay bit-identical
    to the sequential launch (single-device mesh on CPU CI)."""

    def test_bit_identical_to_sequential(self):
        t_max = 1000
        stream = make_stream(4000, 48, t_max, 11)
        seq = ShardedHiggs(shards=4, parallel="none", **PARAMS_SMALL)
        sm = ShardedHiggs(shards=4, parallel="shard_map", **PARAMS_SMALL)
        for sk in (seq, sm):
            sk.insert(*stream)
            sk.flush()
        assert sm._mode == "shard_map" and sm.mesh is not None
        for i in range(4):
            assert_shard_equal(seq.shards[i], sm.shards[i], f"shard {i}")
        batch = query_batch(stream, t_max)
        va, vb = seq.query(batch).values, sm.query(batch).values
        for i, (a, b) in enumerate(zip(va, vb)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), i

    def test_never_auto_resolved(self):
        sh = ShardedHiggs(shards=2, parallel="auto", **PARAMS_SMALL)
        assert sh._mode in ("process", "threads", "none")


class TestShardedPersistence:
    def test_registry_roundtrip(self, tmp_path):
        t_max = 900
        stream = make_stream(3000, 48, t_max, 7)
        sh = make_summary("higgs-sharded", shards=3, parallel="none",
                          **PARAMS_SMALL)
        StreamPipeline(*stream, batch=512).feed(sh)
        sh.save(str(tmp_path), 11)
        got = restore_summary(str(tmp_path))
        assert isinstance(got, ShardedHiggs) and got.n_shards == 3
        for i in range(3):
            assert_shard_equal(sh.shards[i], got.shards[i], f"shard {i}")
        batch = query_batch(stream, t_max)
        va, vb = sh.query(batch).values, got.query(batch).values
        for a, b in zip(va, vb):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert sh.space_bytes() == got.space_bytes()

    @pytest.mark.slow
    @pytest.mark.parametrize("parallel", [
        "none", pytest.param("process", marks=needs_fork)])
    def test_kill_and_resume(self, tmp_path, parallel):
        """A sharded run killed mid-stream and resumed into a fresh
        fleet is bit-identical to an uninterrupted run."""
        t_max = 1500
        stream = make_stream(6000, 64, t_max, 8)
        kw = dict(shards=3, parallel=parallel, **PARAMS_SMALL)
        ref = make_summary("higgs-sharded", **kw)
        StreamPipeline(*stream, batch=512).feed(ref)

        ckpt = str(tmp_path)
        pipe = StreamPipeline(*stream, batch=512)
        sk = make_summary("higgs-sharded", **kw)
        calls = [0]

        def stop():
            calls[0] += 1
            return calls[0] >= 3

        pipe.run_resumable(sk, ckpt, every=2, should_stop=stop)
        sk.close()
        assert pipe.cursor < len(pipe), "kill fired too late"

        pipe2 = StreamPipeline(*stream, batch=512)
        sk2 = make_summary("higgs-sharded", **kw)
        pipe2.run_resumable(sk2, ckpt, every=2, keep=3)
        assert pipe2.cursor == len(pipe2)

        for i in range(3):
            assert_shard_equal(ref.shards[i], sk2.shards[i], f"shard {i}")
        batch = query_batch(stream, t_max)
        va, vb = ref.query(batch).values, sk2.query(batch).values
        for a, b in zip(va, vb):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        ref.close()
        sk2.close()
