"""Device-resident pool storage (PR 8 tentpole): the storage seam must
be invisible — for any insert backend, a sketch with device-resident
pools is bit-identical to the host-storage build across drain, flush,
retention, and snapshot boundaries.  Hypothesis drives the stream
shapes and the batch splits so leaf/drain boundaries land everywhere.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency; install with `pip install .[test]`")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.api.queries import EdgeQuery, VertexQuery
from repro.core.cmatrix import NodeState
from repro.core.higgs import HiggsSketch
from repro.core.params import HiggsParams, RetentionPolicy
from repro.core.pool import (DevicePoolStorage, HostPoolStorage,
                             _LevelPool, _slide_slabs)

SETTINGS = dict(max_examples=10, deadline=None)

# collision-prone small geometry; segment_levels=1 seals segments fast
# enough for retention to fire on hypothesis-sized streams
BASE_KW = dict(d1=4, F1=14, b=2, r=2, segment_levels=1)

BACKENDS = [
    pytest.param("host", id="host-backend"),
    # the fused drain pipeline: only the pallas backend takes it
    pytest.param("pallas", id="pallas-backend"),
    # vector ingest; device storage still takes the fused aggregation
    pytest.param("vector", id="vector-backend"),
]


def kw_for(backend):
    kw = dict(BASE_KW, insert_backend=backend)
    if backend == "pallas":
        kw.update(batched_ingest=True, use_ob=True, interpret=True)
    return kw


def assert_sketch_equal(a: HiggsSketch, b: HiggsSketch, tag=""):
    """Full physical bit-equality: pools (slabs + window bases), leaf
    intervals, overflow store, pending buffer, counters."""
    np.testing.assert_array_equal(a.leaf_starts, b.leaf_starts,
                                  err_msg=tag)
    np.testing.assert_array_equal(a.leaf_ends, b.leaf_ends, err_msg=tag)
    assert a.n_items == b.n_items, tag
    assert len(a.pools) == len(b.pools), tag
    for lvl, (pa, pb) in enumerate(zip(a.pools, b.pools)):
        assert (pa.n, pa.base) == (pb.n, pb.base), (tag, lvl)
        aa, ab = pa.arrs, pb.arrs
        for name in NodeState._fields:
            assert np.array_equal(aa[name][:pa.n], ab[name][:pb.n]), \
                (tag, lvl, name)
    da, db = a.ob.data, b.ob.data
    assert set(da) == set(db), tag
    for key in da:
        for f in da[key]:
            assert np.array_equal(da[key][f], db[key][f]), (tag, key, f)


def assert_same_answers(a, b, stream, t_max, tag=""):
    src, dst = stream[0], stream[1]
    qs = [EdgeQuery(src[:32], dst[:32], 0, t_max),
          EdgeQuery(src[:16], dst[:16], t_max // 4, 3 * t_max // 4),
          VertexQuery(src[:16], 0, t_max, "out"),
          VertexQuery(dst[:16], t_max // 8, t_max, "in")]
    va, vb = a.query(qs).values, b.query(qs).values
    for i, (x, y) in enumerate(zip(va, vb)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (tag, i)


@st.composite
def streams(draw, max_n=900):
    n = draw(st.integers(80, max_n))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    nv = draw(st.integers(4, 64))
    t_max = draw(st.integers(50, 3000))
    src = rng.integers(0, nv, n).astype(np.uint32)
    dst = rng.integers(0, nv, n).astype(np.uint32)
    w = rng.integers(1, 10, n).astype(np.float32)
    t = np.sort(rng.integers(0, t_max, n).astype(np.uint32))
    return (src, dst, w, t), t_max


class TestStorageBitEquality:
    """pool_storage="device" == pool_storage="host", physically."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @given(stream_tm=streams(), cuts=st.lists(st.integers(1, 899),
                                              min_size=1, max_size=3),
           flush_mid=st.booleans())
    @settings(**SETTINGS)
    def test_drain_flush_snapshot_boundaries(self, backend, stream_tm,
                                             cuts, flush_mid):
        stream, t_max = stream_tm
        n = len(stream[0])
        marks = sorted({min(c, n) for c in cuts} | {n})
        host = HiggsSketch(HiggsParams(pool_storage="host",
                                       **kw_for(backend)))
        dev = HiggsSketch(HiggsParams(pool_storage="device",
                                      **kw_for(backend)))
        assert dev._storage == "device" and host._storage == "host"
        lo = 0
        for i, hi in enumerate(marks):
            for sk in (host, dev):
                sk.insert(*(a[lo:hi] for a in stream))
            lo = hi
            if flush_mid and i == 0:
                host.flush()
                dev.flush()
                # mid-stream snapshot barrier: round-trip the device
                # sketch through its host state and keep streaming
                arrays, meta = dev.state_dict()
                dev = HiggsSketch(HiggsParams(pool_storage="device",
                                              **kw_for(backend)))
                dev.load_state(arrays, meta)
                assert dev._storage == "device"
        host.flush()
        dev.flush()
        assert_sketch_equal(host, dev, f"{backend} host-vs-device")
        assert_same_answers(host, dev, stream, t_max,
                            f"{backend} answers")

    @pytest.mark.parametrize("backend", BACKENDS)
    @given(stream_tm=streams(), frac=st.integers(2, 6))
    @settings(**SETTINGS)
    def test_eviction_under_device_residency(self, backend, stream_tm,
                                             frac):
        """Windowed retention on device pools == a fresh device sketch
        over the retained suffix — eviction's pool-level slide/drop ops
        preserve device-slab contents exactly."""
        stream, t_max = stream_tm
        params = HiggsParams(pool_storage="device",
                             retention=RetentionPolicy.window(
                                 max(1, t_max // frac)),
                             **kw_for(backend))
        win = HiggsSketch(params)
        win.insert(*stream)
        win.flush()
        drop = win.segments.items_dropped
        fresh = HiggsSketch(params)
        fresh.insert(*(a[drop:] for a in stream))
        fresh.flush()
        np.testing.assert_array_equal(win.leaf_starts, fresh.leaf_starts)
        np.testing.assert_array_equal(win.leaf_ends, fresh.leaf_ends)
        assert len(win.pools) == len(fresh.pools)
        for pw, pf in zip(win.pools, fresh.pools):
            assert pw.n == pf.n
            assert pf.base == 0          # fresh build: no window bases
            for name in NodeState._fields:
                assert np.array_equal(pw.arrs[name][:pw.n],
                                      pf.arrs[name][:pf.n]), name
        assert_same_answers(win, fresh, stream, t_max,
                            f"{backend} window-vs-fresh")


class TestFusedAggregationCascade:
    """The device-resident aggregation cascade (fused `_aggregate_step`)
    must be bit-identical to the host numpy reference even when a drain
    closes several tree levels at once and parents spill into overflow
    blocks — the regime where the fused path actually cascades."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @given(seed=st.integers(0, 2**31 - 1), nv=st.integers(4, 12))
    @settings(**SETTINGS)
    def test_deep_cascade_with_overflow(self, backend, seed, nv):
        # few vertices + long stream: heavy fingerprint collisions force
        # multi-level parent builds and OB spill on tiny (d1=4, b=2)
        # geometry
        rng = np.random.default_rng(seed)
        n = 900
        stream = (rng.integers(0, nv, n).astype(np.uint32),
                  rng.integers(0, nv, n).astype(np.uint32),
                  rng.integers(1, 10, n).astype(np.float32),
                  np.sort(rng.integers(0, 2000, n).astype(np.uint32)))
        host = HiggsSketch(HiggsParams(pool_storage="host",
                                       **kw_for(backend)))
        dev = HiggsSketch(HiggsParams(pool_storage="device",
                                      **kw_for(backend)))
        for sk in (host, dev):
            sk.insert(*stream)
            sk.flush()
        # the scenario must actually exercise a cascade: ≥2 populated
        # non-leaf levels, and (tiny buckets) overflow entries
        populated = sum(p.n - p.base > 0 for p in dev.pools[1:])
        assert populated >= 2, "stream did not cascade; test is vacuous"
        assert dev.ob.total_entries() > 0, "no OB spill; test is vacuous"
        assert_sketch_equal(host, dev, f"{backend} deep-cascade")
        assert_same_answers(host, dev, stream, 2000,
                            f"{backend} deep-cascade answers")


class TestPoolStorageSeam:
    """Unit-level contracts of the storage seam itself."""

    def test_invalid_storage_rejected(self):
        with pytest.raises(ValueError, match="pool_storage"):
            HiggsParams(pool_storage="gpu")
        with pytest.raises(ValueError):
            _LevelPool(4, 2, storage="gpu")

    def test_auto_storage_resolution(self):
        assert HiggsSketch(HiggsParams())._storage == "host"
        assert HiggsSketch(HiggsParams(**kw_for("pallas")))._storage \
            == "device"

    @pytest.mark.parametrize("kw", [dict(batched_ingest=False),
                                    dict(use_ob=False)])
    def test_env_pallas_backend_rejects_incompatible_params(self,
                                                            monkeypatch, kw):
        # the environment selects the backend exactly like the parameter
        # does: no quiet fall-back to another engine
        monkeypatch.setenv("HIGGS_INSERT_BACKEND", "pallas")
        with pytest.raises(ValueError, match="HIGGS_INSERT_BACKEND=pallas"):
            HiggsSketch(HiggsParams(**kw))

    def test_adopt_slabs_device_only(self):
        pool = _LevelPool(4, 2, storage="host")
        with pytest.raises(ValueError, match="device storage"):
            pool.adopt_slabs({}, 0)

    def test_gather_block_matches_host_view(self):
        from repro.core import cmatrix
        rng = np.random.default_rng(0)
        arrs = cmatrix.empty_node_arrays(8, 4, 2)
        for name in NodeState._fields:
            arrs[name] = rng.integers(
                0, 100, arrs[name].shape).astype(arrs[name].dtype)
        for storage in ("host", "device"):
            pool = _LevelPool(4, 2, storage=storage)
            pool.load(arrs, 8, cap=8, base=0)
            pool.drop_prefix(3)          # global ids now 3..7
            blk = pool.gather_block(3, 4)
            for name in NodeState._fields:
                assert np.array_equal(np.asarray(blk[name]),
                                      arrs[name][3:7]), (storage, name)
            with pytest.raises(ValueError, match="retained window"):
                pool.gather_block(2, 2)  # below the window base


def random_slabs(rng, cap, d, b):
    """Slabs of random bits in every field's dtype (float32 fields take
    any bit pattern, NaN payloads included)."""
    from repro.core import cmatrix
    arrs = cmatrix.empty_node_arrays(cap, d, b)
    return {name: rng.integers(0, 2**32, arr.shape, dtype=np.uint64)
            .astype(np.uint32).view(arr.dtype)
            for name, arr in arrs.items()}


class TestJittedSlide:
    """The device slide is one jitted program per pool capacity, and
    leaves the whole capacity as the host slide does."""

    @pytest.mark.parametrize("cap,n,k", [
        pytest.param(8, 6, 2, id="middle"),
        pytest.param(8, 8, 3, id="n_eq_cap"),
        pytest.param(8, 5, 5, id="k_eq_n"),
        pytest.param(8, 8, 8, id="k_eq_n_eq_cap"),
        pytest.param(8, 7, 1, id="one_row"),
        pytest.param(1, 1, 1, id="cap_one"),
    ])
    def test_device_slide_equals_host_slide(self, cap, n, k):
        d, b = 4, 2
        arrs = random_slabs(np.random.default_rng(cap * 100 + n * 10 + k),
                            cap, d, b)
        host = HostPoolStorage(d, b)
        host.slabs = {name: a.copy() for name, a in arrs.items()}
        host.cap = cap
        dev = DevicePoolStorage(d, b)
        dev.slabs = {name: jnp.asarray(a) for name, a in arrs.items()}
        dev.cap = cap
        host.slide(n, k)
        dev.slide(n, k)
        got = dev.host_view()
        for name in NodeState._fields:
            assert got[name].dtype == host.slabs[name].dtype, name
            assert got[name].shape == (cap, d, d, b), name
            np.testing.assert_array_equal(got[name].view(np.uint32),
                                          host.slabs[name].view(np.uint32),
                                          err_msg=name)

    def test_one_program_per_capacity(self):
        d, b, cap, k = 4, 2, 16, 3
        arrs = random_slabs(np.random.default_rng(1), cap, d, b)
        st = DevicePoolStorage(d, b)
        _slide_slabs.clear_cache()
        for n in (3, 7, 10, 15, 16):
            st.slabs = {name: jnp.asarray(a) for name, a in arrs.items()}
            st.cap = cap
            st.slide(n, k)
        assert _slide_slabs._cache_size() == 1

    def test_slide_leaves_its_input_valid(self):
        # the slide donates nothing: a caller may slide a copy of the
        # live slab dict and keep reading the live arrays
        d, b, cap = 4, 2, 8
        arrs = random_slabs(np.random.default_rng(2), cap, d, b)
        live = {name: jnp.asarray(a) for name, a in arrs.items()}
        st = DevicePoolStorage(d, b)
        st.slabs, st.cap = dict(live), cap
        st.slide(6, 2)
        for name in NodeState._fields:
            np.testing.assert_array_equal(np.asarray(live[name]), arrs[name])
