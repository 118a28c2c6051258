"""Program spans and ingest counters (``repro.runtime.trace``,
``IngestStats``) on the device engine: Pallas leaf insert (interpreted
here), device pools and window retention at the benchmark's rehearsal
size.

Under ``jax.profiler`` every span of the ingest path appears, nested as
the call structure nests; the counters are exact; and tracing changes no
answer and no byte of state.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api.queries import EdgeQuery, IngestStats, VertexQuery
from repro.core.higgs import HiggsSketch
from repro.core.params import HiggsParams, RetentionPolicy
from repro.runtime import trace
from repro.serve.service import SummaryService
from repro.stream.generator import wiki_talk_like_stream

BATCH = 8192
N_EDGES = 5 * BATCH
# a window of about 16,384 edges of the stream's 2**29 time units, as in
# the rehearsal of the benchmark's wiki-talk cell
HORIZON = (1 << 29) * 16384 // N_EDGES
INGEST_SPANS = ("higgs.insert", "higgs.drain", "higgs.drain.split",
                "higgs.drain.stage", "higgs.drain.spill", "higgs.cascade",
                "higgs.cascade.ob", "higgs.fetch", "higgs.lifecycle",
                "higgs.evict")
# child -> the span it lies in
PARENT = {"higgs.drain": "higgs.insert",
          "higgs.drain.split": "higgs.drain",
          "higgs.drain.stage": "higgs.drain",
          "higgs.cascade": "higgs.drain",
          "higgs.cascade.ob": "higgs.cascade",
          "higgs.lifecycle": "higgs.drain",
          "higgs.evict": "higgs.lifecycle"}


def params(**kw):
    base = dict(d1=16, F1=19, b=3, r=4, theta=4, segment_levels=2,
                insert_backend="pallas", pool_storage="device",
                batched_ingest=True, use_ob=True, interpret=True,
                retention=RetentionPolicy.window(HORIZON))
    base.update(kw)
    return HiggsParams(**base)


def host_spans(trace_dir):
    """``[name, start_ns, end_ns]`` of every ``higgs.*`` host event."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events if e.name.startswith("higgs.")]
    return out


def build(stream, profile_dir=None):
    sk = HiggsSketch(params())
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    try:
        for c in range(0, N_EDGES, BATCH):
            sk.insert(*(a[c:c + BATCH] for a in stream))
    finally:
        if profile_dir:
            jax.profiler.stop_trace()
    return sk


@pytest.fixture(scope="module")
def stream():
    return wiki_talk_like_stream(n_edges=N_EDGES, seed=11)


@pytest.fixture(scope="module")
def traced(stream, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    sk = build(stream, d)
    return sk, host_spans(d)


@pytest.fixture(scope="module")
def untraced(stream):
    return build(stream)


def inside(child, parents):
    return any(a <= child[1] and child[2] <= b for _, a, b in parents)


def test_every_ingest_span_is_emitted_and_nested(traced):
    sk, spans = traced
    names = {n for n, _, _ in spans}
    assert set(INGEST_SPANS) <= names
    by = {n: [s for s in spans if s[0] == n] for n in names}
    assert len(by["higgs.insert"]) == N_EDGES // BATCH
    for child, parent in PARENT.items():
        for sp in by[child]:
            assert inside(sp, by[parent]), (child, parent)
    # a fetch waits inside the drain (the ingest spill mask) or inside a
    # cascade level (its spill mask, and its spill coordinates)
    for sp in by["higgs.fetch"]:
        assert inside(sp, by["higgs.drain"])
    for sp in by["higgs.drain.spill"]:
        assert inside(sp, by["higgs.drain"])
    assert sk.segments.n_evicted > 0


def test_ingest_counters_are_exact(traced):
    sk, spans = traced
    st = sk.ingest_stats
    count = {n: sum(1 for s in spans if s[0] == n) for n in INGEST_SPANS}
    cascade = [s for s in spans if s[0] == "higgs.cascade"]
    spill_fetches = sum(
        1 for s in spans if s[0] == "higgs.drain.spill"
        and inside(s, cascade))
    assert st.inserts == N_EDGES // BATCH
    assert st.drains == count["higgs.drain"]
    assert st.leaves_closed == sk.pools[0].total
    # one fetch per drain, one per cascade level built, one more per
    # cascade level that spilled
    assert st.fetches == st.drains + count["higgs.cascade"] + spill_fetches
    assert st.fetches == count["higgs.fetch"]
    assert st.slides == (sk.params.segment_levels + 1) * sk.segments.n_evicted
    assert st.launches == (st.drains + 3 * count["higgs.cascade"]
                           + st.slides)
    assert st.fetch_bytes > 0 and st.staged_bytes > 0 and st.spill_items > 0


def test_counters_do_not_depend_on_the_profiler(traced, untraced):
    assert traced[0].ingest_stats == untraced.ingest_stats


def test_tracing_changes_no_answer_and_no_state(traced, untraced, stream):
    on, off = traced[0], untraced
    a_on, m_on = on.state_dict()
    a_off, m_off = off.state_dict()
    assert m_on == m_off
    assert a_on.keys() == a_off.keys()
    for k in a_on:
        np.testing.assert_array_equal(a_on[k], a_off[k], err_msg=k)
    src, dst, _, t = stream
    ts, te = int(t[N_EDGES // 2]), int(t[-1])
    batch = [EdgeQuery(src[-64:], dst[-64:], ts, te),
             VertexQuery(src[-64:], ts, te, "out"),
             VertexQuery(dst[-64:], ts, te, "in")]
    for x, y in zip(on.query(batch).values, off.query(batch).values):
        np.testing.assert_array_equal(x, y)


def test_pool_growth_is_counted_by_capacity():
    """No retention: every capacity a pool took is counted once, with
    its bytes; every spilled item is in the overflow store."""
    sk = HiggsSketch(params(retention=RetentionPolicy()))
    src, dst, w, t = wiki_talk_like_stream(n_edges=2 * BATCH, seed=5)
    grows = grow_bytes = 0
    caps = []
    for c in range(0, 2 * BATCH, 2048):
        sk.insert(src[c:c + 2048], dst[c:c + 2048], w[c:c + 2048],
                  t[c:c + 2048])
        # a drain reserves each pool once, so a pool grows at most once
        # an insert
        for i, pool in enumerate(sk.pools):
            if pool.cap != (caps[i] if i < len(caps) else 0):
                grows += 1
                grow_bytes += pool.cap * pool.d * pool.d * pool.b * 4 * 5
        caps = [pool.cap for pool in sk.pools]
    st = sk.ingest_stats
    assert grows > len(sk.pools)
    assert (st.pool_grows, st.pool_grow_bytes) == (grows, grow_bytes)
    assert st.spill_items == sk.ob.total_entries()
    assert st.slides == 0


def test_query_path_spans(traced, stream, tmp_path):
    import asyncio
    sk = traced[0]
    src, dst, _, t = stream
    batch = [EdgeQuery(src[-16:], dst[-16:], int(t[-BATCH]), int(t[-1])),
             VertexQuery(src[-16:], int(t[0]), int(t[-1]), "out")]

    async def ask():
        async with SummaryService(sk, readers=1) as svc:
            return await svc.submit(batch)

    d = str(tmp_path / "q")
    jax.profiler.start_trace(d)
    try:
        res = asyncio.run(ask())
    finally:
        jax.profiler.stop_trace()
    names = {n for n, _, _ in host_spans(d)}
    assert {"higgs.serve.round", "higgs.pin", "higgs.plan", "higgs.probe",
            "higgs.ob_scan"} <= names
    want = sk.query(batch).values
    for x, y in zip(res.values, want):
        np.testing.assert_array_equal(x, y)


def test_ingest_stats_are_telemetry_not_state():
    sk = HiggsSketch(HiggsParams(d1=8, F1=14))
    src, dst, w, t = wiki_talk_like_stream(n_edges=4000, seed=2)
    sk.insert(src, dst, w, t)
    assert sk.ingest_stats.inserts == 1
    assert sk.ingest_stats.leaves_closed == sk.pools[0].total
    arrays, meta = sk.state_dict()
    assert not any("ingest" in k for k in list(arrays) + list(meta))
    back = HiggsSketch(HiggsParams(d1=8, F1=14))
    back.load_state(arrays, meta)
    # a restore counts nothing but the one allocation of each pool
    want = IngestStats(pool_grows=len(back.pools))
    want.pool_grow_bytes = back.ingest_stats.pool_grow_bytes
    assert back.ingest_stats == want
    assert sk._pin_replica().ingest_stats == IngestStats()
    snap = sk.ingest_stats.snapshot()
    sk.insert(src[:10], dst[:10], w[:10], t[-1:].repeat(10))
    assert sk.ingest_stats.snapshot()["inserts"] - snap["inserts"] == 1


def test_fetch_counts_one_copy_and_its_bytes():
    st = IngestStats()
    one = trace.fetch(jax.numpy.arange(8, dtype=jax.numpy.int32), st)
    assert isinstance(one, np.ndarray) and (st.fetches, st.fetch_bytes) \
        == (1, 32)
    pair = trace.fetch((jax.numpy.zeros(4, jax.numpy.uint32),
                        jax.numpy.ones(2, jax.numpy.float32)), st)
    assert [a.dtype for a in pair] == [np.uint32, np.float32]
    assert (st.fetches, st.fetch_bytes) == (2, 56)


@pytest.mark.parametrize("backend", ["vector", "host"])
def test_default_engine_spans_and_counters(backend, stream, tmp_path):
    """Host pools, as ``auto`` resolves on a TPU (vector) and on the CPU
    (host): the leaf prep and the numpy level build are spans of the
    drain.  On the vector engine the leaf state's copy back to the host
    pools is the drain's one fetch; the host engine fetches nothing."""
    p = params(insert_backend=backend, pool_storage="host",
               retention=RetentionPolicy())
    sk = HiggsSketch(p)
    d = str(tmp_path / backend)
    jax.profiler.start_trace(d)
    try:
        for c in range(0, 3 * BATCH, BATCH):
            sk.insert(*(a[c:c + BATCH] for a in stream))
    finally:
        jax.profiler.stop_trace()
    spans = host_spans(d)
    by = {n: [s for s in spans if s[0] == n] for n, _, _ in spans}
    st = sk.ingest_stats
    assert len(by["higgs.drain.prep"]) == st.drains == 3
    assert len(by["higgs.cascade.host"]) == len(by["higgs.cascade"]) > 0
    for sp in by["higgs.drain.prep"]:
        assert inside(sp, by["higgs.drain"])
    for sp in by["higgs.cascade.host"]:
        assert inside(sp, by["higgs.cascade"])
    want = st.drains if backend == "vector" else 0
    assert st.fetches == len(by.get("higgs.fetch", [])) == want
    assert (st.fetch_bytes > 0) == (backend == "vector")
    assert st.launches == 0
