"""Answers exact over each id's key, on every engine.

A summary keeps a vertex as the F1 + log2(d1) low bits of its id's hash:
an F1-bit fingerprint and a base row.  Two ids with one fingerprint whose
candidate rows meet (fingerprint twins) are different keys, told apart
only by the chain index ``idx`` an entry is stored with.  The placement
merge and the probes both read it, so every answer equals the exact
answer over the graph with each id replaced by its key.

The reference here is :class:`ExactOracle` fed keyed ids: a few lines of
numpy over the hash, independent of the placement code.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.queries import EdgeQuery, IngestStats, VertexQuery
from repro.core import cmatrix, hashing
from repro.core.cmatrix import EMPTY, NodeState
from repro.core.higgs import HiggsSketch
from repro.core.oracle import ExactOracle
from repro.core.params import HiggsParams
from repro.kernels import ops, ref

DST_SALT = 0x5BD1E995
_MIX1, _MIX2 = 0x7FEB352D, 0x846CA68B


def side_seed(p: HiggsParams, side: str) -> int:
    return p.seed ^ (DST_SALT if side == "d" else 0)


def key_bits(p: HiggsParams) -> int:
    return p.F1 + p.d1.bit_length() - 1


def keys(ids, p: HiggsParams, side: str) -> np.ndarray:
    h = hashing.np_mix32(np.asarray(ids, np.uint32), side_seed(p, side))
    return h & np.uint32((1 << key_bits(p)) - 1)


def unmix32(h, seed: int) -> np.ndarray:
    """The ids whose ``mix32`` under ``seed`` is ``h``."""
    x = np.asarray(h, np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = (x * np.uint32(pow(_MIX2, -1, 1 << 32))).astype(np.uint32)
    x = x ^ (x >> np.uint32(15)) ^ (x >> np.uint32(30))
    x = (x * np.uint32(pow(_MIX1, -1, 1 << 32))).astype(np.uint32)
    x = x ^ (x >> np.uint32(16))
    return x ^ np.uint32(seed)


def id_with(fp: int, base: int, hi: int, p: HiggsParams, side: str) -> int:
    """An id whose hash has fingerprint ``fp``, base row ``base`` and
    ``hi`` in the bits above the key."""
    h = (fp | base << p.F1 | hi << key_bits(p)) & 0xFFFFFFFF
    return int(unmix32(np.array([h], np.uint32), side_seed(p, side))[0])


def chain(base: int, p: HiggsParams) -> list[int]:
    out = [base]
    for _ in range(p.r - 1):
        out.append((5 * out[-1] + 1) % p.d1)
    return out


def twins(u: int, p: HiggsParams, side: str, rng) -> list[int]:
    """Ids with ``u``'s fingerprint and another base row whose candidate
    rows meet ``u``'s: another key that a fingerprint-only merge or
    probe would join with ``u``."""
    h = int(hashing.np_mix32(np.array([u], np.uint32), side_seed(p, side))[0])
    fp, base = h & p.fp_mask, (h >> p.F1) % p.d1
    mine = set(chain(base, p))
    bases = [b for b in range(p.d1) if b != base and mine & set(chain(b, p))]
    hi_top = 1 << (32 - key_bits(p))
    return [id_with(fp, b, int(rng.integers(hi_top)), p, side) for b in bases]


def keyed_oracle(stream, p: HiggsParams) -> ExactOracle:
    src, dst, w, t = stream
    o = ExactOracle()
    o.insert(keys(src, p, "s"), keys(dst, p, "d"), w, t)
    return o


def test_unmix_inverts_the_hash():
    ids = np.random.default_rng(0).integers(0, 2**32, 1000, dtype=np.uint64)
    ids = ids.astype(np.uint32)
    for seed in (0x9E3779B9, 0x9E3779B9 ^ DST_SALT):
        np.testing.assert_array_equal(
            unmix32(hashing.np_mix32(ids, seed), seed), ids)


# ---------------------------------------------------------------------------
# engines against the keyed oracle, on streams with planted twins
# ---------------------------------------------------------------------------

SMALL = dict(d1=4, F1=14, b=2, r=2, theta=4, use_ob=True,
             batched_ingest=True)
ENGINES = {"host": dict(insert_backend="host", pool_storage="host"),
           "vector": dict(insert_backend="vector", pool_storage="host"),
           "pallas-device": dict(insert_backend="pallas",
                                 pool_storage="device", interpret=True)}
N_EDGES = 3000


def planted_stream(seed: int, p: HiggsParams):
    """Hot senders and hot receivers, each with its fingerprint twins
    sending or receiving a few edges among the same vertices, plus
    background edges; timestamps with ties, integer weights."""
    rng = np.random.default_rng(seed)
    hot_s = [int(x) for x in rng.integers(0, 2**31, 3)]
    hot_d = [int(x) for x in rng.integers(0, 2**31, 4)]
    tw_s = [v for u in hot_s for v in twins(u, p, "s", rng)]
    tw_d = [v for u in hot_d for v in twins(u, p, "d", rng)]
    pick = rng.random(N_EDGES)
    src = np.where(pick < 0.6, rng.choice(hot_s, N_EDGES),
                   np.where(pick < 0.8, rng.choice(tw_s, N_EDGES),
                            rng.integers(0, 2**31, N_EDGES)))
    pick = rng.random(N_EDGES)
    dst = np.where(pick < 0.6, rng.choice(hot_d, N_EDGES),
                   np.where(pick < 0.8, rng.choice(tw_d, N_EDGES),
                            rng.integers(0, 2**31, N_EDGES)))
    w = rng.integers(1, 10, N_EDGES).astype(np.float32)
    t = np.sort(rng.integers(0, N_EDGES // 3, N_EDGES)).astype(np.uint32)
    stream = (src.astype(np.uint32), dst.astype(np.uint32), w, t)
    return stream, np.array(hot_s + tw_s, np.uint32), \
        np.array(hot_d + tw_d, np.uint32)


def build(p: HiggsParams, stream, batch=700) -> HiggsSketch:
    sk = HiggsSketch(p)
    for c in range(0, len(stream[0]), batch):
        sk.insert(*(a[c:c + batch] for a in stream))
    sk.flush()
    return sk


def ranges(t):
    lo, hi = int(t[0]), int(t[-1])
    mid = (lo + hi) // 2
    return [(lo, hi), (lo, mid), (mid + 1, hi), (lo + 17, hi - 23)]


@pytest.fixture(scope="module", params=[3, 41, 2**31 + 5])
def planted(request):
    p = HiggsParams(**SMALL)
    stream, senders, receivers = planted_stream(request.param, p)
    return stream, senders, receivers, keyed_oracle(stream, p)


@pytest.mark.parametrize("engine", ENGINES)
def test_engine_is_exact_over_the_key(planted, engine):
    stream, senders, receivers, oracle = planted
    p = HiggsParams(**SMALL, **ENGINES[engine])
    sk = build(p, stream)
    es = np.repeat(senders, len(receivers))
    ed = np.tile(receivers, len(senders))
    ks, kd = keys(senders, p, "s"), keys(receivers, p, "d")
    kes, ked = keys(es, p, "s"), keys(ed, p, "d")
    for ts, te in ranges(stream[3]):
        out = sk.query([EdgeQuery(es, ed, ts, te),
                        VertexQuery(senders, ts, te, "out"),
                        VertexQuery(receivers, ts, te, "in")]).values
        np.testing.assert_array_equal(
            out[0], oracle.edge_query(kes, ked, ts, te), err_msg="edge")
        np.testing.assert_array_equal(
            out[1], oracle.vertex_query(ks, ts, te, "out"), err_msg="out")
        np.testing.assert_array_equal(
            out[2], oracle.vertex_query(kd, ts, te, "in"), err_msg="in")


def test_planted_twins_meet_in_the_host_placement(planted):
    """The streams do plant collisions: the host engine keeps items
    apart from another key's entry with their fingerprints, which a
    fingerprint-only merge would have joined."""
    p = HiggsParams(**SMALL, **ENGINES["host"])
    assert build(p, planted[0]).ingest_stats.key_splits > 0


def test_host_and_vector_engines_stay_bit_identical(planted):
    a = build(HiggsParams(**SMALL, **ENGINES["host"]), planted[0])
    b = build(HiggsParams(**SMALL, **ENGINES["vector"]), planted[0])
    assert len(a.pools) == len(b.pools)
    for pa, pb in zip(a.pools, b.pools):
        assert pa.n == pb.n
        for name in NodeState._fields:
            np.testing.assert_array_equal(pa.arrs[name][:pa.n],
                                          pb.arrs[name][:pb.n], err_msg=name)
    da, db = a.ob.data, b.ob.data
    assert set(da) == set(db)
    for k in da:
        for f in da[k]:
            np.testing.assert_array_equal(da[k][f], db[k][f])


# ---------------------------------------------------------------------------
# the merge rule in each of the five placement implementations
# ---------------------------------------------------------------------------
#
# d = 4, b = 2, r = 2.  Bucket (0, 2) is full of other keys; bucket
# (0, 3) holds a twin's entry, stored at its own round 0.  Item A has the
# twin's fingerprints and timestamp but reaches (0, 3) at round 1 (row
# position 0, column position 1): another key, so it takes the free slot
# with idx 1.  Item B reaches (0, 3) at round 0, as the twin did: the
# same key, so it merges.

D, B, R = 4, 2, 2
F_S, F_D, T = 77, 99, 5


def merge_case():
    fp_s = np.full((D, D, B), EMPTY, np.uint32)
    fp_d = np.full((D, D, B), EMPTY, np.uint32)
    w = np.zeros((D, D, B), np.float32)
    t = np.zeros((D, D, B), np.uint32)
    idx = np.zeros((D, D, B), np.uint32)
    fp_s[0, 2], fp_d[0, 2], w[0, 2], t[0, 2] = [1, 2], [3, 4], 1.0, T
    fp_s[0, 3, 0], fp_d[0, 3, 0], w[0, 3, 0], t[0, 3, 0] = F_S, F_D, 5.0, T
    node = NodeState(fp_s, fp_d, w, t, idx)
    items = dict(fs=np.array([F_S, F_S], np.uint32),
                 fd=np.array([F_D, F_D], np.uint32),
                 rows=np.array([[0, 1], [0, 3]], np.int32),
                 cols=np.array([[2, 3], [3, 1]], np.int32),
                 w=np.array([2.0, 4.0], np.float32),
                 t=np.array([T, T], np.uint32),
                 valid=np.array([True, True]))
    return node, items


def _state4(node):
    return np.stack([node.fp_s, node.fp_d, node.t, node.idx]).copy()


def _from4(s4, wmat):
    return NodeState(s4[0], s4[1], np.asarray(wmat), s4[2], s4[3])


def place_vector(node, it):
    out, _ = cmatrix.place_entries(
        NodeState(*map(jnp.asarray, node)), it["fs"], it["fd"],
        jnp.asarray(it["rows"]), jnp.asarray(it["cols"]), it["w"], it["t"],
        jnp.asarray(it["valid"]), d=D, b=B, r=R, match_time=True)
    return out


def place_pre(node, it):
    orders = cmatrix.host_round_orders(it["rows"], it["cols"], D, R)
    s4, wmat, _ = cmatrix.place_entries_pre(
        jnp.asarray(_state4(node)), jnp.asarray(node.w), it["fs"], it["fd"],
        it["rows"], it["cols"], it["w"], it["t"], jnp.asarray(it["valid"]),
        jnp.asarray(orders), d=D, b=B, r=R, match_time=True)
    return _from4(np.asarray(s4), wmat)


def place_host(node, it, stats=None):
    orders = cmatrix.host_round_orders(it["rows"], it["cols"], D, R)
    s4, wmat, _ = cmatrix.place_entries_host(
        _state4(node), node.w.copy(), it["fs"], it["fd"], it["rows"],
        it["cols"], it["w"], it["t"], it["valid"], orders, d=D, b=B, r=R,
        match_time=True, stats=stats)
    return _from4(s4, wmat)


def place_pallas(node, it):
    out, _ = ops.leaf_insert(
        NodeState(*map(jnp.asarray, node)), *(jnp.asarray(it[k]) for k in (
            "fs", "fd", "rows", "cols", "w", "t", "valid")),
        r=R, interpret=True)
    return out


def place_seq(node, it):
    out, _ = ref.seq_insert_ref(node, it["fs"], it["fd"], it["rows"],
                                it["cols"], it["w"], it["t"], it["valid"],
                                b=B, r=R)
    return out


PLACERS = {"place_entries": place_vector, "place_entries_pre": place_pre,
           "place_entries_host": place_host, "pallas_leaf_insert":
           place_pallas, "seq_insert_ref": place_seq}


@pytest.mark.parametrize("name", PLACERS)
def test_merge_reads_the_chain_index(name):
    node, items = merge_case()
    out = NodeState(*(np.asarray(a) for a in PLACERS[name](node, items)))
    # the twin's entry took B's weight alone; A sits beside it with idx 1
    np.testing.assert_array_equal(out.w[0, 3], [5.0 + 4.0, 2.0])
    np.testing.assert_array_equal(out.idx[0, 3], [0, 1])
    np.testing.assert_array_equal(out.fp_s[0, 3], [F_S, F_S])
    np.testing.assert_array_equal(out.fp_d[0, 3], [F_D, F_D])
    # nothing else moved
    np.testing.assert_array_equal(out.w[0, 2], [1.0, 1.0])
    assert out.w.sum() == 1.0 + 1.0 + 5.0 + 4.0 + 2.0


PROBERS = {
    "cmatrix": (cmatrix.probe_edge, cmatrix.probe_vertex),
    "pallas": (functools.partial(ops.edge_probe, interpret=True),
               functools.partial(ops.vertex_probe, interpret=True)),
}


@pytest.mark.parametrize("name", PROBERS)
def test_probes_read_the_chain_index(name):
    """After the merge case, A's and B's keys share both fingerprints
    and bucket (0, 3); each query counts the entry stored at the chain
    position it met it at.  Fingerprint-only probes read 11 for A's
    edge."""
    node, it = merge_case()
    nodes = NodeState(*(jnp.asarray(a)[None] for a in place_seq(node, it)))
    edge, vertex = PROBERS[name]
    mask = jnp.ones(1, bool)
    rows, cols = jnp.asarray(it["rows"]), jnp.asarray(it["cols"])
    fs, fd = jnp.asarray(it["fs"]), jnp.asarray(it["fd"])
    got = [edge(nodes, mask, fs, fd, rows, cols, 0, 10, match_time=True),
           # both entries lie at row position 0: one source key
           vertex(nodes, mask, fs, rows, 0, 10, direction="out",
                  match_time=True),
           vertex(nodes, mask, fd, cols, 0, 10, direction="in",
                  match_time=True)]
    for g, want in zip(got, ([2.0, 9.0], [11.0, 11.0], [2.0, 9.0])):
        np.testing.assert_array_equal(np.asarray(g), want)


def test_host_placement_counts_the_key_split():
    node, items = merge_case()
    stats = IngestStats()
    place_host(node, items, stats)
    assert stats.key_splits == 1          # A; B merged into its own key


# ---------------------------------------------------------------------------
# key_splits over a whole leaf: planted collisions, counted exactly
# ---------------------------------------------------------------------------

LEAF = dict(d1=16, F1=19, b=2, r=2, theta=4)


def leaf_collisions(p: HiggsParams, n_planted: int):
    """``n_planted`` triples (u, its twin v, receiver x) in disjoint
    buckets of one leaf.  u sends x six edges at t = 1..6; v sends x one
    edge at t = 6 and takes bucket (v's row 0 = u's row 1, x's column 0)
    at its round 0.  u's edges fill its first two buckets, so its t = 6
    edge reaches v's bucket at round 2 and meets v's entry there: one
    key split per triple."""
    src, dst, t = [], [], []
    trip = []
    for k in range(n_planted):
        base_u, col_x = (3, 7, 9)[k], (0, 2, 4)[k]   # disjoint columns
        u = id_with(1000 + k, base_u, 5, p, "s")
        v = id_with(1000 + k, (5 * base_u + 1) % p.d1, 6, p, "s")
        x = id_with(2000 + k, col_x, 7, p, "d")
        trip.append((u, v, x))
    for ts in range(1, 7):
        for u, _, x in trip:
            src.append(u)
            dst.append(x)
            t.append(ts)
    for _, v, x in trip:
        src.append(v)
        dst.append(x)
        t.append(6)
    n = len(src)
    stream = (np.array(src, np.uint32), np.array(dst, np.uint32),
              np.ones(n, np.float32), np.array(t, np.uint32))
    return stream, trip


@pytest.mark.parametrize("n_planted", [1, 2, 3])
def test_key_splits_count_planted_collisions(n_planted):
    p = HiggsParams(**LEAF, insert_backend="host", pool_storage="host")
    stream, trip = leaf_collisions(p, n_planted)
    sk = build(p, stream)
    assert sk.pools[0].total == 1
    assert sk.ingest_stats.key_splits == n_planted
    u, v, x = (np.array(c, np.uint32) for c in zip(*trip))
    out = sk.query([VertexQuery(u, 0, 6, "out"), VertexQuery(v, 0, 6, "out"),
                    EdgeQuery(v, x, 0, 6), VertexQuery(x, 0, 6, "in")])
    for got, want in zip(out.values, (6, 1, 1, 7)):
        np.testing.assert_array_equal(got, np.full(n_planted, want))


def test_device_leaf_placement_counts_no_key_split():
    """The vector engine places leaves on the device, which counts
    nothing: no device program gains an output."""
    p = HiggsParams(**LEAF, insert_backend="vector", pool_storage="host")
    stream, _ = leaf_collisions(p, 3)
    assert build(p, stream).ingest_stats.key_splits == 0


# ---------------------------------------------------------------------------
# the witness: a hot sender and an edgeless fingerprint twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["host", "vector"])
def test_edgeless_twin_answers_zero(engine):
    """u = 12,345 sends 20,000 edges; v = 4,227,136,713 sends none.  Same
    19-bit fingerprint, another base row (1), and v's last candidate row
    is u's base row (12).  A summary that ignores the chain index answers
    v 624 here."""
    p = HiggsParams(**ENGINES[engine])
    u, v, n = 12345, 4227136713, 20000
    fu, fv = (hashing.np_mix32(np.array([x], np.uint32), p.seed)[0]
              for x in (u, v))
    assert fu & p.fp_mask == fv & p.fp_mask
    assert (fu >> p.F1) % p.d1 in chain(int(fv >> p.F1) % p.d1, p)
    assert keys([u], p, "s") != keys([v], p, "s")
    stream = (np.full(n, u, np.uint32), np.arange(1, n + 1, dtype=np.uint32),
              np.ones(n, np.float32), np.arange(n, dtype=np.uint32))
    sk = build(p, stream, batch=4096)
    got = sk.query([VertexQuery(np.array([u, v], np.uint32), 0, n,
                                "out")]).values[0]
    np.testing.assert_array_equal(got, [n, 0])
