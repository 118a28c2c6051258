"""The plain reference agrees with the program where the program is
known to be right, its leaf and retention replay matches the program's
own bookkeeping, and its hashing is the program's."""
import loader
import numpy as np
import pytest
import streams
import traffic
from check import compare
from reference import ExactIndex, Reference, Twins, coords, key, mix32, unmix32

from repro.api import make_summary
from repro.core.oracle import ExactOracle

SKETCH = {"d1": 16, "F1": 19, "b": 3, "r": 4, "theta": 4, "chunk_fill": 0.85,
          "seed": 2654435769, "segment_levels": 2}
WIKI = {"kind": "wiki_talk", "edges": 7833140, "users": 979142, "zipf": 2.2,
        "time_span": 1 << 29}
LKML = {"kind": "lkml", "edges": 1096440, "users": 64496, "zipf": 1.8,
        "reply_share": 0.6, "reply_window": 50, "time_span": 1 << 27}
QUERY = {"items": 16, "recent": 20000, "ranges": [0.01, 0.5, 1.0]}


def build(shape, n, retention, batch=3000, seed=4, **kw):
    arrays = streams.EdgeStream(shape, seed).arrays(n)
    params = {k: v for k, v in SKETCH.items()}
    params.update(kw)
    sk = make_summary("higgs", retention=retention, **params)
    log = []
    for c in range(0, n, batch):
        sk.insert(*(a[c:min(c + batch, n)] for a in arrays))
        log.append((min(c + batch, n), int(sk.structure_version)))
    return sk, arrays, log


def ask(sk, arrays, n, span, seed=1, count=6):
    from run import to_queries
    rng = np.random.default_rng(seed)
    plain = [traffic.make_batch(arrays, n, int(arrays[3][0]), span, QUERY,
                                rng) for _ in range(count)]
    return [(p, sk.query(to_queries(p)), 0) for p in plain]


@pytest.mark.parametrize("shape", [LKML, WIKI])
def test_reference_matches_the_host_engine(shape):
    n = 40_000
    sk, arrays, log = build(shape, n, {"kind": "none"})
    asked = ask(sk, arrays, n, None)
    ref = Reference(arrays, SKETCH, {"kind": "none"})
    verdict, his = compare(ref, log, asked)
    assert verdict["correct"], verdict
    assert verdict["values_compared"] > 500
    # the closed prefix is what the program holds in closed leaves
    assert his[-1] == sk.n_items - sk._buf_len
    assert len(ref.scan.leaf_ends) == len(sk.leaf_starts)


def test_reference_replays_window_retention():
    n, horizon_edges = 60_000, 16384
    t_h = int(horizon_edges * (1 << 29) / 7833140)
    ret = {"kind": "window", "t_horizon": t_h}
    sk, arrays, log = build(WIKI, n, ret, batch=2048)
    ref = Reference(arrays, SKETCH, ret)
    asked = ask(sk, arrays, n, t_h)
    verdict, his = compare(ref, log, asked)
    assert verdict["correct"], verdict
    st = sk.retention_stats()
    assert st["segments_evicted"] > 0
    assert ref.scan.n_evicted == st["segments_evicted"]
    assert ref.scan.lo == st["items_evicted"]


def test_eviction_counts_match_the_program_slides(monkeypatch):
    """The node counts the warm-up replays are those at which the
    program slides each level when a segment is evicted."""
    from repro.core import pool
    from run import eviction_counts
    n, batch, horizon_edges = 60_000, 2048, 16384
    t_h = int(horizon_edges * (1 << 29) / 7833140)
    ret = {"kind": "window", "t_horizon": t_h}
    slides = []
    orig = pool._LevelPool.drop_prefix

    def logged(self, k):
        slides.append((self.d, self.n))
        orig(self, k)
    monkeypatch.setattr(pool._LevelPool, "drop_prefix", logged)
    sk, arrays, log = build(WIKI, n, ret, batch=batch)
    cuts = [c for c, _ in log]
    want = {lvl: {m for d, m in slides if d == 16 * 2 ** (lvl - 1)}
            for lvl in (1, 2, 3)}
    cfg = {"sketch": SKETCH, "retention": ret}
    assert eviction_counts(cfg, arrays[3], cuts, 0) == want
    assert all(want.values())


def test_coords_and_keys_match_the_program():
    """Fingerprints, candidate rows and keys as the program computes
    them."""
    from repro.core import cmatrix, hashing
    ids = np.arange(0, 5000, 7, dtype=np.uint32)
    for side, salt in (("s", 0), ("d", 0x5BD1E995)):
        f1, rows = coords(ids, SKETCH, side)
        h = hashing.np_mix32(ids, SKETCH["seed"] ^ salt)
        np.testing.assert_array_equal(f1, h & ((1 << 19) - 1))
        chain = np.asarray(cmatrix.chain_from_base((h >> 19) % 16, 4, 16))
        want = np.bitwise_or.reduce(1 << chain.astype(np.int64), axis=1)
        np.testing.assert_array_equal(rows, want)
        np.testing.assert_array_equal(key(ids, SKETCH, side),
                                      h & ((1 << 23) - 1))


def test_unmix_inverts_the_hash():
    rng = np.random.default_rng(0)
    h = rng.integers(0, 1 << 32, 10_000, dtype=np.uint64).astype(np.uint32)
    for seed in (0, SKETCH["seed"], SKETCH["seed"] ^ 0x5BD1E995):
        np.testing.assert_array_equal(mix32(unmix32(h, seed), seed), h)


def test_reference_is_the_exact_count_over_keys():
    """Where no two ids share a key the reference is the exact count;
    ids that do share one are summed together, and an id outside the
    stream answers the weight of the stream ids with its key."""
    arrays = streams.EdgeStream(WIKI, 3).arrays(30_000)
    oracle = ExactOracle()
    oracle.insert(*arrays)
    ref = Reference(arrays, SKETCH, {"kind": "none"})
    src = arrays[0]
    v = np.unique(src)
    # an id outside the stream with the key of v[0]
    h = mix32(v[:1], SKETCH["seed"])
    alias = unmix32(h ^ np.uint32(1 << 30), SKETCH["seed"])
    assert alias[0] not in src
    fresh = np.arange(1 << 31, (1 << 31) + 300, dtype=np.uint32)
    q = np.concatenate([v, alias, fresh])
    t_end = int(arrays[3][-1])
    (got,) = ref.answer([("out", q, 0, t_end)], 0, len(src))
    want = oracle.vertex_query(v, 0, t_end, "out")
    keys = key(v, SKETCH, "s")
    assert len(np.unique(keys)) == len(keys)       # no key shared here
    m = len(v)
    np.testing.assert_array_equal(got[:m], want)
    assert got[m] == want[0] > 0
    # ids outside the stream answer 0 unless a stream id has their key
    alone = ~np.isin(key(fresh, SKETCH, "s"), keys)
    assert alone.sum() > 290
    np.testing.assert_array_equal(got[m + 1:][alone], 0)
    assert np.all(got[m + 1:][~alone] > 0)


def test_twins_share_a_fingerprint_and_a_row_not_always_a_key():
    ids = np.arange(0, 200_000, dtype=np.uint32)
    tw = Twins(ids, SKETCH, "s")
    qi, u = tw.pairs(ids[:20_000])
    f_q, r_q = coords(ids[:20_000][qi], SKETCH, "s")
    f_u, r_u = coords(u, SKETCH, "s")
    np.testing.assert_array_equal(f_q, f_u)
    assert np.all(r_q & r_u)
    other = key(u, SKETCH, "s") != key(ids[:20_000][qi], SKETCH, "s")
    assert other.any() and (~other).any()


@pytest.mark.parametrize("side", ["s", "d"])
@pytest.mark.parametrize("config", ["wikitalk-window", "lkml"])
def test_bait_ids_share_the_key_one_bit_below(config, side):
    """At ``F1 - 1`` fingerprint bits a bait is its stream id's key; at
    ``F1`` it has another key, the same fingerprint, candidate rows
    disjoint from the stream id's, and no twin in the stream."""
    cfg = loader.load_config(config)
    sketch = cfg["sketch"]
    n = 40_000
    arrays = streams.EdgeStream(cfg["stream"], 8).arrays(n)
    ids = arrays[0 if side == "s" else 1]
    stream = Twins(ids, sketch, side)
    rng = np.random.default_rng(2**31 + 5)
    u = ids[rng.integers(0, n, 2000)]
    b = traffic.bait_ids(u, sketch, side, stream, rng)
    assert np.all(b != 0xFFFFFFFF)
    coarse = dict(sketch, F1=sketch["F1"] - 1)
    np.testing.assert_array_equal(key(b, coarse, side),
                                  key(u, coarse, side))
    assert np.all(key(b, sketch, side) != key(u, sketch, side))
    f_b, rows_b = coords(b, sketch, side)
    f_u, rows_u = coords(u, sketch, side)
    np.testing.assert_array_equal(f_b, f_u)
    assert not np.any(rows_b & rows_u)
    qi, _ = stream.pairs(b)
    assert len(qi) == 0


def test_baits_split_one_fingerprint_bit():
    """A bait answers exactly at the configuration's F1 and takes its
    stream id's whole weight one fingerprint bit below."""
    n = 40_000
    arrays = streams.EdgeStream(WIKI, 6).arrays(n)
    baits = traffic.Baits(arrays, n, SKETCH)
    rng = np.random.default_rng(1)
    u = arrays[0][rng.integers(0, n, 64)]
    b = baits.ids(u, "s", rng)
    assert np.all(b != 0xFFFFFFFF) and not np.isin(b, arrays[0]).any()
    lo = (1 << 22) - 1
    h_u, h_b = mix32(u, SKETCH["seed"]), mix32(b, SKETCH["seed"])
    np.testing.assert_array_equal(h_u & lo, h_b & lo)
    ref = Reference(arrays, SKETCH, {"kind": "none"})
    t_end = int(arrays[3][-1])
    (want,) = ref.answer([("out", b, 0, t_end)], 0, n)
    assert np.all(want == 0)
    from run import to_queries
    got = {}
    for f1 in (19, 18):
        sk, _, _ = build(WIKI, n, {"kind": "none"}, seed=6, F1=f1)
        sk.flush()
        got[f1] = [np.asarray(v) for v in sk.query(to_queries(
            [("out", b, 0, t_end), ("out", u, 0, t_end)])).values]
    np.testing.assert_array_equal(got[19][0], want)
    np.testing.assert_array_equal(got[18][0], got[18][1])
    assert np.all(got[18][0] > 0)


def test_exact_index_sums_ranges():
    ks = np.array([1, 2, 1, 1, 3], np.uint64)
    kd = np.array([5, 5, 6, 5, 5], np.uint64)
    w = np.array([1, 2, 4, 8, 16], np.float32)
    idx = ExactIndex(ks, kd, w)
    assert idx.sums("out", np.array([1], np.uint64), 0, 5)[0] == 13
    assert idx.sums("out", np.array([1], np.uint64), 1, 3)[0] == 4
    assert idx.sums("in", np.array([5, 7], np.uint64), 0, 5).tolist() == [27, 0]
    pair = (np.uint64(1) << np.uint64(32)) | np.uint64(5)
    assert idx.sums("edge", np.array([pair]), 0, 4)[0] == 9


def test_a_stale_epoch_is_caught():
    n = 20_000
    sk, arrays, log = build(LKML, n, {"kind": "none"})
    asked = ask(sk, arrays, n, None, count=2)
    # the batch was submitted after the last insert, yet answered from
    # the epoch before it
    plain, res, _ = asked[0]
    res.epoch = log[-2][1]
    verdict, _ = compare(Reference(arrays, SKETCH, {"kind": "none"}), log,
                         [(plain, res, log[-1][1])])
    assert not verdict["correct"]
    assert verdict["numbers"]["stale_answers"] == 1

