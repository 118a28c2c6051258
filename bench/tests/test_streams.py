"""The chunked streams keep the shapes of the program's generators."""
import numpy as np
import pytest
import streams

from repro.stream.generator import lkml_like_stream, wiki_talk_like_stream

N = 200_000
LKML = {"kind": "lkml", "edges": 1096440, "users": 64496, "zipf": 1.8,
        "reply_share": 0.6, "reply_window": 50, "time_span": 1 << 27}
WIKI = {"kind": "wiki_talk", "edges": 7833140, "users": 979142, "zipf": 2.2,
        "time_span": 1 << 29}


def top_share(ids, k=10):
    _, counts = np.unique(ids, return_counts=True)
    return np.sort(counts)[::-1][:k].sum() / len(ids)


def reply_share(src, dst, window=50):
    """Share of edges whose receiver is one of the preceding senders."""
    hit = np.zeros(len(src), bool)
    for s in range(1, window):
        hit[s:] |= dst[s:] == src[:-s]
    return hit.mean()


@pytest.mark.parametrize("shape,gen,n_users", [
    (LKML, lkml_like_stream, 1096440), (WIKI, wiki_talk_like_stream, 7833140)])
def test_degree_skew_matches_generator(shape, gen, n_users):
    src, dst, w, t = streams.EdgeStream(shape, seed=5).arrays(N)
    # the generator at the published size has the same user count
    ref = gen(n_edges=n_users, seed=5)
    for ours, theirs in ((src, ref[0][:N]), (dst, ref[1][:N])):
        assert top_share(ours) == pytest.approx(top_share(theirs), rel=0.05)
    assert np.all(w == 1.0)


def test_reply_share_matches_generator():
    src, dst, _, _ = streams.EdgeStream(LKML, seed=5).arrays(N)
    ref = lkml_like_stream(n_edges=1096440, seed=5)
    want = reply_share(ref[0][:N], ref[1][:N])
    assert reply_share(src, dst) == pytest.approx(want, abs=0.01)
    assert reply_share(src, dst) > 0.55


def test_replies_cross_chunk_boundaries():
    src, dst, _, _ = streams.EdgeStream(LKML, seed=9).arrays(
        streams.CHUNK + 1000)
    head = slice(streams.CHUNK, streams.CHUNK + 50)
    assert reply_share(src[streams.CHUNK - 50:streams.CHUNK + 50],
                       dst[streams.CHUNK - 50:streams.CHUNK + 50]) > 0.5
    assert np.any(np.isin(dst[head], src[streams.CHUNK - 49:streams.CHUNK]))


@pytest.mark.parametrize("shape,gen,n_pub", [
    (LKML, lkml_like_stream, 1096440), (WIKI, wiki_talk_like_stream, 7833140)])
def test_mean_gap_matches_generator(shape, gen, n_pub):
    _, _, _, t = streams.EdgeStream(shape, seed=2).arrays(N)
    assert np.all(np.diff(t.astype(np.int64)) >= 0)
    ref_t = gen(n_edges=n_pub, seed=2)[3]
    want = (int(ref_t[-1]) - int(ref_t[0])) / (n_pub - 1)
    got = (int(t[-1]) - int(t[0])) / (N - 1)
    assert got == pytest.approx(want, rel=0.02)
    # equal-timestamp runs occur at the rate a sorted uniform draw has
    assert np.mean(np.diff(t) == 0) == pytest.approx(
        np.mean(np.diff(ref_t[:N]) == 0), abs=0.01)


def test_same_seed_same_edges_however_cut():
    a = streams.EdgeStream(WIKI, seed=2**31 + 12345)
    b = streams.EdgeStream(WIKI, seed=2**31 + 12345)
    b.ensure(3 * streams.CHUNK)
    for x, y in zip(a.arrays(2 * streams.CHUNK + 7),
                    b.arrays(2 * streams.CHUNK + 7)):
        np.testing.assert_array_equal(x, y)
    c = streams.EdgeStream(WIKI, seed=2**31 + 12346).arrays(1000)
    assert not np.array_equal(c[0], a.arrays(1000)[0])
