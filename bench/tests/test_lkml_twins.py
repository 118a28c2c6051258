"""Lkml's fingerprint twins on the summary the ``lkml`` configuration
states, at 240,000 edges of seed 115.

Sender u = 11,273 sends 17,495 edges, 300 of them to 37,943.  Its twin
41,349 sends one edge, to 37,943: the same 19-bit fingerprint, and a
base row that is u's second candidate row, so another key.  A summary
that merged or probed on the fingerprint alone let the twin's level-5
entry take 200 of u's edges; each answer must be the exact count over
the key, as the plain reference gives it."""
import loader
import numpy as np
import pytest
import streams
from reference import LeafScan, Reference, coords, key
from run import sketch_params

from repro.api import make_summary
from repro.api.queries import EdgeQuery, VertexQuery

N, SEED = 240_000, 115
U, TWIN, X = 11_273, 41_349, 37_943


@pytest.fixture(scope="module")
def lkml():
    cfg = loader.load_config("lkml")
    arrays = streams.EdgeStream(cfg["stream"], SEED).arrays(N)
    summary = make_summary("higgs", **sketch_params(cfg, control=False))
    scan = LeafScan(arrays[3], cfg["sketch"], cfg["retention"])
    step = int(cfg["prefill_batch"])
    for c in range(0, N, step):
        summary.insert(*(a[c:c + step] for a in arrays))
        lo, hi = scan.advance(min(c + step, N))
    return cfg, arrays, summary, (lo, hi)


def test_the_twin_shares_a_fingerprint_and_a_row_not_a_key(lkml):
    cfg, (src, dst, _, _), _, _ = lkml
    sk = cfg["sketch"]
    ids = np.array([U, TWIN], np.uint32)
    f1, rows = coords(ids, sk, "s")
    assert f1[0] == f1[1] and rows[0] & rows[1]
    ku, kt = key(ids, sk, "s")
    assert ku != kt
    assert (src == U).sum() == 17_495
    assert ((src == U) & (dst == X)).sum() == 300
    assert list(dst[src == TWIN]) == [X]


def test_hot_sender_and_twin_answer_the_exact_count(lkml):
    cfg, arrays, summary, (lo, hi) = lkml
    te = int(arrays[3][-1])
    ids = np.array([U, TWIN], np.uint32)
    to_x = np.full(2, X, np.uint32)
    want = Reference(arrays, cfg["sketch"], cfg["retention"]).answer(
        [("out", ids, 0, te), ("edge", (ids, to_x), 0, te)], lo, hi)
    got = summary.query([VertexQuery(ids, 0, te, "out"),
                         EdgeQuery(ids, to_x, 0, te)]).values
    np.testing.assert_array_equal(want[0], [17_486, 1])
    np.testing.assert_array_equal(got[0], [17_486, 1])
    np.testing.assert_array_equal(got[1], want[1])
    assert summary.ingest_stats.key_splits > 0
