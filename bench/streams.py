"""Endless, chunked edge streams made from a seed.

The two stream shapes of the benchmark's configurations, generated in
chunks so a run can ask for as many edges as its window needs:

* ``lkml``: the KONECT lkml-reply network's shape.  Senders and
  receivers are Zipf(1.8) over a permuted id space of a fixed number of
  users; 60% of the edges reply to one of the 49 senders before them
  (the receiver is that earlier sender), and the reply chain is carried
  across chunk boundaries.
* ``wiki_talk``: SNAP wiki-talk-temporal's shape.  Senders and
  receivers are Zipf(2.2) over a permuted id space of a fixed number of
  users.

Timestamps are a running sum of integer gaps, ``floor(Exp(mean_gap))``,
whose mean is the source's time span over its edge count, so they are
non-decreasing and runs of equal timestamps occur as in a sorted draw of
uniform integers.  Weights are 1.

Everything is drawn with numpy from ``(seed, chunk index)``, so the same
seed gives the same edges however the stream is cut.  The shapes are
those of ``repro.stream.generator`` (``lkml_like_stream``,
``wiki_talk_like_stream``), copied here so that the benchmark's inputs
do not move when the program's generators do.
"""
from __future__ import annotations

import numpy as np

CHUNK = 1 << 17


class EdgeStream:
    """An endless edge stream; ``take(n)`` makes the first ``n`` edges
    and keeps them (the reference reads them back after the window)."""

    def __init__(self, shape: dict, seed: int):
        self.kind = shape["kind"]
        if self.kind not in ("lkml", "wiki_talk"):
            raise ValueError(f"unknown stream kind {self.kind!r}")
        self.n_users = int(shape["users"])
        self.alpha = float(shape["zipf"])
        self.reply_share = float(shape.get("reply_share", 0.0))
        self.reply_window = int(shape.get("reply_window", 50))
        self.mean_gap = float(shape["time_span"]) / float(shape["edges"])
        self.seed = int(seed)
        rng = np.random.default_rng([self.seed, 0])
        ranks = np.arange(1, self.n_users + 1, dtype=np.float64)
        cdf = np.cumsum(ranks ** -self.alpha)
        self._cdf = cdf / cdf[-1]
        self._perm_s = rng.permutation(self.n_users).astype(np.uint32)
        self._perm_d = rng.permutation(self.n_users).astype(np.uint32)
        self._chunks: list[tuple] = []
        self._t_next = 0
        self.n = 0

    def _zipf(self, rng, n, perm):
        r = np.searchsorted(self._cdf, rng.random(n), side="right")
        return perm[np.minimum(r, self.n_users - 1)]

    def _chunk(self):
        i = len(self._chunks)
        rng = np.random.default_rng([self.seed, 1, i])
        src = self._zipf(rng, CHUNK, self._perm_s)
        dst = self._zipf(rng, CHUNK, self._perm_d)
        if self.reply_share > 0:
            reply = rng.random(CHUNK) < self.reply_share
            shift = rng.integers(1, self.reply_window, CHUNK)
            prev = (self._chunks[-1][0][-self.reply_window:] if i
                    else np.zeros(0, np.uint32))
            hist = np.concatenate([prev, src])
            pos = np.arange(CHUNK) + len(prev) - shift
            # the stream's first edges reply to its first edge, as the
            # generator clamps at index 0
            pos = np.maximum(pos, 0)
            dst = np.where(reply, hist[pos], dst)
        gaps = np.floor(rng.exponential(self.mean_gap, CHUNK))
        t = self._t_next + np.cumsum(gaps)
        if t[-1] >= 2 ** 32:
            raise OverflowError("stream timestamps passed 32 bits")
        self._t_next = int(t[-1])
        w = np.ones(CHUNK, np.float32)
        self._chunks.append((src.astype(np.uint32), dst.astype(np.uint32),
                             w, t.astype(np.uint32)))
        self.n += CHUNK

    def ensure(self, n: int) -> None:
        while self.n < n:
            self._chunk()

    def arrays(self, n: int) -> tuple:
        """(src, dst, w, t) of the first ``n`` edges."""
        self.ensure(n)
        k = -(-n // CHUNK)
        cols = [np.concatenate([c[j] for c in self._chunks[:k]])[:n]
                for j in range(4)]
        return tuple(cols)
