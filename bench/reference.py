"""Plain reference for the benchmark's correctness check.

It computes every answer of a HIGGS summary from the raw stream alone,
and shares no code and no state with the program:

* **Keys.**  A summary keeps a vertex as the ``F1 + log2(d1)`` low bits
  of a 32-bit hash of its id: an ``F1``-bit fingerprint, and a base row
  of ``d1`` from which a linear-congruential chain gives ``r`` candidate
  rows.  An entry stores its fingerprint and the chain index of the row
  it was placed in, so the base row, and with it the whole key, can be
  told apart at every level (HIGGS, arXiv:2412.15516).  Ids
  with the same key are one vertex to the summary, and nothing else is:
  every answer is the exact sum over the graph with each id replaced by
  its key (``key``).  ``Twins`` finds the ids that share a query's
  fingerprint and a candidate row but not its key: a summary that
  matched on the fingerprint alone would add their weight.
* **Visibility.**  Items reach queries only when their leaf closes.  A
  leaf takes ``chunk_size`` items and never splits a run of equal
  timestamps; ``LeafScan`` replays that rule over the same insert calls
  the writer made, so it knows for every cursor which prefix is closed.
* **Retention.**  Under a ``window`` policy, closed leaves are sealed in
  segments of ``theta ** segment_levels`` leaves, and a segment whose
  newest timestamp lies more than ``t_horizon`` behind the newest closed
  leaf is evicted whole; ``LeafScan`` tracks the first retained item.
* **Sums.**  ``ExactIndex`` sums weights over the items of an index
  range whose (source, destination), source or destination keys match;
  timestamps never decrease, so a time range is an index range too.
"""
from __future__ import annotations

import numpy as np

_MIX1 = np.uint32(0x7FEB352D)
_MIX2 = np.uint32(0x846CA68B)
DST_SALT = 0x5BD1E995


def mix32(x: np.ndarray, seed: int) -> np.ndarray:
    x = np.asarray(x, np.uint32) ^ np.uint32(seed & 0xFFFFFFFF)
    x = x ^ (x >> np.uint32(16))
    x = (x * _MIX1).astype(np.uint32)
    x = x ^ (x >> np.uint32(15))
    x = (x * _MIX2).astype(np.uint32)
    return x ^ (x >> np.uint32(16))


def unmix32(h: np.ndarray, seed: int) -> np.ndarray:
    """The id whose ``mix32`` is ``h``: every step of the mix is a
    bijection of 32-bit words."""
    x = np.asarray(h, np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = (x * np.uint32(pow(int(_MIX2), -1, 1 << 32))).astype(np.uint32)
    x = x ^ (x >> np.uint32(15)) ^ (x >> np.uint32(30))
    x = (x * np.uint32(pow(int(_MIX1), -1, 1 << 32))).astype(np.uint32)
    x = x ^ (x >> np.uint32(16))
    return x ^ np.uint32(seed & 0xFFFFFFFF)


def side_seed(sketch: dict, side: str) -> int:
    return int(sketch["seed"]) ^ (DST_SALT if side == "d" else 0)


def key(ids, sketch: dict, side: str) -> np.ndarray:
    """The vertex a summary keeps for each id: fingerprint and base
    row, ``F1 + log2(d1)`` hash bits."""
    h = mix32(ids, side_seed(sketch, side))
    f = int(sketch["F1"])
    base = (h >> np.uint32(f)) % np.uint32(int(sketch["d1"]))
    return (h & np.uint32((1 << f) - 1)) | (base << np.uint32(f))


LCG_A, LCG_C = 5, 1


def coords(ids, sketch: dict, side: str):
    """(fingerprint, bitmask of candidate rows) of ids."""
    h = mix32(ids, side_seed(sketch, side))
    f1 = h & np.uint32((1 << int(sketch["F1"])) - 1)
    d1 = int(sketch["d1"])
    x = ((h >> np.uint32(int(sketch["F1"]))) % np.uint32(d1)).astype(
        np.int64)
    rows = np.zeros(len(f1), np.int64)
    for _ in range(int(sketch["r"])):
        rows |= np.int64(1) << x
        x = (x * LCG_A + LCG_C) % d1
    return f1, rows


class Twins:
    """The ids of one side of the stream that share a query's ``F1``-bit
    fingerprint and a candidate row (its own key included)."""

    def __init__(self, ids, sketch: dict, side: str):
        self.sketch, self.side = sketch, side
        ids = np.unique(np.asarray(ids, np.uint32))
        f1, rows = coords(ids, sketch, side)
        order = np.argsort(f1, kind="stable")
        self.ids, self.f1, self.rows = ids[order], f1[order], rows[order]

    def pairs(self, q_ids):
        """(query index, twin id) for every twin of every query id."""
        f1, rows = coords(np.asarray(q_ids, np.uint32), self.sketch,
                          self.side)
        a = np.searchsorted(self.f1, f1, "left")
        b = np.searchsorted(self.f1, f1, "right")
        n = b - a
        qi = np.repeat(np.arange(len(f1)), n)
        pos = a[qi] + np.arange(len(qi)) - np.repeat(np.cumsum(n) - n, n)
        keep = (self.rows[pos] & rows[qi]) != 0
        return qi[keep], self.ids[pos[keep]]


class LeafScan:
    """Leaf closing and window retention, replayed insert by insert."""

    def __init__(self, t: np.ndarray, sketch: dict, retention: dict):
        self.t = t
        self.chunk = int(sketch["b"] * sketch["d1"] ** 2
                         * sketch["chunk_fill"])
        self.seg_leaves = int(sketch["theta"]) ** int(
            sketch.get("segment_levels", 2))
        self.horizon = (int(retention["t_horizon"])
                        if retention.get("kind") == "window" else None)
        self.pos = 0              # end of the closed prefix
        self.leaf_ends: list[int] = []
        self.n_sealed = 0         # segments sealed so far
        self.n_evicted = 0        # segments evicted so far
        self.lo = 0               # first retained item

    def advance(self, cursor: int) -> tuple[int, int]:
        """The writer has inserted the first ``cursor`` items: close
        every leaf that can be closed, apply retention, and return the
        visible item range ``[lo, hi)``."""
        t, cs = self.t, self.chunk
        while cursor - self.pos >= cs:
            rem = cursor - self.pos
            take = cs
            if take < rem and t[self.pos + take] == t[self.pos + take - 1]:
                tb = t[self.pos + take - 1]
                tail = t[self.pos:cursor]
                run_end = int(np.searchsorted(tail, tb, "right"))
                run_start = int(np.searchsorted(tail, tb, "left"))
                take = run_end if run_start == 0 else run_start
            if take == rem:
                break              # the last run may not have ended yet
            self.pos += take
            self.leaf_ends.append(self.pos)
        if self.horizon is not None:
            self._retain()
        return self.lo, self.pos

    def _retain(self) -> None:
        seg = self.seg_leaves
        while len(self.leaf_ends) >= (self.n_sealed + 1) * seg:
            self.n_sealed += 1
        if not self.leaf_ends:
            return
        t_last = int(self.t[self.leaf_ends[-1] - 1])
        while self.n_evicted < self.n_sealed:
            end = self.leaf_ends[(self.n_evicted + 1) * seg - 1]
            if int(self.t[end - 1]) >= t_last - self.horizon:
                break
            self.n_evicted += 1
            self.lo = end


class ExactIndex:
    """Exact weight sums over keyed edges in an index range."""

    def __init__(self, ks, kd, w):
        n = len(ks)
        self._w = np.asarray(w, np.float64)
        self._tables = {}
        for name, k in (("edge", (ks << np.uint64(32)) | kd),
                        ("out", ks), ("in", kd)):
            uniq, rank = np.unique(k, return_inverse=True)
            comp = rank.astype(np.uint64) << np.uint64(32) | np.arange(
                n, dtype=np.uint64)
            order = np.argsort(comp, kind="stable")
            cum = np.concatenate([[0.0], np.cumsum(self._w[order])])
            self._tables[name] = (uniq, comp[order], cum)

    def sums(self, name: str, k: np.ndarray, lo, hi) -> np.ndarray:
        """Per key ``k[i]``: the weight of items in ``[lo[i], hi[i])``."""
        uniq, comp, cum = self._tables[name]
        k = np.asarray(k, np.uint64)
        r = np.searchsorted(uniq, k)
        found = (r < len(uniq)) & (uniq[np.minimum(r, len(uniq) - 1)] == k)
        base = r.astype(np.uint64) << np.uint64(32)
        lo = np.broadcast_to(np.asarray(lo, np.int64), k.shape)
        hi = np.broadcast_to(np.asarray(hi, np.int64), k.shape)
        hi = np.maximum(hi, lo)
        a = np.searchsorted(comp, base | lo.astype(np.uint64))
        b = np.searchsorted(comp, base | hi.astype(np.uint64))
        return np.where(found, cum[b] - cum[a], 0.0)


class Reference:
    """The answers to typed query batches (as plain tuples, see
    ``traffic``) over the stream prefix an epoch covers."""

    def __init__(self, stream_arrays, sketch: dict, retention: dict):
        src, dst, w, t = (np.asarray(a) for a in stream_arrays)
        self.t, self.sketch = t, sketch
        self.index = ExactIndex(key(src, sketch, "s").astype(np.uint64),
                                key(dst, sketch, "d").astype(np.uint64), w)
        self.scan = LeafScan(t, sketch, retention)

    def _k(self, ids, side: str) -> np.ndarray:
        return key(np.asarray(ids, np.uint32), self.sketch,
                   side).astype(np.uint64)

    def answer(self, batch, lo: int, hi: int) -> list:
        """The exact answer to every query of one batch of ``(kind,
        ids, ts, te)`` queries over the visible items ``[lo, hi)``."""
        out = []
        t = self.t[:hi]
        for kind, ids, ts, te in batch:
            a = max(lo, int(np.searchsorted(t, np.uint32(ts), "left")))
            b = max(a, min(hi, int(np.searchsorted(t, np.uint32(te),
                                                   "right"))))
            if kind in ("out", "in"):
                side = "s" if kind == "out" else "d"
                out.append(self.index.sums(kind, self._k(ids, side), a, b))
                continue
            pair = (self._k(ids[0], "s") << np.uint64(32)) | self._k(
                ids[1], "d")
            sums = self.index.sums("edge", pair, a, b)
            out.append(sums if kind == "edge" else float(sums.sum()))
        return out
