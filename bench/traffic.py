"""Traffic mixes: one general generator driven by a data file each.

A mix (``bench/traffic/<name>.json``) sets

* ``writer``: ``{"mode": "closed", "batch": 8192}`` inserts batch after
  batch as fast as the summary takes them (no mix has callers yet);
* ``check``: the final state is asked ``batches`` batches after the
  window (``check_batches``), each as ``query`` says.

A query batch holds edge, vertex-out, vertex-in, path and subgraph
queries, as ``chip_smoke.py`` asks them, over each of the time ranges
``ranges`` (shares of the span, ending at the newest arrived edge's
timestamp; the span is the retention horizon where the configuration
has one, else the whole history).  Each edge and vertex query asks
``items`` edges or vertices drawn from the ``recent`` most recently
arrived edges (a subgraph half as many), and besides ``bait`` vertex ids
and edges that never occur in the stream, made from stream ids by the
reference's hashing (``bait_ids``): they share their stream ids' whole
key at ``F1 - 1`` fingerprint bits, so a summary with one fingerprint
bit fewer than the configuration states adds the stream id's weight.

A batch is a list of plain queries ``(kind, ids, ts, te)``, with
``kind`` one of ``edge``, ``out``, ``in``, ``path``, ``subgraph`` and
``ids`` a vertex array or a (src, dst) pair of arrays.
"""
from __future__ import annotations

import numpy as np
from reference import Twins, mix32, side_seed, unmix32

KINDS = ("edge", "out", "in", "path", "subgraph")


def bait_ids(u, sketch: dict, side: str, stream: Twins, rng,
             tries: int = 64) -> np.ndarray:
    """For each stream id ``u[i]``, an id that has ``u[i]``'s whole key
    one fingerprint bit below the configuration and another key at it.

    Its hash keeps ``u[i]``'s low ``F1 + log2(d1) - 1`` bits, flips the
    next one, the top bit of the base row at ``F1`` bits, and draws the
    bits above at random.  At ``F1 - 1`` fingerprint bits the key is the
    low ``F1 + log2(d1) - 1`` bits, so the bait and ``u[i]`` are one
    vertex there, probes that read the chain index included: a summary
    with one fingerprint bit fewer adds ``u[i]``'s weight.  At ``F1``
    bits the bait has ``u[i]``'s fingerprint and the base row ``d1 / 2``
    away, whose candidate rows the linear-congruential chain keeps
    disjoint from ``u[i]``'s.  A draw that shares a fingerprint and a
    candidate row with an id of ``stream`` (of which ``u[i]`` is one) is
    refused, so a sound summary answers it exactly, by the fingerprint
    alone as well.
    0xFFFFFFFF where no such id was found in ``tries`` draws."""
    u = np.asarray(u, np.uint32)
    f, d1 = int(sketch["F1"]), int(sketch["d1"])
    if d1 & (d1 - 1):
        raise ValueError(f"bait ids need a power-of-two d1, not {d1}")
    top = f + d1.bit_length() - 2          # the base row's top bit at F1
    hu = mix32(u, side_seed(sketch, side))
    low = hu & np.uint32((1 << top) - 1)
    flip = ((hu >> np.uint32(top)) & np.uint32(1)) ^ np.uint32(1)
    high = rng.integers(0, 1 << (31 - top), (len(u), tries),
                        dtype=np.uint64).astype(np.uint32)
    h = (low[:, None] | (flip[:, None] << np.uint32(top))
         | (high << np.uint32(top + 1)))
    ids = unmix32(h, side_seed(sketch, side)).ravel()
    qi, _ = stream.pairs(ids)
    twin = np.zeros(len(ids), bool)
    twin[qi] = True
    ok = ~twin.reshape(len(u), tries)
    first = np.argmax(ok, axis=1)
    found = ok[np.arange(len(u)), first]
    return np.where(found, ids.reshape(len(u), tries)[np.arange(len(u)),
                                                      first],
                    np.uint32(0xFFFFFFFF))


class Baits:
    """``bait_ids`` against the ids of a stream prefix, per side."""

    def __init__(self, arrays, arrived: int, sketch: dict):
        self.sketch = sketch
        self.stream = {"s": Twins(arrays[0][:arrived], sketch, "s"),
                       "d": Twins(arrays[1][:arrived], sketch, "d")}

    def ids(self, u, side: str, rng) -> np.ndarray:
        return bait_ids(u, self.sketch, side, self.stream[side], rng)


def make_batch(arrays, arrived: int, t_first: int, span: int | None,
               q: dict, rng, baits: Baits | None = None) -> list:
    """One batch when the first ``arrived`` edges have arrived."""
    src, dst, _, t = arrays
    n = int(q["items"])
    lo = max(0, arrived - int(q["recent"]))
    e = rng.integers(lo, arrived, n)
    path = [int(src[e[0]]), int(dst[e[0]])]
    rs, rd = src[lo:arrived], dst[lo:arrived]
    for _ in range(3):
        nxt = np.flatnonzero(rs == path[-1])
        if not len(nxt):
            break
        path.append(int(rd[nxt[0]]))
    path = np.asarray(path, np.uint32)
    sub = e[: n // 2]
    k = int(q.get("bait", 0)) if baits is not None else 0
    v_out, v_in = [src[e]], [dst[e]]
    e_src, e_dst = [src[e]], [dst[e]]
    if k:
        # a stream id behind each bait, drawn from the recent edges
        j = rng.integers(lo, arrived, k)
        v_out.append(baits.ids(src[j], "s", rng))
        v_in.append(baits.ids(dst[j], "d", rng))
        bs, bd = baits.ids(src[j], "s", rng), baits.ids(dst[j], "d", rng)
        keep = (bs != 0xFFFFFFFF) & (bd != 0xFFFFFFFF)
        e_src.append(bs[keep])
        e_dst.append(bd[keep])
    v_out, v_in = (np.concatenate(v) for v in (v_out, v_in))
    v_out = v_out[v_out != 0xFFFFFFFF]
    v_in = v_in[v_in != 0xFFFFFFFF]
    e_src, e_dst = np.concatenate(e_src), np.concatenate(e_dst)
    te = int(t[arrived - 1])
    whole = te - t_first if span is None else span
    batch = []
    for frac in q["ranges"]:
        ts = max(0, te - int(round(whole * float(frac))))
        batch += [("edge", (e_src, e_dst), ts, te),
                  ("out", v_out, ts, te),
                  ("in", v_in, ts, te),
                  ("path", (path[:-1], path[1:]), ts, te),
                  ("subgraph", (src[sub], dst[sub]), ts, te)]
    return batch


def check_batches(arrays, arrived: int, t_first: int, span, check: dict,
                  sketch: dict, seed: int) -> list:
    """The batches asked of the final state."""
    rng = np.random.default_rng([seed, 3])
    q = check["query"]
    baits = Baits(arrays, arrived, sketch) if q.get("bait") else None
    return [make_batch(arrays, arrived, t_first, span, q, rng, baits)
            for _ in range(int(check["batches"]))]
