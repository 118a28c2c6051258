"""Pure-jnp / numpy oracles for the Pallas kernels.

* ``seq_insert_ref``: the paper's Algorithm 1, verbatim sequential
  semantics (per-edge probe of the r x r mapping buckets in lex order,
  merge on an (fp_s, fp_d, t) match stored at this probe's chain index
  k, first empty slot, spill on full).  The
  ``leaf_insert`` kernel must match this bit-for-bit.
* ``edge_probe_ref`` / ``vertex_probe_ref``: the batched probe reference —
  thin wrappers over :mod:`repro.core.cmatrix`.
"""
from __future__ import annotations

import numpy as np

from repro.core import cmatrix
from repro.core.cmatrix import EMPTY, NodeState


def seq_insert_ref(node: NodeState, fs, fd, rows, cols, w, t, valid,
                   *, b: int, r: int):
    """Sequential Alg. 1 on host numpy.  Returns (node', spill mask)."""
    fps = np.array(node.fp_s, np.uint32)
    fpd = np.array(node.fp_d, np.uint32)
    wm = np.array(node.w, np.float32)
    tm = np.array(node.t, np.uint32)
    idxm = np.array(node.idx, np.uint32)
    fs, fd = np.asarray(fs, np.uint32), np.asarray(fd, np.uint32)
    rows, cols = np.asarray(rows), np.asarray(cols)
    w, t = np.asarray(w, np.float32), np.asarray(t, np.uint32)
    valid = np.asarray(valid, bool)
    n = len(fs)
    spill = np.zeros(n, bool)
    for e in range(n):
        if not valid[e]:
            continue
        done = False
        for k in range(r * r):
            i, j = k // r, k % r
            row, col = int(rows[e, i]), int(cols[e, j])
            bucket_fs = fps[row, col]
            match = ((bucket_fs == fs[e]) & (fpd[row, col] == fd[e]) &
                     (tm[row, col] == t[e]) & (idxm[row, col] == k) &
                     (bucket_fs != EMPTY))
            hit = np.nonzero(match)[0]
            if hit.size:
                wm[row, col, hit[0]] += w[e]
                done = True
                break
            free = np.nonzero(bucket_fs == EMPTY)[0]
            if free.size:
                s = free[0]
                fps[row, col, s] = fs[e]
                fpd[row, col, s] = fd[e]
                wm[row, col, s] = w[e]
                tm[row, col, s] = t[e]
                idxm[row, col, s] = k
                done = True
                break
        if not done:
            spill[e] = True
    return NodeState(fps, fpd, wm, tm, idxm), spill


def edge_probe_ref(nodes: NodeState, node_mask, fs, fd, rows, cols, ts, te,
                   match_time: bool):
    return cmatrix.probe_edge(nodes, node_mask, fs, fd, rows, cols, ts, te,
                              match_time=match_time)


def vertex_probe_ref(nodes: NodeState, node_mask, fv, rows, ts, te,
                     direction: str, match_time: bool):
    return cmatrix.probe_vertex(nodes, node_mask, fv, rows, ts, te,
                                direction=direction, match_time=match_time)
