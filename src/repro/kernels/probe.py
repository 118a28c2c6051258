"""Pallas TPU kernels: batched edge / vertex probes over stacked matrices.

TPU adaptation (DESIGN.md §3): arbitrary per-query gathers are hostile to
the TPU vector unit, so the probe is reformulated *gather-free* — each
grid step streams one (matrix, row-tile) block through VMEM and, query
by query, sums the weights of every matching entry, restricting
positions with row/column candidate masks built from an iota.  FLOPs go
up by ~d/r on the VPU, HBM traffic is a single stream over the matrix
pool (the actual bottleneck), and the access pattern is fully
sequential.

Layout (what Mosaic accepts): each matrix field is viewed as a
lane-dense ``(d, d*b)`` tile (element ``(row, col*b + slot)``), the
query scalars and the node mask live in SMEM, and the ``(1, q)`` output
row accumulates across the whole grid.

An entry is a query's only where its fingerprint matches and its stored
chain index ``idx`` names the candidate position the query met it at, as
in :func:`repro.core.cmatrix.probe_edge`, so the ``idx`` slab streams in
beside the others.

Grid: (m, d / TR).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cmatrix import NodeState
from repro.kernels.leaf_insert import default_interpret


def _chain_pos(ch_ref, off, j, r, of):
    """Chain position of each row or column ``of`` among query j's
    candidates ``ch_ref[off:off + r, j]`` (-1: not a candidate); a
    repeated candidate keeps its first position, as insertion does."""
    pos = jnp.full(of.shape, -1, jnp.int32)
    for i in reversed(range(r)):
        pos = jnp.where(of == ch_ref[off + i, j], i, pos)
    return pos


def _row_pos(ch_ref, off, j, r, tr, row0):
    """(tr, 1): chain position of each row of this tile."""
    row_of = jax.lax.broadcasted_iota(jnp.int32, (tr, 1), 0) + row0
    return _chain_pos(ch_ref, off, j, r, row_of)


def _col_pos(ch_ref, off, j, r, b, d):
    """(1, d*b): chain position of each lane's column."""
    col_of = jax.lax.broadcasted_iota(jnp.int32, (1, d * b), 1) // b
    return _chain_pos(ch_ref, off, j, r, col_of)


def _probe_grid(mask_ref, tt_ref, out_ref, match_time, mt_ref, hit_of):
    """Shared grid body: zero the output on the first step, then for a
    live node add each query's sum of matching weights to its lane."""
    ti = pl.program_id(1)

    @pl.when((pl.program_id(0) == 0) & (ti == 0))
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(mask_ref[0, 0, 0] != 0)
    def _probe():
        in_range = None
        if match_time:
            mt = mt_ref[0]
            in_range = (mt >= tt_ref[0]) & (mt <= tt_ref[1])
        lane_q = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)

        def body(j, acc):
            hit, mw = hit_of(j, ti)
            if in_range is not None:
                hit &= in_range
            c = jnp.sum(jnp.where(hit, mw, 0.0), axis=(0, 1), keepdims=True)
            return acc + jnp.where(lane_q == j, c, 0.0)

        q = out_ref.shape[1]
        out_ref[...] += jax.lax.fori_loop(
            0, q, body, jnp.zeros(out_ref.shape, jnp.float32))


def _edge_kernel(mask_ref, fp_ref, ch_ref, tt_ref, mfs_ref, mfd_ref, mw_ref,
                 mt_ref, midx_ref, out_ref, *, match_time: bool, tr: int,
                 r: int, b: int):
    def hit_of(j, ti):
        mfs, mfd = mfs_ref[0], mfd_ref[0]
        d = mfs.shape[1] // b
        ri = _row_pos(ch_ref, 0, j, r, tr, ti * tr)
        cj = _col_pos(ch_ref, r, j, r, b, d)
        # the entry is the query's only at the chain position it met it
        hit = ((mfs == fp_ref[0, j]) & (mfd == fp_ref[1, j])
               & (ri >= 0) & (cj >= 0) & (midx_ref[0] == ri * r + cj))
        return hit, mw_ref[0]

    _probe_grid(mask_ref, tt_ref, out_ref, match_time, mt_ref, hit_of)


def _vertex_kernel(mask_ref, fp_ref, ch_ref, tt_ref, mfp_ref, mw_ref, mt_ref,
                   midx_ref, out_ref, *, match_time: bool, tr: int, r: int,
                   b: int, direction: str):
    def hit_of(j, ti):
        mfp, midx = mfp_ref[0], midx_ref[0]
        if direction == "out":
            # candidates restrict the first matrix axis (tiled); the
            # entry's row position is idx // r
            pos = _row_pos(ch_ref, 0, j, r, tr, ti * tr)
            at = (pos >= 0) & (midx >= pos * r) & (midx < pos * r + r)
        else:
            # candidates restrict the second (column) axis (not tiled);
            # the entry's column position is idx % r
            pos = _col_pos(ch_ref, 0, j, r, b, mfp.shape[1] // b)
            at = functools.reduce(jnp.logical_or,
                                  [midx == i * r + pos for i in range(r)])
            at &= pos >= 0
        return (mfp == fp_ref[0, j]) & at, mw_ref[0]

    _probe_grid(mask_ref, tt_ref, out_ref, match_time, mt_ref, hit_of)


def _row_tile(d: int) -> int:
    return min(d, max(8, 512 // max(d // 8, 1)))


def _probe_call(kernel, nodes: NodeState, fields, node_mask, fp, ch, ts, te,
                interpret):
    m, d, _, b = nodes.fp_s.shape
    q = fp.shape[1]
    tr = _row_tile(d)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    mat_spec = pl.BlockSpec((1, tr, d * b), lambda mi, ti: (mi, ti, 0))
    tt = jnp.stack([jnp.asarray(ts, jnp.uint32), jnp.asarray(te, jnp.uint32)])
    out = pl.pallas_call(
        functools.partial(kernel, tr=tr, b=b),
        grid=(m, d // tr),
        in_specs=[smem((1, 1, 1), lambda mi, ti: (mi, 0, 0)),
                  smem(), smem(), smem()] + [mat_spec] * len(fields),
        out_specs=pl.BlockSpec((1, q), lambda mi, ti: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, q), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(node_mask, jnp.int32).reshape(m, 1, 1), fp, ch, tt,
      *(f.reshape(m, d, d * b) for f in fields))
    return out[0]


def edge_probe_pallas(nodes: NodeState, node_mask, fs, fd, rows, cols,
                      ts, te, *, match_time: bool,
                      interpret: bool | None = None):
    """(q,) sums of matching entry weights; Pallas twin of
    :func:`repro.core.cmatrix.probe_edge`."""
    if interpret is None:
        interpret = default_interpret()
    r = rows.shape[1]
    fp = jnp.stack([jnp.asarray(fs, jnp.uint32), jnp.asarray(fd, jnp.uint32)])
    ch = jnp.concatenate([jnp.asarray(rows, jnp.int32).T,
                          jnp.asarray(cols, jnp.int32).T])
    kernel = functools.partial(_edge_kernel, match_time=match_time, r=r)
    return _probe_call(kernel, nodes,
                       (nodes.fp_s, nodes.fp_d, nodes.w, nodes.t,
                        nodes.idx.astype(jnp.int32)),
                       node_mask, fp, ch, ts, te, interpret)


def vertex_probe_pallas(nodes: NodeState, node_mask, fv, rows, ts, te, *,
                        direction: str, match_time: bool,
                        interpret: bool | None = None):
    """(q,) sums for vertex queries; Pallas twin of
    :func:`repro.core.cmatrix.probe_vertex`."""
    if interpret is None:
        interpret = default_interpret()
    r = rows.shape[1]
    fp = jnp.asarray(fv, jnp.uint32)[None]
    ch = jnp.asarray(rows, jnp.int32).T
    kernel = functools.partial(_vertex_kernel, match_time=match_time, r=r,
                               direction=direction)
    fpm = nodes.fp_s if direction == "out" else nodes.fp_d
    return _probe_call(kernel, nodes,
                       (fpm, nodes.w, nodes.t, nodes.idx.astype(jnp.int32)),
                       node_mask, fp, ch, ts, te, interpret)
