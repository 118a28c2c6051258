"""Pallas TPU kernel: faithful Algorithm-1 leaf insertion, fully in VMEM.

The whole leaf matrix (d=16: ~15 KiB across the five SoA fields) stays
in VMEM for the leaf's chunk, so one kernel program performs the
paper's *sequential* per-edge insertion with zero HBM round-trips — the
TPU analogue of the paper's cache-resident subtree argument.  Edge
order is preserved exactly (fori_loop), making this the bit-faithful
reference path; the vectorized chunk path (``cmatrix.insert_chunk``) is
the throughput-oriented alternative (DESIGN.md §3).

Layout (what Mosaic accepts): each matrix field is viewed as a
lane-dense ``(d, d*b)`` tile — element ``(row, col*b + slot)`` — and
the per-edge scalars (fingerprints, weight bits, timestamp, validity,
chain rows and columns) travel as one int32 ``(5 + 2r, n)`` block in
SMEM.  Per edge, instead of indexing the ``r*r`` candidate buckets one
by one, the kernel ranks every slot of the tile at once: a candidate
slot's key is ``probe*2b + slot`` when it matches the edge and
``probe*2b + b + slot`` when it is empty, so the minimum key is exactly
the slot that Algorithm 1's probe loop would stop at (first bucket in
probe order holding a match or a free slot; a match beats a free slot
within the bucket).  The uint32 fields are bit-cast to int32 around the
call: only equality is ever tested on them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cmatrix import NodeState

_NO_SLOT = 1 << 30          # key of a slot the edge cannot take


def default_interpret() -> bool:
    """Auto-detected Pallas mode: compile to Mosaic on TPU, interpret on
    CPU/other backends (shared by every kernel wrapper; callers thread an
    explicit override via ``HiggsParams.interpret``)."""
    return jax.default_backend() != "tpu"


def _kernel(pk_ref, fps_in, fpd_in, wm_in, tm_in, idx_in,
            fps_ref, fpd_ref, wm_ref, tm_ref, idx_ref, spill_ref,
            *, r: int, b: int, n: int):
    # one program per leaf; the *_in refs alias the outputs (in-place)
    del fps_in, fpd_in, wm_in, tm_in, idx_in
    tile = fps_ref.shape[1:]                                 # (d, d*b)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (tile[0], 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile[1]), 1)
    col_of = lane // b
    slot = lane - col_of * b
    edge_of = jax.lax.broadcasted_iota(jnp.int32, spill_ref.shape[1:], 1)
    spill_ref[...] = jnp.zeros(spill_ref.shape, spill_ref.dtype)

    def edge_body(e, _):
        fs, fd, tv = pk_ref[0, 0, e], pk_ref[0, 1, e], pk_ref[0, 3, e]
        wv = jax.lax.bitcast_convert_type(
            jnp.full(tile, pk_ref[0, 2, e], jnp.int32), jnp.float32)
        is_valid = pk_ref[0, 4, e] != 0
        # chain position of each row / column (-1: not a candidate);
        # reversed so a repeated address keeps its first position
        ri = jnp.full(row_of.shape, -1, jnp.int32)
        cj = jnp.full(col_of.shape, -1, jnp.int32)
        for i in reversed(range(r)):
            ri = jnp.where(row_of == pk_ref[0, 5 + i, e], i, ri)
            cj = jnp.where(col_of == pk_ref[0, 5 + r + i, e], i, cj)
        k = ri * r + cj                                  # probe index
        cand = (ri >= 0) & (cj >= 0)
        bfs, bfd, bt = fps_ref[0], fpd_ref[0], tm_ref[0]
        empty = bfs == -1                                # EMPTY as int32
        match = (bfs == fs) & (bfd == fd) & (bt == tv) & ~empty
        key = jnp.where(cand & match, k * (2 * b) + slot,
                        jnp.where(cand & empty, k * (2 * b) + b + slot,
                                  _NO_SLOT))
        best = jnp.min(jnp.min(key, axis=1, keepdims=True), axis=0,
                       keepdims=True)
        placed = best < _NO_SLOT
        write = (key == best) & placed & is_valid
        ins = write & empty
        wm_ref[0] = jnp.where(write, wm_ref[0] + wv, wm_ref[0])
        fps_ref[0] = jnp.where(ins, fs, bfs)
        fpd_ref[0] = jnp.where(ins, fd, bfd)
        tm_ref[0] = jnp.where(ins, tv, bt)
        idx_ref[0] = jnp.where(ins, k, idx_ref[0])
        spilled = jnp.where(is_valid & ~placed, 1, 0)
        spill_ref[0] = jnp.where(edge_of == e, spilled, spill_ref[0])
        return 0

    jax.lax.fori_loop(0, n, edge_body, 0)


def _as_i32(x, dtype):
    return jax.lax.bitcast_convert_type(jnp.asarray(x, dtype), jnp.int32)


def leaf_insert_batched_pallas(nodes: NodeState, fs, fd, rows, cols, w, t,
                               valid, *, r: int,
                               interpret: bool | None = None):
    """Sequential Alg.-1 insertion for a stacked batch of leaves in ONE
    launch with ``grid=(n_leaves,)`` — program l owns leaf l's matrix
    tile in VMEM and its chunk scalars in SMEM.

    nodes: stacked (L, d, d, b) NodeState; fs/fd/w/t/valid: (L, n);
    rows/cols: (L, n, r).  Returns (stacked NodeState', (L, n) int32).
    """
    if interpret is None:
        interpret = default_interpret()
    L, n = fs.shape
    d, _, b = nodes.fp_s.shape[1:]
    rows, cols = jnp.asarray(rows), jnp.asarray(cols)
    # (L, 5 + 2r, n): fs, fd, weight bits, t, valid, r chain rows, r
    # chain columns — per-position slices, so no transpose is built
    pk = jnp.stack(
        [_as_i32(fs, jnp.uint32), _as_i32(fd, jnp.uint32),
         _as_i32(w, jnp.float32), _as_i32(t, jnp.uint32),
         jnp.asarray(valid, jnp.int32)]
        + [rows[..., i].astype(jnp.int32) for i in range(r)]
        + [cols[..., i].astype(jnp.int32) for i in range(r)], axis=1)
    mats = tuple(
        getattr(nodes, f).reshape(L, d, d * b) if f == "w"
        else _as_i32(getattr(nodes, f), jnp.uint32).reshape(L, d, d * b)
        for f in NodeState._fields)
    mat_spec = pl.BlockSpec((1, d, d * b), lambda l: (l, 0, 0))
    fn = pl.pallas_call(
        functools.partial(_kernel, r=r, b=b, n=n),
        grid=(L,),
        in_specs=[pl.BlockSpec((1, 5 + 2 * r, n), lambda l: (l, 0, 0),
                               memory_space=pltpu.SMEM)]
        + [mat_spec] * 5,
        out_specs=(mat_spec,) * 5
        + (pl.BlockSpec((1, 1, n), lambda l: (l, 0, 0)),),
        out_shape=tuple(jax.ShapeDtypeStruct(m.shape, m.dtype)
                        for m in mats)
        + (jax.ShapeDtypeStruct((L, 1, n), jnp.int32),),
        input_output_aliases={1: 0, 2: 1, 3: 2, 4: 3, 5: 4},
        interpret=interpret,
    )
    *out, spill = fn(pk, *mats)
    fields = (o.reshape(L, d, d, b) if f == "w" else
              jax.lax.bitcast_convert_type(o, jnp.uint32).reshape(L, d, d, b)
              for f, o in zip(NodeState._fields, out))
    return NodeState(*fields), spill.reshape(L, n)


def leaf_insert_pallas(node: NodeState, fs, fd, rows, cols, w, t, valid,
                       *, r: int, interpret: bool | None = None):
    """Run the faithful sequential insert kernel on one leaf.

    Returns (NodeState', spill mask (n,) int32).
    """
    nodes = NodeState(*(a[None] for a in node))
    out, spill = leaf_insert_batched_pallas(
        nodes, *(jnp.asarray(a)[None]
                 for a in (fs, fd, rows, cols, w, t, valid)),
        r=r, interpret=interpret)
    return NodeState(*(a[0] for a in out)), spill[0]
