"""Persistent fused-drain pipeline: hash -> placement -> pool append in
one launch against device-resident level pools.

The classic pallas path (`HiggsSketch._insert_leaves_pallas`) hashes on
host, uploads hashed chunk tensors, runs the grid-over-leaves kernel,
then downloads the full node batch so the host pool can append it —
every drain pays h2d for the chunk *and* d2h for the nodes.  This module
keeps the whole exchange on device:

* a small ring of reusable ("pinned") host staging blocks receives the
  raw drained spans — src/dst/weight-bits/timestamp packed as one
  ``(4, lead, pad)`` uint32 tensor plus per-leaf lengths, the only h2d
  transfer per drain;
* one jitted step (``_ingest_step``) hashes the staged items with the
  bit-exact ``hashing.mix32`` device twin, derives fingerprints and LCG
  chain addresses, runs ``leaf_insert_batched_pallas``, and scatters the
  finished leaves into the *donated* capacity slabs of the level-1 pool
  — pool state is never re-uploaded;
* only the per-item spill mask returns to host (the overflow store is a
  host structure); spilled hash values are recomputed on host from the
  staged raw items, which is bit-identical by construction.

Validity is derived on device from the staged lengths, so stale bytes in
a reused staging slot are unreachable: the kernel starts invalid items
as already-placed and the scatter drops rows past the live leaf count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cmatrix, hashing
from repro.core.cmatrix import NodeState
from repro.core.cmatrix import pow2_pad as _pow2_pad
from repro.core.params import HiggsParams
from repro.kernels import leaf_insert as _li


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _append_rows(fp_s, fp_d, w, t, idx, nodes: NodeState, n0, m):
    """Scatter the first ``m`` of ``nodes`` into the donated slabs at rows
    ``n0..n0+m``; the other rows go out of range and are dropped."""
    li = jnp.arange(nodes.fp_s.shape[0], dtype=jnp.int32)
    tgt = jnp.where(li < m, n0 + li, jnp.int32(fp_s.shape[0]))
    return tuple(slab.at[tgt].set(vals, mode="drop")
                 for slab, vals in zip((fp_s, fp_d, w, t, idx), nodes))


@functools.partial(jax.jit,
                   static_argnames=("r", "F1", "d1", "b", "seed",
                                    "interpret"),
                   donate_argnums=(0, 1, 2, 3, 4))
def _ingest_step(fp_s, fp_d, w, t, idx, stage, lengths, n0, nl, *,
                 r: int, F1: int, d1: int, b: int, seed: int,
                 interpret: bool):
    """Fused drain step over donated pool slabs.

    fp_s..idx: (cap, d, d, b) level-1 slabs (donated, returned updated).
    stage: (4, lead, pad) uint32 raw items; lengths: (lead,) int32.
    n0/nl: traced scalars (append offset, live leaf count) — their
    values never enter the compile cache key, so steady-state drains hit
    one executable per (capacity, staging-shape) pair.
    """
    src, dst, wbits, tt = stage[0], stage[1], stage[2], stage[3]
    lead, pad = src.shape
    valid = (jax.lax.broadcasted_iota(jnp.int32, (lead, pad), 1)
             < lengths[:, None])
    hs = hashing.mix32(src, seed)
    hd = hashing.mix32(dst, seed ^ 0x5BD1E995)
    fs = hashing.fingerprint(hs, F1)
    fd = hashing.fingerprint(hd, F1)
    rows = cmatrix.chain_from_base(hashing.address(hs, F1, d1), r, d1)
    cols = cmatrix.chain_from_base(hashing.address(hd, F1, d1), r, d1)
    wf = jax.lax.bitcast_convert_type(wbits, jnp.float32)
    nodes = cmatrix.make_nodes(lead, d1, b)
    nodes, spill = _li.leaf_insert_batched_pallas(
        nodes, fs, fd, rows, cols, wf, tt.astype(jnp.uint32), valid,
        r=r, interpret=interpret)
    slabs = _append_rows(fp_s, fp_d, w, t, idx, nodes, n0, nl)
    spill_mask = jnp.where(valid, spill, 0)
    return slabs + (spill_mask,)


@functools.partial(jax.jit, static_argnames=("rows",))
def _take_rows(fp_s, fp_d, w, idx, i0, *, rows: int):
    """``rows`` consecutive slab rows from physical offset ``i0`` (traced)
    — the ready child block of one aggregation step.  Rows past the live
    count come back as garbage; the step drops what they produce."""
    at = i0 + jnp.arange(rows, dtype=jnp.int32)
    return tuple(jnp.take(s, at, axis=0) for s in (fp_s, fp_d, w, idx))


@functools.partial(jax.jit,
                   static_argnames=("mp", "theta", "level", "params"))
def _aggregate_step(c_fp_s, c_fp_d, c_w, c_idx, ob_pack, *,
                    mp: int, theta: int, level: int, params):
    """Fused aggregation step: build ``mp`` parents from their children.

    c_*: (mp * theta, d, d, b) child block (:func:`_take_rows`).
    ob_pack: (6, mp, ob_pad) uint32 host-staged overflow columns —
    f1s/f1d/bs/bd, weight bits, validity — packed as ONE tensor like the
    ingest staging block (the overflow store is a host structure;
    zero-width when no child carries OB entries).  ``mp`` is the
    pow2-padded parent count bounding jit shape variety exactly like the
    host batched path.  No operand depends on a pool's capacity, so this
    costly program compiles once per (mp, level, ob_pad), not again at
    every slab growth; :func:`_take_rows` and :func:`_append_rows` move
    the rows.

    Bit-identical to :meth:`HiggsSketch._build_parents_batched`'s host
    reference: the device ``recover_leaf_coords``/``coords_at_level``
    twins are exact, invalid entries get the same zeroed coordinates,
    and ``cmatrix.round_orders`` reproduces ``host_round_orders``'s
    stable permutation, so ``aggregate_children_pre`` places the same
    entries in the same rounds.

    Returns (parent NodeState (mp, dp, dp, b), spill mask, canonical
    spill columns f1s, f1d, base_s, base_d, w).
    """
    d, b = c_fp_s.shape[1], c_fp_s.shape[3]
    per = theta * d * d * b
    e_fs = c_fp_s.reshape(mp, per)
    e_fd = c_fp_d.reshape(mp, per)
    e_w = c_w.reshape(mp, per)
    e_idx = c_idx.reshape(mp, per)
    grid = jnp.arange(d, dtype=jnp.uint32)
    shape5 = (mp, theta, d, d, b)
    e_row = jnp.broadcast_to(grid[None, None, :, None, None],
                             shape5).reshape(mp, per)
    e_col = jnp.broadcast_to(grid[None, None, None, :, None],
                             shape5).reshape(mp, per)
    e_valid = e_fs != cmatrix.EMPTY

    f1s, base_s = cmatrix.recover_leaf_coords(e_row, e_fs, e_idx, level,
                                              params, "s")
    f1d, base_d = cmatrix.recover_leaf_coords(e_col, e_fd, e_idx, level,
                                              params, "d")
    w_all = e_w
    if ob_pack.shape[2]:
        ob_w = jax.lax.bitcast_convert_type(ob_pack[4], jnp.float32)
        f1s = jnp.concatenate([f1s, ob_pack[0]], axis=1)
        f1d = jnp.concatenate([f1d, ob_pack[1]], axis=1)
        base_s = jnp.concatenate([base_s, ob_pack[2]], axis=1)
        base_d = jnp.concatenate([base_d, ob_pack[3]], axis=1)
        w_all = jnp.concatenate([w_all, ob_w], axis=1)
        e_valid = jnp.concatenate([e_valid, ob_pack[5] != 0], axis=1)

    plevel = level + 1
    fp_s_p, rows_p = cmatrix.coords_at_level(f1s, base_s, plevel, params)
    fp_d_p, cols_p = cmatrix.coords_at_level(f1d, base_d, plevel, params)
    # EMPTY entries recover garbage coordinates; zero them exactly like
    # the host reference so placement ranks agree bit for bit
    rows_p = jnp.where(e_valid[..., None], rows_p, jnp.uint32(0))
    cols_p = jnp.where(e_valid[..., None], cols_p, jnp.uint32(0))
    r = params.r if params.use_mmb else 1
    orders = cmatrix.round_orders(rows_p, cols_p, r)
    state4, wmat, spill = cmatrix.aggregate_children_pre(
        fp_s_p, fp_d_p, rows_p, cols_p, w_all, e_valid, orders,
        params, level)
    parents = NodeState(state4[:, 0], state4[:, 1], wmat, state4[:, 2],
                        state4[:, 3])
    return parents, spill, f1s, f1d, base_s, base_d, w_all


class DrainPipeline:
    """Double-buffered staging + fused launch for one sketch.

    Staging blocks rotate over two slots per (lead, pad) shape so the
    host can pack drain N+1 while the device may still be consuming the
    upload of drain N (on TPU the copies are async; on CPU the structure
    degenerates gracefully to a reused scratch buffer).
    """

    def __init__(self, params: HiggsParams):
        self.params = params
        self._slots: dict = {}
        self._turn: dict = {}

    def _next_slot(self, lead: int, pad: int):
        key = (lead, pad)
        slots = self._slots.get(key)
        if slots is None:
            slots = tuple((np.zeros((4, lead, pad), np.uint32),
                           np.zeros((lead,), np.int32))
                          for _ in range(2))
            self._slots[key] = slots
            self._turn[key] = 0
        i = self._turn[key]
        self._turn[key] = 1 - i
        return slots[i]

    def ingest(self, pool, buf: np.ndarray, spans, lead: int, pad: int):
        """Stage the drained spans and run one fused append launch.

        Returns ``(base_slot, spill_mask (nl, pad) bool, stage)`` where
        ``stage`` is the packed raw staging block (for host-side spill
        hash recovery) and ``base_slot`` the pool slot of leaf 0.
        """
        p = self.params
        nl = len(spans)
        stage, lengths = self._next_slot(lead, pad)
        for i, (s, e) in enumerate(spans):
            m = e - s
            stage[:, i, :m] = buf[:, s:e]
            lengths[i] = m
        lengths[nl:] = 0
        pool.reserve(pool.n + nl)
        slabs = pool.device_slabs()
        r = p.r if p.use_mmb else 1
        interpret = (_li.default_interpret() if p.interpret is None
                     else p.interpret)
        out = _ingest_step(
            slabs["fp_s"], slabs["fp_d"], slabs["w"], slabs["t"],
            slabs["idx"], jnp.asarray(stage), jnp.asarray(lengths),
            np.int32(pool.n), np.int32(nl),
            r=r, F1=p.F1, d1=p.d1, b=p.b, seed=p.seed,
            interpret=interpret)
        new_slabs = dict(zip(NodeState._fields, out[:5]))
        # the only d2h of the drain: the (small) spill mask feeding the
        # host overflow store
        spill = np.asarray(out[5])[:nl].astype(bool)
        base_slot = pool.adopt_slabs(new_slabs, nl)
        return base_slot, spill, stage

    def aggregate(self, child_pool, parent_pool, level: int, u0: int,
                  m: int, ob):
        """Build ``m`` ready parents at ``level`` in one fused launch and
        append them to the donated parent slabs — the device-resident twin
        of the host batched aggregation (no ``gather_block`` fetch).

        ``ob`` is the host-stacked overflow-column dict from
        :meth:`HiggsSketch._gather_child_obs_stacked` (or ``None``),
        packed here into one uint32 staging tensor — the only tensor
        h2d operand besides three scalars.  Returns
        ``(spill_mask (m, N) bool, coords)`` where ``coords`` are the
        canonical spill columns ``(f1s, f1d, base_s, base_d, w)`` as
        *lazy* device arrays: the caller materializes them only when the
        spill mask is non-empty, so the steady-state cascade pays d2h
        for nothing but the small mask.
        """
        p = self.params
        theta = p.theta
        mp = _pow2_pad(m, lo=1)            # bound jit shape variety
        parent_pool.reserve(parent_pool.n + m)
        pslabs = parent_pool.device_slabs()
        cslabs = child_pool.device_slabs()
        if ob is None:
            ob_pack = np.zeros((6, mp, 0), np.uint32)
        else:
            obp = ob["w"].shape[1]
            ob_pack = np.zeros((6, mp, obp), np.uint32)
            for row, k in enumerate(("f1s", "f1d", "bs", "bd")):
                ob_pack[row, :m] = ob[k]
            ob_pack[4, :m] = ob["w"].view(np.uint32)
            ob_pack[5, :m] = ob["valid"]
        block = _take_rows(
            cslabs["fp_s"], cslabs["fp_d"], cslabs["w"], cslabs["idx"],
            np.int32(u0 * theta - child_pool.base), rows=mp * theta)
        parents, spill, *coords = _aggregate_step(
            *block, jnp.asarray(ob_pack), mp=mp, theta=theta, level=level,
            params=p)
        slabs = _append_rows(
            pslabs["fp_s"], pslabs["fp_d"], pslabs["w"], pslabs["t"],
            pslabs["idx"], parents, np.int32(parent_pool.n), np.int32(m))
        parent_pool.adopt_slabs(dict(zip(NodeState._fields, slabs)), m)
        # the only mandatory d2h of the cascade level: the spill mask
        # feeding the host overflow store
        return np.asarray(spill)[:m].astype(bool), coords
