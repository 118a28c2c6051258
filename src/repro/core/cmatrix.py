"""Compressed-matrix operations: batched insertion, aggregation re-bucketing,
and probe (query) primitives.  Pure jnp — these double as the reference
implementations for the Pallas kernels in ``repro.kernels``.

Design notes (see DESIGN.md §3 for the TPU adaptation rationale):

* A node's matrix is an SoA pytree of ``(d, d, b)`` arrays: ``fp_s``,
  ``fp_d``, ``w``, ``idx`` (MMB chain index pair) and — leaves only — ``t``.
  ``fp_s == EMPTY`` marks a free entry.
* Insertion is *chunked*: a whole chunk of stream items is placed with
  ``r*r`` bounded rounds of (merge, claim-free-slots) vector phases, which
  preserves the paper's semantics at chunk granularity (stable sorts keep
  arrival order within a bucket).  Items that fail every mapping bucket are
  returned compacted for the caller's overflow block — nothing is dropped,
  so the one-sided error guarantee survives.
* An entry belongs to a key only where its fingerprints match AND its
  stored ``idx`` names the chain position at which the item (or query)
  reached it: same fingerprints at one address under another chain
  position are another key's (another base row).  Both the placement
  merge (phase A) and the probes read ``idx``, so a summary is exact
  over each id's F1 + log2(d1) bit key, never merging two keys.
* Aggregation (paper Alg. 2) recovers each stored entry's leaf-level LCG
  chain in closed form from its (address, fingerprint, chain-index) triple,
  shifts R fingerprint bits per level into the address, and re-places the
  entries into the parent matrix with the same machinery.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.core.params import HiggsParams

EMPTY = np.uint32(0xFFFFFFFF)
_A = 5   # LCG multiplier (a % 4 == 1 -> full period mod 2^k)
_C = 1   # LCG increment (odd)


def pow2_pad(n: int, lo: int = 8) -> int:
    """Next power of two >= n (floor lo) — shared pad policy for chunk
    buffers and pool gathers; insertion and probes must agree on it."""
    return max(lo, 1 << max(0, (n - 1).bit_length()))


def lcg_tables(r: int, d: int):
    """Closed-form LCG coefficients: x_k = A_k * x_0 + B_k (mod d)."""
    A, B = [], []
    a_k, b_k = 1, 0
    for _ in range(r):
        A.append(a_k % d)
        B.append(b_k % d)
        a_k, b_k = a_k * _A, b_k * _A + _C
    inv = [pow(a % d, -1, d) if d > 1 else 0 for a in A]
    return (np.asarray(A, np.uint32), np.asarray(B, np.uint32),
            np.asarray(inv, np.uint32))


def chain_from_base(x0, r: int, d: int):
    """All r chain positions from base address x0; shape (..., r)."""
    A, B, _ = lcg_tables(r, d)
    x0 = jnp.asarray(x0, jnp.uint32)[..., None]
    return (x0 * A + B) % jnp.uint32(d)


def chain_base_from_pos(x_k, k, r: int, d: int):
    """Recover x0 from the value at (data-dependent) chain index k."""
    A, B, Ainv = lcg_tables(r, d)
    a_inv = jnp.take(jnp.asarray(Ainv), k)
    b_k = jnp.take(jnp.asarray(B), k)
    return (a_inv * (jnp.asarray(x_k, jnp.uint32) - b_k)) % jnp.uint32(d)


class NodeState(NamedTuple):
    """One compressed matrix.  ``t`` is all-zeros for non-leaf nodes."""
    fp_s: jax.Array  # (d, d, b) uint32
    fp_d: jax.Array  # (d, d, b) uint32
    w: jax.Array     # (d, d, b) float32
    t: jax.Array     # (d, d, b) uint32
    idx: jax.Array   # (d, d, b) uint32 — MMB chain index pair i*r+j


def make_node(d: int, b: int) -> NodeState:
    # distinct buffers per field (donation forbids aliased arguments)
    return NodeState(fp_s=jnp.full((d, d, b), EMPTY, jnp.uint32),
                     fp_d=jnp.full((d, d, b), EMPTY, jnp.uint32),
                     w=jnp.zeros((d, d, b), jnp.float32),
                     t=jnp.zeros((d, d, b), jnp.uint32),
                     idx=jnp.zeros((d, d, b), jnp.uint32))


def make_nodes(n: int, d: int, b: int) -> NodeState:
    """``n`` fresh matrices stacked on axis 0 (the batched-ingest layout)."""
    return NodeState(fp_s=jnp.full((n, d, d, b), EMPTY, jnp.uint32),
                     fp_d=jnp.full((n, d, d, b), EMPTY, jnp.uint32),
                     w=jnp.zeros((n, d, d, b), jnp.float32),
                     t=jnp.zeros((n, d, d, b), jnp.uint32),
                     idx=jnp.zeros((n, d, d, b), jnp.uint32))


def empty_node_arrays(n: int, d: int, b: int) -> dict[str, np.ndarray]:
    """``n`` fresh matrices as host numpy field arrays — the level-pool
    storage layout, shared by pool growth and snapshot restore so both
    agree on the EMPTY/zero initialization of unused capacity."""
    shape = (n, d, d, b)
    return {name: np.full(shape, EMPTY, np.uint32)
            if name in ("fp_s", "fp_d")
            else np.zeros(shape, np.float32 if name == "w" else np.uint32)
            for name in NodeState._fields}


# ---------------------------------------------------------------------------
# placement: the shared (merge, claim) multi-round engine
# ---------------------------------------------------------------------------

def place_entries(node: NodeState, fs, fd, rows, cols, w, t, valid,
                  *, d: int, b: int, r: int, match_time: bool):
    """Place up to n items into one matrix.

    rows/cols: (n, r) candidate addresses at *this* level, lex probe order
    (i, j) over the r x r mapping buckets.  At round k = i * r + j an item
    merges only into an entry with its fingerprints (and ``t`` where
    ``match_time``) stored with ``idx == k``; else it claims a free slot
    there or goes on to round k + 1.  Returns (node', placed (n,)).
    """
    n = fs.shape[0]
    placed = ~valid
    fs = jnp.asarray(fs, jnp.uint32)
    fd = jnp.asarray(fd, jnp.uint32)
    t = jnp.asarray(t, jnp.uint32)
    w = jnp.asarray(w, jnp.float32)

    state = node
    for k in range(r * r):
        i, j = k // r, k % r
        row = rows[:, i].astype(jnp.int32)
        col = cols[:, j].astype(jnp.int32)
        active = ~placed

        # --- phase A: merge into an entry of this key, placed at round k -
        e_fs = state.fp_s[row, col]          # (n, b)
        e_fd = state.fp_d[row, col]
        e_t = state.t[row, col]
        match = (e_fs == fs[:, None]) & (e_fd == fd[:, None]) & \
            (e_fs != EMPTY) & (state.idx[row, col] == jnp.uint32(k))
        if match_time:
            match &= e_t == t[:, None]
        has_match = jnp.any(match, axis=-1) & active
        slot = jnp.argmax(match, axis=-1).astype(jnp.int32)
        add_w = jnp.where(has_match, w, 0.0)
        new_w = state.w.at[row, col, slot].add(add_w)
        state = state._replace(w=new_w)
        placed = placed | has_match
        active = ~placed

        # --- phase B: claim free slots, arrival order within a bucket ---
        bid = (row * d + col).astype(jnp.int32)
        bid_m = jnp.where(active, bid, d * d)          # inactive to the end
        order = jnp.argsort(bid_m, stable=True)
        sb = bid_m[order]
        pos = jnp.arange(n, dtype=jnp.int32)
        is_first = jnp.concatenate([jnp.ones((1,), bool), sb[1:] != sb[:-1]])
        group_start = jax.lax.associative_scan(
            jnp.maximum, jnp.where(is_first, pos, 0))
        rank_sorted = pos - group_start
        rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)

        emp = (state.fp_s == EMPTY).reshape(d * d, b)
        emp_before = jnp.cumsum(emp, axis=-1) - emp.astype(jnp.int32)
        free_cnt = jnp.sum(emp, axis=-1)
        # slot_table[bucket, m] = entry index of the m-th free slot
        hit = emp[:, None, :] & (emp_before[:, None, :] ==
                                 jnp.arange(b, dtype=jnp.int32)[None, :, None])
        slot_table = jnp.argmax(hit, axis=-1).astype(jnp.int32)  # (d*d, b)

        accept = active & (rank < free_cnt[bid])
        m = jnp.clip(rank, 0, b - 1)
        tgt = slot_table[bid, m]
        # route non-accepted writes out of bounds; mode="drop" discards them,
        # so accepted writes never race with no-op writes (distinct
        # (bucket, rank) => distinct target entries among accepted).
        rowa = jnp.where(accept, row, d)
        state = NodeState(
            fp_s=state.fp_s.at[rowa, col, tgt].set(fs, mode="drop"),
            fp_d=state.fp_d.at[rowa, col, tgt].set(fd, mode="drop"),
            w=state.w.at[rowa, col, tgt].add(w, mode="drop"),
            t=state.t.at[rowa, col, tgt].set(t, mode="drop"),
            idx=state.idx.at[rowa, col, tgt].set(jnp.uint32(k), mode="drop"),
        )
        placed = placed | accept
    return state, placed & valid


# ---------------------------------------------------------------------------
# leaf chunk insertion
# ---------------------------------------------------------------------------

def _premerge(hs, hd, t, w, valid):
    """Merge duplicate (hs, hd, t) items: weight summed into the first
    occurrence, the rest invalidated.  Stable lexicographic grouping."""
    n = hs.shape[0]
    o = jnp.argsort(t, stable=True)
    for key in (hd, hs):
        o = o[jnp.argsort(key[o], stable=True)]
    o = o[jnp.argsort(~valid[o], stable=True)]   # invalid items to the end
    ks, kd, kt, kv = hs[o], hd[o], t[o], valid[o]
    same = (ks[1:] == ks[:-1]) & (kd[1:] == kd[:-1]) & (kt[1:] == kt[:-1])
    same = jnp.concatenate([jnp.zeros((1,), bool), same]) & kv
    seg = jnp.cumsum(~same) - 1
    wsum = jax.ops.segment_sum(w[o], seg, num_segments=n)
    first = ~same
    w_new = jnp.zeros((n,), w.dtype).at[o].set(
        jnp.where(first, wsum[seg], 0.0))
    valid_new = jnp.zeros((n,), bool).at[o].set(first & kv)
    return w_new, valid_new


def _insert_chunk_impl(node: NodeState, hs, hd, w, t, valid,
                       params: HiggsParams):
    d, b, r, F1 = params.d1, params.b, params.r if params.use_mmb else 1, params.F1
    fs = hashing.fingerprint(hs, F1)
    fd = hashing.fingerprint(hd, F1)
    rows = chain_from_base(hashing.address(hs, F1, d), r, d)
    cols = chain_from_base(hashing.address(hd, F1, d), r, d)
    w, valid = _premerge(hs, hd, t, w, valid)
    node, placed = place_entries(node, fs, fd, rows, cols, w, t, valid,
                                 d=d, b=b, r=r, match_time=True)
    spill = valid & ~placed
    order = jnp.argsort(~spill, stable=True)      # spilled first, in order
    out = {k: v[order] for k, v in
           dict(hs=hs, hd=hd, w=w, t=t).items()}
    return node, out, jnp.sum(spill)


@functools.partial(jax.jit, static_argnames=("params",), donate_argnums=(0,))
def insert_chunk(node: NodeState, hs, hd, w, t, valid,
                 params: HiggsParams):
    """Insert a chunk of raw stream items (already hashed vertex ids) into a
    leaf matrix.  Returns (node', spill dict, n_spilled)."""
    return _insert_chunk_impl(node, hs, hd, w, t, valid, params)


# ---------------------------------------------------------------------------
# preordered batched engine
#
# The legacy path above is the bit-exact reference; the batched engine
# below produces IDENTICAL matrices but moves every sort to the host:
# all per-round stable orders (and the premerge grouping) depend only on
# the *inputs*, never on placement state, so numpy's O(n) radix sort
# precomputes them once and the device does pure gather/scan/scatter
# work — XLA's comparison sorts were the dominant CPU ingestion cost.
# Ranks within a bucket come from a segmented scan over the precomputed
# order, which yields exactly the legacy argsort ranks.
# ---------------------------------------------------------------------------


def host_chain_from_base(x0: np.ndarray, r: int, d: int) -> np.ndarray:
    """NumPy twin of :func:`chain_from_base` (same uint32 wraparound)."""
    A, B, _ = lcg_tables(r, d)
    x0 = np.asarray(x0, np.uint32)[..., None]
    return ((x0 * A).astype(np.uint32) + B).astype(np.uint32) % np.uint32(d)


def host_leaf_coords(hs: np.ndarray, hd: np.ndarray, params: HiggsParams):
    """(fs, fd, rows, cols) for hashed ids — host twin of the coordinate
    block at the top of :func:`insert_chunk`."""
    F1, d = params.F1, params.d1
    r = params.r if params.use_mmb else 1
    mask = np.uint32((1 << F1) - 1)
    fs = hs & mask
    fd = hd & mask
    rows = host_chain_from_base((hs >> np.uint32(F1)) % np.uint32(d), r, d)
    cols = host_chain_from_base((hd >> np.uint32(F1)) % np.uint32(d), r, d)
    return fs, fd, rows, cols


def host_premerge_meta(hs, hd, t, valid):
    """Per-leaf stable lexsort order + duplicate-run mask: the host twin
    of ``_premerge``'s grouping (which depends only on inputs)."""
    L, n = hs.shape
    order = np.empty((L, n), np.int32)
    same = np.empty((L, n), bool)
    for i in range(L):
        o = np.lexsort((t[i], hd[i], hs[i], ~valid[i]))
        order[i] = o
        ks, kd, kt = hs[i][o], hd[i][o], t[i][o]
        s = (ks[1:] == ks[:-1]) & (kd[1:] == kd[:-1]) & (kt[1:] == kt[:-1])
        same[i] = np.concatenate([[False], s]) & valid[i][o]
    return order, same


def host_round_orders(rows: np.ndarray, cols: np.ndarray, d: int,
                      r: int) -> np.ndarray:
    """(..., r*r, n) stable argsort of every round's bucket ids (radix)."""
    i_idx = np.repeat(np.arange(r), r)
    j_idx = np.tile(np.arange(r), r)
    # (..., n, r*r) -> (..., r*r, n)
    bids = (rows[..., i_idx].astype(np.int64) * d +
            cols[..., j_idx].astype(np.int64))
    bids = np.swapaxes(bids, -1, -2)
    return np.argsort(bids, axis=-1, kind="stable").astype(np.int32)


def round_orders(rows, cols, r: int):
    """Device twin of :func:`host_round_orders` (the fused aggregation
    step computes its orders on device so nothing crosses the d2h
    barrier).  Instead of the host's single int64 ``row * d + col`` key
    it runs a two-pass stable radix — stable argsort by the minor key
    (col), then by the major key (row) — which yields the identical
    permutation for every ``d`` without widening past uint32.
    """
    i_idx = np.repeat(np.arange(r), r)
    j_idx = np.tile(np.arange(r), r)
    # (..., n, r*r) -> (..., r*r, n)
    rk = jnp.swapaxes(rows[..., i_idx], -1, -2).astype(jnp.uint32)
    ck = jnp.swapaxes(cols[..., j_idx], -1, -2).astype(jnp.uint32)
    o1 = jnp.argsort(ck, axis=-1, stable=True)
    o2 = jnp.argsort(jnp.take_along_axis(rk, o1, axis=-1), axis=-1,
                     stable=True)
    return jnp.take_along_axis(o1, o2, axis=-1).astype(jnp.int32)


def _premerge_host(w, valid, order, same):
    """NumPy twin of :func:`_premerge_pre` — float32 accumulation in the
    same (ascending sorted-position) order as the device segment_sum."""
    n = w.shape[0]
    seg = np.cumsum(~same) - 1
    wsum = np.zeros((n,), np.float32)
    np.add.at(wsum, seg, w[order])
    first = ~same
    kv = valid[order]
    w_new = np.zeros((n,), np.float32)
    w_new[order] = np.where(first, wsum[seg], np.float32(0.0))
    valid_new = np.zeros((n,), bool)
    valid_new[order] = first & kv
    return w_new, valid_new


def place_entries_host(state4, wmat, fs, fd, rows, cols, w, t, valid,
                       orders, *, d: int, b: int, r: int, match_time: bool,
                       stats=None):
    """NumPy twin of :func:`place_entries_pre`: phase-exact placement on
    the host.  On CPU backends this outruns the XLA scatter/gather path
    (no dispatch, no transfers, C-speed fancy indexing) while producing
    the same matrices; accumulation order matches the device scatters
    (``np.add.at`` processes updates in index order).

    With ``stats`` (an :class:`~repro.api.queries.IngestStats`), adds to
    ``stats.key_splits`` the items that met an entry with their
    fingerprints at their address under another chain index (another
    key's entry, which a fingerprint-only merge would have joined).
    """
    n = fs.shape[0]
    placed = ~valid
    split = np.zeros((n,), bool)
    t = np.asarray(t, np.uint32)
    w = np.asarray(w, np.float32)
    for k in range(r * r):
        if not (~placed).any():
            break
        i, j = k // r, k % r
        row = rows[:, i].astype(np.int64)
        col = cols[:, j].astype(np.int64)
        active = ~placed

        # phase A: merge into an entry of this key, placed at round k.
        # Only active items are gathered; matched weights are added in
        # index order, as the device scatter adds them (its other
        # updates add 0.0)
        act = np.nonzero(active)[0]
        ra, ca = row[act], col[act]
        e = state4[:, ra, ca]                      # (4, n_active, b)
        twin = (e[0] == fs[act, None]) & (e[1] == fd[act, None]) & \
            (e[0] != EMPTY)
        if match_time:
            twin &= e[2] == t[act, None]
        match = twin & (e[3] == np.uint32(k))
        merged = match.any(axis=-1)
        split[act[twin.any(axis=-1) & ~merged]] = True
        np.add.at(wmat, (ra[merged], ca[merged],
                         match[merged].argmax(axis=-1)), w[act[merged]])
        placed[act[merged]] = True
        active = ~placed

        # phase B: claim free slots, arrival order within a bucket
        bid = row * d + col
        order = orders[k]
        sb = bid[order]
        act_s = active[order].astype(np.int64)
        excl = np.cumsum(act_s) - act_s
        is_first = np.concatenate([[True], sb[1:] != sb[:-1]])
        seg_base = np.maximum.accumulate(np.where(is_first, excl, 0))
        rank = np.empty((n,), np.int64)
        rank[order] = excl - seg_base

        emp = (state4[0] == EMPTY).reshape(d * d, b)
        free_cnt = emp.sum(axis=-1)
        accept = active & (rank < free_cnt[bid])
        a = np.nonzero(accept)[0]
        if len(a):
            emp_before = np.cumsum(emp, axis=-1) - emp
            hit = emp[:, None, :] & (emp_before[:, None, :] ==
                                     np.arange(b)[None, :, None])
            slot_table = hit.argmax(axis=-1)
            tgt = slot_table[bid[a], rank[a]]
            ra, ca = row[a], col[a]
            state4[0, ra, ca, tgt] = fs[a]
            state4[1, ra, ca, tgt] = fd[a]
            state4[2, ra, ca, tgt] = t[a]
            state4[3, ra, ca, tgt] = np.uint32(k)
            wmat[ra, ca, tgt] += w[a]          # distinct targets
            placed[a] = True
    if stats is not None:
        stats.key_splits += int(split.sum())
    return state4, wmat, placed & valid


def _empty_state4_host(d: int, b: int):
    state4 = np.zeros((4, d, d, b), np.uint32)
    state4[0] = EMPTY
    state4[1] = EMPTY
    return state4


def insert_chunks_host(fs, fd, rows, cols, w, t, valid, pm_order, pm_same,
                       orders, params: HiggsParams, stats=None):
    """Host twin of :func:`insert_chunks_pre` (same stacked signature and
    returns, numpy arrays); ``stats`` as :func:`place_entries_host`."""
    d, b = params.d1, params.b
    r = params.r if params.use_mmb else 1
    L, n = fs.shape
    state4 = np.stack([_empty_state4_host(d, b) for _ in range(L)])
    wmat = np.zeros((L, d, d, b), np.float32)
    spill = np.zeros((L, n), bool)
    w_m = np.zeros((L, n), np.float32)
    for i in range(L):
        wm, vm = _premerge_host(w[i], valid[i], pm_order[i], pm_same[i])
        w_m[i] = wm
        _, _, placed = place_entries_host(
            state4[i], wmat[i], fs[i], fd[i], rows[i], cols[i], wm, t[i],
            vm, orders[i], d=d, b=b, r=r, match_time=True, stats=stats)
        spill[i] = vm & ~placed
    return state4, wmat, spill, w_m


def aggregate_children_host(fp_s_p, fp_d_p, rows_p, cols_p, w, valid,
                            orders, params: HiggsParams, level: int,
                            stats=None):
    """Host twin of :func:`aggregate_children_pre` (same stacked
    signature and returns, numpy arrays); ``stats`` as
    :func:`place_entries_host`."""
    b = params.b
    r = params.r if params.use_mmb else 1
    dp = params.d(level + 1)
    m, n = fp_s_p.shape
    state4 = np.stack([_empty_state4_host(dp, b) for _ in range(m)])
    wmat = np.zeros((m, dp, dp, b), np.float32)
    spill = np.zeros((m, n), bool)
    t0 = np.zeros((n,), np.uint32)
    for i in range(m):
        _, _, placed = place_entries_host(
            state4[i], wmat[i], fp_s_p[i], fp_d_p[i], rows_p[i], cols_p[i],
            w[i].astype(np.float32), t0, valid[i], orders[i],
            d=dp, b=b, r=r, match_time=False, stats=stats)
        spill[i] = valid[i] & ~placed
    return state4, wmat, spill


def _premerge_pre(w, valid, order, same):
    """Device half of premerge given host grouping meta; same outputs as
    ``_premerge``."""
    n = w.shape[0]
    seg = jnp.cumsum(~same) - 1
    wsum = jax.ops.segment_sum(w[order], seg, num_segments=n)
    first = ~same
    kv = valid[order]
    w_new = jnp.zeros((n,), w.dtype).at[order].set(
        jnp.where(first, wsum[seg], 0.0))
    valid_new = jnp.zeros((n,), bool).at[order].set(first & kv)
    return w_new, valid_new


def place_entries_pre(state4, wmat, fs, fd, rows, cols, w, t, valid, orders,
                      *, d: int, b: int, r: int, match_time: bool):
    """Sort-free twin of :func:`place_entries`.

    state4: (4, d, d, b) uint32 stack of (fp_s, fp_d, t, idx); wmat:
    (d, d, b) float32; orders: (r*r, n) host-precomputed stable orders of
    each round's bucket ids.  Produces bit-identical placements: the rank
    of an active item within its bucket equals the legacy
    argsort-and-group rank (count of earlier active same-bucket items).
    """
    n = fs.shape[0]
    fs = jnp.asarray(fs, jnp.uint32)
    fd = jnp.asarray(fd, jnp.uint32)
    t = jnp.asarray(t, jnp.uint32)
    w = jnp.asarray(w, jnp.float32)
    pos1 = jnp.ones((1,), bool)
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)

    def round_body(carry):
        state4, wmat, placed, k = carry
        i, j = k // r, k % r
        row = jnp.take(rows, i, axis=1)
        col = jnp.take(cols, j, axis=1)
        active = ~placed

        # --- phase A: merge into an entry of this key, placed at round k -
        g = state4[:, row, col]                    # (4, n, b)
        e_fs, e_fd, e_t = g[0], g[1], g[2]
        match = (e_fs == fs[:, None]) & (e_fd == fd[:, None]) & \
            (e_fs != EMPTY) & (g[3] == k.astype(jnp.uint32))
        if match_time:
            match &= e_t == t[:, None]
        has_match = jnp.any(match, axis=-1) & active
        slot = jnp.argmax(match, axis=-1).astype(jnp.int32)
        add_w = jnp.where(has_match, w, 0.0)
        wmat = wmat.at[row, col, slot].add(add_w)
        placed = placed | has_match
        active = ~placed

        # --- phase B: claim free slots, arrival order within a bucket ---
        bid = (row * d + col).astype(jnp.int32)
        order = jnp.take(orders, k, axis=0)
        sb = bid[order]
        act_s = jnp.where(active[order], 1, 0).astype(jnp.int32)
        csum = jnp.cumsum(act_s)
        excl = csum - act_s                        # actives before, global
        is_first = jnp.concatenate([pos1, sb[1:] != sb[:-1]])
        # excl is non-decreasing, so a max-scan of segment-start values
        # broadcasts each segment's base count
        seg_base = jax.lax.associative_scan(
            jnp.maximum, jnp.where(is_first, excl, 0))
        rank = jnp.zeros((n,), jnp.int32).at[order].set(excl - seg_base)

        emp = (state4[0] == EMPTY).reshape(d * d, b)
        emp_before = jnp.cumsum(emp, axis=-1) - emp.astype(jnp.int32)
        free_cnt = jnp.sum(emp, axis=-1)
        hit = emp[:, None, :] & (emp_before[:, None, :] ==
                                 jnp.arange(b, dtype=jnp.int32)[None, :, None])
        slot_table = jnp.argmax(hit, axis=-1).astype(jnp.int32)

        accept = active & (rank < free_cnt[bid])
        m = jnp.clip(rank, 0, b - 1)
        tgt = slot_table[bid, m]
        rowa = jnp.where(accept, row, d)
        upd = jnp.stack([fs, fd, t,
                         jnp.broadcast_to(k.astype(jnp.uint32), (n,))])
        state4 = state4.at[:, rowa, col, tgt].set(upd, mode="drop")
        wmat = wmat.at[rowa, col, tgt].add(w, mode="drop")
        placed = placed | accept
        return state4, wmat, placed, k + 1

    def round_cond(carry):
        # rounds where every item is already placed are no-ops in the
        # reference loop — skipping them is free and result-identical
        _, _, placed, k = carry
        return (k < r * r) & jnp.any(~placed)

    state4, wmat, placed, _ = jax.lax.while_loop(
        round_cond, round_body,
        (state4, wmat, ~valid, jnp.asarray(0, jnp.int32)))
    return state4, wmat, placed & valid


def _empty_state4(d: int, b: int):
    fps = jnp.full((2, d, d, b), EMPTY, jnp.uint32)
    rest = jnp.zeros((2, d, d, b), jnp.uint32)
    return jnp.concatenate([fps, rest])


@functools.partial(jax.jit, static_argnames=("params",))
def insert_chunks_pre(fs, fd, rows, cols, w, t, valid, pm_order, pm_same,
                      orders, params: HiggsParams):
    """Batched multi-leaf insertion: ONE vmapped launch over a stacked
    ``(n_leaves, chunk_pad)`` batch with host-precomputed orders.

    Returns (state4 (L, 4, d, d, b), wmat (L, d, d, b), spill mask
    (L, n) bool, premerged weights (L, n)); state4 rows are
    (fp_s, fp_d, t, idx).  Bit-identical to per-leaf :func:`insert_chunk`.
    """
    d, b = params.d1, params.b
    r = params.r if params.use_mmb else 1

    def one(fs_i, fd_i, rows_i, cols_i, w_i, t_i, valid_i, po_i, ps_i, o_i):
        w_m, v_m = _premerge_pre(w_i, valid_i, po_i, ps_i)
        state4, wmat, placed = place_entries_pre(
            _empty_state4(d, b), jnp.zeros((d, d, b), jnp.float32),
            fs_i, fd_i, rows_i, cols_i, w_m, t_i, v_m, o_i,
            d=d, b=b, r=r, match_time=True)
        return state4, wmat, v_m & ~placed, w_m

    return jax.vmap(one)(fs, fd, rows, cols, w, t, valid, pm_order,
                         pm_same, orders)


# ---------------------------------------------------------------------------
# aggregation (paper Alg. 2, with closed-form chain recovery)
# ---------------------------------------------------------------------------

def recover_leaf_coords(addr, fp, idx_pair, level: int, params: HiggsParams,
                        side: str):
    """From a stored entry at `level`, recover (leaf fp F1 bits, leaf base
    address), for one side ('s' -> chain index i, 'd' -> j)."""
    r = params.r if params.use_mmb else 1
    R, F1, d1 = params.R, params.F1, params.d1
    s = R * (level - 1)
    k = (idx_pair // r) if side == "s" else (idx_pair % r)
    leaf_pos = (addr >> jnp.uint32(s)).astype(jnp.uint32)
    fbits = addr & jnp.uint32((1 << s) - 1)
    f1 = (fbits << jnp.uint32(F1 - s)) | fp if s else fp
    base = chain_base_from_pos(leaf_pos, k.astype(jnp.int32), r, d1)
    return f1, base


def coords_at_level(f1, base, level: int, params: HiggsParams):
    """(fp_l, rows_l (n, r)) probe/placement coordinates at a tree level,
    derived by shifting the leaf-level chain (DESIGN.md §3)."""
    r = params.r if params.use_mmb else 1
    R, F1, d1 = params.R, params.F1, params.d1
    s = R * (level - 1)
    rows1 = chain_from_base(base, r, d1)                      # (n, r)
    fp_l = f1 & jnp.uint32((1 << (F1 - s)) - 1)
    if s == 0:
        return fp_l, rows1
    top = (f1 >> jnp.uint32(F1 - s)).astype(jnp.uint32)
    rows_l = (rows1 << jnp.uint32(s)) | top[..., None]
    return fp_l, rows_l


def _aggregate_impl(children: NodeState, ob_f1s, ob_f1d, ob_bs, ob_bd,
                    ob_w, ob_valid, params: HiggsParams, level: int):
    theta, d, _, b = children.fp_s.shape
    r = params.r if params.use_mmb else 1
    plevel = level + 1
    dp = params.d(plevel)

    rows_idx = jnp.arange(d, dtype=jnp.uint32)
    row_grid = jnp.broadcast_to(rows_idx[None, :, None, None], children.fp_s.shape)
    col_grid = jnp.broadcast_to(rows_idx[None, None, :, None], children.fp_s.shape)

    def flat(x):
        return x.reshape(-1)

    e_fs, e_fd = flat(children.fp_s), flat(children.fp_d)
    e_w, e_idx = flat(children.w), flat(children.idx)
    e_row, e_col = flat(row_grid), flat(col_grid)
    e_valid = e_fs != EMPTY

    f1s, base_s = recover_leaf_coords(e_row, e_fs, e_idx, level, params, "s")
    f1d, base_d = recover_leaf_coords(e_col, e_fd, e_idx, level, params, "d")

    if ob_f1s is not None:
        f1s = jnp.concatenate([f1s, jnp.asarray(ob_f1s, jnp.uint32)])
        f1d = jnp.concatenate([f1d, jnp.asarray(ob_f1d, jnp.uint32)])
        base_s = jnp.concatenate([base_s, jnp.asarray(ob_bs, jnp.uint32)])
        base_d = jnp.concatenate([base_d, jnp.asarray(ob_bd, jnp.uint32)])
        e_w = jnp.concatenate([e_w, jnp.asarray(ob_w, jnp.float32)])
        e_valid = jnp.concatenate([e_valid, jnp.asarray(ob_valid, bool)])

    fp_s_p, rows_p = coords_at_level(f1s, base_s, plevel, params)
    fp_d_p, cols_p = coords_at_level(f1d, base_d, plevel, params)

    parent = make_node(dp, b)
    t0 = jnp.zeros_like(e_w, dtype=jnp.uint32)
    parent, placed = place_entries(parent, fp_s_p, fp_d_p, rows_p, cols_p,
                                   e_w, t0, e_valid,
                                   d=dp, b=b, r=r, match_time=False)
    spill = e_valid & ~placed
    order = jnp.argsort(~spill, stable=True)
    out = dict(f1s=f1s[order], f1d=f1d[order], base_s=base_s[order],
               base_d=base_d[order], w=e_w[order])
    return parent, out, jnp.sum(spill)


@functools.partial(jax.jit, static_argnames=("params", "level"))
def aggregate_children(children: NodeState, ob_f1s, ob_f1d, ob_bs, ob_bd,
                       ob_w, ob_valid, params: HiggsParams, level: int):
    """Aggregate theta child matrices (stacked on axis 0) at `level` plus
    their overflow-block items (canonical (f1, base) form) into one parent
    matrix at level+1.

    Returns (parent NodeState, spill dict {f1s, f1d, base_s, base_d, w},
    count).  Spilled items go to the parent's host-side overflow block.
    """
    return _aggregate_impl(children, ob_f1s, ob_f1d, ob_bs, ob_bd,
                           ob_w, ob_valid, params, level)


def host_recover_leaf_coords(addr, fp, idx_pair, level: int,
                             params: HiggsParams, side: str):
    """NumPy twin of :func:`recover_leaf_coords` (same uint32 wraparound)."""
    r = params.r if params.use_mmb else 1
    R, F1, d1 = params.R, params.F1, params.d1
    s = R * (level - 1)
    k = (idx_pair // np.uint32(r)) if side == "s" \
        else (idx_pair % np.uint32(r))
    leaf_pos = (addr >> np.uint32(s)).astype(np.uint32)
    fbits = (addr & np.uint32((1 << s) - 1)).astype(np.uint32)
    f1 = ((fbits << np.uint32(F1 - s)) | fp).astype(np.uint32) if s else fp
    _, B, Ainv = lcg_tables(r, d1)
    k = k.astype(np.int64)
    base = ((Ainv[k] * (leaf_pos - B[k]).astype(np.uint32))
            .astype(np.uint32) % np.uint32(d1))
    return f1, base


def host_coords_at_level(f1, base, level: int, params: HiggsParams):
    """NumPy twin of :func:`coords_at_level`."""
    r = params.r if params.use_mmb else 1
    R, F1, d1 = params.R, params.F1, params.d1
    s = R * (level - 1)
    rows1 = host_chain_from_base(base, r, d1)
    fp_l = (f1 & np.uint32((1 << (F1 - s)) - 1)).astype(np.uint32)
    if s == 0:
        return fp_l, rows1
    top = (f1 >> np.uint32(F1 - s)).astype(np.uint32)
    rows_l = ((rows1 << np.uint32(s)) | top[..., None]).astype(np.uint32)
    return fp_l, rows_l


@functools.partial(jax.jit, static_argnames=("params", "level"))
def aggregate_children_pre(fp_s_p, fp_d_p, rows_p, cols_p, w, valid, orders,
                           params: HiggsParams, level: int):
    """Build every ready parent at a level in ONE vmapped launch over
    host-prepared parent-level coordinates (entries + OB items already
    concatenated and recovered on the host).

    fp_s_p/fp_d_p/w/valid: (m, N); rows_p/cols_p: (m, N, r); orders:
    (m, r*r, N).  Returns (state4 (m, 4, dp, dp, b), wmat, spill mask
    (m, N)).  Bit-identical to per-parent :func:`aggregate_children`.
    """
    b = params.b
    r = params.r if params.use_mmb else 1
    dp = params.d(level + 1)

    def one(fs_i, fd_i, rows_i, cols_i, w_i, v_i, o_i):
        t0 = jnp.zeros_like(fs_i, dtype=jnp.uint32)
        state4, wmat, placed = place_entries_pre(
            _empty_state4(dp, b), jnp.zeros((dp, dp, b), jnp.float32),
            fs_i, fd_i, rows_i, cols_i, w_i, t0, v_i, o_i,
            d=dp, b=b, r=r, match_time=False)
        return state4, wmat, v_i & ~placed

    return jax.vmap(one)(fp_s_p, fp_d_p, rows_p, cols_p, w, valid, orders)


# ---------------------------------------------------------------------------
# probes (query primitives) — reference implementations for the kernels
# ---------------------------------------------------------------------------

def probe_edge(nodes: NodeState, node_mask, fs, fd, rows, cols, ts, te, *,
               match_time: bool):
    """Sum of the weights of a batch of edge queries' entries over a batch
    of matrices.

    nodes: stacked NodeState with leading axis m; node_mask: (m,) bool for
    padded node lists.
    fs/fd: (q,), rows/cols: (q, r), ts/te: scalars or (q,).
    Returns (q,) float32.

    An entry at candidate bucket (rows[a], cols[c]) is the query's only
    where its fingerprints match and its stored ``idx`` is ``a * r + c``,
    the chain position the query reached it at: the same fingerprints
    under another position belong to another base row, another key.
    Each entry is therefore counted once, even for repeated candidates.
    """
    q, r = rows.shape
    wmask = jnp.where(node_mask, 1.0, 0.0)[:, None, None, None]
    pos = (jnp.arange(r, dtype=jnp.uint32)[:, None] * r
           + jnp.arange(r, dtype=jnp.uint32)[None, :])[None, :, :, None]

    def one(fs_i, fd_i, row_i, col_i, ts_i, te_i):
        # (m, r, r, b) gathered buckets
        at = (slice(None), row_i[:, None], col_i[None, :])
        # EMPTY (0xFFFFFFFF) can never equal an F-bit fingerprint, so the
        # equality test alone excludes free entries.
        match = (nodes.fp_s[at] == fs_i) & (nodes.fp_d[at] == fd_i) & \
            (nodes.idx[at] == pos)
        if match_time:
            et = nodes.t[at]
            match &= (et >= ts_i) & (et <= te_i)
        return jnp.sum(jnp.where(match, nodes.w[at] * wmask, 0.0))

    ts = jnp.broadcast_to(jnp.asarray(ts, jnp.uint32), (q,))
    te = jnp.broadcast_to(jnp.asarray(te, jnp.uint32), (q,))
    return jax.vmap(one)(fs, fd, rows.astype(jnp.int32),
                         cols.astype(jnp.int32), ts, te)


def probe_vertex(nodes: NodeState, node_mask, fv, rows, ts, te, *,
                 direction: str, match_time: bool):
    """Vertex query: sum weights over r candidate rows (source direction)
    or columns (destination direction) across m matrices.

    fv: (q,), rows: (q, r).  Returns (q,) float32.  An entry in candidate
    row ``rows[a]`` is the query's only where its fingerprint matches and
    its stored chain index names position ``a`` on that side:
    ``idx // r == a`` (out) or ``idx % r == a`` (in).
    """
    r = rows.shape[1]
    wmask = jnp.where(node_mask, 1.0, 0.0)[:, None, None, None]
    pos = jnp.arange(r, dtype=jnp.uint32)[None, :, None, None]

    def one(fv_i, row_i):
        if direction == "out":
            efp, ew, et, ei = (a[:, row_i, :, :] for a in (
                nodes.fp_s, nodes.w, nodes.t, nodes.idx))   # (m, r, d, b)
            at_pos = ei // r == pos
        else:
            efp, ew, et, ei = (a[:, :, row_i, :].transpose(0, 2, 1, 3)
                               for a in (nodes.fp_d, nodes.w, nodes.t,
                                         nodes.idx))
            at_pos = ei % r == pos
        match = (efp == fv_i) & at_pos             # EMPTY never matches
        if match_time:
            match &= (et >= ts) & (et <= te)
        return jnp.sum(jnp.where(match, ew * wmask, 0.0))

    ts = jnp.asarray(ts, jnp.uint32)
    te = jnp.asarray(te, jnp.uint32)
    return jax.vmap(one)(fv, rows.astype(jnp.int32))
