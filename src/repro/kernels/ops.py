"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU backends (this container) and False
on TPU, where the kernels compile to Mosaic.  The probe kernels tile
(matrix, row-tile) blocks through VMEM; for very large upper-level
matrices (d >= 1024) callers should keep the query batch q modest
(<= 128) so the (q, tr, d, b) compare tile stays within VMEM/VREG budget —
the benchmark harness and HiggsSketch respect this.
"""
from __future__ import annotations

import functools

import jax

from repro.core.cmatrix import NodeState
from repro.kernels import leaf_insert as _li
from repro.kernels import probe as _pr

# shared auto-detect (kept under the old private name for callers)
_default_interpret = _li.default_interpret


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def leaf_insert(node: NodeState, fs, fd, rows, cols, w, t, valid, *,
                r: int, interpret: bool | None = None):
    if interpret is None:
        interpret = _default_interpret()
    return _li.leaf_insert_pallas(node, fs, fd, rows, cols, w, t, valid,
                                  r=r, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def leaf_insert_batched(nodes: NodeState, fs, fd, rows, cols, w, t, valid,
                        *, r: int, interpret: bool | None = None):
    """One grid-over-leaves launch for a stacked (L, n) chunk batch."""
    if interpret is None:
        interpret = _default_interpret()
    return _li.leaf_insert_batched_pallas(nodes, fs, fd, rows, cols, w, t,
                                          valid, r=r, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("match_time", "interpret"))
def edge_probe(nodes: NodeState, node_mask, fs, fd, rows, cols, ts, te, *,
               match_time: bool, interpret: bool | None = None):
    if interpret is None:
        interpret = _default_interpret()
    return _pr.edge_probe_pallas(nodes, node_mask, fs, fd, rows, cols,
                                 ts, te, match_time=match_time,
                                 interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("direction", "match_time", "interpret"))
def vertex_probe(nodes: NodeState, node_mask, fv, rows, ts, te, *,
                 direction: str, match_time: bool,
                 interpret: bool | None = None):
    if interpret is None:
        interpret = _default_interpret()
    return _pr.vertex_probe_pallas(nodes, node_mask, fv, rows, ts, te,
                                   direction=direction,
                                   match_time=match_time,
                                   interpret=interpret)


# ---------------------------------------------------------------------------
# stacked-shard probe entry points (repro.shard)
# ---------------------------------------------------------------------------
#
# A sharded fleet answers fan-out queries by probing the SAME query batch
# against S shards' node pools at one (level, time-range class).  These
# entry points take the pools stacked on a leading shard axis — NodeState
# fields (S, m, d, d, b), node_mask (S, m) — and return per-shard partial
# sums (S, q) from ONE launch, so the fleet keeps the single-sketch
# planner's one-dispatch-per-(level, class) contract.  The body vmaps the
# reference probes (pure jnp, identical arithmetic to the per-shard path);
# on a multi-device host the caller shards the leading axis across the
# device mesh first (ShardedHiggs.place_stacked) and XLA partitions the
# launch.

@functools.partial(jax.jit, static_argnames=("match_time",))
def edge_probe_stacked(nodes: NodeState, node_mask, fs, fd, rows, cols,
                       ts, te, *, match_time: bool):
    from repro.core import cmatrix

    def one(n, m):
        return cmatrix.probe_edge(n, m, fs, fd, rows, cols, ts, te,
                                  match_time=match_time)

    return jax.vmap(one)(nodes, node_mask)


@functools.partial(jax.jit, static_argnames=("direction", "match_time"))
def vertex_probe_stacked(nodes: NodeState, node_mask, fv, rows, ts, te, *,
                         direction: str, match_time: bool):
    from repro.core import cmatrix

    def one(n, m):
        return cmatrix.probe_vertex(n, m, fv, rows, ts, te,
                                    direction=direction,
                                    match_time=match_time)

    return jax.vmap(one)(nodes, node_mask)


# ---------------------------------------------------------------------------
# higgsxla shape corpus (compiled-path analyzer entry points)
# ---------------------------------------------------------------------------
#
# Each kernel wrapper above declares representative trace shapes here;
# ``python -m repro.analysis.xla`` traces them and gates transfer /
# recompile / dtype / structure / cost budgets in CI.  Shapes mirror the
# production callers: drains pow2-pad the chunk axis (lo=64) and the
# jitted backends pow2-pad the leaf axis (higgs._close_leaves_batched),
# so ONE compile key per pow2 bucket is the declared contract
# (``expected_compile_keys``).  ``host_args`` marks operands that are
# materialized from host numpy at the call site — the transfer budget
# the ROADMAP device-resident refactor ratchets toward zero.

def xla_entry_points():
    import jax.numpy as jnp

    from repro.analysis.xla.registry import EntryPoint, TraceCase
    from repro.core import cmatrix
    from repro.core.params import HiggsParams

    p = HiggsParams()
    d, b, r, n = p.d1, p.b, p.r, 1024
    u32, i32, f32 = jnp.uint32, jnp.int32, jnp.float32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    def node(lead=()):
        shp = (*lead, d, d, b)
        return NodeState(sds(shp, u32), sds(shp, u32), sds(shp, f32),
                         sds(shp, u32), sds(shp, u32))

    def chunk(lead=()):
        vec = (*lead, n)
        return (sds(vec, u32), sds(vec, u32), sds((*vec, r), u32),
                sds((*vec, r), u32), sds(vec, f32), sds(vec, u32),
                sds(vec, jnp.bool_))

    def build_leaf_insert():
        cases = [TraceCase("d16_n1024", (node(), *chunk()),
                           {"r": r, "interpret": True})]
        return leaf_insert, ("r", "interpret"), cases

    def build_ingest_fused():
        from repro.kernels.pipeline import _ingest_step
        cap = 64
        slabs = tuple(node((cap,)))
        kw = {"r": r, "F1": p.F1, "d1": d, "b": b, "seed": p.seed,
              "interpret": True}
        cases = [TraceCase(f"L{L}_n{n}",
                           (*slabs, sds((4, L, n), u32), sds((L,), i32),
                            sds((), i32), sds((), i32)), dict(kw))
                 for L in (4, 8)]
        return _ingest_step, ("r", "F1", "d1", "b", "seed",
                              "interpret"), cases

    def probe_args(m, q):
        return (node((m,)), sds((m,), jnp.bool_), sds((q,), u32),
                sds((q,), u32), sds((q, r), u32), sds((q, r), u32),
                sds((), u32), sds((), u32))

    def build_edge_probe():
        cases = [
            TraceCase("m8_q16", probe_args(8, 16),
                      {"match_time": False, "interpret": True}),
            TraceCase("m8_q16_filtered", probe_args(8, 16),
                      {"match_time": True, "interpret": True}),
        ]
        return edge_probe, ("match_time", "interpret"), cases

    def build_vertex_probe():
        m, q = 8, 16
        args = (node((m,)), sds((m,), jnp.bool_), sds((q,), u32),
                sds((q, r), u32), sds((), u32), sds((), u32))
        cases = [TraceCase("m8_q16_out", args,
                           {"direction": "out", "match_time": True,
                            "interpret": True})]
        return vertex_probe, ("direction", "match_time", "interpret"), cases

    def build_edge_probe_stacked():
        S, m, q = 4, 8, 16
        args = (node((S, m)), sds((S, m), jnp.bool_), sds((q,), u32),
                sds((q,), u32), sds((q, r), u32), sds((q, r), u32),
                sds((), u32), sds((), u32))
        cases = [TraceCase("S4_m8_q16", args, {"match_time": True})]
        return edge_probe_stacked, ("match_time",), cases

    def build_vertex_probe_stacked():
        S, m, q = 4, 8, 16
        args = (node((S, m)), sds((S, m), jnp.bool_), sds((q,), u32),
                sds((q, r), u32), sds((), u32), sds((), u32))
        cases = [TraceCase("S4_m8_q16_in", args,
                           {"direction": "in", "match_time": True})]
        return vertex_probe_stacked, ("direction", "match_time"), cases

    def build_insert_chunks_vector():
        pv = HiggsParams(insert_backend="vector")
        L = 4
        args = (sds((L, n), u32), sds((L, n), u32), sds((L, n, r), u32),
                sds((L, n, r), u32), sds((L, n), f32), sds((L, n), u32),
                sds((L, n), jnp.bool_), sds((L, n), i32),
                sds((L, n), jnp.bool_), sds((L, r * r, n), i32))
        cases = [TraceCase("L4_n1024", args, {"params": pv})]
        return cmatrix.insert_chunks_pre, ("params",), cases

    def build_aggregate_fused():
        from repro.kernels.pipeline import _aggregate_step
        # production shapes: the theta-child block of two level-1
        # parents, sliced from the level-1 slabs; the overflow columns
        # are the only tensor h2d operand
        level, mp, obp = 1, 2, 16
        block = (sds((mp * p.theta, d, d, b), u32),
                 sds((mp * p.theta, d, d, b), u32),
                 sds((mp * p.theta, d, d, b), f32),
                 sds((mp * p.theta, d, d, b), u32))
        ob_pack = sds((6, mp, obp), u32)
        cases = [TraceCase("l1_m2", (*block, ob_pack),
                           {"mp": mp, "theta": p.theta, "level": level,
                            "params": p})]
        return _aggregate_step, ("mp", "theta", "level", "params"), cases

    # the row moves around the step, at the pool capacities of a
    # 1,096,440-edge wiki-talk stream (1682 leaves: level-1 slabs hold
    # 2048 rows, level-2 slabs 512)
    def build_take_rows():
        from repro.kernels.pipeline import _take_rows
        mp, cap = 2, 2048
        slab = (cap, d, d, b)
        args = (sds(slab, u32), sds(slab, u32), sds(slab, f32),
                sds(slab, u32), sds((), i32))
        cases = [TraceCase(f"l1_cap{cap}_rows{mp * p.theta}", args,
                           {"rows": mp * p.theta})]
        return _take_rows, ("rows",), cases

    def build_append_rows():
        from repro.kernels.pipeline import _append_rows
        mp, cap, dp = 2, 512, p.d(2)
        dts = (u32, u32, f32, u32, u32)
        slabs = tuple(sds((cap, dp, dp, b), dt) for dt in dts)
        parents = NodeState(*(sds((mp, dp, dp, b), dt) for dt in dts))
        cases = [TraceCase(f"l2_cap{cap}_m{mp}",
                           (*slabs, parents, sds((), i32), sds((), i32)),
                           {})]
        return _append_rows, (), cases

    interp = frozenset({"interpret"})
    return [
        # pallas leaf insertion: chunks arrive as host numpy (w/t/valid;
        # hashes transfer upstream of the fs/rows device precompute)
        EntryPoint("kernels.leaf_insert", build_leaf_insert,
                   host_args=(5, 6, 7), fetch_output=True,
                   expected_compile_keys=1, tags=interp),
        # the production pallas drain: device-resident pool slabs are
        # donated, only the packed raw staging block + per-leaf lengths
        # cross h2d and nothing returns but the small spill mask
        # (fetched separately, outside this launch's output contract)
        EntryPoint("kernels.ingest_fused", build_ingest_fused,
                   host_args=(5, 6, 7, 8), fetch_output=False,
                   expected_compile_keys=2, tags=interp),
        EntryPoint("kernels.edge_probe", build_edge_probe,
                   host_args=tuple(range(8)), fetch_output=True,
                   expected_compile_keys=2, tags=interp),
        EntryPoint("kernels.vertex_probe", build_vertex_probe,
                   host_args=tuple(range(6)), fetch_output=True,
                   expected_compile_keys=1, tags=interp),
        # stacked-shard probes: pools are device-placed (place_stacked);
        # only query coords + scalars cross per launch
        EntryPoint("kernels.edge_probe_stacked", build_edge_probe_stacked,
                   host_args=(2, 3, 4, 5, 6, 7), fetch_output=True,
                   expected_compile_keys=1),
        EntryPoint("kernels.vertex_probe_stacked",
                   build_vertex_probe_stacked,
                   host_args=(2, 3, 4, 5), fetch_output=True,
                   expected_compile_keys=1),
        # vector insert backend: every operand is jnp.asarray'd from host
        EntryPoint("kernels.insert_chunks_vector",
                   build_insert_chunks_vector,
                   host_args=tuple(range(10)), fetch_output=True,
                   expected_compile_keys=1),
        # the fused aggregation cascade: the child block is sliced off
        # the device-resident slabs and the parents are appended to the
        # donated parent slabs by two small capacity-keyed launches
        # (pipeline._take_rows/_append_rows, which take the three
        # position scalars); this step's only tensor h2d operand is the
        # packed OB staging block (a host structure, six uint32 rows like
        # ingest's raw staging), and nothing returns but the small spill
        # mask (fetched separately, outside this launch's output
        # contract).  Host-storage backends aggregate through the numpy
        # twin with no XLA site.
        EntryPoint("kernels.aggregate_fused", build_aggregate_fused,
                   host_args=(4,),
                   fetch_output=False, expected_compile_keys=1),
        # the cascade's row moves: the child block leaves the donated
        # child slabs from a host offset, the parents land in the donated
        # parent slabs at a host offset and count; only those scalars
        # cross, nothing returns
        EntryPoint("kernels.aggregate_take_rows", build_take_rows,
                   host_args=(4,), fetch_output=False,
                   expected_compile_keys=1),
        EntryPoint("kernels.aggregate_append_rows", build_append_rows,
                   host_args=(6, 7), fetch_output=False,
                   expected_compile_keys=1),
    ]
