"""Compile rehearsal for one TPU v5e chip: every kernel and jitted step of
the chip path, compiled (not run) for a described ``v5e:2x2`` topology at
production shapes.  This is where Mosaic's tiling and memory-space
refusals show up without a chip; interpret-mode tests cannot see them.

Production shapes: the default sketch (d1=16, F1=19, b=3, r=4), chunks
padded to 1024 items, a 8192-item pipeline batch aligned to 12 leaves and
padded to 16, and level-1 slabs at the 16384-leaf capacity a
wiki-talk-scale stream (7.8M edges) grows to.  The sharded fleet's
probe is compiled as one program over the described 4-chip mesh.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro import compat
from repro.api import planner
from repro.core import cmatrix
from repro.core.cmatrix import NodeState
from repro.core.params import HiggsParams
from repro.kernels import leaf_insert, ops, pipeline, probe

P = HiggsParams()
D, B, R = P.d1, P.b, P.r
N = 1024              # chunk pad
L = 16                # leaves per drain, pow2-padded
CAP = 16384           # level-1 slab capacity
u32, i32, f32, b1 = jnp.uint32, jnp.int32, jnp.float32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("shard",))


def _compile(fn, sharding, *shapes, **static):
    """Compile ``fn(*args, **static)`` for the described chip; ``fn`` is a
    production jit (static arguments by keyword) or a plain function."""
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*args, **static).compile()


def _sds(shape, dt):
    return jax.ShapeDtypeStruct(shape, dt)


def _nodes(lead, d=D):
    shp = (*lead, d, d, B)
    return NodeState(_sds(shp, u32), _sds(shp, u32), _sds(shp, f32),
                     _sds(shp, u32), _sds(shp, u32))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_insert_chunks_vector(one_chip):
    pv = HiggsParams(insert_backend="vector")
    _compile(cmatrix.insert_chunks_pre, one_chip,
             _sds((L, N), u32), _sds((L, N), u32), _sds((L, N, R), u32),
             _sds((L, N, R), u32), _sds((L, N), f32), _sds((L, N), u32),
             _sds((L, N), b1), _sds((L, N), i32), _sds((L, N), b1),
             _sds((L, R * R, N), i32), params=pv)


@pytest.mark.parametrize("level,mp,ob_pad", [(1, 4, 64), (2, 1, 0)])
def test_aggregate_step(one_chip, level, mp, ob_pad):
    block = _nodes((mp * P.theta,), P.d(level))
    _compile(pipeline._aggregate_step, one_chip, block.fp_s, block.fp_d,
             block.w, block.idx, _sds((6, mp, ob_pad), u32), mp=mp,
             theta=P.theta, level=level, params=P)


def test_aggregate_row_moves(one_chip):
    child = _nodes((CAP,))
    _compile(pipeline._take_rows, one_chip, child.fp_s, child.fp_d, child.w,
             child.idx, _sds((), i32), rows=4 * P.theta)
    _compile(pipeline._append_rows, one_chip, *_nodes((CAP // 4,), 2 * D),
             _nodes((4,), 2 * D), _sds((), i32), _sds((), i32))


def test_ingest_step(one_chip):
    compiled = _compile(
        pipeline._ingest_step, one_chip, *_nodes((CAP,)),
        _sds((4, L, N), u32), _sds((L,), i32), _sds((), i32),
        _sds((), i32), r=R, F1=P.F1, d1=D, b=B, seed=P.seed,
        interpret=False)
    _assert_kernel(compiled)


def test_leaf_insert(one_chip):
    def fn(node, fs, fd, rows, cols, w, t, valid):
        return leaf_insert.leaf_insert_pallas(node, fs, fd, rows, cols, w,
                                              t, valid, r=R,
                                              interpret=False)
    _assert_kernel(_compile(
        fn, one_chip, _nodes(()), _sds((N,), u32), _sds((N,), u32),
        _sds((N, R), i32), _sds((N, R), i32), _sds((N,), f32),
        _sds((N,), u32), _sds((N,), b1)))


@pytest.mark.parametrize("level,cap,m,q,match_time", [
    (1, CAP, 64, 128, False),
    (1, CAP, 8, 128, True),
    (3, CAP // 16, 16, 128, False),
])
def test_edge_probe_fused(one_chip, level, cap, m, q, match_time):
    _compile(planner._edge_probe_fused, one_chip, _nodes((cap,), P.d(level)),
             _sds((m,), i32), _sds((m,), b1), _sds((q,), u32),
             _sds((q,), u32), _sds((q,), u32), _sds((q,), u32),
             _sds((), u32), _sds((), u32), level=level, params=P,
             match_time=match_time)


@pytest.mark.parametrize("direction", ["out", "in"])
def test_vertex_probe_fused(one_chip, direction):
    _compile(planner._vertex_probe_fused, one_chip, _nodes((CAP,)),
             _sds((64,), i32), _sds((64,), b1), _sds((128,), u32),
             _sds((128,), u32), _sds((), u32), _sds((), u32), level=1,
             params=P, direction=direction, match_time=False)


@pytest.mark.parametrize("d,m,q", [(D, 64, 128), (4 * D, 16, 128)])
@pytest.mark.parametrize("match_time", [False, True])
def test_edge_probe_pallas(one_chip, d, m, q, match_time):
    def fn(nodes, mask, fs, fd, rows, cols, ts, te):
        return probe.edge_probe_pallas(nodes, mask, fs, fd, rows, cols, ts,
                                       te, match_time=match_time,
                                       interpret=False)
    _assert_kernel(_compile(
        fn, one_chip, _nodes((m,), d), _sds((m,), b1), _sds((q,), u32),
        _sds((q,), u32), _sds((q, R), i32), _sds((q, R), i32),
        _sds((), u32), _sds((), u32)))


@pytest.mark.parametrize("d,m,q", [(D, 64, 128), (4 * D, 16, 128)])
@pytest.mark.parametrize("direction", ["out", "in"])
def test_vertex_probe_pallas(one_chip, d, m, q, direction):
    def fn(nodes, mask, fv, rows, ts, te):
        return probe.vertex_probe_pallas(nodes, mask, fv, rows, ts, te,
                                         direction=direction,
                                         match_time=True, interpret=False)
    _assert_kernel(_compile(
        fn, one_chip, _nodes((m,), d), _sds((m,), b1), _sds((q,), u32),
        _sds((q, R), i32), _sds((), u32), _sds((), u32)))


@pytest.mark.parametrize("kind", ["edge", "vertex_in"])
def test_stacked_probe_shard_map(four_chips, kind):
    """The 4-shard fleet's probe: one program across a 4-chip mesh, the
    shard axis split, the queries replicated (ShardedHiggs.run_stacked
    under parallel="shard_map"); 512 level-1 nodes per shard."""
    shard, rep = PartitionSpec("shard"), PartitionSpec()

    def on(spec, shape, dt):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(four_chips, spec))

    m, q = 512, 128
    nodes = NodeState(*(on(shard, (4, m, D, D, B), dt)
                        for dt in (u32, u32, f32, u32, u32)))
    mask = on(shard, (4, m), b1)
    if kind == "edge":
        fn = functools.partial(ops.edge_probe_stacked, match_time=True)
        args = (on(rep, (q,), u32), on(rep, (q,), u32),
                on(rep, (q, R), u32), on(rep, (q, R), u32))
    else:
        fn = functools.partial(ops.vertex_probe_stacked, direction="in",
                               match_time=True)
        args = (on(rep, (q,), u32), on(rep, (q, R), u32))
    args += (on(rep, (), u32), on(rep, (), u32))
    mapped = compat.shard_map(fn, mesh=four_chips,
                              in_specs=(shard, shard) + (rep,) * len(args),
                              out_specs=shard)
    compiled = jax.jit(mapped).lower(nodes, mask, *args).compile()
    assert compiled.output_shardings.spec == shard
