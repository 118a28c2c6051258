"""Trace every registered entry point over its shape corpus.

For each (entry, case) the tracer runs the real jax pipeline —
``jit(fn).trace(*avals)`` for the jaxpr, then lower + compile for the
optimized HLO — and distills one :class:`Artifact`: the compile-cache
key, transfer inventory, callback/convert/f64 evidence, structural HLO
findings and trip-count-scaled costs.  Rules (``rules.py``) never look
at jax objects, only at artifacts, so they stay cheap to unit-test.

Trace *failures* are artifacts too: an implicit ``np.asarray`` on a
tracer raises at trace time, which is exactly the X1 evidence we want,
so exceptions are classified rather than propagated.
"""
from __future__ import annotations

import dataclasses
import math
import re

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np

from repro.analysis.xla import lowering
from repro.analysis.xla.registry import EntryPoint, TraceCase
from repro.launch import hlo_analysis

#: jaxpr primitives that round-trip through the host per call
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "infeed", "outfeed")

_HLO_CALLBACK_RE = re.compile(
    r'custom_call_target="([^"]*(?:callback|infeed|outfeed)[^"]*)"')


@dataclasses.dataclass
class Artifact:
    """Everything the rules need to know about one traced case."""
    entry: EntryPoint
    case: TraceCase
    cache_key: str | None = None
    python_scalars: int = 0
    host_operands: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    callback_prims: tuple = ()
    hlo_callbacks: tuple = ()
    upcasts: tuple = ()                # sorted unique (src, dst) pairs
    f64_avals: int = 0
    hlo_f64: bool = False
    structural: list = dataclasses.field(default_factory=list)
    unknown_trip_counts: int = 0
    flops: int = 0
    bytes_accessed: int = 0
    error_kind: str | None = None      # "host_materialization"|"trace_error"
    error: str | None = None


def _leaf_spec(x) -> tuple[tuple, str, bool]:
    """(shape, dtype, weak_type) — the compile-cache signature of one
    argument leaf.  Bare python scalars are weak-typed and churn the
    cache; everything else keys on its concrete aval."""
    if isinstance(x, (bool, int, float, complex)):
        return ((), type(x).__name__, True)
    shape = tuple(getattr(x, "shape", ()) or ())
    dtype = str(np.dtype(getattr(x, "dtype", np.asarray(x).dtype)))
    return (shape, dtype, bool(getattr(x, "weak_type", False)))


def _leaf_nbytes(x) -> int:
    shape, dtype, _ = _leaf_spec(x)
    return math.prod(shape) * np.dtype(dtype if dtype not in
                                       ("bool", "int", "float", "complex")
                                       else np.float64).itemsize


def case_cache_key(case: TraceCase, static_argnames: tuple[str, ...]) -> str:
    """Deterministic string form of the jit compile-cache key: dynamic
    leaf avals (shape/dtype/weak) + static kwarg values."""
    static = set(static_argnames)
    dyn_kwargs = {k: v for k, v in case.kwargs.items() if k not in static}
    leaves = jax.tree_util.tree_leaves((case.args, dyn_kwargs))
    parts = []
    for leaf in leaves:
        shape, dtype, weak = _leaf_spec(leaf)
        parts.append(f"{dtype}[{','.join(map(str, shape))}]"
                     f"{'*' if weak else ''}")
    statics = [f"{k}={case.kwargs[k]!r}" for k in sorted(static)
               if k in case.kwargs]
    return ",".join(parts) + "|" + ",".join(statics)


def _dtype_kind(dt) -> str:
    """f/i/u kind that also classifies the ml_dtypes floats (numpy
    reports bfloat16 etc. as kind 'V')."""
    if jnp.issubdtype(dt, jnp.floating):
        return "f"
    if jnp.issubdtype(dt, jnp.signedinteger):
        return "i"
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return "u"
    return "?"


def _walk_eqns(jaxpr, visit) -> None:
    """Depth-first over every equation incl. sub-jaxprs (scan/while/
    pallas bodies live in eqn params)."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    _walk_eqns(sub.jaxpr, visit)
                elif isinstance(sub, jex_core.Jaxpr):
                    _walk_eqns(sub, visit)


def _scan_jaxpr(art: Artifact, closed) -> None:
    callbacks: list[str] = []
    upcasts: set[tuple[str, str]] = set()
    f64 = [0]

    def visit(eqn):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS or name.endswith("_callback"):
            callbacks.append(name)
        if name == "convert_element_type":
            src = np.dtype(eqn.invars[0].aval.dtype)
            dst = np.dtype(eqn.params["new_dtype"])
            if (_dtype_kind(src) == _dtype_kind(dst)
                    and _dtype_kind(src) in "fiu"
                    and dst.itemsize > src.itemsize):
                upcasts.add((src.name, dst.name))
        for var in eqn.outvars:
            dt = getattr(getattr(var, "aval", None), "dtype", None)
            if dt is not None and np.dtype(dt) in (np.float64, np.complex128):
                f64[0] += 1

    _walk_eqns(closed.jaxpr, visit)
    art.callback_prims = tuple(sorted(set(callbacks)))
    art.upcasts = tuple(sorted(upcasts))
    art.f64_avals = f64[0]


def trace_case(entry: EntryPoint, jitted, static_argnames: tuple[str, ...],
               case: TraceCase) -> Artifact:
    art = Artifact(entry=entry, case=case)
    static = set(static_argnames)
    dyn_kwargs = {k: v for k, v in case.kwargs.items() if k not in static}
    art.python_scalars = sum(
        isinstance(x, (bool, int, float, complex))
        for x in jax.tree_util.tree_leaves((case.args, dyn_kwargs)))
    art.cache_key = case_cache_key(case, static_argnames)
    for i in entry.host_args:
        sub = jax.tree_util.tree_leaves(case.args[i])
        art.host_operands += len(sub)
        art.h2d_bytes += sum(_leaf_nbytes(x) for x in sub)

    try:
        traced = jitted.trace(*case.args, **case.kwargs)
        closed = traced.jaxpr
        _scan_jaxpr(art, closed)
        if entry.fetch_output:
            art.d2h_bytes = sum(
                math.prod(a.shape) * np.dtype(a.dtype).itemsize
                for a in closed.out_avals)
        record, hlo = lowering.compiled_report(traced.lower())
    except Exception as e:                    # trace evidence, not a crash
        name = type(e).__name__
        art.error = f"{name}: {e}".splitlines()[0][:300]
        art.error_kind = ("host_materialization"
                          if "Tracer" in name or "Concretization" in name
                          else "trace_error")
        return art
    art.flops = int(record["hlo_flops"])
    art.bytes_accessed = int(record["hlo_bytes_accessed"])
    art.unknown_trip_counts = int(record["unknown_trip_counts"])
    art.structural = hlo_analysis.structural_findings(hlo)
    art.hlo_f64 = "f64[" in hlo
    art.hlo_callbacks = tuple(sorted(set(_HLO_CALLBACK_RE.findall(hlo))))
    return art


def trace_entry(entry: EntryPoint) -> list[Artifact]:
    fn, static_argnames, cases = entry.build()
    jitted = lowering.jit_entry(fn, static_argnames=static_argnames)
    return [trace_case(entry, jitted, static_argnames, c) for c in cases]


def trace_entries(entries: list[EntryPoint]) -> list[Artifact]:
    out: list[Artifact] = []
    for entry in entries:
        out.extend(trace_entry(entry))
    return out
