"""The program's own spans and named scopes in a profiler trace.

The summary opens host spans named ``higgs.*`` (``repro.runtime.trace``)
and its fused device steps carry named scopes (``jax.named_scope``), so
one trace says what the host did while the device idled and which phase
of a device program took its time.  ``devtrace`` reads neither; this
module does, on the same plain event form:

* ``load(profile_dir)`` returns ``devtrace.load``'s events, plus every
  host event named ``higgs.*`` and, on each device plane, a line
  ``"XLA Scopes"`` of ``[program/scope, start_ns, dur_ns]`` for every
  outermost device operation (the body of a ``while`` runs inside the
  loop's own event, and counts as the loop's time).  The program is the
  ``XLA Modules`` event around the operation; the scope is the first
  named scope of the operation's ``op_name``, which the trace keeps
  only in the HLO of each program (the ``Hlo Proto`` stats of its
  ``/host:metadata`` plane), read here straight from the ``.xplane.pb``;
* ``reduce(events)`` works on that form alone, inside the harness's
  ``bench.traced`` span:

  - ``spans``: per span name, ``count``, ``total_s`` and ``self_s`` (its
    time less the union of the spans nested in it);
  - ``device_scopes``: device seconds per ``program/scope``;
  - ``idle_s``: the device's idle seconds by the span innermost over
    them (``loop`` where no span is open), averaged over device planes;
  - ``idle_gaps``: the longest device idle gaps, each labelled with the
    span that is innermost over most of the gap (``bench.insert``, then
    ``loop`` where no span is open).

A reader calls ``of(ctx)``: the reduction the harness put in
``ctx["spans"]``, or else that of the trace the harness wrote to
``.bench_trace`` in the checkout.  Where the program opened no
``higgs.*`` span, ``of`` returns ``None``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from pathlib import Path

import devtrace

PREFIX = "higgs."
HARNESS = ("bench.traced", "bench.insert")
SCOPES_LINE = "XLA Scopes"
# the directory ``bench/run.py`` traces into, in the checkout
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"
METADATA_PLANE = "/host:metadata"
_WRAPPER = re.compile(r"^[\w.-]*\(.*\)$")     # jit(f), vmap(g), ...


def _module_program(name: str) -> str:
    """``jit__aggregate_step(123)`` -> ``_aggregate_step``."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_scope(op_name: str) -> str:
    """The first named scope of an ``op_name`` path, ``""`` for none:
    the last component is the operation itself, and transform wrappers
    (``jit(f)``) are no scopes."""
    parts = [p for p in op_name.split("/")[:-1]
             if p and not _WRAPPER.match(p)]
    return parts[0] if parts else ""


# -- the few protobuf messages of an XSpace that carry op names ---------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entry:
# key = 1, value = 2), stat_metadata = 5; XEventMetadata: name = 2,
# stats = 5; XStat: metadata_id = 1, bytes_value = 6; XStatMetadata:
# name = 2; HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2; HloInstructionProto: name = 1,
# metadata = 7; OpMetadata.op_name = 2.

def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """``(field number, value)`` of one protobuf message: an int for
    varints, a memoryview for length-delimited fields."""
    i, end = 0, len(b)
    while i < end:
        tag, i = _varint(b, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield tag >> 3, v


def _field(b, num, default=None):
    for f, v in _fields(b):
        if f == num:
            return v
    return default


def _hlo_op_names(hlo) -> dict:
    """``{instruction: op_name}`` over every computation of a
    serialized HloProto."""
    out = {}
    for f, module in _fields(hlo):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                name, meta = _field(ins, 1), _field(ins, 7)
                op = _field(meta, 2) if meta is not None else None
                if name is not None and op is not None:
                    out[bytes(name).decode()] = bytes(op).decode()
    return out


def op_names(xplane_path: str) -> dict:
    """``{program: {instruction: op_name}}`` from the HLO the profiler
    stored for each program it saw; programs are named as the device's
    ``XLA Modules`` events are (``jit__aggregate_step(<id>)``)."""
    with open(xplane_path, "rb") as fh:
        data = memoryview(fh.read())
    for f, plane in _fields(data):
        if f != 1 or bytes(_field(plane, 2, b"")) != METADATA_PLANE.encode():
            continue
        stat_names = {}
        for g, v in _fields(plane):
            if g == 5:
                meta = _field(v, 2)
                stat_names[_field(v, 1)] = bytes(_field(meta, 2, b"")).decode()
        out = {}
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _field(entry, 2)
            name = bytes(_field(meta, 2, b"")).decode()
            for h, stat in _fields(meta):
                if h != 5:
                    continue
                if stat_names.get(_field(stat, 1)) == "Hlo Proto":
                    out[name] = _hlo_op_names(_field(stat, 6, b""))
        return out
    return {}


def load(profile_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(files[-1])
    ops_of = op_names(files[-1])
    out: dict = {}
    for plane in pd.planes:
        device = plane.name.startswith(devtrace.DEVICE_PREFIX)
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        if device:
            mods = sorted([float(e.start_ns), float(e.duration_ns), e.name]
                          for e in lines.get(devtrace.MODULES_LINE, []))
            starts = [m[0] for m in mods]
            scoped, outer_end = [], float("-inf")
            for ev in sorted(lines.get(devtrace.OPS_LINE, []),
                             key=lambda e: (e.start_ns, -e.duration_ns)):
                s = float(ev.start_ns)
                if s < outer_end:
                    continue              # inside an operation's event
                outer_end = s + float(ev.duration_ns)
                i = bisect.bisect_right(starts, s) - 1
                module = (mods[i][2] if i >= 0
                          and s < mods[i][0] + mods[i][1] else "?")
                # "%fusion.31 = (...) fusion(...)": the instruction name
                ins = ev.name.split(" ", 1)[0].lstrip("%")
                scope = op_scope(ops_of.get(module, {}).get(ins, ""))
                prog = _module_program(module)
                scoped.append([f"{prog}/{scope}" if scope else prog, s,
                               float(ev.duration_ns)])
            for name in (devtrace.OPS_LINE, devtrace.MODULES_LINE):
                if lines.get(name):
                    out.setdefault(plane.name, {})[name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in lines[name]]
            if scoped:
                out.setdefault(plane.name, {})[SCOPES_LINE] = scoped
            continue
        for name, evs in lines.items():
            keep = [[e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in evs
                    if e.name in HARNESS or e.name.startswith(PREFIX)]
            if keep:
                out.setdefault(plane.name, {})[name] = keep
    return out


def _innermost(spans: list) -> list:
    """Cut one thread's properly nested spans ``[name, a, b]`` into
    pieces ``[name, a, b]``, each named by the innermost span open over
    it; time outside every span gives no piece."""
    spans = [sp for sp in spans if sp[2] > sp[1]]
    pieces, stack, t = [], [], None
    # at one instant, ends before starts, and outer starts first
    bounds = sorted([(a, 0, -b, n) for n, a, b in spans]
                    + [(b, -1, 0, n) for n, a, b in spans])
    for x, kind, _, n in bounds:
        if stack and x > t:
            pieces.append([stack[-1], t, x])
        t = x
        if kind == 0:
            stack.append(n)
        elif stack:
            stack.pop()
    return pieces


def reduce(events: dict, top: int = 10) -> dict | None:
    """Spans, device scopes and labelled idle gaps inside
    ``bench.traced``; ``None`` without that span or without any
    ``higgs.*`` span in it."""
    traced = [(s, s + d) for lines in events.values()
              for evs in lines.values() for n, s, d in evs
              if n == "bench.traced"]
    if not traced:
        return None
    lo, hi = traced[0]
    threads = []
    for plane, lines in events.items():
        if plane.startswith(devtrace.DEVICE_PREFIX):
            continue
        for evs in lines.values():
            spans = [[n, a, b] for n, a, b in devtrace._clip(
                [e for e in evs if e[0] != "bench.traced"], lo, hi)]
            if spans:
                threads.append(spans)
    if not any(n.startswith(PREFIX) for t in threads for n, _, _ in t):
        return None

    stats: dict = {}
    pieces = []
    for spans in threads:
        for n, a, b in spans:
            st = stats.setdefault(n, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
            st["count"] += 1
            st["total_s"] += (b - a) / 1e9
        pieces += _innermost(spans)
    for n, a, b in pieces:
        stats[n]["self_s"] += (b - a) / 1e9

    scopes: dict = {}
    gaps = []
    idle: dict = {}
    devices = sorted(p for p in events
                     if p.startswith(devtrace.DEVICE_PREFIX))
    for plane in devices:
        lines = events[plane]
        for key, a, b in devtrace._clip(lines.get(SCOPES_LINE, []), lo, hi):
            scopes[key] = scopes.get(key, 0.0) + (b - a) / 1e9
        busy = devtrace._union([(a, b) for _, a, b in devtrace._clip(
            lines.get(devtrace.OPS_LINE, []), lo, hi)])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        mine = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps += mine
        starts = [a for a, _ in mine]
        spanned = 0.0
        for n, s, e in pieces:
            j = max(bisect.bisect_right(starts, s) - 1, 0)
            while j < len(mine) and mine[j][0] < e:
                ov = min(e, mine[j][1]) - max(s, mine[j][0])
                if ov > 0:
                    idle[n] = idle.get(n, 0.0) + ov / 1e9 / len(devices)
                    spanned += ov
                j += 1
        rest = sum(b - a for a, b in mine) - spanned
        idle["loop"] = idle.get("loop", 0.0) + rest / 1e9 / len(devices)
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover: dict = {}
        for n, s, e in pieces:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[n] = cover.get(n, 0.0) + ov
        label = max(cover, key=cover.get) if cover else "loop"
        labelled.append([label, (b - a) / 1e9])
    return {"window_s": (hi - lo) / 1e9, "spans": stats,
            "idle_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            "device_scopes": dict(sorted(scopes.items(),
                                         key=lambda kv: -kv[1])),
            "idle_gaps": labelled}


_CACHE: dict = {}


def of(ctx: dict):
    """The span reduction a reader works on: ``ctx["spans"]`` where the
    harness put one there, else the reduction of the trace under
    ``TRACE_DIR`` when the run traced (``ctx["trace"]`` set), read once
    per process."""
    if "spans" in ctx:
        return ctx["spans"]
    if ctx.get("trace") is None:
        return None
    if "red" not in _CACHE:
        try:
            _CACHE["red"] = reduce(load(str(TRACE_DIR)))
        except FileNotFoundError:
            _CACHE["red"] = None
    return _CACHE["red"]


def per_edge_us(ctx: dict, seconds) -> float | None:
    n = ctx["counters"].get("edges_traced", 0)
    return seconds / n * 1e6 if n and seconds is not None else None


if __name__ == "__main__":
    import json
    red = reduce(load(sys.argv[1] if len(sys.argv) > 1 else str(TRACE_DIR)))
    print(json.dumps(red, indent=1))
