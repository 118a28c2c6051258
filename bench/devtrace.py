"""Reduction of a profiler trace to the numbers the readers need.

``load(profile_dir)`` turns the ``.xplane.pb`` that ``jax.profiler``
wrote into plain events: ``{plane: {line: [[name, start_ns, dur_ns],
...]}}``, keeping only the device planes and the host thread that holds
the benchmark's own annotations.  ``reduce(events)`` then works on that
plain form alone, so the test suite checks it on a small recorded trace
without a chip.

* The traced window is the host span ``bench.traced``, which the harness
  opens right after the profiler starts and closes right before it
  stops.
* Device busy time is the union of the intervals of the device's XLA
  operations within that window (per device plane, averaged over the
  planes).
* A program's device time is the sum of its ``XLA Modules`` events in
  the window; programs are named by their jitted function
  (``jit__ingest_step(...)``), which is what the readers match.
* Each idle gap on the device is attributed to the harness span it
  overlaps most (``bench.insert``), or to ``loop``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("bench.traced", "bench.insert")


def load(profile_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(files[-1])
    out: dict = {}
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            evs = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                   for ev in line.events
                   if device or ev.name in HOST_SPANS]
            if evs and (device and line.name in (OPS_LINE, MODULES_LINE)
                        or not device):
                out.setdefault(plane.name, {})[line.name] = evs
    return out


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as fh:
        json.dump(events, fh)


def read_saved(path: str) -> dict:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def reduce(events: dict, top: int = 10) -> dict | None:
    """Busy and window seconds, per-program device seconds, the top
    device programs and the longest idle gaps by host span."""
    host = [(n, s, s + d) for lines in events.values()
            for evs in lines.values() for n, s, d in evs
            if n in HOST_SPANS]
    traced = [(s, e) for n, s, e in host if n == "bench.traced"]
    devices = sorted(p for p in events if p.startswith(DEVICE_PREFIX))
    if not traced or not devices:
        return None
    lo, hi = traced[0]
    busy_ns, programs, gaps = 0.0, {}, []
    spans = [(n, s, e) for n, s, e in host if n != "bench.traced"]
    for plane in devices:
        lines = events[plane]
        ops = list(_clip(lines.get(OPS_LINE, []), lo, hi))
        busy = _union([(a, b) for _, a, b in ops])
        busy_ns += sum(b - a for a, b in busy)
        for name, a, b in _clip(lines.get(MODULES_LINE, []), lo, hi):
            programs[name] = programs.get(name, 0.0) + (b - a) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    best = []
    for a, b in gaps:
        cover = {}
        for n, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[n] = cover.get(n, 0.0) + ov
        label = max(cover, key=cover.get) if cover else "loop"
        best.append([label, (b - a) / 1e9])
    best.sort(key=lambda g: -g[1])
    by_time = sorted(programs.items(), key=lambda kv: -kv[1])
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": busy_ns / len(devices) / 1e9,
            "programs": programs,
            "device_ops": [[n, s] for n, s in by_time[:top]],
            "idle_gaps": best[:top]}


def program_seconds(reduced: dict, names) -> float:
    """Device seconds of every program whose name holds one of
    ``names`` (jitted function names)."""
    return sum(s for prog, s in reduced["programs"].items()
               if any(n in prog for n in names))


def describe(profile_dir: str, sample: int = 8) -> dict:
    """Every plane and line of a trace with its event count and a few
    event names: what to look at before keying a reader on names."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    pd = ProfileData.from_file(files[-1])
    out = {}
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            out[f"{plane.name} | {line.name}"] = {
                "events": len(evs), "distinct": len(names),
                "first_ns": evs[0].start_ns if evs else None,
                "names": names[:sample]}
    return out
