"""The trace reduction, on a half-second slice of a trace recorded on a
TPU v5e (an ingest window of the device engine)."""
import gzip
import json
from pathlib import Path

import devtrace
import numpy as np
import pytest

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json.gz"


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as fh:
        return json.load(fh)


def traced(events):
    return next(e for lines in events.values() for evs in lines.values()
                for e in evs if e[0] == "bench.traced")


def test_busy_is_the_union_of_device_ops(events):
    red = devtrace.reduce(events)
    _, lo, dur = traced(events)
    assert red["window_s"] == pytest.approx(dur / 1e9)
    # brute force: mark every 100 ns bin an op covers
    step = 100.0
    grid = np.zeros(int(dur / step) + 1, bool)
    for _, s, d in events["/device:TPU:0"]["XLA Ops"]:
        a, b = max(s, lo), min(s + d, lo + dur)
        if b > a:
            grid[int((a - lo) / step):int(np.ceil((b - lo) / step))] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * step / 1e9, rel=0.01)
    assert 0 < red["busy_s"] < red["window_s"]


def test_program_seconds_sum_module_events(events):
    red = devtrace.reduce(events)
    _, lo, dur = traced(events)
    want = sum(min(s + d, lo + dur) - max(s, lo)
               for n, s, d in events["/device:TPU:0"]["XLA Modules"]
               if "_aggregate_step" in n and s < lo + dur and s + d > lo)
    got = devtrace.program_seconds(red, ["_aggregate_step"])
    assert got == pytest.approx(want / 1e9)
    assert devtrace.program_seconds(red, ["_ingest_step"]) > 0
    assert devtrace.program_seconds(red, ["no_such_program"]) == 0
    names = [n for n, _ in red["device_ops"]]
    assert len(names) <= 10
    secs = [s for _, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_idle_gaps_fill_the_rest_and_name_the_host_span(events):
    red = devtrace.reduce(events, top=10_000)
    total = red["busy_s"] + sum(s for _, s in red["idle_gaps"])
    assert total == pytest.approx(red["window_s"], rel=1e-6)
    labels = {n for n, _ in red["idle_gaps"]}
    assert labels <= {"bench.insert", "loop"}
    assert "bench.insert" in labels


def test_no_window_or_no_device_reads_nothing(events):
    host_only = {k: v for k, v in events.items() if k.startswith("/host")}
    assert devtrace.reduce(host_only) is None
    no_span = {k: {ln: [e for e in evs if e[0] != "bench.traced"]
                   for ln, evs in v.items()} for k, v in events.items()}
    assert devtrace.reduce(no_span) is None
