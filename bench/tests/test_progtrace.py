"""The program-span reduction (``progtrace``) and the readers built on
it, on hand-built events; the device-trace reduction it sits beside is
pinned on the recorded slice it was written against."""
import gzip
import json
from pathlib import Path

import devtrace
import loader
import progtrace
import pytest

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                     # ns per ms


def host(*evs):
    return {"/host:CPU": {"python": [list(e) for e in evs]}}


def nested():
    """One insert of 10 ms: a drain with a 2 ms split, a 3 ms fetch and
    a 1 ms cascade that holds a 0.5 ms fetch; the device runs twice."""
    ev = host(["bench.traced", 0, 12 * MS],
              ["bench.insert", 1 * MS, 10 * MS],
              ["higgs.insert", 1 * MS, 10 * MS],
              ["higgs.drain", 1.5 * MS, 9 * MS],
              ["higgs.drain.split", 1.5 * MS, 2 * MS],
              ["higgs.fetch", 4 * MS, 3 * MS],
              ["higgs.cascade", 8 * MS, 1 * MS],
              ["higgs.fetch", 8.25 * MS, 0.5 * MS])
    ev["/device:TPU:0"] = {
        "XLA Ops": [["fusion.1", 4 * MS, 3 * MS],
                    ["sort.2", 8.25 * MS, 0.5 * MS]],
        "XLA Modules": [["jit__ingest_step(1)", 4 * MS, 3 * MS],
                        ["jit__aggregate_step(2)", 8.25 * MS, 0.5 * MS]],
        progtrace.SCOPES_LINE: [["_ingest_step/place", 4 * MS, 3 * MS],
                                ["_aggregate_step/orders", 8.25 * MS,
                                 0.5 * MS]]}
    return ev


def test_self_time_is_total_less_nested_spans():
    sp = progtrace.reduce(nested())["spans"]
    assert sp["higgs.fetch"]["count"] == 2
    assert sp["higgs.fetch"]["total_s"] == pytest.approx(3.5e-3)
    assert sp["higgs.drain"]["total_s"] == pytest.approx(9e-3)
    # 9 ms less the split (2), the first fetch (3) and the cascade (1)
    assert sp["higgs.drain"]["self_s"] == pytest.approx(3e-3)
    assert sp["higgs.cascade"]["self_s"] == pytest.approx(0.5e-3)
    assert sp["higgs.insert"]["self_s"] == pytest.approx(1e-3)
    assert sp["bench.insert"]["self_s"] == pytest.approx(0.0)
    total_self = sum(s["self_s"] for s in sp.values())
    assert total_self == pytest.approx(10e-3)


def test_gaps_take_the_innermost_span_over_most_of_them():
    red = progtrace.reduce(nested(), top=10)
    got = sorted((round(s * 1e3, 6), n) for n, s in red["idle_gaps"])
    # [0, 4): loop 1, insert 0.5, split 2, drain 0.5 -> split
    # [7, 8.25): drain 1, cascade 0.25 -> drain
    # [8.75, 12): cascade 0.25, drain 1.5, insert 0.5, loop 1 -> drain
    assert got == [(1.25, "higgs.drain"), (3.25, "higgs.drain"),
                   (4.0, "higgs.drain.split")]


def test_idle_time_by_innermost_span():
    idle = progtrace.reduce(nested())["idle_s"]
    want = {"loop": 2.0, "higgs.drain.split": 2.0, "higgs.drain": 3.0,
            "higgs.insert": 1.0, "higgs.cascade": 0.5}
    assert idle == {k: pytest.approx(v * 1e-3) for k, v in want.items()}


def test_gap_outside_every_span_is_loop():
    ev = host(["bench.traced", 0, 10 * MS], ["higgs.insert", 0, 2 * MS])
    ev["/device:TPU:0"] = {"XLA Ops": [["op", 1 * MS, 1 * MS]]}
    red = progtrace.reduce(ev)
    assert red["idle_gaps"][0] == ["loop", pytest.approx(8e-3)]
    assert red["idle_gaps"][1] == ["higgs.insert", pytest.approx(1e-3)]


def test_device_scopes_clip_to_the_window():
    ev = nested()
    ev["/device:TPU:0"][progtrace.SCOPES_LINE].append(
        ["_aggregate_step/place", 11 * MS, 3 * MS])     # 1 ms inside
    sc = progtrace.reduce(ev)["device_scopes"]
    assert sc == {"_ingest_step/place": pytest.approx(3e-3),
                  "_aggregate_step/place": pytest.approx(1e-3),
                  "_aggregate_step/orders": pytest.approx(0.5e-3)}
    assert list(sc) == sorted(sc, key=lambda k: -sc[k])


def test_no_program_span_reads_nothing():
    assert progtrace.reduce(host(["bench.traced", 0, MS],
                                 ["bench.insert", 0, MS])) is None
    assert progtrace.reduce(host(["higgs.insert", 0, MS])) is None
    with gzip.open(DATA / "trace_small.json.gz", "rt") as fh:
        assert progtrace.reduce(json.load(fh)) is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_aggregate_step)/orders/jit(sort)/sort", "orders"),
    ("jit(_aggregate_step)/jit(main)/recover/shift_right_logical",
     "recover"),
    ("jit(_ingest_step)/append/jit(_append_rows)", "append"),
    ("jit(_take_rows)/gather", ""),
    ("sort", ""),
    ("", ""),
])
def test_op_scope(op_name, scope):
    assert progtrace.op_scope(op_name) == scope


def ctx(spans, edges=1000, trace=True):
    return {"trace": {} if trace else None, "backend": "pallas",
            "counters": {"edges_traced": edges}, "spans": spans}


@pytest.mark.parametrize("name,want", [
    # drain 9 ms less 3.5 ms of fetches, over 1,000 edges
    ("drain_host_us_per_edge", 5.5),
    ("drain_wait_us_per_edge", 3.5),
    ("fetches_per_kedge", 2.0),
])
def test_span_reader_value(name, want):
    red = progtrace.reduce(nested())
    assert loader.load_reader(name)(ctx(red)) == pytest.approx(want)


def test_lifecycle_reader_value():
    ev = nested()
    ev["/host:CPU"]["python"].append(["higgs.lifecycle", 9.5 * MS, 0.25 * MS])
    red = progtrace.reduce(ev)
    assert loader.load_reader("lifecycle_us_per_edge")(
        ctx(red, edges=500)) == pytest.approx(0.5)


NEW = ("drain_host_us_per_edge", "drain_wait_us_per_edge",
       "lifecycle_us_per_edge", "fetches_per_kedge")


@pytest.mark.parametrize("name", NEW)
def test_span_reader_finds_nothing(name):
    """No program spans (the parent's trace), no edges, or no trace:
    nothing, never 0."""
    red = progtrace.reduce(nested())
    read = loader.load_reader(name)
    assert read(ctx(None)) is None
    assert read(ctx({"spans": {}})) is None
    assert read(ctx(red, edges=0)) is None
    assert read({"trace": None, "backend": "pallas",
                 "counters": {"edges_traced": 1000}}) is None


def test_devtrace_reads_the_same_on_the_recorded_slice():
    """What the three accepted per-layer metrics read stays as it was:
    the reduction of the recorded slice, number for number."""
    with gzip.open(DATA / "trace_small.json.gz", "rt") as fh:
        red = devtrace.reduce(json.load(fh))
    assert red["window_s"] == 0.5
    assert red["busy_s"] == pytest.approx(0.233700672, rel=1e-12)
    assert len(red["programs"]) == 21
    assert red["programs"]["jit__ingest_step(14923263172667083456)"] \
        == pytest.approx(0.015541401, rel=1e-12)
    assert devtrace.program_seconds(red, ["_aggregate_step"]) \
        == pytest.approx(0.14008451800000002 + 0.07141459, rel=1e-12)


# -- a slice of a chip trace with program spans and named scopes ----------
# One insert of the wikitalk-window cell on a TPU v5e (``progtrace.load``
# of a ``--trace 1`` run, cut to that insert with a ``bench.traced`` span
# around it): the drain, both cascade levels, their fetches and spills.

@pytest.fixture(scope="module")
def spans_trace():
    with gzip.open(DATA / "trace_spans.json.gz", "rt") as fh:
        return json.load(fh)


PARENT = {"higgs.drain": "higgs.insert", "higgs.drain.split": "higgs.drain",
          "higgs.drain.stage": "higgs.drain", "higgs.fetch": "higgs.drain",
          "higgs.cascade": "higgs.drain",
          "higgs.cascade.ob": "higgs.cascade",
          "higgs.drain.spill": "higgs.drain",
          "higgs.lifecycle": "higgs.drain"}


def test_recorded_spans_nest_and_account_for_the_insert(spans_trace):
    evs = [e for lines in spans_trace.values() for evs in lines.values()
           for e in evs if e[0].startswith(("higgs.", "bench."))]
    by = {}
    for n, s, d in evs:
        by.setdefault(n, []).append((s, s + d))
    assert set(PARENT) <= set(by)
    for child, parent in PARENT.items():
        for a, b in by[child]:
            assert any(x <= a and b <= y for x, y in by[parent]), child
    sp = progtrace.reduce(spans_trace)["spans"]
    # the program's spans account for the harness's insert
    assert sp["higgs.insert"]["total_s"] == pytest.approx(
        sp["bench.insert"]["total_s"], rel=0.05)
    assert sp["higgs.fetch"]["count"] == 5
    assert sp["higgs.cascade"]["count"] == 2


def test_recorded_scopes_split_the_cascade_program(spans_trace):
    red = progtrace.reduce(spans_trace)
    sc = red["device_scopes"]
    dev = devtrace.reduce(spans_trace)
    agg = devtrace.program_seconds(dev, ["_aggregate_step"])
    parts = {k.split("/", 1)[1]: v for k, v in sc.items()
             if k.startswith("_aggregate_step/")}
    assert set(parts) == {"recover", "coords", "orders", "place"}
    assert sum(parts.values()) >= 0.9 * agg
    # the outermost operations fill the program's time, less the
    # microseconds between them
    assert sum(v for k, v in sc.items()
               if k.split("/")[0] == "_aggregate_step") \
        == pytest.approx(agg, rel=1e-3)
    assert {"_ingest_step/hash", "_ingest_step/place",
            "_ingest_step/append"} <= set(sc)


def test_recorded_idle_time_is_put_down_to_spans(spans_trace):
    red = progtrace.reduce(spans_trace)
    dev = devtrace.reduce(spans_trace)
    idle = dev["window_s"] - dev["busy_s"]
    assert sum(red["idle_s"].values()) == pytest.approx(idle, rel=1e-6)
    assert all(n.startswith("higgs.") for n, _ in red["idle_gaps"])
    assert [s for _, s in red["idle_gaps"]] \
        == [s for _, s in dev["idle_gaps"]]


def test_op_names_are_read_from_the_profile(tmp_path):
    """The HLO the profiler stores per program carries each operation's
    ``op_name``, named scopes included."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("orders"):
            y = jnp.sort(x)
        with jax.named_scope("place"):
            return y * 2 + 1

    x = jnp.arange(64.0)[::-1]
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    ops = progtrace.op_names(str(path))
    prog = next(k for k in ops if k.startswith("jit_step("))
    scopes = {progtrace.op_scope(v) for v in ops[prog].values()}
    assert {"orders", "place"} <= scopes
