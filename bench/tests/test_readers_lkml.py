"""The readers of the default engine's host layers (``lkml.ingest``):
``leaf_prep_us_per_edge`` reads the ``higgs.drain.prep`` spans and
``cascade_host_us_per_edge`` the ``higgs.cascade.host`` spans, per edge
of the traced window, on a reduction of hand-built events."""
import loader
import progtrace
import pytest

MS = 1e6                     # ns per ms


def default_engine_insert():
    """One insert of 10 ms on the default engine: a drain with a 1.5 ms
    leaf prep, a 2 ms copy of the leaf state, and two cascade levels
    whose numpy builds take 3 ms and 0.75 ms."""
    ev = {"/host:CPU": {"python": [list(e) for e in (
        ["bench.traced", 0, 12 * MS],
        ["bench.insert", 1 * MS, 10 * MS],
        ["higgs.insert", 1 * MS, 10 * MS],
        ["higgs.drain", 1.5 * MS, 9 * MS],
        ["higgs.drain.split", 1.5 * MS, 0.25 * MS],
        ["higgs.drain.prep", 1.75 * MS, 1.5 * MS],
        ["higgs.fetch", 3.5 * MS, 2 * MS],
        ["higgs.cascade", 6 * MS, 3.25 * MS],
        ["higgs.cascade.ob", 6 * MS, 0.1 * MS],
        ["higgs.cascade.host", 6.1 * MS, 3 * MS],
        ["higgs.cascade", 9.3 * MS, 0.9 * MS],
        ["higgs.cascade.host", 9.35 * MS, 0.75 * MS])]}}
    ev["/device:TPU:0"] = {
        "XLA Ops": [["fusion.1", 3.5 * MS, 1.5 * MS]],
        "XLA Modules": [["jit_insert_chunks_pre(1)", 3.5 * MS, 1.5 * MS]],
        progtrace.SCOPES_LINE: []}
    return ev


def ctx(spans, edges=1000):
    return {"trace": {}, "backend": "vector",
            "counters": {"edges_traced": edges}, "spans": spans}


@pytest.mark.parametrize("name,edges,want", [
    ("leaf_prep_us_per_edge", 1000, 1.5),
    ("leaf_prep_us_per_edge", 500, 3.0),
    ("cascade_host_us_per_edge", 1000, 3.75),
    ("cascade_host_us_per_edge", 250, 15.0),
])
def test_reader_value(name, edges, want):
    red = progtrace.reduce(default_engine_insert())
    assert loader.load_reader(name)(ctx(red, edges)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["leaf_prep_us_per_edge",
                                  "cascade_host_us_per_edge"])
def test_reader_finds_nothing_without_its_spans(name):
    """A trace without the default engine's host spans (the device
    engine's, or a program that does not open them), no edges, or no
    spans at all: nothing, never 0."""
    read = loader.load_reader(name)
    red = progtrace.reduce(default_engine_insert())
    for span in ("higgs.drain.prep", "higgs.cascade.host"):
        del red["spans"][span]
    assert read(ctx(red)) is None
    assert read(ctx(progtrace.reduce(default_engine_insert()), 0)) is None
    assert read(ctx({"spans": {}})) is None
