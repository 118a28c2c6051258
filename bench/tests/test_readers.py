"""Each per-layer reader on trace and counter fixtures: the number it
reads, and nothing where there is nothing to read."""
import loader
import pytest

TRACE = {"window_s": 4.0, "busy_s": 1.0,
         "programs": {"jit_insert_chunks_pre(1)": 0.02,
                      "jit__ingest_step(2)": 0.03,
                      "jit__aggregate_step(3)": 0.004,
                      "jit__take_rows(4)": 0.0005,
                      "jit__append_rows(5)": 0.0005},
         "device_ops": [], "idle_gaps": []}
COUNTERS = {"edges_traced": 10_000}


def ctx(backend="vector", trace=TRACE, counters=COUNTERS):
    return {"trace": trace, "backend": backend, "counters": counters}


@pytest.mark.parametrize("name,c,want", [
    ("device_idle.ingest", ctx(), 0.75),
    ("place_dev_us_per_edge", ctx("vector"), 2.0),
    ("place_dev_us_per_edge", ctx("pallas"), 3.0),
    ("cascade_dev_us_per_edge", ctx("pallas"), 0.5),
])
def test_reader_value(name, c, want):
    assert loader.load_reader(name)(c) == pytest.approx(want)


@pytest.mark.parametrize("name", [m["name"] for m in
                                  loader.load_benchmark()["per_layer"]])
def test_reader_finds_nothing(name):
    """No trace and no counters: the reader returns nothing, never 0."""
    empty = {"trace": None, "backend": "vector",
             "counters": {"edges_traced": 0}}
    assert loader.load_reader(name)(empty) is None


def test_trace_readers_skip_programs_that_did_not_run():
    quiet = dict(TRACE, programs={"jit_insert_chunks_pre(1)": 0.02})
    assert loader.load_reader("cascade_dev_us_per_edge")(
        ctx(trace=quiet)) is None
    assert loader.load_reader("place_dev_us_per_edge")(
        ctx("host")) is None
