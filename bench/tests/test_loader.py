"""The harness finds every configuration, traffic mix and reader by the
names in ``BENCHMARK.json``; a new cell needs only new files."""
import json
import shutil

import loader
import pytest

BENCH = loader.load_benchmark()


def test_every_cell_resolves_to_its_files():
    for w in BENCH["workloads"]:
        cfg = loader.load_config(w["config"])
        mix = loader.load_traffic(w["traffic"])
        assert cfg["name"] == w["config"]
        assert mix["writer"]["mode"] == "closed"
        assert mix["check"]["batches"] > 0
    for c in BENCH["configs"]:
        assert (loader.ROOT / c["file"]).is_file()
        assert loader.load_config(c["name"])["reduced"] == c["reduced"]
    for m in BENCH["per_layer"]:
        assert callable(loader.load_reader(m["name"]))


def test_each_cell_reports_setup_another_e2e_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in loader.metrics_of(BENCH, w["name"],
                                                    "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert loader.metrics_of(BENCH, w["name"], "per_layer")
        for m in loader.metrics_of(BENCH, w["name"], "per_layer"):
            assert m["moves"] in e2e


def test_a_new_cell_needs_only_new_files(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix and a
    metric as files plus entries: the loader finds them, and no file
    that was there changes."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(loader.BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(loader.ROOT / "BENCHMARK.json", tmp_path)
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    cfg = loader.load_config("lkml", bench_dir)
    cfg["name"] = "lkml-budget"
    cfg["retention"] = {"kind": "budget", "max_bytes": 1e8}
    (bench_dir / "configs" / "lkml-budget.json").write_text(json.dumps(cfg))
    mix = loader.load_traffic("ingest", bench_dir)
    mix["writer"]["batch"] = 4096
    (bench_dir / "traffic" / "ingest-small.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "leaves_per_s.py").write_text(
        "def read(ctx):\n    return ctx['counters'].get('leaves_per_s')\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "lkml-budget.ingest-small",
                               "config": "lkml-budget",
                               "traffic": "ingest-small", "chips": 1,
                               "why": "budget retention"})
    bench["per_layer"].append({"name": "leaves_per_s", "unit": "leaves/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "drain", "moves": "ingest_eps",
                               "workloads": ["lkml-budget.ingest-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = loader.load_benchmark(tmp_path)
    w = loader.find_cell(got, "lkml-budget.ingest-small")
    assert loader.load_config(w["config"], bench_dir)["retention"][
        "kind"] == "budget"
    assert loader.load_traffic(w["traffic"], bench_dir)["writer"][
        "batch"] == 4096
    names = [m["name"] for m in loader.metrics_of(got, w["name"],
                                                  "per_layer")]
    assert names == ["leaves_per_s"]
    assert loader.load_reader("leaves_per_s", bench_dir)(
        {"counters": {"leaves_per_s": 3.0}}) == 3.0
    for p, data in before.items():
        assert p.read_bytes() == data


def test_unknown_names_fail_loudly():
    with pytest.raises(KeyError):
        loader.find_cell(BENCH, "no.such-cell")
    with pytest.raises(FileNotFoundError):
        loader.load_config("no-such-config")
    with pytest.raises(FileNotFoundError):
        loader.load_reader("no_such_metric")
