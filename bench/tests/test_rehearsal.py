"""Every cell end to end on the CPU at its rehearsal size, with Pallas
interpreted: the flow runs, the comparison holds, and no device result
is ever printed.  Also: the control and the planted faults come out not
correct, and a run without a TPU exits non-zero with no result."""
import json
import os
import subprocess
import sys

import loader
import pytest

CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]


def run(cell, *extra, seed=2**31 + 77, seconds=2, plant=None):
    root = loader.ROOT
    RUN = str(root / "bench" / "run.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), *extra]
    if plant is None:
        cmd = [sys.executable, RUN, *argv]
    else:
        code = (f"import sys; sys.argv = [{RUN!r}] + {argv!r}; "
                f"sys.path.insert(0, {str(root / 'bench')!r}); "
                f"sys.path.insert(0, {str(root / 'src')!r}); "
                f"import faults; faults.plant({plant!r}); "
                f"import run; sys.exit(run.main())")
        env["PYTHONPATH"] = str(loader.BENCH / "tests")
        cmd = [sys.executable, "-c", code]
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=root, timeout=900)
    return p


def rehearsal_result(p) -> dict:
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1].startswith("rehearsal: "), lines[-1]
    for line in lines:
        assert not line.startswith("{"), "a CPU run printed a result line"
    return json.loads(lines[-1][len("rehearsal: "):])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct(cell):
    out = rehearsal_result(run(cell, "--rehearsal"))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["values_compared"]["value"] > 0
    assert "setup_s" in out["metrics"]
    assert out["device"]["platform"] == "cpu"


def test_without_a_tpu_no_result():
    p = run(CELLS[0])
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The control keeps one fingerprint bit fewer than the
    configuration states: the baits take their stream ids' weight."""
    out = rehearsal_result(run(cell, "--rehearsal", "--control"))
    assert not out["correct"]
    assert out["checks"]["above_exact"]["value"] > 0


@pytest.mark.parametrize("fault,cell", [
    (f, c) for c in CELLS
    for f in ("state_unchanged", "half_batch", "answer_altered")])
def test_planted_fault_is_not_correct(fault, cell):
    out = rehearsal_result(run(cell, "--rehearsal", plant=fault))
    assert not out["correct"], (fault, out["checks"])
