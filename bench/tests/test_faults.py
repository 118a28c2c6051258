"""The probe plants do what ``faults`` says: both answer a stream id
exactly, and only the fingerprint-only probes add a twin's weight to an
id that shares its fingerprint and a candidate row but not its key."""
import json
import os
import subprocess
import sys

import faults
import loader
import numpy as np
import pytest
from reference import coords, key, mix32, side_seed, unmix32

SKETCH = loader.load_config("wikitalk-window")["sketch"]

ASK = """
import json, sys
import numpy as np
import faults
faults.plant(sys.argv[1])
from repro.api import make_summary
from repro.api.queries import VertexQuery
u, v, n = (int(a) for a in sys.argv[2:5])
sk = make_summary("higgs", **json.loads(sys.argv[5]))
dst = np.arange(1, n + 1, dtype=np.uint32)
sk.insert(np.full(n, u, np.uint32), dst, np.ones(n, np.float32),
          np.arange(n, dtype=np.uint32))
sk.flush()
res = sk.query([VertexQuery(np.array([u, v], np.uint32), 0, n, "out")])
print(json.dumps([float(x) for x in np.asarray(res.values[0])]))
"""


def twin_of(u: int) -> int:
    """An id with ``u``'s fingerprint whose second candidate row is
    ``u``'s base row: another key, found in the row ``u`` fills first."""
    f, d1 = SKETCH["F1"], SKETCH["d1"]
    h = int(mix32(np.array([u], np.uint32), side_seed(SKETCH, "s"))[0])
    base = (h >> f) % d1
    prev = (base - 1) * pow(5, -1, d1) % d1        # 5 * prev + 1 = base
    top = f + d1.bit_length() - 1                  # bits above the key
    hv = (h & ((1 << f) - 1)) | (prev << f) | (h >> top << top)
    return int(unmix32(np.array([hv], np.uint32), side_seed(SKETCH, "s"))[0])


@pytest.mark.parametrize("probes", faults.PROBES)
def test_probe_plants(probes):
    u = 12345
    v = twin_of(u)
    f_u, rows_u = coords(np.array([u, v], np.uint32), SKETCH, "s")
    assert f_u[0] == f_u[1] and rows_u[0] & rows_u[1]
    assert key(np.array([u]), SKETCH, "s") != key(np.array([v]), SKETCH, "s")
    n = 4000
    params = {k: x for k, x in SKETCH.items()
              if k not in ("insert_backend", "pool_storage")}
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(loader.BENCH / "tests"), str(loader.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", ASK, probes, str(u), str(v),
                        str(n), json.dumps(params)], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got_u, got_v = json.loads(p.stdout.strip().splitlines()[-1])
    assert got_u == n
    if probes == "probes_exact_key":
        assert got_v == 0
    else:
        assert got_v > 0
