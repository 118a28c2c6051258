"""Faults planted beneath the timed path, for the tests that see
``correct`` come out false.  ``plant(name)`` patches the program in
this process before the benchmark runs."""
import numpy as np


def plant(name: str) -> None:
    from repro.core.higgs import HiggsSketch
    if name == "state_unchanged":
        # an insert that returns with the state as it was
        HiggsSketch.insert = lambda self, src, dst, w, t: None
    elif name == "half_batch":
        # every other edge of each batch left out
        orig = HiggsSketch.insert

        def half(self, src, dst, w, t):
            keep = slice(0, None, 2)
            orig(self, src[keep], dst[keep], w[keep], t[keep])
        HiggsSketch.insert = half
    elif name == "answer_altered":
        # one edge answer off by one where the probes produce it
        import repro.api.planner as planner
        orig = planner.QueryPlanner._edge_batch

        def altered(self, *a, **kw):
            out = orig(self, *a, **kw)
            if len(out):
                out = np.array(out)
                out[0] += 1.0
            return out
        planner.QueryPlanner._edge_batch = altered
    else:
        raise ValueError(f"unknown fault {name!r}")
