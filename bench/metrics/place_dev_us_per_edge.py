"""Device microseconds of the leaf placement programs per edge ingested
in the traced window: ``insert_chunks_pre`` (vector engine) or
``_ingest_step`` (Pallas leaf insert, device pools)."""
from devtrace import program_seconds

PROGRAMS = {"vector": ("insert_chunks_pre",), "pallas": ("_ingest_step",)}


def read(ctx):
    tr, n = ctx["trace"], ctx["counters"].get("edges_traced", 0)
    names = PROGRAMS.get(ctx["backend"])
    if tr is None or not n or not names:
        return None
    s = program_seconds(tr, names)
    return s / n * 1e6 if s > 0 else None
