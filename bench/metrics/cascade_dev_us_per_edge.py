"""Device microseconds of the aggregation cascade's programs
(``_aggregate_step``, ``_take_rows``, ``_append_rows``) per edge
ingested in the traced window.  Under host pools the cascade is numpy
and there is nothing to read."""
from devtrace import program_seconds

PROGRAMS = ("_aggregate_step", "_take_rows", "_append_rows")


def read(ctx):
    tr, n = ctx["trace"], ctx["counters"].get("edges_traced", 0)
    if tr is None or not n:
        return None
    s = program_seconds(tr, PROGRAMS)
    return s / n * 1e6 if s > 0 else None
