"""Blocking device-to-host fetches of the drain per 1,000 edges ingested
in the traced window.  Each fetch opens exactly one ``higgs.fetch`` span
and adds one to ``IngestStats.fetches``, so the spans inside the traced
window count the counter's increments there."""
import progtrace


def read(ctx):
    red = progtrace.of(ctx)
    n = ctx["counters"].get("edges_traced", 0)
    if red is None or not n or "higgs.fetch" not in red["spans"]:
        return None
    return red["spans"]["higgs.fetch"]["count"] * 1000 / n
