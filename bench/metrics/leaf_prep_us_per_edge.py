"""Host microseconds of the leaf prep per edge ingested in the traced
window: the time inside ``higgs.drain.prep`` spans, where the vector and
host engines hash the drained edges, lay them out by leaf and compute
their chains, premerge groups and round orders before placement."""
import progtrace


def read(ctx):
    red = progtrace.of(ctx)
    if red is None or "higgs.drain.prep" not in red["spans"]:
        return None
    return progtrace.per_edge_us(
        ctx, red["spans"]["higgs.drain.prep"]["total_s"])
