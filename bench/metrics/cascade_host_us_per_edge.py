"""Host microseconds of the numpy level builds per edge ingested in the
traced window: the time inside ``higgs.cascade.host`` spans, where under
host pools each ready level's children are read back to leaf
coordinates, given round orders and placed into their parents."""
import progtrace


def read(ctx):
    red = progtrace.of(ctx)
    if red is None or "higgs.cascade.host" not in red["spans"]:
        return None
    return progtrace.per_edge_us(
        ctx, red["spans"]["higgs.cascade.host"]["total_s"])
