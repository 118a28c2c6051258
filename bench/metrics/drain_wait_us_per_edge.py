"""Host microseconds spent blocked on the device per edge ingested in
the traced window: the time inside ``higgs.fetch`` spans, the drain's
only waits on the device (spill masks and spill coordinates)."""
import progtrace


def read(ctx):
    red = progtrace.of(ctx)
    if red is None or "higgs.fetch" not in red["spans"]:
        return None
    return progtrace.per_edge_us(ctx, red["spans"]["higgs.fetch"]["total_s"])
