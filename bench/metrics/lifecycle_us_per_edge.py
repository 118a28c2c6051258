"""Host microseconds of the segment lifecycle (sealing and eviction,
``higgs.lifecycle`` spans) per edge ingested in the traced window."""
import progtrace


def read(ctx):
    red = progtrace.of(ctx)
    if red is None or "higgs.lifecycle" not in red["spans"]:
        return None
    return progtrace.per_edge_us(
        ctx, red["spans"]["higgs.lifecycle"]["total_s"])
