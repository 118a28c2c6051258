"""Host microseconds of the drain per edge ingested in the traced
window: time inside ``higgs.drain`` spans not spent waiting in
``higgs.fetch`` (every fetch of the ingest path lies inside a drain).
This is the host work between the drain's device launches, which the
device spends idle."""
import progtrace


def read(ctx):
    red = progtrace.of(ctx)
    if red is None or "higgs.drain" not in red["spans"]:
        return None
    sp = red["spans"]
    wait = sp.get("higgs.fetch", {}).get("total_s", 0.0)
    return progtrace.per_edge_us(ctx, sp["higgs.drain"]["total_s"] - wait)
