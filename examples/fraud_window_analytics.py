"""Scenario: streaming transaction monitoring with bounded memory and
concurrent compliance analysts.

A payment network emits (payer -> payee, amount, t) edges around the
clock.  Compliance asks every morning: "how much flowed through this
suspicious ring during last night's window?" — a temporal subgraph
query.  The monitor must run forever, so it uses the *real windowed
sketch*: ``retention=window`` keeps only the last day resident (old
segments are evicted wholesale) and the summary's footprint plateaus,
while an unbounded summary grows with every day of traffic.  Answers
inside the retained window are bit-identical to a sketch built from
that window's traffic alone, and we assert the ring-flow estimate
against the exact oracle.

Analysts don't wait for end-of-stream either: the last act serves the
windowed monitor through a :class:`SummaryService` session — the stream
still ingesting, several analysts querying concurrently — and every
answer names the immutable read epoch it was served from, so two
analysts comparing notes on the same epoch are guaranteed bit-identical
numbers no matter how the writer raced them.

    PYTHONPATH=src python examples/fraud_window_analytics.py
"""
import asyncio

import numpy as np

from repro.api import SubgraphQuery, make_summary
from repro.stream.generator import power_law_stream
from repro.stream.pipeline import StreamPipeline

DAY = 86_400
N_DAYS = 3
NIGHT = 14_400                   # 0:00-4:00 of each day


def simulate_traffic(seed: int = 13):
    """N_DAYS of background traffic + a planted ring firing nightly."""
    rng = np.random.default_rng(seed)
    src, dst, w, t = power_law_stream(n_edges=80_000 * N_DAYS,
                                      n_vertices=5_000, skew=2.0,
                                      t_max=N_DAYS * DAY, seed=seed)
    ring = [4801, 4802, 4803, 4804]
    ring_edges = [(ring[i], ring[(i + 1) % 4]) for i in range(4)]
    r_src, r_dst, r_w, r_t = [], [], [], []
    for day in range(N_DAYS):
        night = day * DAY + rng.integers(0, NIGHT, 600).astype(np.uint32)
        r_src.append(np.array([e[0] for e in ring_edges] * 150, np.uint32))
        r_dst.append(np.array([e[1] for e in ring_edges] * 150, np.uint32))
        r_w.append(rng.exponential(900.0, 600).astype(np.float32))
        r_t.append(np.sort(night))
    src = np.concatenate([src] + r_src)
    dst = np.concatenate([dst] + r_dst)
    w = np.concatenate([w] + r_w)
    t = np.concatenate([t] + r_t)
    order = np.argsort(t, kind="stable")
    return (src[order], dst[order], w[order], t[order].astype(np.uint32),
            ring_edges)


def main():
    src, dst, w, t, ring_edges = simulate_traffic()
    sketches = {
        # the production monitor: last day resident, older segments gone
        "HIGGS-window": make_summary("higgs", d1=16, F1=19,
                                     retention=f"window:{DAY}"),
        # the PR 5 motivation: the same sketch without a lifecycle
        "HIGGS-unbounded": make_summary("higgs", d1=16, F1=19),
    }
    oracle = make_summary("oracle")
    for sk in sketches.values():
        sk.insert(src, dst, w, t)
        sk.flush()
    oracle.insert(src, dst, w, t)

    last_night = ((N_DAYS - 1) * DAY, (N_DAYS - 1) * DAY + NIGHT - 1)
    batch = [SubgraphQuery(ring_edges, *last_night)]
    true = oracle.query(batch).values[0]
    print(f"ring flow during last night "
          f"[{last_night[0]}, {last_night[1]}]: exact={true:,.0f}")
    for name, sk in sketches.items():
        est = sk.query(batch).values[0]
        err = abs(est - true) / max(true, 1)
        line = (f"  {name:16s}: {est:,.0f}  (rel err {err:.2%}, "
                f"summary {sk.space_bytes() / 1e6:.1f} MB")
        stats = sk.retention_stats()
        if stats["policy"] != "none":
            line += (f", {stats['segments_evicted']} segments evicted, "
                     f"window starts at item {stats['items_evicted']:,}")
        print(line + ")")
        # the windowed sketch must answer the in-window query to HIGGS's
        # usual fidelity — eviction may not add error on retained data
        assert err <= 0.01, (
            f"{name}: last-night ring flow off by {err:.2%}")

    win = sketches["HIGGS-window"]
    unb = sketches["HIGGS-unbounded"]
    print(f"resident bytes: windowed {win.space_bytes():,.0f} vs "
          f"unbounded {unb.space_bytes():,.0f} "
          f"({unb.space_bytes() / win.space_bytes():.1f}x) after "
          f"{N_DAYS} days — the windowed monitor has plateaued")
    assert win.space_bytes() < unb.space_bytes() / 2

    asyncio.run(live_analysts(src, dst, w, t, ring_edges))


async def live_analysts(src, dst, w, t, ring_edges):
    """Serve the windowed monitor while the stream is still arriving:
    four analysts polling the nightly ring flow concurrently, answers
    epoch-pinned and coalesced into shared probe launches."""
    monitor = make_summary("higgs", d1=16, F1=19,
                           retention=f"window:{DAY}")
    pipe = StreamPipeline(src, dst, w, t, batch=16_384)
    nights = [(day * DAY, day * DAY + NIGHT - 1) for day in range(N_DAYS)]
    async with monitor.serve(readers=2) as svc:
        svc.attach_stream(pipe)

        async def analyst(night):
            answers = []
            while not svc._writer_task.done():
                res = await svc.submit([SubgraphQuery(ring_edges, *night)])
                answers.append(res)
            answers.append(await svc.submit(
                [SubgraphQuery(ring_edges, *night)]))
            return answers

        per_analyst = await asyncio.gather(*[analyst(n) for n in nights],
                                           analyst(nights[-1]))
    print(f"\nlive serving: {svc.stats.queries_served} analyst queries "
          f"over {svc.stats.rounds} coalesced rounds "
          f"({svc.stats.epochs_pinned} epochs pinned while "
          f"{svc.stats.batches_ingested} stream batches drained)")
    # two analysts watching the same night on the same epoch must agree
    # exactly — that is the epoch-consistency contract
    a, b = per_analyst[-2], per_analyst[-1]
    by_epoch = {res.epoch: res.values[0] for res in a}
    agreed = 0
    for res in b:
        if res.epoch in by_epoch:
            assert res.values[0] == by_epoch[res.epoch]
            agreed += 1
    assert agreed > 0, "analysts never landed on a shared epoch"
    print(f"analysts agreed bit-exactly on {agreed} shared epoch "
          f"answer(s); final ring flow {b[-1].values[0]:,.0f} at epoch "
          f"{b[-1].epoch}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
