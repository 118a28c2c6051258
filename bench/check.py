"""The comparison that decides ``correct``.

Every answer to a batch due in the window (or, in cells without
callers, to the check batches asked of the final state) is held against
the plain reference (``reference.py``) at the stream prefix that its
read epoch covers.  The epoch is ``QueryResult.epoch``, the summary's
``structure_version`` when it was pinned; the writer logged the version
after every insert, so an epoch names an insert call, and the reference
replays the same insert calls to know what was visible then.

Numbers compared, each with its limit:

* ``below_exact``: the most by which an answer falls below the exact
  weight over the keyed graph (``reference.key``): limit 0.
* ``above_exact``: the most by which an answer rises above it: limit 0.
  The configurations state that answers are exact over the keyed graph;
  weights are whole numbers and the sums stay far below 2**24, so a
  sound summary is exact to the last bit.
* ``stale_answers``: batches answered from an epoch older than the
  summary as it stood when the batch was submitted, or from an epoch no
  insert produced (a pin must see every closed edge): limit 0.
* ``missing_answers``: batches that never got an answer: limit 0.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"below_exact": 0.0, "above_exact": 0.0, "stale_answers": 0,
          "missing_answers": 0}


def _gaps(got, want) -> tuple[float, float]:
    below = above = 0.0
    for a, e in zip(got, want):
        d = np.asarray(a, np.float64) - np.asarray(e, np.float64)
        if d.size:
            below = max(below, float(-d.min()))
            above = max(above, float(d.max()))
    return below, above


def compare(ref, inserts: list, requests: list) -> dict:
    """``inserts``: (items inserted so far, structure_version) of every
    insert call in order, set-up included; ``requests``: (plain batch,
    QueryResult or None, version when submitted).  Returns the verdict
    and the closed prefix after each insert."""
    visible = {0: (0, 0)}
    his = []
    for cursor, version in inserts:
        visible[version] = ref.scan.advance(cursor)
        his.append(visible[version][1])
    below, above, stale, missing, bad, compared = 0.0, 0.0, 0, 0, 0, 0
    for plain, res, ver_sub in requests:
        if res is None:
            missing += 1
            continue
        if res.epoch not in visible or res.epoch < ver_sub:
            stale += 1
            continue
        lo, hi = visible[res.epoch]
        b, a = _gaps(res.values, ref.answer(plain, lo, hi))
        compared += sum(np.size(v) for v in res.values)
        bad += b > LIMITS["below_exact"] or a > LIMITS["above_exact"]
        below, above = max(below, b), max(above, a)
    numbers = {"below_exact": below, "above_exact": above,
               "stale_answers": stale, "missing_answers": missing}
    ok = all(numbers[k] <= LIMITS[k] for k in LIMITS)
    return {"correct": ok, "failed": bad + stale + missing,
            "attempted": len(requests), "values_compared": compared,
            "numbers": numbers}, his
