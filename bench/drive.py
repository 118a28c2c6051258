"""The measured window: a closed-loop writer.

The writer calls ``summary.insert`` batch after batch until the window
closes.  Every insert is logged with the harness's clock; nothing here
computes a metric.  With a trace asked for, the writer starts the
profiler between two inserts once the trace is due and stops it after
the first insert that returns past its planned end, so the traced span
holds whole inserts, however long one takes.
"""
from __future__ import annotations

import dataclasses
import time

import jax
from jax.profiler import TraceAnnotation

now = time.perf_counter


@dataclasses.dataclass
class Log:
    # per insert: start, return, items inserted so far, structure_version
    inserts: list = dataclasses.field(default_factory=list)
    # items inserted in the traced span
    edges_traced: int = 0


def insert(summary, arrays, c0: int, c1: int, log: Log) -> None:
    t0 = now()
    with TraceAnnotation("bench.insert"):
        summary.insert(*(a[c0:c1] for a in arrays))
    log.inserts.append((t0, now(), c1, int(summary.structure_version)))


@dataclasses.dataclass
class Phase:
    """One stretch of the writer, from ``cursor`` until ``t_stop``
    (perf_counter seconds)."""
    t_stop: float
    cursor: int             # items inserted before t0
    batch: int
    trace: tuple | None = None   # (start, stop) of the profiler, absolute
    trace_dir: str | None = None


class _Tracer:
    def __init__(self, ph: Phase, log: Log):
        self.ph, self.log = ph, log
        self.on, self.done = False, ph.trace is None

    def before(self, t: float, cursor: int) -> None:
        if self.done or self.on or t < self.ph.trace[0]:
            return
        # no Python function tracing: it would slow the host it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.ph.trace_dir, profiler_options=opts)
        self.ann = TraceAnnotation("bench.traced")
        self.ann.__enter__()
        self.on, self.c_a = True, cursor

    def after(self, cursor: int, closing: bool) -> None:
        if not self.on or self.done:
            return
        if now() < self.ph.trace[1] and not closing:
            return
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.log.edges_traced = cursor - self.c_a
        self.done = True


def run_phase(summary, arrays, ph: Phase, log: Log, limit: int) -> int:
    """Insert batch after batch until ``t_stop``; returns the items
    inserted so far."""
    c = ph.cursor
    tracer = _Tracer(ph, log)
    while True:
        t = now()
        if t >= ph.t_stop or c + ph.batch > limit:
            tracer.after(c, closing=True)
            return c
        tracer.before(t, c)
        insert(summary, arrays, c, c + ph.batch, log)
        c += ph.batch
        tracer.after(c, closing=False)
