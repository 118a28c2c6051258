"""Program spans and the drain's one blocking fetch, on the profiler's clock.

``span(name)`` is ``jax.profiler.TraceAnnotation``: a host span that
costs one enter and one exit (under a microsecond) when no profiler is
running, and otherwise lands in the ``.xplane.pb`` the profiler writes,
on the same clock as the device planes.  Spans only record; nothing
reads them back to decide anything, and nothing here reads a clock.

``fetch(x, stats)`` is where the ingest path waits on the device: it
copies device arrays to the host inside a ``higgs.fetch`` span and
counts the copy in an :class:`~repro.api.queries.IngestStats`.
``spanned(name)`` wraps a whole function in a span.

Span names are fixed literals (``docs/API.md`` lists them); a name never
carries a per-call value.
"""
from __future__ import annotations

import functools

import jax
from jax.profiler import TraceAnnotation as span

__all__ = ["span", "spanned", "fetch"]


def spanned(name: str):
    """Decorator: run the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def fetch(x, stats):
    """``jax.device_get(x)`` (an array, or a tuple of arrays copied
    together) inside a ``higgs.fetch`` span, counted once in
    ``stats.fetches`` and by its bytes in ``stats.fetch_bytes``."""
    with span("higgs.fetch"):
        out = jax.device_get(x)
    stats.fetches += 1
    stats.fetch_bytes += sum(a.nbytes for a in jax.tree.leaves(out))
    return out
