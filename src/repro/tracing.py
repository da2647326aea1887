"""Host spans on the profiler's clock.

    with span("machine.lfsr_init", problems=P, runs=R):
        ...
    with phase("fabric.engine", rec, "t_engine", sweep=s, color=c):
        ...

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace runs
(``jax.profiler.trace`` or ``start_trace``) it is a host event of that
trace, on the device trace's clock, named by its fixed ``name`` and
carrying ``counts`` as the event's stats; with no trace running it writes
nothing and costs about a microsecond. The profiler is the only switch:
the trace is the store, and nothing here keeps spans of its own.

``phase`` is a span that also adds its host-clock duration to
``ledger[key]``, for a ledger the program keeps whether traced or not.
"""
from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation


def span(name: str, **counts) -> TraceAnnotation:
    """One span; ``set_metadata(**counts)`` on it adds counts known only
    inside."""
    return TraceAnnotation(name, **counts)


@contextlib.contextmanager
def phase(name: str, ledger: dict, key: str, **counts):
    """A span whose host-clock duration is also added to ``ledger[key]``."""
    t0 = time.perf_counter()
    with TraceAnnotation(name, **counts):
        yield
    ledger[key] += time.perf_counter() - t0
