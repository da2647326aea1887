"""The program's own spans (``repro.tracing``) in a reduced trace.

``Reduced.host`` holds every host event as (start, end, name) on the
trace's clock; the program's spans are among them under their fixed
names. On a program that writes no such span these helpers find nothing,
and the readers built on them return None."""
from __future__ import annotations

import numpy as np


def named(t, name: str) -> list:
    """(start, end) of each span called ``name`` that starts in the
    traced window, in order."""
    return sorted((s, e) for s, e, n in t.host
                  if n == name and t.lo <= s < t.hi)


def mean_inside_ms(t, parents: list, child: str):
    """Mean over ``parents`` (start, end) of the summed length of the
    ``child`` spans that start inside each, in ms; None where the trace
    has no ``child`` span or no parent."""
    kids = named(t, child)
    if not kids or not parents:
        return None
    return float(np.mean([sum(e - s for s, e in kids if lo <= s < hi)
                          for lo, hi in parents])) / 1e6
