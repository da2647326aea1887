"""Readings from the program's own ledgers (spans it already records)."""
from __future__ import annotations


def fabric_mean_ms(window, key: str):
    """Mean of one ``meta['fabric']['per_sweep']`` phase time per outer
    sweep over the window's calls, in ms; None where there is no ledger."""
    vals = []
    for c in window.calls:
        fab = c.report.meta.get("fabric") if c.report.meta else None
        if fab:
            vals.extend(s[key] for s in fab["per_sweep"])
    return 1e3 * sum(vals) / len(vals) if vals else None
