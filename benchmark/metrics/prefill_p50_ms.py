"""Median of pop -> first token: one padded-bucket prefill dispatch and
its sync."""

import statistics


def read(run):
    pf = run["spans"].get("prefill_s")
    return statistics.median(pf) * 1e3 if pf else None
