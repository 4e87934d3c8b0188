"""95th percentile of the gaps between a request's consecutive tokens:
a prefill stalls every running slot, and only this shows it."""

from benchmark import harness


def read(run):
    gaps = run["spans"].get("itl_s")
    return harness.percentile(gaps, 0.95) * 1e3 if gaps else None
