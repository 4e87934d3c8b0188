"""Median gap between a request's consecutive tokens as the engine's
``on_tokens`` hook stamped them; other callers' prefills (whole long
prompts, every slot waiting) are in the gaps they fall into."""

import statistics


def read(run):
    gaps = run["spans"].get("itl_s")
    return statistics.median(gaps) * 1e3 if gaps else None
