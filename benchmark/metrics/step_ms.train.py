"""Median wall time of one ``train_steps(feed, 1)`` call, loss on the
host included, over the steady steps of the window."""

import statistics


def read(run):
    steps = run["spans"].get("step_s")
    return statistics.median(steps) * 1e3 if steps else None
