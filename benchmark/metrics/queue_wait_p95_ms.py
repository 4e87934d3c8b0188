"""95th percentile of due time -> the scheduler's pop (``on_pop``): the
wait in the queue, generator lateness included."""

from benchmark import harness


def read(run):
    waits = run["spans"].get("queue_wait_s")
    return harness.percentile(waits, 0.95) * 1e3 if waits else None
