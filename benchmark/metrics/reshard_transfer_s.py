"""Mean of ``ReshardEvent.stall_s`` over the window's reshards: snapshot,
new mesh and the device-to-device move of the state (the first step's
re-trace is not in it)."""

import statistics


def read(run):
    xs = run["spans"].get("transfer_s")
    return statistics.mean(xs) if xs else None
