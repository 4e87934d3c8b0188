"""What the cell's own family says a decode step has to read."""


def read(run):
    resident = run["counters"].get("resident_tokens_mean")
    if resident is None:
        return None
    return run["cell"].family.needed.decode_step_bytes(
        run["config"], resident) / 1e6
