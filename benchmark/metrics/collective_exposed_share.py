"""Share of the traced steady steps in which the device ran a collective
operation (all-gather, all-reduce, reduce-scatter; the ``-done`` half of
an asynchronous one is its wait). Operations of one core run one after
another on the ``XLA Ops`` line, so while one of these is there, no
compute is: this time is exposed."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["busy_s"]:
        return None
    return 100.0 * tr["collective_s"] / tr["window_s"]
