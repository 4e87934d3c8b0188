"""Share of the traced window in which a Mosaic (Pallas) kernel ran:
the flash-attention forward and backward calls are the only ones in the
train step."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["mosaic_s"]:
        return None
    return 100.0 * tr["mosaic_s"] / tr["window_s"]
