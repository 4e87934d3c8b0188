"""Published peak rates of one chip, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s per chip. A device that
is not in the table is an error, never a default: a share of an assumed
peak is not a measurement.
"""

PEAKS = {
    # device_kind as jax reports it: (bf16 FLOP/s, HBM bytes/s)
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
}


def peak(device_kind: str):
    """(FLOP/s, bytes/s) of one chip of this kind; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/reduce/peaks.py"
            " - add its published rates with their source") from None
