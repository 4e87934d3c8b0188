"""Median device time of one run of the decode block program
(``edl_serve_block`` on the ``XLA Modules`` line): what the device took
for a step, without the host's share of the step period."""

from benchmark.reduce import program


def read(run):
    return program.block_device_ms(run)
