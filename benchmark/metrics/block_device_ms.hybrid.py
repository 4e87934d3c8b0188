"""Median device time of one run of the decode block program
(``edl_serve_block`` on the ``XLA Modules`` line) of the hybrid model:
one decode step of 72 slots through all 40 layers at the cell's
``horizon`` of 1."""

from benchmark.reduce import program


def read(run):
    return program.block_device_ms(run)
