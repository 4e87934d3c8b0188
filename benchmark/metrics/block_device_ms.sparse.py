"""Median device time of one run of the decode block program
(``edl_serve_block`` on the ``XLA Modules`` line) of the sparse latent-
attention expert model: one decode step of 32 slots at the cell's
``horizon`` of 1, index scores, selection and gathered attention in
it."""

from benchmark.reduce import program


def read(run):
    return program.block_device_ms(run)
