"""Mean over the window's decode blocks of the share of the padded
latent cache that a block fetches: the S-blocks
``edl_decode_attn_latent`` reads up to each slot's last token over all
the blocks there are, as the engine reckons it from its slot table at
every ``serving.dispatch``."""

from benchmark.reduce import serving


def read(run):
    return serving.kv_read_share(run)
