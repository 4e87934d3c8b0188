"""Mean over the window's decode blocks of the share of the padded KV
cache that a block fetches: the S-blocks ``edl_decode_attn`` reads up to
each slot's last token (an idle slot costs one) over all the blocks
there are, as the engine reckons it from its slot table at every
``serving.dispatch``. 1.0 is the dense read of everything."""

from benchmark.reduce import serving


def read(run):
    return serving.kv_read_share(run)
