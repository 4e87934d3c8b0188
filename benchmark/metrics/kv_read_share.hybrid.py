"""Mean over the window's decode blocks of the share of the padded
keys and values that a block fetches: the S-blocks ``edl_decode_attn``
reads up to each slot's last token over all the blocks there are, as
the engine reckons it from its slot table at every
``serving.dispatch``, on the same span as ``state_live_share``."""

from benchmark.reduce import ssm_hybrid


def read(run):
    return ssm_hybrid.kv_read_share(run)
