"""Mean over the window's decode blocks of the share of the padded
cache's BYTES a block fetches: the index keys and the latent rows of the
key blocks up to the farthest live position, every live slot's, over
both arrays whole, as the engine reckons it from its slot table at
every ``serving.dispatch``. The decode step reads every live latent row
and masks those not chosen, so this stands beside
``kv_selected_share.sparse`` (what it attends): the gap between the two
is the dense read a kernel over the chosen rows would save."""

from benchmark.reduce import serving


def read(run):
    return serving.kv_read_share(run)
