"""Share of the traced window that is device self time under the two
retention scopes: ``attn.retention_step`` (a decode step's read, decay,
update and query of every live slot's state: the kernel
``edl_retention_step`` and the normaliser beside it) and
``attn.retention_chunk`` (a prefill's chunks). The mechanism's own
share, inside ``attn_time_share.state``."""

from benchmark.reduce import retention


def read(run):
    return retention.scope_share(run, retention.STEP, retention.CHUNK)
