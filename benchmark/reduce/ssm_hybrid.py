"""What the cell ``granite4h.decode-hybrid``'s readers share: device
self time under the scopes ``models/ssm_hybrid.py`` and ``ops/ssm.py``
name (``ssm`` around a state-space layer's mixing, inside it
``ssm.step`` in the decode block and ``ssm.chunk`` in the prefill
programs: dotted, which ``reduce/program.py``'s phase list does not
hold), the two shares the engine writes onto its ``serving.dispatch``
spans (``state_live_share`` and ``kv_read_share``: the model holds both
kinds of cache), and the buckets the traced prefill programs took.
Every function gives ``None`` where there is nothing to read: no trace,
a CPU rehearsal, or a program without the scopes or the attributes (the
parent's). The traced-window readers are ``reduce/retention.py``'s and
``reduce/mla_moe.py``'s: self time of the operations under a scope,
inside the programs a prefix names, and the mean of an attribute over
the window's dispatch spans."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.reduce import retention

LAYER, STEP, CHUNK = "ssm", "ssm.step", "ssm.chunk"
scope_time = retention.scope_time
scope_share = retention.scope_share
dispatch_counter = retention.dispatch_counter
blocks_traced = retention.blocks_traced
prefilled_tokens_traced = retention.prefilled_tokens_traced
# mean live slots of the window's decode blocks, from the dispatch
# spans' ``state_live_share``
live_slots = retention.live_slots


def kv_read_share(run: Dict) -> Optional[float]:
    """Mean ``kv_read_share`` of the window's dispatch spans, the
    chip's to report (a rehearsal's cache is a toy's)."""
    if run["device"]["platform"] != "tpu":
        return None
    return dispatch_counter(run, "kv_read_share")
