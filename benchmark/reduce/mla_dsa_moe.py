"""What the cell ``glm5.long-sparse``'s readers share: device self time
under the scopes ``models/glm_dsa.py`` names (``attn.index``: the
indexer's projections and scores, ``attn.select``: the choice of
positions, ``attn.sparse``: the attention over the chosen, all under
``attn``; ``moe`` and ``moe.experts`` as the other expert model's), the
counters the engine writes onto its ``serving.dispatch`` spans
(``kv_selected_share`` and ``kv_live_tokens`` from the slot table,
``experts_hit_share`` from the block program, the ``rids`` that ride a
block), and the decode steps the trace holds. A share of a peak is
reckoned from the TRACED dispatches' own attributes: the window starts
empty, and its mean is not what its last seconds' blocks needed. Every
function gives ``None`` where there is nothing to read: no trace, a CPU
rehearsal, or a program without the scopes or the attributes (the
parent's). The traced-window readers are ``reduce/retention.py``'s and
``reduce/mla_moe.py``'s."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from benchmark.reduce import mla_moe, program, retention, serving

INDEX, SELECT, SPARSE = "attn.index", "attn.select", "attn.sparse"
EXPERTS = "moe.experts"
scope_time = retention.scope_time
scope_share = retention.scope_share
dispatch_counter = retention.dispatch_counter
horizon = mla_moe.horizon


def traced_dispatches(run: Dict) -> List:
    """The ring's ``serving.dispatch`` spans that the trace holds too
    (joined by ``seq``): the decode blocks whose device time the trace's
    scopes give. The window's fill is not its end, so what a traced
    block needed is read from the traced dispatches, not from the mean
    of the window's."""
    planes = program.planes_of(run)
    if not planes:
        return []
    seqs = {int(ev[3]["seq"]) for ev in program.annotations(planes)
            if ev[0] == program.ANNOTATION_PREFIX + serving.DISPATCH}
    spans, _ = program.ring()
    return [s for seq, s in sorted(spans.items())
            if seq in seqs and s.name == serving.DISPATCH
            and "kv_selected_share" in s.attrs]


def traced(run: Dict, name: str) -> Optional[float]:
    """Mean of an attribute over the traced dispatches (``live_slots``:
    the requests that ride each)."""
    spans = traced_dispatches(run)
    if name == "live_slots":
        values = [len(s.attrs["rids"]) for s in spans]
    else:
        values = [float(s.attrs[name]) for s in spans if name in s.attrs]
    return statistics.fmean(values) if values else None


def steps_traced(run: Dict) -> Optional[float]:
    """Decode steps of the block programs the trace holds."""
    blocks = retention.blocks_traced(run)
    return blocks * horizon(run) if blocks else None


def block_seconds(run: Dict, *scopes: str) -> Optional[float]:
    """Device self time under the scopes inside the decode block
    programs; None where any of them is not there."""
    total = 0.0
    for scope in scopes:
        timed = scope_time(run, scope, program.BLOCK_PROGRAM)
        if not timed:
            return None
        total += timed[0]
    return total


def on_the_chip(run: Dict) -> bool:
    return run["device"]["platform"] == "tpu"
