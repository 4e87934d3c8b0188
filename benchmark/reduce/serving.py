"""What the serving engine's own spans say about a window's decode
blocks, read from the tracer's ring (``program.ring``): no profiler
session is needed, and a program without the attribute (the parent of
the PR that added it) gives ``None``."""

from __future__ import annotations

import statistics
from typing import Dict, Optional

from benchmark.reduce import program

DISPATCH = "serving.dispatch"
WARM = "warm-"


def kv_read_share(run: Dict) -> Optional[float]:
    """Mean ``kv_read_share`` of the window's ``serving.dispatch`` spans:
    S-blocks of the KV cache a decode block fetches over the blocks of
    the padded cache (1.0: everything is read). A block that carries
    only warm-up requests is set-up, not the window. Like the other
    numbers about the device's work it is read on the chip alone: a CPU
    rehearsal's cache is a toy's."""
    if run["device"]["platform"] != "tpu":
        return None
    spans, _ = program.ring()
    shares = [
        float(s.attrs["kv_read_share"]) for s in spans.values()
        if s.name == DISPATCH and "kv_read_share" in s.attrs
        and any(not str(r).startswith(WARM) for r in s.attrs.get("rids", ()))
    ]
    return statistics.fmean(shares) if shares else None
