"""What a rematerialised layer keeps for its backward, from the room
its step has on a device.

Two sides know half each. The trainer knows a device's memory and what
it holds there whatever the model does: the state and the batch. The
model knows which of a layer's values its backward could reuse instead
of computing them again, what each costs in bytes, and what its forward
and backward fill besides (gradients included: when each is complete
is the model's shape). They meet at trace time,
as ``ops.flash_attention.interpret_kernels`` does: the trainer opens
:func:`offer` around the trace of its step, and a model that traces a
rematerialised layer under it calls :func:`choose` with its candidates
in order of seconds spared a byte. The answer is what fits the room,
taken in that order; with no offer open it is nothing, which is full
rematerialisation. What was chosen is left on the offer for the
trainer's span and memory ledger (``train.trainer.make_train_step``).

The compiler has the last word: the trainer compiles the step it
traced, holds ``memory_analysis()`` against the device's limit, and
where the compiler refuses or the margin is gone it traces again one
rung lower (``back_off``). That is the rare path; the estimate is there
so that the common one is one compile.
"""

from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass
from typing import Iterator, Optional, Sequence, Tuple


@dataclass
class Offer:
    """One trace's offer and, after it, the model's answer."""

    # a device's bytes that the model's forward and backward may fill
    room_bytes: int
    # rungs to stay below what the room allows: one more after each
    # step the compiler refused
    back_off: int = 0
    # the answer: the names kept, their bytes on a device, and how many
    # entries of the model's list they are (0: nothing left to give up)
    kept: Tuple[str, ...] = ()
    kept_bytes: int = 0
    rungs: int = 0


_OFFER: contextvars.ContextVar[Optional[Offer]] = contextvars.ContextVar(
    "edl_remat_offer", default=None)


@contextlib.contextmanager
def offer(room_bytes: int, back_off: int = 0) -> Iterator[Offer]:
    """While open, a model tracing a rematerialised layer may keep what
    fits ``room_bytes``. Read at TRACE time: open it around the first
    lowering of the jitted step, and give each attempt a function of
    its own to jit (jit's cache of traces is keyed by the function and
    does not see the offer)."""
    o = Offer(int(room_bytes), int(back_off))
    token = _OFFER.set(o)
    try:
        yield o
    finally:
        _OFFER.reset(token)


def choose(candidates: Sequence[Tuple[Tuple[str, ...], int]],
           working_bytes: int) -> Tuple[str, ...]:
    """The names to keep: ``candidates`` is the model's list, best
    first, of (names kept together, their bytes on a device over all
    layers); ``working_bytes`` is what the model's step fills on a
    device with nothing kept. In the list's order, every entry that
    still fits the open offer's room beside that and beside the
    entries taken before it (one too large is passed over, a smaller
    one after it may fit); then the last ``back_off`` of those are
    given up again. ``()`` with no offer open or no room."""
    o = _OFFER.get()
    if o is None:
        return ()
    free = o.room_bytes - int(working_bytes)
    chosen = []
    for names, nbytes in candidates:
        if nbytes <= free:
            free -= nbytes
            chosen.append((names, nbytes))
    chosen = chosen[:max(len(chosen) - o.back_off, 0)]
    o.kept = tuple(n for names, _ in chosen for n in names)
    o.kept_bytes = sum(b for _, b in chosen)
    o.rungs = len(chosen)
    return o.kept
