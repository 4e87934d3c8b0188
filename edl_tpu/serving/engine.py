"""Continuous-batching generation engine — a slot-table decode loop
over the llama KV-cache path, driven in fused multi-step HORIZON
blocks with a double-buffered async host pipeline.

The decode roofline is HBM-bound and batch-sensitive: a
one-request-at-a-time server streams the full weight set per token for
ONE token. This engine keeps
a fixed table of ``max_slots`` KV slots and decodes every active slot
in one batched step, prefill-inserting new requests into free slots and
evicting finished ones BETWEEN blocks — requests are the elastic
membership, and the decode program never changes shape while they come
and go.

Three per-token costs the PR-1 engine paid are gone:

* **one dispatch per token** → one dispatch per ``horizon`` tokens:
  ``llama.decode_horizon_slots`` scans H decode steps inside one
  program, with per-slot termination (EOS / budget) handled on device
  so finished rows freeze inside the block and greedy output stays
  token-identical to sequential ``generate``;
* **a blocking ``np.asarray`` per token** → a double-buffered pipeline:
  the non-cache carries (tok/pos/active/rem) come back as DEVICE
  arrays, so block k+1 dispatches before the host ever syncs block k's
  token matrix; bookkeeping drains the previous block while the device
  runs the next;
* **a fresh full KV cache allocation + copy per step** → buffer
  donation: both the fused-decode and prefill programs take ``kc``/
  ``vc`` with ``donate_argnums``, so XLA updates the cache in place.
  The engine enforces the stale-reference invariant itself
  (:meth:`ContinuousBatchingEngine._assert_donated`): a donated buffer
  that survives a dispatch means the in-place update silently
  regressed to a copy.

jit stability across membership changes is still the design center,
mirroring ``llama._generate_program``:

* ONE compiled block program per (cfg, max_slots, max_len, horizon,
  sampling) — per-row positions/masks, so a join or evict changes
  host-side bookkeeping only, never the program;
* O(log max_prompt) compiled prefill programs — prompts pad into
  power-of-two buckets and ``llama.prefill_padded`` takes the real
  length as a traced scalar (causality makes end-padding invisible);
  the prefill program also scatters the new K/V into the slot row,
  samples the first token, and resets the slot's device-side decode
  state, so admission is one dispatch;
* programs are memoized module-level in an LRU (move-to-end on hit,
  evict-oldest at the cap — a cache-clear here used to drop the hot
  decode program mid-traffic), so engines are cheap to construct and
  tests/harnesses reuse compiles.

**The KV layout is decided in one place.** Three layouts are served:
contiguous (one row a slot), paged (``block_size > 0``: a pool of
blocks behind per-slot tables) and paged + quantized (``kv_quant``:
int8 / int4 pools with scale planes). What the cache's arrays ARE is
one tuple, ``self._cache``, allocated from one spec a layout; the
engine never looks inside it: every program takes it as ``*cache``,
donates it by position and hands it back, and every dispatch rebinds
it whole. WHICH programs serve the layout, ``__init__`` chooses once;
the operands a paged program wants in front of the cache (a block
table, a ``start``) come from ``_block_table`` / ``_slot_table``,
empty for the contiguous layout. So each kind of dispatch (decode
block, verify, final prefill piece, prefill chunk, block copy) is
written once, and ``self._paged`` is asked only where the HOST's
bookkeeping differs (admission by blocks, tables, prefix cache, frees).

Admission lands on BLOCK boundaries (``InterleavePolicy.block_budget``
— the drain-to-admit budget): when the queue is non-empty but no slot
is known-free, the engine drains in-flight blocks first so a freed
slot admits now rather than a block later. That drain is the one place
serving latency is traded for admission latency; with free slots in
view, admission never blocks the pipeline.

Greedy decode (temperature == 0, the default) is token-identical to
sequential ``llama.generate`` per request at EVERY horizon — the
correctness contract ``tests/test_serving.py`` pins, including EOS
hit mid-block and mid-stream join/evict. Temperature sampling is
supported but uses the engine's own per-block key schedule (a batched
server cannot replay ``generate``'s per-request key walk).

**Crash safety.** Donation makes a mid-dispatch exception nasty: the
consumed ``kc``/``vc`` are already dead, so the engine cannot simply
retry the block. Instead the host keeps enough state to rebuild from
NOTHING — every slot retains its request's prompt, and host
``generated`` is the committed truth. On any exception escaping
``_dispatch_block`` / ``_admit``'s prefill / ``_drain_one``, the
engine discards all in-flight blocks, reallocates the KV cache and
device slot-state, and re-prefills each live slot from
``prompt + generated`` — under greedy decoding the prefill over the
full context emits exactly the token the lost decode step would have,
so the replay is token-identical to a fault-free run (the contract
``tests/test_serving_recovery.py`` pins, with faults injected via
``edl_tpu.utils.faults``). Recovery attempts are bounded PER REQUEST
(``max_recoveries``, default 2): a request that keeps sinking recovery
passes finishes with outcome ``"failed"`` instead of wedging the
engine. Requests carry optional deadlines (``deadline_s``): between
blocks the engine evicts overdue slots (outcome ``"timeout"``) and
sheds queued requests whose deadline passed while waiting
(``rejected:timeout``) — overload drops the stalest work instead of
growing the queue without bound.

**Flight recorder.** Every request-lifecycle decision (submit / admit
/ reject / prefill / block / finish) and every recovery pass lands in
the process flight recorder (edl_tpu/obs/events.py) keyed by ``rid``,
so ``edl postmortem`` reconstructs any request's timeline — and each
``_recover`` dumps the ring to ``$EDL_BLACKBOX_DIR`` (when set) before
rebuilding, the black box that explains what led to the crash.

**Latency decomposition.** The engine stamps each request's phases
separately — queue wait ends at the scheduler pop (``on_pop``),
prefill ends when the first token lands, and every fused block's
dispatch→drain wall time is observed per drain (``on_block``) — so
TTFT decomposes into "queue grew" vs "prefill slowed" and the
``serve.finish`` event carries the full breakdown (plus the request's
``tenant``/``slo_class`` labels); obs/slo.py turns the per-request
records into goodput-under-SLO.
"""

from __future__ import annotations

import contextlib
import time
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.models import llama
from edl_tpu.obs import compilewatch
from edl_tpu.obs import costmodel as _cm
from edl_tpu.obs import memledger
# the kernel's module (and with it Pallas) is imported here, at module
# depth, not first by `llama.attention` inside a prefill program's
# trace: under the trace's deep Python stack that import (hundreds of
# enum classes) kept crossing a boundary of CPython's 16 KiB
# frame-stack chunks, an mmap/munmap a call, +0.8 s on an engine's
# first prefill (PERF.md section 6, PR 24)
from edl_tpu.ops import flash_attention as _flash_attention  # noqa: F401
from edl_tpu.ops import decode_attention as _decode_attention  # noqa: F401
from edl_tpu.serving import paged as _paged
from edl_tpu.serving import spec as _spec
from edl_tpu.serving.metrics import ServingMetrics
from edl_tpu.serving.scheduler import (
    AdmissionError,
    InterleavePolicy,
    Request,
    RequestQueue,
)
from edl_tpu.obs import disttrace
from edl_tpu.obs import events as flight
from edl_tpu.utils import faults, tracing
from edl_tpu.utils.logging import kv_logger

log = kv_logger("serving")

compilewatch.install()  # compile telemetry for every program built here

_programs: "OrderedDict" = OrderedDict()
_PROGRAM_CAP = 128


def _named(name: str):
    """Decorator, under ``jax.jit``: the function takes the name its
    program is known by. ``jax.jit`` names the XLA module after the
    function, so this is what the profiler's ``XLA Modules`` line and
    the compile telemetry (``edl_compile_seconds{program}``) tell the
    engine's programs apart by."""

    def rename(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn

    return rename


@jax.named_scope("head")
def _first_token(logits, key, temperature, sampling: bool):
    """The token a final prefill piece emits, from its last position's
    logits [1, V]: greedy, or drawn at ``temperature``. Part of the
    model's ``head`` phase in a profile."""
    if sampling:
        t0 = jax.random.categorical(key, logits / temperature, axis=-1)
    else:
        t0 = jnp.argmax(logits, axis=-1)
    return t0.astype(jnp.int32)[0]


def _memo(key, make):
    """Module-level LRU program cache: hits move to the end, inserts
    past the cap evict the LEAST-recently-used entry — never the whole
    cache (the old clear-everything eviction dropped the hot decode
    program the moment a 129th prefill bucket appeared)."""
    fn = _programs.get(key)
    if fn is not None:
        _programs.move_to_end(key)
        return fn
    while len(_programs) >= _PROGRAM_CAP:
        _programs.popitem(last=False)
    fn = _programs[key] = make()
    return fn


# What the engine asks of the config of a model it serves from the
# contiguous cache (``LlamaConfig`` answers with what this engine
# always ran; the paged, quantized, chunked and verify programs below
# are that model's alone) --
# ``cfg.serve_cache_spec(slots, max_len)``: the ((shape, dtype), ...) of
# the cache's arrays, each ``[L, slots, ...]``: the depth ``L`` is the
# array's own (a model with layers of two kinds holds arrays of two
# depths) and what follows the slot axis is the model's (positions of a
# KV cache, ``max_len`` of them; a recurrent layer's state, sized by
# the slots alone). ``max_len`` is what bounds a prompt's bucket and a
# request's total length;
# ``cfg.serve_cache_kinds``: one name per array of the spec, what the
# memory ledger files it under (``edl_hbm_bytes{category=}``): "kv" for
# rows a position, "state" for a state a slot;
# ``cfg.serve_prefill(params, tokens [1, Tb], last)`` -> (logits [1, V],
# one ``[L, 1, ...]`` array of a slot's rows per cache array), which
# the engine writes into the slot. A prompt is END-padded to its bucket:
# what the cache holds afterwards must be what it holds after position
# ``last``;
# ``cfg.serve_decode_block(params, tok, pos, active, rem, eosv, cache,
# horizon=, key=, temperature=, sampling=)`` -> (toks [B, H], tok, pos,
# active, rem, cache, counters), ``counters`` a dict of device scalars
# about the block (may be empty) that the engine drains with the
# block's tokens onto its ``serving.dispatch`` span;
# ``cfg.serve_attn_block(max_len)``: positions of one S-block its decode
# attention fetches (``max_len``: the whole slot at once);
# ``cfg.serve_cache_read(held, max_len, block)`` -> {name: share}: what
# share of each kind of cache the model has the block about to be
# dispatched reads, under the names they have on the dispatch span and
# in ``CostModel.decode_block`` (``kv_read_share`` of a positional
# cache, ``state_live_share`` of a per-slot state; a model with both
# answers both), from the host's slot table: ``held`` has one entry a
# slot, the tokens it holds or None for an idle one; ``block`` is the
# S-block above. Beside the six, ``_SEAM_OPTIONAL``: a config MAY say
# into how many pieces of query rows its one prefill program walks a
# bucket (``cfg.serve_prefill_pieces(bucket)``): the ``serving.prefill``
# span then carries ``pieces``.
_SEAM = ("serve_cache_spec", "serve_cache_kinds", "serve_prefill",
         "serve_decode_block", "serve_attn_block", "serve_cache_read")
_SEAM_OPTIONAL = ("serve_prefill_pieces",)


def _block_program(cfg, b: int, s: int, horizon: int, sampling: bool):
    """(params, tok, pos, active, rem, eosv, *cache, key, temperature)
    -> (toks [B, H], tok, pos, active, rem, *cache, counters). One
    fused horizon of H decode steps — the single program every
    membership composition runs — as the config's model runs it. The
    cache arrays (kc, vc for the dense decoder) AND the consumed
    slot-state vectors are donated: the cache updates in place and the
    returned carries are the only live references."""

    def make():
        n = len(cfg.serve_cache_spec(b, s))

        @partial(jax.jit, donate_argnums=(1, 2, 3, 4) + tuple(range(6, 6 + n)))
        @_named("edl_serve_block")
        def run(params, tok, pos, active, rem, eosv, *rest):
            *cache, key, temperature = rest
            toks, tok, pos, active, rem, cache, counters = (
                cfg.serve_decode_block(
                    params, tok, pos, active, rem, eosv, tuple(cache),
                    horizon=horizon, key=key, temperature=temperature,
                    sampling=sampling,
                )
            )
            return (toks, tok, pos, active, rem, *cache, counters)

        return run

    return _memo(("block", cfg, b, s, horizon, sampling), make)


def _prefill_program(cfg, tb: int, sampling: bool):
    """(params, tokens [1, Tb], last, slot, max_new, eos, tok, pos,
    active, rem, eosv, *cache, key, temperature) -> (first_tok, tok,
    pos, active, rem, eosv, *cache): prefill one padded prompt, scatter
    its rows into cache row ``slot``, emit the first generated token,
    and reset the slot's device-side decode state (position, budget,
    stop token, active mask — EOS-on-first-token and max_new == 1
    deactivate on device exactly like the host bookkeeping) — one
    dispatch per admission. ``last``/``slot``/``max_new``/``eos`` are
    traced, so one program serves every (length, slot, budget) inside
    the bucket. The cache and the slot-state vectors are donated, same
    contract as the block program."""

    def make():
        n = len(cfg.serve_cache_spec(1, tb))

        @partial(jax.jit, donate_argnums=tuple(range(6, 11 + n)))
        @_named(f"edl_serve_prefill_{tb}")
        def run(params, tokens, last, slot, max_new, eos,
                tok, pos, active, rem, eosv, *rest):
            *cache, key, temperature = rest
            logits, rows = cfg.serve_prefill(params, tokens, last)
            cache = [
                jax.lax.dynamic_update_slice(
                    c, r, (0, slot) + (0,) * (c.ndim - 2))
                for c, r in zip(cache, rows)
            ]
            t0 = _first_token(logits, key, temperature, sampling)
            tok = tok.at[slot].set(t0)
            pos = pos.at[slot].set(last + 1)
            hit = (eos >= 0) & (t0 == eos)
            active = active.at[slot].set(~hit & (max_new > 1))
            rem = rem.at[slot].set(jnp.maximum(max_new - 1, 0))
            eosv = eosv.at[slot].set(eos)
            return (t0, tok, pos, active, rem, eosv, *cache)

        return run

    return _memo(("prefill", cfg, tb, sampling), make)


def _paged_layout(kv_quant: str):
    """(arrays in the paged cache tuple, suffix of its programs' names):
    the K and V pools, and under ``kv_quant`` their scale planes too."""
    return (2, "") if kv_quant == "off" else (4, "_q")


def _block_program_paged(
    cfg: llama.LlamaConfig, b: int, nb: int, m: int, bs: int,
    horizon: int, sampling: bool, kv_quant: str = "off",
):
    """The paged twin of :func:`_block_program`: same carries plus the
    [B, M] block table in front of the cache (read-only, NOT donated —
    the host rebuilds it from its allocator truth each dispatch). The
    cache is the block POOL, (kc, vc) [L, nb, bs, KV, hd], under
    ``kv_quant`` int8 (packed int4 under the same dtype) with the scale
    planes (ks, vs) [L, nb, KV] behind it; every array of it is donated
    under the same stale-reference contract — a stale scale reference
    is as unsafe as a stale pool. ``kv_quant="off"`` traces the plain
    paged program."""
    n, q = _paged_layout(kv_quant)

    def make():
        @partial(jax.jit, donate_argnums=(1, 2, 3, 4) + tuple(range(7, 7 + n)))
        @_named("edl_serve_block_paged" + q)
        def run(params, tok, pos, active, rem, eosv, table, *rest):
            *cache, key, temperature = rest
            toks, tok, pos, active, rem, cache = (
                llama.decode_horizon_slots_paged(
                    params, tok, pos, active, rem, eosv, table, tuple(cache),
                    cfg, block_size=bs, horizon=horizon, key=key,
                    temperature=temperature, sampling=sampling,
                    kv_quant=kv_quant,
                )
            )
            # no counters from this model's paged block: the arity of
            # :func:`_block_program`, so one host call serves both
            return (toks, tok, pos, active, rem, *cache, {})

        return run

    return _memo(
        ("block-paged", kv_quant, cfg, b, nb, m, bs, horizon, sampling), make
    )


def _prefill_paged_program(cfg: llama.LlamaConfig, tb: int, bs: int,
                           sampling: bool, kv_quant: str = "off"):
    """Final-piece paged prefill: run the bucketed tail of a prompt
    (logical positions ``start .. start+last``) through
    ``llama.prefill_paged``, sample the first token, and reset the
    slot's device decode state — the paged twin of
    :func:`_prefill_program`, with ``start`` and the slot's table row
    in front of the cache. Earlier positions (prefix-cache hits or
    previously dispatched chunks) are already resident in the pool."""
    n, q = _paged_layout(kv_quant)

    def make():
        @partial(jax.jit,
                 donate_argnums=tuple(range(6, 11)) + tuple(range(13, 13 + n)))
        @_named(f"edl_serve_prefill_paged{q}_{tb}")
        def run(params, tokens, last, slot, max_new, eos,
                tok, pos, active, rem, eosv, start, table, *rest):
            *cache, key, temperature = rest
            logits, cache = llama.prefill_paged(
                params, tokens, start, last, table, tuple(cache), cfg, bs,
                kv_quant=kv_quant,
            )
            t0 = _first_token(logits, key, temperature, sampling)
            tok = tok.at[slot].set(t0)
            pos = pos.at[slot].set(start + last + 1)
            hit = (eos >= 0) & (t0 == eos)
            active = active.at[slot].set(~hit & (max_new > 1))
            rem = rem.at[slot].set(jnp.maximum(max_new - 1, 0))
            eosv = eosv.at[slot].set(eos)
            return (t0, tok, pos, active, rem, eosv, *cache)

        return run

    return _memo(("prefill-paged", kv_quant, cfg, tb, bs, sampling), make)


def _prefill_chunk_program(cfg: llama.LlamaConfig, c: int, bs: int,
                           kv_quant: str = "off"):
    """One NON-final prefill chunk: write ``c`` prompt tokens' K/V into
    the pool at ``start .. start+c-1`` and return only the cache — no
    logits consumed, no slot state touched, so a long prompt advances
    one bounded dispatch at a time between decode blocks instead of
    one monolithic prefill that starves running slots."""
    n, q = _paged_layout(kv_quant)

    def make():
        @partial(jax.jit, donate_argnums=tuple(range(4, 4 + n)))
        @_named(f"edl_serve_prefill_chunk{q}_{c}")
        def run(params, tokens, start, table, *cache):
            _, cache = llama.prefill_paged(
                params, tokens, start, jnp.int32(c - 1), table, cache,
                cfg, bs, kv_quant=kv_quant,
            )
            return cache

        return run

    return _memo(("prefill-chunk", kv_quant, cfg, c, bs), make)


def _copy_block_program(cfg: llama.LlamaConfig, nb: int, bs: int,
                        kv_quant: str = "off"):
    """(*cache, src, dst) -> cache: copy one physical KV block (``src``
    → ``dst``, traced indices) in EVERY array of the cache — the
    copy-on-write primitive: a slot about to write into a SHARED block
    gets a private copy first, so prefix-cache blocks are immutable
    while referenced. Under ``kv_quant`` the block's SCALES move with
    its values — a copied block re-quantized under the wrong scale
    would silently rescale the whole shared prefix."""
    n, q = _paged_layout(kv_quant)

    def make():
        @partial(jax.jit, donate_argnums=tuple(range(n)))
        @_named("edl_serve_block_copy" + q)
        def run(*rest):
            *cache, src, dst = rest
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    c, jax.lax.dynamic_slice_in_dim(c, src, 1, axis=1),
                    dst, axis=1)
                for c in cache
            )

        return run

    return _memo(("blockcopy", kv_quant, cfg, nb, bs), make)


def _verify_program(cfg: llama.LlamaConfig, b: int, s: int, d: int):
    """(params, tok, draft [B, D], pos, active, rem, eosv, kc, vc) ->
    (outs [B, D+1], tok, pos, active, rem, kc, vc). One speculative
    draft–verify dispatch: D+1 query lanes per slot in ONE weight
    pass, longest greedy-consistent draft prefix committed on device
    (``llama.verify_step_slots``). Same donation contract as the block
    program — kc/vc and the consumed slot-state vectors are donated;
    eosv and the fresh host-built draft matrix are not."""

    def make():
        @partial(jax.jit, donate_argnums=(1, 3, 4, 5, 7, 8))
        @_named("edl_serve_verify")
        def run(params, tok, draft, pos, active, rem, eosv, kc, vc):
            return llama.verify_step_slots(
                params, tok, draft, pos, active, rem, eosv, kc, vc, cfg
            )

        return run

    return _memo(("verify", cfg, b, s, d), make)


def _verify_program_paged(
    cfg: llama.LlamaConfig, b: int, nb: int, m: int, bs: int, d: int,
    kv_quant: str = "off",
):
    """The paged twin of :func:`_verify_program`: same carries plus
    the [B, M] block table in front of the cache (read-only, NOT
    donated, same as the paged block program)."""
    n, q = _paged_layout(kv_quant)

    def make():
        @partial(jax.jit, donate_argnums=(1, 3, 4, 5) + tuple(range(8, 8 + n)))
        @_named("edl_serve_verify_paged" + q)
        def run(params, tok, draft, pos, active, rem, eosv, table, *cache):
            *state, cache = llama.verify_step_slots_paged(
                params, tok, draft, pos, active, rem, eosv, table, cache,
                cfg, block_size=bs, kv_quant=kv_quant,
            )
            return (*state, *cache)

        return run

    return _memo(("verify-paged", kv_quant, cfg, b, nb, m, bs, d), make)


class SpecAcceptGuard:
    """Live quality gate for the quantized-KV path: speculative
    acceptance rate is a free, always-on probe of output quality (the
    verifier's argmax IS the model's output — if quantization bends the
    distribution, drafts stop matching and acceptance falls before any
    offline eval would notice). The guard warms up a baseline from the
    first ``warmup`` verify blocks, then freezes it and flags DEGRADED
    when the acceptance EMA drops more than ``tol`` (absolute rate
    points) below baseline. Publishes ``edl_kv_quant_quality_ok``
    (1 healthy / 0 degraded) and emits a flight event once per
    transition — an operator alarm, not an automatic fallback (the
    identity lane is a restart away with ``--kv-quant off``)."""

    def __init__(self, registry, *, warmup: int = 20, tol: float = 0.05,
                 alpha: float = 0.1):
        self.warmup = int(warmup)
        self.tol = float(tol)
        self.alpha = float(alpha)
        self.baseline: Optional[float] = None
        self.ema: Optional[float] = None
        self.ok = True
        self._seen = 0
        self._acc_sum = 0.0
        self._g_ok = registry.gauge(
            "edl_kv_quant_quality_ok",
            "1 while the quantized-KV spec-acceptance EMA holds its "
            "warmed-up baseline, 0 after a degradation (serving/engine"
            ".py SpecAcceptGuard)",
        )
        self._g_ok.set(1.0)

    def observe(self, drafted: int, accepted: int) -> None:
        """Feed one verify block's (drafted, accepted) counts."""
        if drafted <= 0:
            return
        rate = accepted / drafted
        self.ema = (
            rate if self.ema is None
            else (1 - self.alpha) * self.ema + self.alpha * rate
        )
        if self.baseline is None:
            self._seen += 1
            self._acc_sum += rate
            if self._seen >= self.warmup:
                self.baseline = self._acc_sum / self._seen
            return
        degraded = self.ema < self.baseline - self.tol
        if degraded == self.ok:  # transition either way
            self.ok = not degraded
            self._g_ok.set(1.0 if self.ok else 0.0)
            flight.emit(
                "serve.kv_quant_quality",
                severity="warn" if degraded else "info",
                ok=self.ok, ema=round(self.ema, 4),
                baseline=round(self.baseline, 4), tol=self.tol,
            )


@dataclass
class _Slot:
    """Host-side state of one occupied KV slot. The device holds the
    authoritative decode state on the HOT path, but the host copy is
    the RECOVERY truth: ``prompt`` + ``generated`` is everything needed
    to re-prefill this slot into a freshly allocated cache after a
    crash, and ``generated`` only ever contains drained (committed)
    tokens. ``deadline`` is the absolute eviction time on the engine
    clock (None = no deadline); ``recoveries`` counts how many engine
    recovery passes this request has survived."""

    rid: str
    prompt: List[int]
    max_new: int
    eos_id: Optional[int]
    generated: List[int] = field(default_factory=list)
    deadline: Optional[float] = None
    recoveries: int = 0
    tenant: Optional[str] = None
    slo_class: Optional[str] = None
    # chunked prefill (paged mode): next prompt index still to prefill;
    # None once the final piece ran and the slot is decoding
    pf_next: Optional[int] = None
    # admission sequence number — preemption under pool pressure evicts
    # the YOUNGEST slot (least sunk work)
    born: int = 0


@dataclass
class RequestResult:
    rid: str
    tokens: List[int]
    outcome: str  # done | eos | timeout | failed


class ContinuousBatchingEngine:
    """In-process continuous-batching server over a model's param tree.

    ``cfg`` is the model's (it answers ``_SEAM``): ``llama.LlamaConfig``
    the dense decoder, ``deepseek_v3.DeepseekV3Config`` the latent-
    attention expert model. For the dense decoder ``params`` is
    anything ``llama.generate`` accepts: a dense export tree
    (``load_export``), a sharded one (``load_export_sharded``), or the
    weight-only int8 records (``quantize_params_int8``), and the KV
    cache is [L, max_slots, max_len, KV, hd] in ``cfg.dtype`` (another
    model's is what its ``serve_cache_spec`` says) — sized once,
    donated through every dispatch, updated in place. The paged,
    quantized-cache, prefix, chunked-prefill and speculative options
    are the dense decoder's: another model is refused with them.

    ``horizon`` is the fused block depth: one device dispatch runs H
    decode steps with per-slot termination on device. H=1 reproduces
    the classic per-token iteration exactly (TTFT-optimal); larger H
    divides dispatch + host-sync overhead by H at the cost of admission
    landing on block boundaries (a new request waits up to H-1 steps
    longer mid-block). Greedy tokens are identical at every H.

    Drive it with :meth:`submit` + :meth:`step` (one admit/dispatch/
    drain block iteration — the soak harness interleaves arrivals
    here) or :meth:`run` (drain everything). Completed requests land
    in ``results`` and the metrics hooks fire along the way.
    """

    def __init__(
        self,
        params: Any,
        cfg: llama.LlamaConfig,
        *,
        max_slots: int = 8,
        max_len: int = 256,
        horizon: int = 1,
        queue: Optional[RequestQueue] = None,
        metrics: Optional[ServingMetrics] = None,
        policy: Optional[InterleavePolicy] = None,
        temperature: float = 0.0,
        seed: int = 0,
        min_bucket: int = 8,
        max_recoveries: int = 2,
        block_size: int = 0,
        pool_blocks: Optional[int] = None,
        prefix_cache: bool = False,
        prefill_chunk: int = 0,
        kv_quant: str = "off",
        spec_k: int = 0,
        spec_ngram: int = 3,
        spec_min_accept: float = 0.0,
        clock=time.monotonic,
    ):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if max_recoveries < 0:
            raise ValueError(
                f"max_recoveries must be >= 0, got {max_recoveries}"
            )
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k > 0:
            # speculation is greedy-only: acceptance compares drafts to
            # argmax, and a sampled stream has no "the" next token to
            # match — fail loudly instead of silently changing the
            # sampling distribution
            if temperature > 0:
                raise ValueError(
                    "spec_k > 0 requires greedy decoding "
                    f"(temperature 0), got temperature {temperature}"
                )
            if spec_ngram < 1:
                raise ValueError(
                    f"spec_ngram must be >= 1, got {spec_ngram}"
                )
        missing = [f for f in _SEAM if not hasattr(cfg, f)]
        if missing:
            raise TypeError(
                f"{type(cfg).__name__} cannot be served: it lacks "
                f"{', '.join(missing)}"
            )
        (self._prefill_pieces,) = (
            getattr(cfg, name, None) for name in _SEAM_OPTIONAL)
        if not isinstance(cfg, llama.LlamaConfig) and (
            block_size or prefix_cache or prefill_chunk
            or kv_quant != "off" or spec_k
        ):
            raise ValueError(
                f"{type(cfg).__name__} is served from the contiguous cache "
                "alone: block_size, prefix_cache, prefill_chunk, kv_quant "
                "and spec_k are the dense decoder's (llama.LlamaConfig)"
            )
        # quantized paged KV (kv_quant != "off"): the pool stores int8
        # (or packed int4) entries + per-block-per-kv-head f32 scales;
        # decode moves 2-4x fewer cache bytes. "off" is the identity
        # lane — byte-identical programs, no scale planes allocated.
        if kv_quant not in ("off", "int8", "int4"):
            raise ValueError(
                f"kv_quant must be one of off/int8/int4, got {kv_quant!r}"
            )
        if kv_quant != "off" and not block_size > 0:
            raise ValueError(
                "kv_quant requires the paged KV cache (block_size > 0)"
            )
        self.kv_quant = str(kv_quant)
        # paged KV mode (block_size > 0): the cache is a pool of
        # fixed-size blocks addressed through per-slot block tables —
        # HBM scales with RESIDENT tokens, not slots x max_len, and
        # admission gates on free blocks instead of free slots.
        # `_cache_spec` is what the cache's arrays are, ((shape, dtype),
        # ...): all `_alloc_device_state` needs to know of the layout
        self._paged = block_size > 0
        if self._paged:
            if max_len % block_size != 0:
                raise ValueError(
                    f"max_len {max_len} must be a multiple of "
                    f"block_size {block_size}"
                )
            self._m = max_len // block_size  # table width (blocks/slot)
            if pool_blocks is None:
                # default: the contiguous engine's capacity + scratch —
                # same HBM, pressure-free (the bench shrinks this)
                pool_blocks = max_slots * self._m + 1
            if pool_blocks < self._m + 1:
                # usable pool must cover ONE full-length sequence, the
                # invariant that makes preemption-to-fit always succeed
                raise ValueError(
                    f"pool_blocks {pool_blocks} < {self._m + 1} "
                    f"(scratch + one full sequence of {self._m} blocks)"
                )
            if prefill_chunk < 0:
                raise ValueError(
                    f"prefill_chunk must be >= 0, got {prefill_chunk}"
                )
            # a block POOL for K and for V, not a slot slab. Quantized:
            # int8 entries (int4 packs two per byte along head_dim, and
            # raises here on an odd one) + per-block-per-kv-head f32
            # scale planes for K and V. A zero scale decodes a zero
            # block — the recovery realloc is self-consistent.
            quant = kv_quant != "off"
            L, kvh = cfg.n_layers, cfg.n_kv_heads
            hdp = llama.kvq_packed_head_dim(kv_quant, cfg.head_dim)
            pool = ((L, pool_blocks, block_size, kvh, hdp),
                    jnp.int8 if quant else cfg.dtype)
            scale = ((L, pool_blocks, kvh), jnp.float32)
            self._cache_spec = (pool, pool) + ((scale, scale) if quant else ())
        elif prefix_cache or prefill_chunk:
            raise ValueError(
                "prefix_cache/prefill_chunk require block_size > 0"
            )
        else:
            self._m = 0
            self._cache_spec = cfg.serve_cache_spec(max_slots, max_len)
        self.block_size = int(block_size)
        self.pool_blocks = int(pool_blocks) if self._paged else 0
        self.prefill_chunk = int(prefill_chunk)
        self._use_prefix = bool(prefix_cache)
        self._admit_seq = 0
        # resident on the device ONCE: an export loads as host numpy, and
        # a host array handed to a jitted program is re-uploaded on
        # every dispatch (the whole weight set per decode block). A
        # tree already on devices (sharded --mesh load) passes through.
        self.params = jax.device_put(params)
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.horizon = horizon
        # the default queue holds at least a request a slot: a full
        # server's callers may all send at once
        self.queue = queue or RequestQueue(
            max_total_len=max_len, max_depth=max(64, max_slots), clock=clock)
        if self.queue.max_total_len > max_len:
            raise ValueError(
                f"queue admits up to {self.queue.max_total_len} total "
                f"tokens but KV slots hold {max_len}"
            )
        self.metrics = metrics or ServingMetrics(clock=clock)
        self.policy = policy or InterleavePolicy()
        self.temperature = float(temperature)
        self.min_bucket = min_bucket
        self.max_recoveries = max_recoveries
        self.recoveries = 0  # engine-total recovery passes
        self.clock = clock
        self.results: Dict[str, RequestResult] = {}
        self._sampling = self.temperature > 0
        self._key = jax.random.PRNGKey(seed)
        self._slots: List[Optional[_Slot]] = [None] * max_slots
        # request popped from the queue but not yet slotted — requeued
        # at the head if the admission prefill faults
        self._admitting: Optional[Request] = None
        # half-close flag (graceful drain): admission stops, in-flight
        # slots run to completion, queued requests stay intact for the
        # caller to hand elsewhere (the router's scale-down/swap path)
        self._draining = False
        # hardware-efficiency observability (doc/observability.md
        # "Hardware efficiency"): the analytic cost model prices each
        # dispatched program, the efficiency meter turns drained-block
        # wall time into live edl_mfu{phase}/edl_bw_util_ratio{phase}
        # gauges, and the memory ledger holds this engine's long-lived
        # HBM (params / kv / slot_state) under an owner key released
        # automatically when the engine is garbage-collected.
        self._ledger = memledger.default_ledger()
        self._ledger_owner = f"engine-{id(self)}"
        pbytes = memledger.tree_nbytes(params)
        self._cost = _cm.CostModel(
            cfg, peak=_cm.detect_peak(),
            param_bytes_total=pbytes or None,
            kv_bytes_per_el=_cm.kv_quant_bytes_per_el(self.kv_quant),
            kv_block_size=(
                self.block_size if self.kv_quant != "off" else 0
            ),
        )
        self._eff = _cm.EfficiencyMeter(
            self._cost.peak, registry=self.metrics.registry
        )
        # every block runs max_slots rows for `horizon` steps. The paged
        # programs and the dense contiguous one read the full padded
        # cache: a constant cost. The contiguous `use_flash` program
        # (`edl_decode_attn`) reads each slot's live S-blocks, and a
        # recurrent model the live slots' states, so those blocks are
        # priced one by one at dispatch (`cfg.serve_cache_read`)
        self._block_cost = self._cost.decode_block(
            max_slots, horizon, max_len
        )
        self._attn_block = (
            max_len if self._paged
            else cfg.serve_attn_block(max_len)
        )
        # speculative draft–verify (spec_k > 0): each verify dispatch
        # scores spec_k host-drafted tokens + the pending token in one
        # weight pass. Drafting is on-host n-gram prompt lookup over
        # prompt + generated; the policy disables drafting per request
        # when measured acceptance can't beat plain horizon decode.
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.spec_min_accept = float(spec_min_accept)
        self._spec_policy = (
            _spec.SpecPolicy(min_accept=self.spec_min_accept)
            if self.spec_k > 0 else None
        )
        # the quantized path's live quality gate: only meaningful when
        # speculation provides the acceptance probe
        self._kvq_guard = (
            SpecAcceptGuard(self.metrics.registry)
            if self.kv_quant != "off" and self.spec_k > 0 else None
        )
        self._verify_cost = (
            self._cost.verify_block(max_slots, self.spec_k + 1, max_len)
            if self.spec_k > 0 else None
        )
        self._ledger.register(self._ledger_owner, "params", pbytes, "params")
        weakref.finalize(self, self._ledger.release_owner, self._ledger_owner)
        self._alloc_device_state()
        # the layout's programs, chosen here and nowhere else: the two
        # every engine runs, and the per-bucket / per-chunk / per-draft
        # factories bound to the layout's arguments (built on first
        # use, through the memo)
        if self._paged:
            geom = (max_slots, self.pool_blocks, self._m, self.block_size)
            self._decode = _block_program_paged(
                cfg, *geom, horizon, self._sampling, self.kv_quant)
            self._copyblk = _copy_block_program(
                cfg, self.pool_blocks, self.block_size, self.kv_quant)
            self._prefill_for = partial(
                _prefill_paged_program, cfg, bs=self.block_size,
                sampling=self._sampling, kv_quant=self.kv_quant)
            self._chunk_for = partial(
                _prefill_chunk_program, cfg, bs=self.block_size,
                kv_quant=self.kv_quant)
            self._verify_for = partial(
                _verify_program_paged, cfg, *geom, kv_quant=self.kv_quant)
        else:
            self._decode = _block_program(
                cfg, max_slots, max_len, horizon, self._sampling
            )
            self._prefill_for = partial(
                _prefill_program, cfg, sampling=self._sampling)
            self._verify_for = partial(
                _verify_program, cfg, max_slots, max_len)
        log.info(
            "engine ready",
            slots=max_slots,
            max_len=max_len,
            horizon=horizon,
            cache_mb=round(self._cache_nbytes() / 2**20, 1),
            paged=self._paged,
            block_size=self.block_size,
            pool_blocks=self.pool_blocks,
            kv_quant=self.kv_quant,
            sampling=self._sampling,
        )

    # the cache's arrays (the pools' scale planes among them), donated
    # and rebound together: `_cache_nbytes` is the one byte count
    def _cache_nbytes(self) -> int:
        return sum(c.nbytes for c in self._cache)

    def _alloc_device_state(self) -> None:
        """(Re)allocate the device-side slot decode state — the block
        program's carry — plus the KV cache and the in-flight queue.
        Called at construction AND by :meth:`_recover`, which rebuilds
        the device world from the host's bookkeeping truth. The host
        NEVER syncs these on the hot path — it feeds the returned
        device arrays straight into the next dispatch and reconstructs
        its bookkeeping view from drained token matrices instead."""
        max_slots = self.max_slots
        self._dtok = jnp.zeros(max_slots, jnp.int32)
        self._dpos = jnp.zeros(max_slots, jnp.int32)
        self._dact = jnp.zeros(max_slots, bool)
        self._drem = jnp.zeros(max_slots, jnp.int32)
        self._deos = jnp.full((max_slots,), -1, jnp.int32)
        self._cache = tuple(
            jnp.zeros(shape, dtype) for shape, dtype in self._cache_spec
        )
        if self._paged:
            # block 0 of the pool is SCRATCH (pads and frozen/inactive
            # lanes write there, nothing reads it). The allocator,
            # tables, and prefix cache are HOST truth rebuilt here from
            # nothing: after a recovery the pool is zeros, so every
            # prior block (including cached prefixes) is invalid and
            # the re-prefill repopulates what it needs.
            self._balloc = _paged.BlockAllocator(
                self.pool_blocks, self.block_size
            )
            self._prefix = (
                _paged.PrefixCache(self._balloc) if self._use_prefix
                else None
            )
            self._tables: List[List[int]] = [
                [_paged.SCRATCH] * self._m for _ in range(max_slots)
            ]
            # scrapeable shrink: pool bytes (values + scales) over the
            # pool's token capacity — 4.12 B/tok bf16 vs 2.12 int8 on
            # the flagship shape (scales add ~1/(2·bs) back)
            self._ledger.set_kv_bytes_per_token(
                self._ledger_owner, self._cache_nbytes(),
                self.pool_blocks * self.block_size,
            )
        # lanes whose slot was evicted while the DEVICE row was still
        # active (deadline evictions are host-bookkeeping only): blocks
        # dispatched before the eviction still carry the old request's
        # real tokens in that lane, so the lane must not be reused
        # until every such block has drained (see _admit). A fresh
        # device state has no active rows — always starts empty.
        self._stale: set = set()
        # dispatched-but-undrained blocks as (token matrix, dispatch
        # stamp) pairs — the stamp feeds the block-latency histogram
        # at drain; depth <= 2 transiently inside step(), <= 1 between
        # steps — the double buffer
        self._inflight: Deque[tuple] = deque()
        # None until the first dispatch reveals whether this backend
        # honors donation (CPU/TPU do; a backend that copies instead
        # just loses the in-place win, not correctness)
        self._donates: Optional[bool] = None
        # ledger re-registration under the SAME keys: a recovery's
        # realloc REPLACES the entries (donation-/recovery-aware — the
        # gauge cannot drift across crash/recover cycles; exp_chaos
        # pins the exact figure), and the efficiency busy-clock resets
        # so discarded in-flight time is not charged
        # each array under its own kind: a model with a per-slot state
        # beside a positional cache sets both gauges
        kinds = (("kv",) * len(self._cache) if self._paged
                 else self.cfg.serve_cache_kinds)
        for kind in sorted(set(kinds)):
            self._ledger.register(
                self._ledger_owner, kind,
                sum(c.nbytes for c, k in zip(self._cache, kinds)
                    if k == kind), kind)
        self._ledger.register(
            self._ledger_owner, "slot_state",
            self._dtok.nbytes + self._dpos.nbytes + self._dact.nbytes
            + self._drem.nbytes + self._deos.nbytes,
            "slot_state",
        )
        self._t_eff_last = self.clock()

    # -- request intake -----------------------------------------------------

    def submit(
        self,
        rid: str,
        prompt: List[int],
        max_new: int,
        eos_id: Optional[int] = None,
        deadline_s: Optional[float] = None,
        *,
        tenant: Optional[str] = None,
        slo_class: Optional[str] = None,
    ) -> None:
        """Queue a request; raises :class:`AdmissionError` (and counts
        the rejection) when admission control refuses it. ``deadline_s``
        is a relative latency budget from now: past it the request is
        shed from the queue or its slot evicted (outcome "timeout").
        ``tenant``/``slo_class`` are attribution labels carried through
        the outcome counters and flight-recorder events."""
        self.metrics.on_submit(rid, tenant=tenant, slo_class=slo_class)
        labels = {}
        if tenant is not None:
            labels["tenant"] = tenant
        if slo_class is not None:
            labels["slo_class"] = slo_class
        flight.emit("serve.submit", rid=rid, prompt_len=len(prompt),
                    max_new=int(max_new), **labels)
        if rid in self.results or any(
            s is not None and s.rid == rid for s in self._slots
        ):
            self._reject(rid, "bad_request", f"duplicate request id {rid!r}")
        bad = [t for t in prompt if not 0 <= int(t) < self.cfg.vocab]
        if bad:
            self._reject(
                rid, "bad_request",
                f"{rid}: prompt tokens {bad[:4]} outside [0, {self.cfg.vocab})",
            )
        if deadline_s is not None and deadline_s <= 0:
            self._reject(
                rid, "bad_request",
                f"{rid}: deadline_s must be > 0, got {deadline_s}",
            )
        try:
            self.queue.submit(
                Request(rid=rid, prompt=list(map(int, prompt)),
                        max_new=int(max_new), eos_id=eos_id,
                        deadline_s=deadline_s, tenant=tenant,
                        slo_class=slo_class)
            )
        except AdmissionError as e:
            self.metrics.on_reject(rid, e.reason)
            flight.emit("serve.reject", severity="warn", rid=rid,
                        reason=e.reason)
            raise

    def _reject(self, rid: str, reason: str, msg: str) -> None:
        """Typed admission rejection: counted once, on the timeline
        once, then raised."""
        self.metrics.on_reject(rid, reason)
        flight.emit("serve.reject", severity="warn", rid=rid, reason=reason)
        raise AdmissionError(reason, msg)

    # -- the engine loop ----------------------------------------------------

    @property
    def active_slots(self) -> int:
        """Occupied slots in the HOST view (drained bookkeeping; an
        in-flight block may already have finished some on device)."""
        return sum(1 for s in self._slots if s is not None)

    @property
    def has_work(self) -> bool:
        return (
            self.active_slots > 0
            or (self.queue.depth > 0 and not self._draining)
            or bool(self._inflight)
        )

    @property
    def draining(self) -> bool:
        """True after :meth:`half_close`: admission is closed, queued
        requests are residuals awaiting :meth:`take_residual`."""
        return self._draining

    def step(self) -> int:
        """One engine iteration: admit up to the block budget of queued
        requests into free slots (prefill-insert), dispatch ONE fused
        horizon block over every active slot, then drain the PREVIOUS
        block's token matrix while the new one runs on device. Returns
        tokens observed this iteration (prefill first-tokens included;
        decode tokens surface at the drain of their block).

        Any exception escaping the iteration (a device failure, an
        injected fault) triggers :meth:`_recover` instead of
        propagating: in-flight work is discarded, device state rebuilt,
        and live requests replayed — the engine object stays usable and
        no accepted request is silently lost."""
        try:
            # parent of admit / account / dispatch / drain / replay: its
            # self time is the eviction pass, what follows a dispatch's
            # program call (_block_dispatched) and its children's own
            # opening and closing
            with tracing.span("serving.step"):
                return self._step_inner()
        except Exception as e:
            self._recover(e)
            return 0

    def _step_inner(self) -> int:
        emitted = 0
        self._evict_overdue()
        if self.queue.depth > 0 and not self._draining:
            if self._inflight and not any(s is None for s in self._slots):
                # drain-to-admit: no slot is known-free, but an
                # in-flight block may have finished one — sync now so
                # the freed slot admits this boundary, not next
                emitted += self._drain_all()
            with tracing.span(
                "serving.admit", queue_depth=self.queue.depth
            ) as admit:
                was = self._admit_seq
                emitted += self._admit()
                admit["admitted"] = self._admit_seq - was
        if self.prefill_chunk:
            # one bounded prefill chunk per prefilling slot per step,
            # interleaved with the decode block below — a long prompt
            # no longer starves running slots behind one monolithic
            # prefill dispatch
            emitted += self._advance_prefills()
        # the host work between an admission's first-token sync and the
        # next block: this stretch, and under the same name what a
        # dispatch does before its program call
        with tracing.span("serving.account"):
            decoding = self._account_step()
        if decoding:
            if self.spec_k > 0:
                emitted += self._step_spec()
            else:
                self._dispatch_block()
                # double buffer: block k+1 is now on device; drain
                # block k (bookkeeping overlaps the device work, no
                # idle bubble)
                while len(self._inflight) > 1:
                    emitted += self._drain_one()
        else:
            emitted += self._drain_all()
        return emitted

    def _account_step(self) -> int:
        """The step's gauges (live slots, queue depth, cache occupancy);
        returns how many slots are decoding."""
        active_n = self.active_slots
        self.metrics.on_step(active_n, self.max_slots, self.queue.depth)
        if self._paged:
            # block-aware occupancy: allocated blocks over the usable
            # pool (scratch excluded) — the effective-concurrency-at-
            # fixed-HBM figure ROADMAP item 1 wanted, plus the free-
            # block headroom admission gates on
            self._ledger.set_kv_usage(
                self._ledger_owner, self._balloc.allocated_blocks,
                self.pool_blocks - 1,
            )
            self._ledger.set_kv_blocks_free(
                self._ledger_owner, self._balloc.free_blocks
            )
        else:
            # live KV occupancy: tokens actually resident (prompt +
            # committed generation, capped at the slot length) over the
            # allocated capacity
            used = sum(
                min(len(s.prompt) + len(s.generated), self.max_len)
                for s in self._slots
                if s is not None
            )
            self._ledger.set_kv_usage(
                self._ledger_owner, used, self.max_slots * self.max_len
            )
        # slots still mid-chunked-prefill have no decode state yet —
        # the block dispatch runs only when someone is actually decoding
        return sum(
            1 for s in self._slots if s is not None and s.pf_next is None
        )

    def _step_spec(self) -> int:
        """One speculative iteration: draft per decoding slot from its
        committed ``prompt + generated`` history, dispatch ONE verify
        step over every slot (slots with no usable draft ride along as
        -1 sentinels = one plain decode step), and drain synchronously.

        Spec mode trades the double buffer for drafting freshness: the
        drafter needs block k's committed tokens to propose block
        k+1's continuation, so each dispatch syncs before the next —
        the dispatch amortization now comes from accepted tokens per
        verify, not from pipelining. When NO slot drafts (nothing
        repeats yet, or the policy disabled everyone) the step falls
        back to a plain horizon block, so a non-repetitive stream pays
        the horizon path's cost, one sync earlier."""
        emitted = self._drain_all()
        drafts: Dict[int, List[int]] = {}
        for i, sl in enumerate(self._slots):
            if sl is None or sl.pf_next is not None:
                continue
            if not self._spec_policy.should_draft(sl.rid):
                continue
            row = _spec.draft_ngram(
                sl.prompt + sl.generated, self.spec_ngram, self.spec_k
            )
            if row:
                drafts[i] = row
        if drafts:
            self._dispatch_verify(drafts)
        else:
            self._dispatch_block()
        emitted += self._drain_all()
        return emitted

    def run(self, max_steps: Optional[int] = None) -> Dict[str, RequestResult]:
        """Drain queue + slots (or stop after ``max_steps``)."""
        steps = 0
        while self.has_work and (max_steps is None or steps < max_steps):
            self.step()
            steps += 1
        if self._inflight:
            # a max_steps stop can land with blocks dispatched but
            # undrained — tokens the device already produced would be
            # missing from ``results``; sync them before returning
            try:
                self._drain_all()
            except Exception as e:
                self._recover(e)
        return dict(self.results)

    # -- graceful drain (half-close) ----------------------------------------

    def half_close(self) -> None:
        """Stop admitting queued requests. In-flight slots keep decoding
        to their natural finish; queued requests are untouched and stay
        admission-validated for whoever picks them up (the fleet router
        requeues them onto another replica on scale-down/weight swap).
        Idempotent."""
        if self._draining:
            return
        self._draining = True
        flight.emit(
            "serve.halfclose",
            queued=self.queue.depth, active=self.active_slots,
        )

    def reopen(self) -> None:
        """Undo :meth:`half_close` (a cancelled drain resumes admission)."""
        self._draining = False

    def take_residual(self) -> List[Request]:
        """Pop every still-queued request, in FIFO order. Only
        meaningful after :meth:`half_close`; the caller owns the
        returned requests (requeue them elsewhere or fail them) — the
        engine forgets them."""
        residual: List[Request] = []
        while True:
            req = self.queue.pop()
            if req is None:
                break
            residual.append(req)
        flight.emit(
            "serve.drained",
            residual=len(residual), served=len(self.results),
        )
        return residual

    def drain(self, max_steps: Optional[int] = None) -> List[Request]:
        """Graceful half-close drain: stop admission, run in-flight
        slots to completion (every accepted request reaches a terminal
        outcome in ``results``), then return the residual queued
        requests intact. After this returns no further token can be
        emitted — there is no active slot and no in-flight block left.
        ``max_steps`` bounds the finish loop (None = run to quiescence;
        a bounded drain may return with slots still live)."""
        self.half_close()
        steps = 0
        while (self.active_slots > 0 or self._inflight) and (
            max_steps is None or steps < max_steps
        ):
            self.step()
            steps += 1
        if self._inflight:
            try:
                self._drain_all()
            except Exception as e:
                self._recover(e)
        return self.take_residual()

    # -- internals ----------------------------------------------------------

    def _next_key(self):
        if not self._sampling:
            return self._key  # untraced constant path, never consumed
        self._key, sub = jax.random.split(self._key)
        return sub

    def _temp(self):
        return jnp.float32(self.temperature if self._sampling else 1.0)

    def _assert_donated(self, *old) -> None:
        """The stale-buffer invariant behind ``donate_argnums``: after
        a dispatch, every donated input reference must be DEAD — the
        engine holds only the returned arrays. A live old buffer means
        XLA fell back to copying (the per-step cache copy this engine
        exists to eliminate), except on backends that never donate,
        detected once and logged rather than failed."""
        if self._donates is None:
            self._donates = old[-1].is_deleted()
            if not self._donates:
                log.warn(
                    "buffer donation inactive on this backend; "
                    "the KV cache copies once per dispatch"
                )
        if not self._donates:
            return
        for a in old:
            if not a.is_deleted():
                raise AssertionError(
                    "donated buffer still live after dispatch — the "
                    "in-place cache update regressed to a copy "
                    f"(shape {a.shape}, dtype {a.dtype})"
                )

    def _block_table(self) -> tuple:
        """The layout's operand in front of the cache in a decode or
        verify dispatch: the [B, M] table snapshot of the slots that
        are decoding — none, ``()``, for the contiguous layout, which
        has no table. Grows coverage BEFORE building the snapshot: the
        dispatch may advance each decoding slot past a block boundary
        (``_ensure_cover`` sizes the window to max(horizon, K), so
        every position an accepted run can commit is mapped), and
        coverage may preempt other slots under pool pressure —
        preempted rows then fall through to the all-scratch default."""
        if not self._paged:
            return ()
        for i, sl in enumerate(self._slots):
            if sl is not None and sl.pf_next is None:
                self._ensure_cover(i)
        tbl = np.zeros((self.max_slots, self._m), np.int32)
        for i, sl in enumerate(self._slots):
            if sl is not None and sl.pf_next is None:
                tbl[i] = self._tables[i]
        # the table is a TRACED operand snapshot: alloc/share/free
        # between dispatches are host bookkeeping, never a retrace
        return (jnp.asarray(tbl),)

    def _dispatch_block(self) -> None:
        with tracing.span("serving.account"):
            where = self._block_table()
            old = (self._dtok, self._dpos, self._dact,
                   self._drem) + self._cache
            # one list a block: the drain of this block reads it again
            rids = [s.rid for s in self._slots if s is not None]
            attrs = {"horizon": self.horizon, "rids": rids}
            cost = self._block_cost
            if not self._paged:
                # what of each kind of cache this block reads, under the
                # model's own names for them
                shares = self._cache_read()
                attrs.update(shares)
                cost = self._cost.decode_block(
                    self.max_slots, self.horizon, self.max_len, shares
                )
        # span measures the ENQUEUE cost only (the dispatch is async);
        # the device-side block time shows up as serving.drain on the
        # block that finally syncs it — together they are the
        # dispatch/block breakdown the obs bridge exposes. ``rids``
        # lists the slots riding this block, so /trace filters on the
        # same correlation key as /events?rid= (block spans are shared
        # across requests; per-request identity is the attr, not the
        # span).
        with tracing.span("serving.dispatch", **attrs) as attrs:
            (toks, self._dtok, self._dpos, self._dact, self._drem,
             *cache, counters) = self._decode(
                self.params, *old[:4], self._deos, *where, *old[4:],
                self._next_key(), self._temp(),
            )
            self._cache = tuple(cache)
        # what the model counted on the device during the block comes
        # back with its tokens, onto this dispatch's span
        # edl: no-lint[donation-safety] the tail probes these dead refs
        self._block_dispatched(old, "decode", toks, cost, rids, counted=(
            (counters, attrs) if counters else None))

    def _block_dispatched(self, old: tuple, kind: str, toks, cost, rids,
                          drafted=None, counted=None, **event) -> None:
        """What follows the program call of a decode or verify
        dispatch, in this order: count it, probe the donation, put it
        on the timeline, offer the chaos site, queue its token matrix
        for the drain."""
        self.metrics.on_dispatch(kind)
        # deliberate read of the donated refs: is_deleted() PROBES that
        # donation actually happened (the runtime half of this invariant)
        self._assert_donated(*old)
        flight.emit("serve.block", active=self.active_slots,
                    horizon=self.horizon, **event)
        # chaos site: a crash HERE is the worst case — the donated
        # inputs are dead, the carries are rebound, and the block's
        # token matrix (under speculation: the accepted tokens) is
        # about to be lost
        faults.fault_point("serve.dispatch")
        # per-block lane membership: lane i's tokens belong to slot i's
        # occupant AT DISPATCH — a lane mid-chunked-prefill (or later
        # re-occupied) must not have this block's tokens replayed into
        # it at drain (the device lane still carries a previous
        # request's decode state until the final prefill piece resets
        # it)
        members = {
            i: s.rid for i, s in enumerate(self._slots)
            if s is not None and s.pf_next is None
        }
        self._inflight.append(
            (toks, self.clock(), members, cost, drafted, rids, counted)
        )

    def _cache_read(self):
        """{name: share} of the contiguous cache that the block about
        to be dispatched reads, as the model's config reckons it from
        the host's slot table (the tokens each slot holds, None for an
        idle one): ``kv_read_share`` of a positional cache
        (``llama.positional_read_share``), ``state_live_share`` of a
        per-slot state, both of a model that holds both."""
        return self.cfg.serve_cache_read([
            None if s is None else
            min(len(s.prompt) + len(s.generated), self.max_len)
            for s in self._slots
        ], self.max_len, self._attn_block)

    def _kv_read_share(self) -> float:
        """The share alone (the benchmark's tests ask it by this name)."""
        return self._cache_read()["kv_read_share"]

    def _dispatch_verify(self, drafts: Dict[int, List[int]]) -> None:
        """One speculative verify dispatch: assemble the [B, D] draft
        matrix (-1 sentinel lanes for undrafted/absent slots — a
        sentinel row is exactly one plain decode step, so membership
        and per-slot disable never change the program) and run the
        verify program over every slot. Same dispatch discipline as
        ``_dispatch_block``, through the same ``_block_dispatched``:
        donated carries, ``_assert_donated`` probe, ``serve.dispatch``
        chaos site — a crash here recovers
        identically (``generated`` holds only drained tokens, so the
        replay's committed truth is complete mid-speculation)."""
        d = self.spec_k
        with tracing.span("serving.account"):
            dm = np.full((self.max_slots, d), -1, np.int32)
            drafted: Dict[int, int] = {}
            for i, row in drafts.items():
                row = row[:d]
                dm[i, :len(row)] = row
                drafted[i] = len(row)
            where = self._block_table()
            old = (self._dtok, self._dpos, self._dact,
                   self._drem) + self._cache
            rids = [s.rid for s in self._slots if s is not None]
        with tracing.span("serving.dispatch", horizon=self.horizon,
                          rids=rids, spec_k=d):
            (toks, self._dtok, self._dpos, self._dact, self._drem,
             *cache) = self._verify_for(d)(
                self.params, old[0], jnp.asarray(dm), *old[1:4],
                self._deos, *where, *old[4:],
            )
            self._cache = tuple(cache)
        # edl: no-lint[donation-safety] the tail probes these dead refs
        self._block_dispatched(old, "verify", toks, self._verify_cost, rids,
                               drafted=drafted, spec_k=d)

    def _drain_one(self) -> int:
        """Sync the OLDEST in-flight block's [B, H] token matrix and
        replay it into the host bookkeeping: append per-slot tokens,
        stamp per-block metrics, finish EOS/budget rows. Frozen lanes
        read -1 and terminate the row's replay — the device freezes a
        row at exactly the step the host would finish it, so the two
        views never disagree."""
        # rids: the list the block's dispatch built (the requests that
        # ride the block being synced)
        with tracing.span("serving.drain", rids=self._inflight[0][5]):
            blk, t_dispatch, members, cost, drafted, rids, counted = (
                self._inflight.popleft()
            )
            # chaos site: the popped block is lost on a crash here —
            # its tokens exist only on device, recovery must regenerate
            faults.fault_point("serve.drain")
            out = np.asarray(blk)
            if counted is not None:
                # the block's program has ended: these are ready too
                counters, dispatch_attrs = counted
                dispatch_attrs.update(
                    (k, float(v)) for k, v in counters.items())
        # the block is on the host: from here the device has nothing of
        # this block to wait for, and the replay below is host work
        with tracing.span("serving.replay", rids=rids) as replay:
            # dispatch -> drained wall time: the decode-phase granule of
            # the latency decomposition (end-to-end as the host saw it)
            now = self.clock()
            self.metrics.on_block(now - t_dispatch)
            # roofline accounting: the block's analytic cost (horizon or
            # verify, stamped at dispatch) over its busy window, clipped
            # against the previous drain so the double buffer cannot
            # charge overlapped device time twice
            self._eff.observe(
                "decode", cost, now - max(self._t_eff_last, t_dispatch)
            )
            self._t_eff_last = now
            emitted = 0
            spec_drafted = spec_accepted = 0
            for i in range(self.max_slots):
                sl = self._slots[i]
                if sl is None:
                    continue  # freed by an earlier drain; lanes are -1
                if members.get(i) != sl.rid:
                    # lane belonged to a different occupant (or none) when
                    # this block dispatched — its tokens are not this
                    # request's
                    continue
                n = 0
                outcome = None
                for t in out[i]:
                    t = int(t)
                    if t < 0:
                        break
                    sl.generated.append(t)
                    n += 1
                    if sl.eos_id is not None and t == sl.eos_id:
                        outcome = "eos"
                        break
                    if len(sl.generated) >= sl.max_new:
                        outcome = "done"
                        break
                if n:
                    self.metrics.on_tokens(sl.rid, n)
                    emitted += n
                if drafted is not None and drafted.get(i, 0) > 0:
                    # verify-block bookkeeping: of this row's emitted run,
                    # everything but the bonus token was an accepted draft
                    # (EOS/budget truncation included — the device emit
                    # mask and this host replay agree lane for lane)
                    nd = drafted[i]
                    acc = max(0, n - 1)
                    spec_drafted += nd
                    spec_accepted += acc
                    self._spec_policy.observe(sl.rid, nd, acc)
                    flight.emit("serve.verify", rid=sl.rid, drafted=nd,
                                accepted=acc, emitted=n)
                if outcome:
                    self._finish(i, outcome)
            if drafted is not None:
                self.metrics.on_spec(spec_drafted, spec_accepted)
                if self._kvq_guard is not None:
                    self._kvq_guard.observe(spec_drafted, spec_accepted)
            replay["tokens"] = emitted
        return emitted

    def _drain_all(self) -> int:
        emitted = 0
        while self._inflight:
            emitted += self._drain_one()
        return emitted

    def _bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _evict_overdue(self) -> None:
        """Deadline enforcement between blocks: a live slot past its
        absolute deadline finishes NOW with what it has (outcome
        "timeout"). Bookkeeping-only like every eviction — the device
        row keeps decoding until the slot is reused, drains skip it.
        Counted exactly ONCE, as completed{outcome=timeout} via
        ``_finish`` — never also as a rejection. The lane is marked
        STALE: unlike an EOS/budget finish, the device never froze
        this row, so in-flight blocks still carry the old request's
        real tokens in it and admission must drain them before reuse
        (tests/test_serving.py pins the no-leak contract)."""
        now = self.clock()
        for i, sl in enumerate(self._slots):
            if sl is not None and sl.deadline is not None and now > sl.deadline:
                self._finish(i, "timeout")
                self._stale.add(i)

    def _shed_expired(self, req: Request) -> bool:
        """Queue-side load shedding: a popped request whose deadline
        passed while it waited is finished as ``rejected:timeout``
        without ever touching the device — an overloaded engine drops
        the stalest work instead of prefilling tokens nobody will
        consume. Counted exactly ONCE, as a rejection — deliberately
        NOT through ``_finish``/``on_finish``: a shed request was
        never admitted, so it must not inflate ``completed`` (the
        double-count audit tests/test_serving.py pins)."""
        dl = req.deadline_at()
        if dl is None or self.clock() <= dl:
            return False
        self.metrics.on_reject(req.rid, "timeout")
        flight.emit("serve.reject", severity="warn", rid=req.rid,
                    reason="timeout", shed=True,
                    queued_s=round(self.clock() - req.submit_s, 6))
        self.results[req.rid] = RequestResult(
            rid=req.rid, tokens=[], outcome="timeout"
        )
        return True

    def _admit(self) -> int:
        free = [i for i, s in enumerate(self._slots) if s is None]
        budget = self.policy.block_budget(
            len(free), self.queue.depth, self.horizon
        )
        emitted = 0
        for _ in range(budget):
            req = self.queue.pop()
            if req is None:
                break
            if self._shed_expired(req):
                continue
            if self._paged and not self._pg_admittable(req):
                # admission gates on BLOCKS, not slots: the prompt's
                # non-hit blocks must fit in free + cache-evictable
                # pool right now. Head-of-line keeps its FIFO position
                # and retries next boundary (drains free blocks).
                self.queue.requeue_front(req)
                break
            # queue wait ends at the pop — from here the clock charges
            # the prefill phase (the decomposition's first boundary)
            self.metrics.on_pop(req.rid)
            # the same boundary as a span: the scheduler's own wait,
            # submit to pop. The engine's clock is not the tracer's, so
            # the span is placed by its length, ending now
            wait = self.clock() - req.submit_s
            tracing.tracer().record(
                "serving.queue", time.perf_counter() - wait, wait,
                {"rid": req.rid},
            )
            slot = free.pop(0)
            # from here to the bookkeeping commit the request exists
            # only in this local — publish it so a prefill crash
            # requeues it at the head instead of losing it
            self._admitting = req
            if slot in self._stale and self._inflight:
                # the lane was deadline-evicted while its device row
                # was still decoding: blocks dispatched before the
                # eviction carry the OLD request's tokens in this lane,
                # and replaying them into the new occupant would leak
                # tokens across requests — sync them out first
                emitted += self._drain_all()
            self._stale.discard(slot)
            if self._paged:
                start = self._pg_setup_table(slot, req.prompt,
                                             rid=req.rid)
                if self.prefill_chunk and (
                    len(req.prompt) - start > self.prefill_chunk
                ):
                    # long prompt: admit now with its blocks reserved,
                    # prefill in bounded chunks interleaved with decode
                    # blocks (_advance_prefills) instead of one
                    # monolithic dispatch that starves running slots
                    sl = _Slot(
                        rid=req.rid, prompt=list(req.prompt),
                        max_new=req.max_new, eos_id=req.eos_id,
                        generated=[], deadline=req.deadline_at(),
                        tenant=req.tenant, slo_class=req.slo_class,
                        pf_next=start, born=self._admit_seq,
                    )
                    self._admit_seq += 1
                    self._slots[slot] = sl
                    self._admitting = None
                    self.metrics.on_admit(req.rid, len(req.prompt))
                    flight.emit("serve.admit", rid=req.rid, slot=slot,
                                prompt_len=len(req.prompt), chunked=True)
                    continue
                tok0 = self._pg_prefill(
                    slot, req.prompt, start, req.max_new, req.eos_id,
                    site="serve.prefill", rid=req.rid,
                )
                self._pg_cache_insert(slot, req.prompt)
            else:
                tok0 = self._prefill_into(
                    slot, req.prompt, req.max_new, req.eos_id,
                    site="serve.prefill", rid=req.rid,
                )
            self.metrics.on_admit(req.rid, len(req.prompt))
            flight.emit("serve.admit", rid=req.rid, slot=slot,
                        prompt_len=len(req.prompt))
            sl = _Slot(
                rid=req.rid, prompt=list(req.prompt), max_new=req.max_new,
                eos_id=req.eos_id, generated=[tok0],
                deadline=req.deadline_at(),
                tenant=req.tenant, slo_class=req.slo_class,
                born=self._admit_seq,
            )
            self._admit_seq += 1
            self._slots[slot] = sl
            self._admitting = None
            self.metrics.on_token(req.rid)
            emitted += 1
            if sl.eos_id is not None and tok0 == sl.eos_id:
                self._finish(slot, "eos")
            elif sl.max_new <= 1:
                self._finish(slot, "done")
        return emitted

    def _prefill_into(
        self,
        slot: int,
        seq: List[int],
        max_new: int,
        eos_id: Optional[int],
        site: Optional[str] = None,
        rid: Optional[str] = None,
        replay: bool = False,
    ) -> int:
        """Prefill-insert ``seq`` into ``slot``: run it through the
        layout's prefill (one dispatch from position 0 for the
        contiguous cache; for the paged one the slot's table is set up
        first, prefix hits are skipped and leading chunks run inline),
        reset the row's device decode state to a ``max_new``-token
        budget, and return the first sampled token. Shared by admission
        (``seq`` = the prompt) and crash recovery (``seq`` = prompt +
        generated — greedy argmax over the full context emits exactly
        the token the lost decode step would have)."""
        if self._paged:
            start = self._pg_setup_table(slot, seq, rid=rid)
            tok0 = self._pg_prefill(slot, seq, start, max_new, eos_id,
                                    site=site, rid=rid, replay=replay)
            if not replay:
                self._pg_cache_insert(slot, seq)
            return tok0
        return self._dispatch_prefill_final(
            slot, seq, 0, max_new, eos_id, site=site, rid=rid, replay=replay,
        )

    # -- paged KV management ------------------------------------------------
    #
    # Everything below is HOST bookkeeping over edl_tpu/serving/paged.py
    # — allocation, prefix sharing, copy-on-write, preemption, frees.
    # The device only ever sees a snapshot block table per dispatch.
    #
    # Eviction/reuse safety rides on device program ordering: an
    # in-flight block dispatched with the OLD table executes before any
    # later-dispatched prefill that reuses a freed block (single-stream
    # execution), and the new owner rewrites every position it will
    # read before reading it — so a stale lane's writes into a
    # reclaimed block are always overwritten before they are observed.

    def _pg_admittable(self, req: Request) -> bool:
        """Paged admission gate: the prompt's non-hit blocks must fit
        in the pool right now (free + cache-evictable). Decode-time
        growth is NOT reserved — it comes from later frees or from
        preempting the youngest slot (``pool_blocks >= m + 1`` makes a
        lone request always able to finish)."""
        hits = 0
        if self._prefix is not None:
            hits = len(self._prefix.match(req.prompt))
        needed = max(
            _paged.blocks_for(len(req.prompt), self.block_size) - hits, 1
        )
        avail = self._balloc.free_blocks
        if self._prefix is not None:
            avail += self._prefix.evictable()
        return avail >= needed

    def _pg_setup_table(self, slot: int, seq: List[int],
                        rid: Optional[str] = None) -> int:
        """Build slot ``slot``'s block table for ``seq``: map prefix-
        cache hits as SHARED entries (one ref each), allocate private
        blocks for the rest, and return the position prefill starts at
        (hit positions are already resident — their prefill is
        skipped). A FULL hit still re-prefills the last prompt token
        (the logits source for the first generated token), so the final
        shared block is copy-on-written first."""
        tbl = self._tables[slot]
        assert all(b == _paged.SCRATCH for b in tbl), (
            f"slot {slot} table not clean at setup: {tbl}"
        )
        bs = self.block_size
        hits: List[int] = []
        if self._prefix is not None:
            hits = self._prefix.match(seq)
            self._prefix.hits += len(hits)
            if not hits:
                self._prefix.misses += 1
        nb = _paged.blocks_for(len(seq), bs)
        full = nb > 0 and len(hits) == nb  # only when len(seq) % bs == 0
        start = len(seq) - 1 if full else len(hits) * bs
        for j, bid in enumerate(hits):
            self._balloc.incref(bid)
            tbl[j] = bid
        for j in range(len(hits), nb):
            tbl[j] = self._pg_alloc_or_preempt(slot)
        if full:
            self._pg_make_writable(slot, nb - 1)
        if hits:
            self._ledger.count_prefix_hits(len(hits))
            flight.emit("serve.prefix_hit", rid=rid,
                        blocks=len(hits), full=full)
        return start

    def _pg_prefill(self, slot: int, seq: List[int], start: int,
                    max_new: int, eos_id: Optional[int],
                    site: Optional[str] = None, rid: Optional[str] = None,
                    replay: bool = False) -> int:
        """Prefill positions ``start..len(seq)-1`` into the slot's
        mapped blocks and return the first generated token. With
        ``prefill_chunk`` set the leading pieces run as bounded chunk
        dispatches INLINE here (admission defers long prompts to
        ``_advance_prefills`` instead — this inline loop serves replay,
        where interleaving has no one to yield to)."""
        chunk = self.prefill_chunk
        if chunk:
            while len(seq) - start > chunk:
                self._dispatch_prefill_chunk(slot, seq, start,
                                             rid=rid, site=site)
                start += chunk
        return self._dispatch_prefill_final(
            slot, seq, start, max_new, eos_id,
            site=site, rid=rid, replay=replay,
        )

    def _slot_table(self, slot: int, start: int) -> tuple:
        """The layout's operands in front of the cache in a prefill
        dispatch: where the piece starts and the slot's table row —
        none, ``()``, for the contiguous layout (a whole prompt from
        position 0 into row ``slot``)."""
        if not self._paged:
            return ()
        return (jnp.int32(start),
                jnp.asarray(np.asarray(self._tables[slot], np.int32)))

    def _dispatch_prefill_chunk(self, slot: int, seq: List[int],
                                start: int, rid: Optional[str] = None,
                                site: Optional[str] = None) -> None:
        """One non-final prefill chunk: K/V for ``prefill_chunk``
        prompt tokens written into the slot's blocks, no logits, no
        slot-state reset — the cache donated like every other
        dispatch."""
        c = self.prefill_chunk
        toks = np.asarray(seq[start:start + c], np.int32)[None, :]
        t_pf = self.clock()
        where = self._slot_table(slot, start)
        old = self._cache
        with tracing.span("serving.prefill", bucket=c, rid=rid,
                          chunk=True):
            self._cache = tuple(self._chunk_for(c)(
                self.params, jnp.asarray(toks), *where, *old,
            ))
            # edl: no-lint[donation-safety] the tail probes these dead refs
            self._prefill_dispatched(old, c, t_pf, site, None,
                                     "serve.prefill_chunk", rid=rid,
                                     slot=slot, start=start, chunk=c)

    def _dispatch_prefill_final(
        self, slot: int, seq: List[int], start: int, max_new: int,
        eos_id: Optional[int], site: Optional[str] = None,
        rid: Optional[str] = None, replay: bool = False,
    ) -> int:
        """The one prefill dispatch that lands a first token: run the
        bucketed TAIL of ``seq`` (positions ``start..``) through the
        layout's prefill program, write its K/V (into cache row
        ``slot``, or through the slot's table), sample the first token,
        and reset the slot's device decode state. The contiguous layout
        is ``start = 0``; under paging earlier positions are already
        resident (prefix hits / chunks)."""
        n = len(seq) - start
        tb = self._bucket(n)
        toks = np.zeros((1, tb), np.int32)
        toks[0, :n] = seq[start:]
        t_pf = self.clock()
        where = self._slot_table(slot, start)
        old = (self._dtok, self._dpos, self._dact, self._drem,
               self._deos) + self._cache
        # request trace root, DERIVED from the rid: the prefill span
        # and the serve.prefill event share trace id
        # derived_trace_id("rid", rid) without any id exchange, so a
        # fleet trace and the event log agree on the request's identity
        rid_root = (
            disttrace.root("rid", rid) if rid is not None
            else contextlib.nullcontext()
        )
        # a model that walks a bucket in pieces inside its one program
        # says how many (``_SEAM_OPTIONAL``; models/glm_dsa.py)
        pieces = self._prefill_pieces
        extra = {"pieces": pieces(tb)} if pieces and not self._paged else {}
        with rid_root, tracing.span("serving.prefill", bucket=tb, rid=rid,
                                    **extra):
            (tok0, self._dtok, self._dpos, self._dact, self._drem,
             self._deos, *cache) = self._prefill_for(tb)(
                self.params,
                jnp.asarray(toks),
                jnp.int32(n - 1),
                jnp.int32(slot),
                jnp.int32(max_new),
                jnp.int32(-1 if eos_id is None else eos_id),
                *old[:5], *where, *old[5:],
                self._next_key(),
                self._temp(),
            )
            self._cache = tuple(cache)
            # the paged layout's event says where the piece started
            at = {"start": start} if self._paged else {}
            # edl: no-lint[donation-safety] the tail probes these dead refs
            return self._prefill_dispatched(old, tb, t_pf, site, tok0,
                                            "serve.prefill", rid=rid, slot=slot,
                                            bucket=tb, replay=replay, **at)

    def _prefill_dispatched(self, old: tuple, width: int, t_pf: float,
                            site: Optional[str], tok0, event: str,
                            **fields) -> Optional[int]:
        """What follows the program call of a prefill dispatch (final
        piece or chunk), in this order: count it, probe the donation,
        put it on the timeline, offer the chaos site, sync the first
        token (a final piece has one), charge the roofline meter."""
        self.metrics.on_dispatch("prefill")
        self._assert_donated(*old)
        flight.emit(event, **fields)
        if site is not None:
            # chaos site (admission only — recovery replays are not
            # re-faulted at the same site, the dispatch sites cover
            # post-recovery failures)
            faults.fault_point(site)
        # admission is a sync point by design: the first token IS the
        # TTFT sample, so it must be observed now, not a block later
        # (and any block dispatched before this admission completed on
        # device as a dependency of the prefill)
        first = None if tok0 is None else int(np.asarray(tok0))
        now = self.clock()
        self._eff.observe(
            "prefill", self._cost.prefill(width),
            now - max(self._t_eff_last, t_pf),
        )
        self._t_eff_last = now
        return first

    def _advance_prefills(self) -> int:
        """One bounded chunk per chunk-prefilling slot per step — the
        interleave that keeps decode blocks flowing while long prompts
        prefill. The FINAL piece lands the first token and flips the
        slot to decoding."""
        emitted = 0
        for i in range(self.max_slots):
            sl = self._slots[i]
            if sl is None or sl.pf_next is None:
                continue
            start = sl.pf_next
            if len(sl.prompt) - start > self.prefill_chunk:
                self._dispatch_prefill_chunk(
                    i, sl.prompt, start, rid=sl.rid, site="serve.prefill"
                )
                sl.pf_next = start + self.prefill_chunk
                continue
            sl.pf_next = None
            tok0 = self._dispatch_prefill_final(
                i, sl.prompt, start, sl.max_new, sl.eos_id,
                site="serve.prefill", rid=sl.rid,
            )
            self._pg_cache_insert(i, sl.prompt)
            sl.generated.append(tok0)
            self.metrics.on_token(sl.rid)
            emitted += 1
            if sl.eos_id is not None and tok0 == sl.eos_id:
                self._finish(i, "eos")
            elif sl.max_new <= 1:
                self._finish(i, "done")
        return emitted

    def _pg_cache_insert(self, slot: int, prompt: List[int]) -> None:
        """Publish the slot's FULL prompt blocks into the prefix cache
        (chain keys — a hit implies the whole prefix matched). Existing
        keys are no-op touches, so identical prompts converge on the
        first publisher's blocks."""
        if self._prefix is None:
            return
        tbl = self._tables[slot]
        for j, key in enumerate(
            _paged.chain_keys(prompt, self.block_size)
        ):
            self._prefix.insert(key, tbl[j])

    def _ensure_cover(self, i: int) -> None:
        """Alloc-on-demand as ``pos`` crosses block boundaries: before
        a decode dispatch, map every block the slot's ACTIVE lane can
        write within the next ``horizon * (in-flight + 1)`` positions
        (in-flight blocks advance the device past the host view).
        Frozen-lane rewrites past the budget route to scratch on
        device and are masked on read, so they need no coverage."""
        sl = self._slots[i]
        t0 = len(sl.prompt) + len(sl.generated)
        # the per-dispatch advance bound: a horizon block moves a lane
        # up to `horizon` positions, a verify dispatch up to spec_k+1
        # (full acceptance + bonus) — cover whichever this engine runs
        adv = max(self.horizon, self.spec_k + 1)
        need = min(
            self.max_len,
            len(sl.prompt) + sl.max_new,
            t0 + adv * (len(self._inflight) + 1),
        )
        tbl = self._tables[i]
        for j in range(_paged.blocks_for(need, self.block_size)):
            if tbl[j] == _paged.SCRATCH:
                tbl[j] = self._pg_alloc_or_preempt(i)

    def _pg_alloc_or_preempt(self, slot: int) -> int:
        """One block, by any means: the free list, then evicting
        refcount-1 prefix-cache entries (LRU), then preempting the
        youngest OTHER slot back to the queue. The construction
        invariant (usable pool >= one full sequence) means a lone
        survivor always gets its block."""
        while True:
            bid = self._balloc.alloc()
            if bid is not None:
                return bid
            if self._prefix is not None and self._prefix.evict_one():
                continue
            if not self._pg_preempt(exclude=slot):
                raise RuntimeError(
                    "KV pool exhausted with nothing left to preempt"
                )

    def _pg_preempt(self, exclude: int) -> bool:
        """Preempt the youngest slot (≠ ``exclude``) under pool
        pressure: free its blocks, mark the lane stale, and requeue the
        request AT THE HEAD for restart-by-recomputation. ``submit_s=0``
        with the ABSOLUTE deadline keeps ``deadline_at()`` correct
        across the round trip."""
        victims = [
            (sl.born, i) for i, sl in enumerate(self._slots)
            if sl is not None and i != exclude
        ]
        if not victims:
            return False
        _, i = max(victims)
        sl = self._slots[i]
        flight.emit("serve.preempt", severity="warn", rid=sl.rid,
                    slot=i, generated=len(sl.generated))
        self._pg_free_slot(i)
        self._slots[i] = None
        self._stale.add(i)
        self.queue.requeue_front(Request(
            rid=sl.rid, prompt=list(sl.prompt), max_new=sl.max_new,
            eos_id=sl.eos_id, deadline_s=sl.deadline, submit_s=0.0,
            recoveries=sl.recoveries, tenant=sl.tenant,
            slo_class=sl.slo_class,
        ))
        return True

    def _pg_free_slot(self, i: int) -> None:
        """Drop the slot's reference on every mapped block. Free and
        table-clear happen TOGETHER — a freed id left behind in a table
        is the aliasing hazard the kv-block check rule flags. Shared
        blocks survive under their remaining refs (prefix cache /
        other slots); reclaimed ones are rewritten by their next owner
        before any read (program ordering, see section comment)."""
        tbl = self._tables[i]
        for j, bid in enumerate(tbl):
            if bid != _paged.SCRATCH:
                self._balloc.free(bid)
                tbl[j] = _paged.SCRATCH

    def _pg_make_writable(self, slot: int, j: int) -> None:
        """Copy-on-write table entry ``j``: if the mapped block is
        shared (refcount > 1), copy it into a private block on device,
        point the table at the copy, and drop the shared ref. Shared
        blocks are immutable while referenced — this is the only path
        that lets a slot write into previously shared territory."""
        tbl = self._tables[slot]
        bid = tbl[j]
        if self._balloc.refcount(bid) <= 1:
            return
        dst = self._pg_alloc_or_preempt(slot)
        # every array of the cache: a quantized block's scales move with
        # its values
        old = self._cache
        self._cache = tuple(
            self._copyblk(*old, jnp.int32(bid), jnp.int32(dst)))
        # edl: no-lint[donation-safety] deliberate is_deleted() probe of the donation contract
        self._assert_donated(*old)
        tbl[j] = dst
        self._balloc.free(bid)
        flight.emit("serve.kv_cow", slot=slot, block=j)

    def _finish(self, slot: int, outcome: str) -> None:
        sl = self._slots[slot]
        if self._spec_policy is not None:
            self._spec_policy.forget(sl.rid)
        self.results[sl.rid] = RequestResult(
            rid=sl.rid, tokens=list(sl.generated), outcome=outcome
        )
        self.metrics.on_finish(sl.rid, outcome)
        # the finish event carries the phase decomposition (and the
        # tenant/SLO labels), so a postmortem timeline shows WHERE the
        # request's time went, not just when it ended
        phases = {
            k: round(v, 6)
            for k, v in self.metrics.phase_breakdown(sl.rid).items()
        }
        labels = {}
        if sl.tenant is not None:
            labels["tenant"] = sl.tenant
        if sl.slo_class is not None:
            labels["slo_class"] = sl.slo_class
        flight.emit(
            "serve.finish",
            severity="info" if outcome in ("done", "eos") else "warn",
            rid=sl.rid, outcome=outcome, tokens=len(sl.generated),
            **labels, **phases,
        )
        # eviction is bookkeeping only: the device already froze the
        # row (active mask), the freed cache row is dead weight until
        # the next prefill-insert overwrites it, and the block program
        # never changes shape. Paged mode additionally returns the
        # slot's block references to the pool (shared prefix blocks
        # survive under the cache's ref).
        if self._paged:
            self._pg_free_slot(slot)
        self._slots[slot] = None

    # -- crash recovery ------------------------------------------------------

    def _recover(self, err: Exception) -> None:
        """Rebuild the engine from host truth after an exception escaped
        a dispatch/prefill/drain. The device world (donated caches,
        slot-state carries, in-flight token matrices) is assumed GONE —
        some of it genuinely is: donated inputs are dead and undrained
        blocks hold tokens the host never saw. What survives is exactly
        what each slot retains: ``prompt + generated`` (only drained
        tokens ever enter ``generated``). Recovery:

        1. requeue a request caught mid-admission (popped, not slotted)
           at the queue HEAD — it keeps its FIFO position;
        2. charge every live slot one recovery attempt; requests past
           ``max_recoveries`` finish with outcome "failed" (bounded
           recovery — a poisoned request cannot wedge the engine);
        3. drop in-flight blocks, reallocate the KV cache and device
           slot-state from zeros;
        4. re-prefill each surviving slot from ``prompt + generated``
           with its REMAINING budget — under greedy decoding the full-
           context prefill emits exactly the token the lost decode step
           would have, so post-recovery output is token-identical to a
           fault-free run (the tests/test_serving_recovery.py contract;
           temperature sampling recovers too, but the key schedule
           shifts, so sampled continuations may differ).

        A fault DURING recovery recurses (step 2's per-request bound
        makes the recursion terminate: every pass either finishes a
        request or burns one of its bounded attempts)."""
        log.warn(
            "engine fault; recovering",
            error=f"{type(err).__name__}: {err}",
            inflight=len(self._inflight),
            live=self.active_slots,
        )
        with tracing.span("serving.recover"):
            requeued = None
            if self._admitting is not None:
                # the mid-admission request is charged like a slotted
                # one — otherwise a request whose prefill always faults
                # would requeue forever, never burning its budget
                req = self._admitting
                self._admitting = None
                req.recoveries += 1
                if req.recoveries > self.max_recoveries:
                    self.results[req.rid] = RequestResult(
                        rid=req.rid, tokens=[], outcome="failed"
                    )
                    self.metrics.on_finish(req.rid, "failed")
                    flight.emit("serve.finish", severity="warn",
                                rid=req.rid, outcome="failed", tokens=0)
                else:
                    self.queue.requeue_front(req)
                    requeued = req.rid
            live = []
            for i, sl in enumerate(self._slots):
                if sl is None:
                    continue
                sl.recoveries += 1
                if sl.recoveries > self.max_recoveries:
                    self._finish(i, "failed")
                else:
                    live.append(i)
            self.recoveries += 1
            self.metrics.on_recovery(len(live))
            # the flight-recorder entry names every request this pass
            # replays (postmortem verifies each one re-prefills and
            # finishes), then the black box snapshots the timeline
            # that LED here — before the rebuild mutates anything else
            flight.emit(
                "serve.recover", severity="warn",
                error=f"{type(err).__name__}: {err}",
                rids=[self._slots[i].rid for i in live],
                requeued=requeued,
                recovery_n=self.recoveries,
            )
            flight.crash_dump("serving", err)
            self._alloc_device_state()
            for i in live:
                try:
                    self._replay_slot(i)
                except Exception as e2:
                    self._recover(e2)
                    return

    def _replay_slot(self, slot: int) -> None:
        """Re-prefill one live slot from ``prompt + generated``: the
        prefill emits the NEXT token (appended like any generated
        token), rebuilds the row's K/V, and resets its device budget to
        the tokens still owed. EOS/budget termination is re-checked on
        the emitted token exactly like admission."""
        sl = self._slots[slot]
        # a slot caught mid-chunked-prefill replays its whole prompt
        # inline — the fresh pool has none of its earlier chunks
        sl.pf_next = None
        seq = sl.prompt + sl.generated
        remaining = sl.max_new - len(sl.generated)
        tok = self._prefill_into(slot, seq, remaining, sl.eos_id,
                                 rid=sl.rid, replay=True)
        sl.generated.append(tok)
        self.metrics.on_token(sl.rid)
        if sl.eos_id is not None and tok == sl.eos_id:
            self._finish(slot, "eos")
        elif len(sl.generated) >= sl.max_new:
            self._finish(slot, "done")
