"""PAPI serving engine: mixed continuous batching + speculative decoding +
dynamic FC-path scheduling.

The runtime loop the paper describes (§5.2.2), realized over the JAX models:

  1. admit waiting requests into free KV-cache slots (mixed continuous
     batching — token-level scheduling, no drain barrier);
  2. run one decoding iteration for every active slot: either a plain
     decode step (TLP=1) or a draft-propose / target-verify speculative
     window (TLP>1, greedy & lossless);
  3. gather the iteration's output tokens, count <|eos|>, update the
     scheduler's RLP; the scheduler compares RLP*TLP against the calibrated
     alpha and picks the FC execution path ("pu" MXU vs "pim" fc_gemv) for
     the *next* iteration.

Slots are fixed-capacity (static shapes: the decode step is compiled once
per TLP value).  Inactive slots decode garbage that is masked out — the
standard padded-batch serving trade.

Chunked prefill (no prompt truncation, ever)
--------------------------------------------
``prefill_len`` sizes the compiled prefill *window*, not the longest
servable prompt.  Admission feeds an arbitrarily long prompt through the
fixed-shape program in waves of `prefill_len`-token chunks: chunk 0 runs
the batched `prefill_to_slots` / `prefill_to_pages` call (positions
0..P-1), every later chunk runs `models.prefill_chunk` — the decode path
at the slot's running offset, with per-slot masked KV writes so the ragged
final chunk and concurrently-decoding slots never touch each other's
cache.  Only the final chunk's logits produce the request's first output
token, which makes the stream bit-identical to a one-shot prefill of the
whole prompt (tested against that oracle).  Dense admission budgets the
slab for ``len(prompt) + max_new + spec window`` and rejects honestly
(``finished_reason="rejected"``) when the FULL prompt cannot fit; paged
admission reserves pages for the full prompt up front and maps them before
chunk 0, so every chunk scatters straight onto its pages.  A prompt that
fits one window takes exactly the pre-chunking path.

KV layouts (``kv_layout=``)
---------------------------
``"dense"`` (default): one `(layers, max_slots, cache_capacity, ...)` slab;
every request pre-reserves a full uniform slot, so per-request context is
capped at `cache_capacity` and short requests strand the rest of theirs.

``"paged"``: the Attn-PIM bank-row layout.  KV lives in a pool of
fixed-size pages (`models.init_paged_cache`), per-slot block tables map
logical KV blocks to physical pages, and `serving.kv_pages.PagedKVManager`
runs admission on a PAGE budget: a request enters iff pages for
`prompt + max_new_tokens + spec_len` are available (reserved up front,
mapped lazily as the sequence grows, returned on speculative rewind, freed
on finish).  A single request may span nearly the whole pool — context
length is bounded by pooled memory, not a per-slot slab.  Decode attention
either gathers pages into the XLA path or — with ``attn_pim=True`` — runs
the block-table Pallas kernel (`kernels.paged_decode_attention`), which
reads each slot's live pages through the block table for ANY TLP (plain
decode, speculative verify windows, chunked-prefill waves), whatever
`max_blocks` is: `gather_kv_pages` never appears in
a jitted program under attn_pim.  Token streams are identical to the
dense engine on any workload both can hold (tested).  Per-iteration pool
stats (pages used/free, watermark, fragmentation) ride on `IterStats`.

Failure model & graceful degradation
------------------------------------
The engine degrades instead of livelocking or emitting garbage (see
docs/ARCHITECTURE.md for the full policy):

  * **pool-pressure preemption** — when paged admission has deferred the
    head of the queue for ``preempt_after`` consecutive iterations (or the
    pool occupancy crosses ``preempt_watermark`` while a deferral is
    pending), the YOUNGEST in-flight request is preempted: its pages are
    released and it is requeued at the back as ``prompt + tokens-so-far``,
    which chunked prefill recomputes bit-identically (the requeued
    request's first output token is exactly the decode step the preemption
    skipped).  The oldest in-flight request is never preempted, so it
    always runs to completion and the head of the queue always admits in
    bounded time — no livelock.
  * **deadlines and cancellation** — ``ServeRequest.deadline_s`` bounds a
    request's wall-clock time from submit(); `cancel(req_id)` works on
    queued and in-flight requests alike.  Both finish honestly
    (``finished_reason="timeout"/"cancelled"``) with tokens-so-far and
    drain their pages/reservations.
  * **finite-logits guard** — every fused decode step checks its logits
    for NaN/Inf ON DEVICE; a poisoned step is discarded (the functional
    cache update is simply not assigned) and the iteration re-runs on the
    tested XLA oracle path — unfused plain decode, "pu" FC, XLA attention
    — with the speculation window clamped to 1 for that step
    (`IterStats.degraded`).  `serving.faults.FaultInjector` forces this
    path (and admission failure / artificial latency) deterministically.
  * **no-progress watchdog** — ``stall_limit`` consecutive iterations in
    which nothing was admitted, decoded, finished, or preempted while work
    is pending raise `EngineStallError` carrying a pool/queue/slot
    snapshot, instead of spinning silently to ``max_iterations``.  `run()`
    exhaustion itself no longer drops in-flight requests: they are
    returned as ``finished_reason="aborted"`` results with tokens-so-far,
    pages released.
  * **invariant checking** — ``debug_invariants=True`` runs the page
    allocator's `check()` every iteration and turns a violation into
    `AllocatorInvariantError` with the allocator snapshot attached (the
    whole serving test suite runs with the flag on).

Device-resident hot path
------------------------
PAPI's premise is that the per-iteration scheduling decision is O(1) and a
reschedule costs nothing but the dispatch — which only holds if the Python
orchestration around the decode step is free.  The default (``fused=True``)
hot path therefore keeps one engine iteration (nearly) a single device
program:

  * the k-step draft loop + target verify + accept-longest-prefix +
    cache-rewind run inside ONE jitted function (`jax.lax.scan` over the
    draft steps, vectorized accept via `sampler.accept_speculative`), and
    the host fetches one `(out, accepted, finished_eos)` bundle per
    iteration instead of k+1 per-step syncs;
  * admission prefils ALL newly-freed slots in one compiled
    `models.prefill_to_slots` call (fixed [max_slots, prefill_len] batch +
    a [max_slots] src map), replacing the per-request temp-cache
    allocation + per-key `.at[slot].set` scatter;
  * inactive slots are parked with a fixed-shape boolean mask
    (`jnp.where(mask, 1, pos)`) instead of the recompile-prone dynamic
    `jnp.asarray(inactive)` gather index.

``fused=False`` preserves the seed's per-draft-step host loop and per-slot
Python accept reference — kept as the oracle for the property tests and the
`benchmarks/engine_hotpath.py` A/B.

Mesh execution (§5.3)
---------------------
Pass ``mesh=`` (e.g. `launch.mesh.make_serving_mesh(dp, tp)`) and the engine
becomes mesh-native; ``rules`` defaults to
`distributed.sharding.serve_rules()`:

  * params are `device_put` onto `models.param_shardings` — FC weights split
    over the tensor ("model") axis, i.e. one FC-PIM weight bank per shard;
  * the KV cache is placed by `models.cache_shardings` — under serve rules
    the cache *sequence* dim lands on the tensor axis (context-parallel KV
    slices); with ``attn_pim=True`` the rules instead store the cache split
    over KV *heads*, the same units the flash-decode kernel shard_maps over,
    so each Attn-PIM shard sits next to its resident KV slice and no
    per-step resharding occurs;
  * every jitted entry point (prefill waves, both fused step programs, the
    legacy host loop) is traced inside ``axis_rules(rules, mesh)``, so the
    `shard()` annotations in the model resolve and GSPMD partitions the
    step.  The "pim" FC path additionally runs `fc_gemv` under `shard_map`
    (see `models.linear`), and ``attn_pim=True`` routes every decode-path
    attention — plain decode, TLP>1 speculative verify windows, and
    chunked-prefill waves — through the (windowed) flash-decode Pallas
    kernel sharded one unit per KV-head shard.

The scheduler's per-iteration FC_PU <-> FC_PIM flip keeps working under a
mesh because the jit caches are keyed on the variant — each (kind, tlp,
variant) traces its own partitioned executable once, and a reschedule is
still just the dispatch of the other one.  Greedy token streams are
unchanged by the mesh (reduction reorder moves logits by ulps, never the
argmax — asserted 1-device vs 8-device in `tests/test_serving_sharded.py`).

Compiled-function cache keys
----------------------------
All jitted entry points are cached on ``(kind, tlp, fc_variant,
pim_interpret)``.  The FC variant MUST be part of the key: `papi_linear`
reads the variant from a host thread-local at *trace* time, so a cache
keyed only on (kind, tlp) — as the seed did — would bake in whichever
variant was active at first call and silently ignore every later scheduler
flip.  With the variant in the key, each path traces at most twice (pu +
pim) and a reschedule really is just a dispatch of the other executable.

Host-transfer accounting: every device->host sync goes through
`PapiEngine._fetch`, which bumps ``host_transfers``; per-iteration deltas
are recorded in `IterStats.transfers` so the benchmark can count round
trips instead of guessing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import logging
import time
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.scheduler import PapiScheduler
from repro.debug.sanitize import EngineSanitizer
from repro.distributed.sharding import axis_rules, serve_rules
from repro.models import (cache_shardings, decode_step, init_cache,
                          init_paged_cache, mixed_step,
                          paged_cache_shardings, param_shardings,
                          prefill_chunk, prefill_to_pages, prefill_to_slots)
from repro.models.layers import attn_impl
from repro.models.linear import current_fc_interpret, current_fc_variant, fc_variant
from repro.serving.faults import FAULT_INF, FAULT_NAN, FAULT_NONE, FaultInjector
from repro.serving.journal import (SNAPSHOT_VERSION, Journal, recover,
                                   write_snapshot)
from repro.serving.kv_pages import PagedKVManager
from repro.serving.sampler import accept_speculative, greedy
from repro.serving.telemetry import (NULL_TRACER, Tracer, format_program_key,
                                     phase)

# the serving subsystem's logger: deferral (DEBUG), preemption / unhappy
# finishes (INFO), degraded re-runs (WARNING), stalls (ERROR).  Unconfigured
# it propagates to the root handler-less logger, i.e. stays silent —
# `launch.serve --log-level` wires basicConfig for the CLI.
log = logging.getLogger("repro.serving")


@dataclasses.dataclass
class ServeRequest:
    req_id: int
    prompt: list[int]
    max_new_tokens: int
    # wall-clock budget in seconds, measured from submit(); None = unbounded.
    # An expired request finishes with finished_reason="timeout" and its
    # tokens-so-far at the next step boundary.
    deadline_s: float | None = None


@dataclasses.dataclass
class ServeResult:
    req_id: int
    tokens: list[int]
    prompt_len: int
    iterations: int
    finished_reason: str = "length"
    # --- per-request serving latencies (see serving/metrics.py) ---
    # wall-clock seconds; None when the phase never happened (a request
    # cancelled in the queue has no TTFT).  The *_iters twins count engine
    # iterations instead — deterministic for a fixed arrival schedule, so
    # the BENCH gate can bound p99 TTFT without wall-clock flake.
    queue_delay_s: float | None = None   # submit -> first admission
    ttft_s: float | None = None          # submit -> first token
    tpot_s: float | None = None          # mean inter-token gap after TTFT
    queue_delay_iters: int | None = None
    ttft_iters: int | None = None


@dataclasses.dataclass
class TokenEvent:
    """One streamed event from `PapiEngine.serve`: a committed token on a
    live request, or (``finished=True``) the request's completion.  The
    final event carries ``token == -1``, ``index == len(result.tokens)``,
    the ``finished_reason`` and the full `ServeResult`; per-token events
    index the caller-visible stream (a preempted request's indices continue
    across its re-admission — the re-prefilled tokens are never re-sent)."""
    req_id: int
    token: int
    index: int
    iteration: int
    finished: bool = False
    reason: str | None = None
    result: ServeResult | None = None


@dataclasses.dataclass
class _ResumedRequest:
    """Internal requeue record for a preempted request: the original prompt
    extended with every token already emitted, so chunked admission
    recomputes the KV bit-identically and the continuation's first output
    token is exactly the decode step the preemption skipped.  The caller's
    `ServeRequest` is never touched; `done` / `orig_prompt_len` let result
    emission reassemble the caller-visible stream."""
    req_id: int
    prompt: list[int]          # original prompt + tokens emitted so far
    max_new_tokens: int        # remaining generation budget
    deadline_s: float | None
    done: list[int]            # tokens emitted before the preemption(s)
    orig_prompt_len: int


class EngineStallError(RuntimeError):
    """`run()` made no progress — nothing admitted, decoded, finished, or
    preempted — for `stall_limit` consecutive iterations while requests
    were still pending.  ``snapshot`` carries the pool/queue/slot state at
    the stall (see `PapiEngine._snapshot`)."""

    def __init__(self, message: str, snapshot: dict):
        super().__init__(message)
        self.snapshot = snapshot


class EngineCrashError(RuntimeError):
    """A `crash` fault fired: the engine dies at the top of the iteration,
    exactly like a process kill — no results emitted, no pages drained,
    no journal finalization.  Recovery cold-starts a fresh engine and
    `restore()`s from the journal/snapshot (see serving/journal.py)."""

    def __init__(self, message: str, iteration: int):
        super().__init__(message)
        self.iteration = iteration


class AllocatorInvariantError(RuntimeError):
    """A `debug_invariants=True` engine caught the page allocator violating
    its invariants (double-map / leak / over-reservation).  ``snapshot``
    carries the engine + allocator state at the violation."""

    def __init__(self, message: str, snapshot: dict):
        super().__init__(message)
        self.snapshot = snapshot


def _named(name: str, fn):
    """``fn`` under the function name ``name``, so that its jitted program
    is ``jit_<name>`` in the profiler's trace (a `partial` has none)."""
    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


def _inject_fault(logits, code):
    """Apply the iteration's fault code (a traced int32 scalar) to the
    logits inside the jitted step: FAULT_NAN poisons with NaN, FAULT_INF
    models an overflowed kernel accumulator.  FAULT_NONE is the identity,
    so fault-free engines trace the same program."""
    poison = jnp.where(code == FAULT_NAN, jnp.nan, jnp.inf)
    return jnp.where(code == FAULT_NONE, logits,
                     jnp.full_like(logits, poison))


@dataclasses.dataclass
class IterStats:
    iteration: int
    rlp: int
    tlp: int
    ai_estimate: float
    fc_variant: str
    new_tokens: int
    accepted: float        # mean accepted tokens per active slot (spec dec)
    wall_s: float
    transfers: int = 0     # device->host sync round-trips this iteration
    # failure-model counters (see the module docstring):
    preemptions: int = 0   # in-flight requests preempted this iteration
    deferral_age: int = 0  # consecutive iterations the queue head deferred
    degraded: int = 0      # 1 if the finite-logits guard degraded this step
    # paged KV layout only (zeros under the dense layout):
    kv_pages_used: int = 0       # pages holding live KV right now
    kv_pages_free: int = 0       # pages on the free list
    kv_page_watermark: int = 0   # peak pages used over the engine lifetime
    kv_fragmentation: float = 0.0  # tail-of-page waste share of mapped rows
    # continuous-batching serve loop only (zeros under offline run()):
    arrivals: int = 0        # requests that arrived this iteration
    admitted: int = 0        # requests admitted to slots this iteration
    queued: int = 0          # queue depth after this iteration's admission
    prefill_slots: int = 0   # slots mid-chunked-prefill this iteration
    decode_slots: int = 0    # slots that ran a decode step this iteration
    # where the host's time went (perf_counter, always on):
    host_s: float = 0.0      # wall_s less the time waiting in _fetch
    gc_s: float = 0.0        # GC pauses since the previous IterStats
    jit_misses: int = 0      # _calls that grew their program's jit cache


class PapiEngine:
    """Serving engine over one device by default, or over a whole mesh.

    ``mesh``/``rules`` make the engine mesh-native (see the module
    docstring): params and the KV cache are placed on `serve_rules()`
    shardings and every compiled step runs partitioned.  ``attn_pim=True``
    additionally moves every decode-path attention — plain decode,
    speculative verify windows (TLP>1), chunked-prefill waves, dense or
    paged — onto the (windowed) Pallas flash-decode kernel, the Attn-PIM
    unit, sharded per KV shard under a mesh.  `launch.serve` drives both
    layouts from the CLI."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        max_slots: int = 8,
        cache_capacity: int = 256,
        prefill_len: int = 64,
        alpha: float = 32.0,
        spec_len: int = 1,
        draft: tuple[ModelConfig, Any] | None = None,
        eos_token: int = 2,
        pim_interpret: bool | None = None,
        fused: bool = True,
        mesh: Any | None = None,
        rules: dict | None = None,
        attn_pim: bool = False,
        kv_layout: str = "dense",
        page_size: int = 16,
        num_pages: int | None = None,
        max_blocks: int | None = None,
        faults: FaultInjector | None = None,
        preempt_after: int | None = 8,
        preempt_watermark: float | None = None,
        stall_limit: int | None = 256,
        debug_invariants: bool = False,
        tracer: Tracer | None = None,
        sanitize: bool = False,
        journal: Journal | str | None = None,
    ) -> None:
        assert cfg.has_decode_step, f"{cfg.name} is encoder-only"
        assert kv_layout in ("dense", "paged"), kv_layout
        self.cfg, self.params = cfg, params
        self.max_slots = max_slots
        self.capacity = cache_capacity
        self.prefill_len = prefill_len
        self.eos_token = eos_token
        self.spec_len = spec_len
        self.pim_interpret = pim_interpret
        self.fused = fused
        self.mesh = mesh
        self.kv_layout = kv_layout
        # attn_pim stores the KV cache head-sharded instead of seq-sharded so
        # the flash-decode kernel's per-KV-shard units match the resident
        # layout (no per-step resharding) — see serve_rules(attn_pim=True).
        # The paged layout always takes the head-sharded rules under a mesh:
        # its pool dim replaced the sequence dim and physical page ids index
        # the whole pool, so KV heads are the only dim that can divide the
        # pools across devices (seq-sharded rules would silently replicate
        # the entire pool on every device).
        self.rules = (dict(rules) if rules is not None
                      else (serve_rules(attn_pim=attn_pim
                                        or kv_layout == "paged")
                            if mesh is not None else None))
        self.attn_pim = attn_pim
        # telemetry: NULL_TRACER's hooks are no-ops, so the traced-off hot
        # path is unchanged (gated by the traced-vs-untraced A/B in
        # benchmarks/engine_hotpath.py); the phase spans reach the
        # profiler's trace either way
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # per-step counters behind IterStats.host_s / gc_s / jit_misses
        self._fetch_s = 0.0        # seconds waiting in _fetch, cumulative
        self._gc_s = 0.0           # GC pause seconds not yet reported
        self._gc_span: phase | None = None
        self._jit_misses = 0       # jit-cache misses, cumulative
        # runtime sanitizer (repro.debug.sanitize): transfer-guard scopes
        # around every step, per-iteration transfer-budget assertions, and
        # a jit-cache compile census — None keeps the hot path untouched
        self._sanitizer = EngineSanitizer() if sanitize else None
        self.scheduler = PapiScheduler(cfg, alpha=alpha, tlp=spec_len,
                                       eos_token=eos_token)
        self.scheduler.initial_schedule(0, spec_len)

        self.kv: PagedKVManager | None = None
        if kv_layout == "paged":
            # default pool: the same KV bytes the dense layout would hold
            # (max_slots dense slots of cache_capacity), plus the garbage
            # page — but pooled, so ONE request may span nearly all of it
            if num_pages is None:
                num_pages = max(max_slots * cache_capacity // page_size, 1) + 1
            # max_blocks bounds the block-table width: per-request context
            # AND the width of the XLA path's gathered KV view (which pays
            # for max_blocks * page_size per slot per step regardless of
            # live length).  The paged kernel (attn_pim) reads only each
            # slot's live pages, whatever the width.  Default None = the
            # whole usable pool, i.e. one request may span nearly all of
            # it; cap it when serving many short requests from a large pool.
            self.kv = PagedKVManager(num_pages=num_pages, page_size=page_size,
                                     max_slots=max_slots,
                                     max_blocks=max_blocks)
            # per-call allocator events (map/unmap/reserve) are the highest-
            # volume kind: attached only when debugging invariants or when
            # the tracer opted in explicitly
            if self.tracer.enabled and (debug_invariants
                                        or self.tracer.page_events):
                self.kv.tracer = self.tracer
            self.cache = init_paged_cache(cfg, max_slots, num_pages,
                                          page_size, self.kv.max_blocks)
        else:
            self.cache = init_cache(cfg, max_slots, cache_capacity)
        if mesh is not None:
            self.params = jax.device_put(
                self.params, param_shardings(cfg, self.rules, mesh))
            self.cache = jax.device_put(
                self.cache, self._cache_shardings(cfg))
        # per-slot host state
        self.slot_req: list[ServeRequest | None] = [None] * max_slots
        self.slot_tokens: list[list[int]] = [[] for _ in range(max_slots)]
        self.slot_last: np.ndarray = np.zeros(max_slots, np.int32)
        # full prompt tokens prefilled per slot (chunked admission writes the
        # whole prompt): the device cache position of a live slot is
        # slot_prompt[s] + len(slot_tokens[s]) - 1 (see _slot_pos)
        self.slot_prompt: np.ndarray = np.zeros(max_slots, np.int32)
        # effective generation budget per slot — the admission-clamped
        # max_new_tokens lives HERE, never written back into the caller's
        # ServeRequest (resubmitting the same object must see it pristine)
        self.slot_budget: np.ndarray = np.zeros(max_slots, np.int64)
        self.queue: list[ServeRequest] = []
        self.results: list[ServeResult] = []
        self.stats: list[IterStats] = []
        self.iteration = 0
        self.host_transfers = 0
        # --- failure model (see the module docstring) ---
        self.faults = faults
        self.preempt_after = preempt_after
        self.preempt_watermark = preempt_watermark
        self.stall_limit = stall_limit
        self.debug_invariants = debug_invariants
        # admission order per slot: the victim policy preempts the highest
        # sequence number (youngest), never the lowest (oldest)
        self._admit_seq = 0
        self.slot_seq: list[int] = [0] * max_slots
        self._defer_head: int | None = None   # req_id of the deferring head
        self._defer_age = 0                   # consecutive deferred steps
        self._deferred_head: int | None = None  # set by _admit on deferral
        self._degraded_this_step = False
        self._stalled = 0                     # consecutive no-progress steps
        self.preemptions = 0                  # engine-lifetime total
        self.degraded_steps = 0               # engine-lifetime total
        self.preempted_ids: set[int] = set()
        # wall-clock submit time (deadline base) and admission-delay
        # bookkeeping, keyed by req_id; first submission/admission wins
        self._submit_t: dict[int, float] = {}
        self.submit_iteration: dict[int, int] = {}
        self.admit_iteration: dict[int, int] = {}
        # latency accounting for serve(): wall-clock admission / first-token
        # stamps (setdefault — a preempted request keeps its originals)
        self._admit_t: dict[int, float] = {}
        self._first_tok_t: dict[int, float] = {}
        self.first_token_iteration: dict[int, int] = {}
        # --- durability (serving/journal.py) ---
        # write-ahead journal: a path opens (and torn-tail-truncates) a
        # Journal with the default flush policy; pass a Journal instance to
        # choose the policy.  _journal_done tracks tokens already journaled
        # per req_id so the end-of-step flush appends only deltas.
        if journal is None or isinstance(journal, Journal):
            self.journal: Journal | None = journal
        else:
            self.journal = Journal(journal)
        self._journal_done: dict[int, int] = {}
        if self.journal is not None and self.tracer.enabled:
            self.tracer.emit("journal", 0, op="open",
                             path=str(self.journal.path),
                             records=self.journal.records_kept,
                             truncated_bytes=self.journal.truncated_bytes)
        # --- continuous batching (serve()) ---
        # prompt tokens prefilled so far per slot; a slot is MID-PREFILL
        # while slot_offset < slot_prompt (only possible under serve(),
        # which spreads chunk waves across iterations — offline admission
        # always runs a prompt's waves to completion inside _admit_wave)
        self.slot_offset: np.ndarray = np.zeros(max_slots, np.int64)
        self.stream_chunks = False   # serve() flips this on for its lifetime
        self._arrived_this_step = 0  # set by serve(), recorded in IterStats
        # chunked prefill masks its KV writes per slot; SSM state has no
        # sequence dim to mask, so stateful families keep single-window
        # prefill and reject longer prompts honestly
        self._can_chunk = cfg.family in ("dense", "moe", "vlm", "audio")

        if draft is not None:
            self.draft_cfg, self.draft_params = draft
            if self.kv is not None:
                # the draft's KV lives at the same logical positions, so it
                # pages through the SAME allocator + block tables (shared
                # geometry, per-model page contents)
                self.draft_cache = init_paged_cache(
                    self.draft_cfg, max_slots, num_pages, page_size,
                    self.kv.max_blocks)
            else:
                self.draft_cache = init_cache(self.draft_cfg, max_slots,
                                              cache_capacity)
            if mesh is not None:
                self.draft_params = jax.device_put(
                    self.draft_params,
                    param_shardings(self.draft_cfg, self.rules, mesh))
                self.draft_cache = jax.device_put(
                    self.draft_cache, self._cache_shardings(self.draft_cfg))
        else:
            self.draft_cfg = self.draft_params = self.draft_cache = None

        # jit caches, keyed (kind, tlp, fc_variant, interpret) — see module
        # docstring for why the variant must be in the key.
        self._decode_jit: dict[tuple, Any] = {}
        self._prefill_jit: dict[tuple, Any] = {}

    # ------------------------------------------------------------------ API
    def submit(self, req: ServeRequest) -> None:
        self.queue.append(req)
        self._submit_t.setdefault(req.req_id, self._now())
        self.submit_iteration.setdefault(req.req_id, self.iteration)
        if self.journal is not None:
            self.journal.append("submit", req_id=req.req_id,
                                prompt=list(req.prompt),
                                max_new=int(req.max_new_tokens),
                                dl=req.deadline_s)
        if self.tracer.enabled:
            self.tracer.emit("submit", self.iteration, req_id=req.req_id,
                             prompt_len=len(req.prompt),
                             max_new=req.max_new_tokens)

    @property
    def active_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def run(self, max_iterations: int = 10_000, *,
            abort_in_flight: bool = True) -> list[ServeResult]:
        while (self.queue or self.active_slots) and self.iteration < max_iterations:
            self.step()
        if abort_in_flight and self.active_slots and (
                self.iteration >= max_iterations):
            # iteration exhaustion must not drop in-flight requests on the
            # floor: return them honestly with their tokens-so-far and
            # drain their pages/reservations.  (Queued requests stay
            # queued — a later run() call picks them up.)  Tests that step
            # an engine incrementally pass ``abort_in_flight=False`` to
            # keep the in-flight state across run() calls.
            for s in list(self.active_slots):
                self._finish_slot(s, "aborted")
        return self.results

    def serve(self, arrivals, *, max_iterations: int = 100_000):
        """Continuous-batching serve loop: a generator of `TokenEvent`s over
        a LIVE arrival stream.

        ``arrivals`` is any iterable polled once per engine iteration;
        each ``next()`` yields the requests arriving at that iteration — a
        `ServeRequest`, a list of them, or None for a quiet tick — and
        exhaustion closes the arrival stream (the loop then drains the
        queue and in-flight slots and returns).  The iteration counter is
        the schedule's time axis: a trace replayed against the same engine
        configuration admits, decodes, and finishes deterministically.

        Every iteration the loop admits from the queue, advances newly
        admitted long prompts by one chunk wave MIXED with the ongoing
        decodes (TLP=1: one fused device program for both; speculative: the
        chunk wave plus the fused verify program — either way no prefill
        stall, no drain barrier), and yields each newly committed token as
        a `TokenEvent`.  A finished request yields a final event carrying
        its `ServeResult`, latencies stamped (queue delay / TTFT / TPOT —
        see serving/metrics.py; aggregate with `latency_summary`).

        Token streams are bit-identical to the offline ``submit()`` +
        ``run()`` oracle for the same request set, for every engine
        configuration (dense/paged, greedy/speculative, mesh, attn_pim) —
        gated in `benchmarks/engine_hotpath.py --arrivals`.  PR 6 semantics
        hold under live load: deadlines expire queued AND in-flight
        requests, `cancel()` works mid-stream, pool-pressure preemption
        requeues (the stream pauses, indices continue after re-admission,
        nothing is re-sent), and fault injection degrades the mixed wave
        onto the oracle path like any other poisoned step.

        Iteration exhaustion aborts in-flight requests honestly
        (``finished_reason="aborted"``, final events still delivered) —
        same contract as ``run()``.  Closing the generator early (a
        ``break``, ``close()``, or GC) does the same from its ``finally``:
        in-flight slots finish as "aborted" (results in ``self.results``;
        no events can be yielded during GeneratorExit), the page pool
        drains, queued requests stay queued, and the engine remains
        usable for a subsequent ``submit()`` + ``run()``.

        An exception propagating out of ``step()`` — `EngineCrashError`
        from the ``crash`` fault, `EngineStallError`, ... — is a
        (simulated) process death, NOT an early close: it re-raises with
        no abort cleanup and no journal finalization, so a journaled run
        recovers the in-flight requests via ``--resume`` instead of
        finding them durably marked "aborted".
        """
        arrivals = iter(arrivals)
        streamed: dict[int, int] = {}   # req_id -> tokens already yielded
        reported = len(self.results)    # results already turned into events
        stream_open = True
        completed = False
        crashed = False
        prev = self.stream_chunks
        self.stream_chunks = True
        gc.callbacks.append(self._on_gc)
        try:
            while True:
                if stream_open:
                    with phase("serve.arrivals", self.tracer):
                        try:
                            got = next(arrivals)
                        except StopIteration:
                            stream_open = False
                        else:
                            if got is None:
                                got = []
                            elif isinstance(got, ServeRequest):
                                got = [got]
                            for req in got:
                                self.submit(req)
                            self._arrived_this_step = len(got)
                if not stream_open and not (self.queue or self.active_slots):
                    completed = True
                    return
                if self.iteration >= max_iterations:
                    for s in list(self.active_slots):
                        self._finish_slot(s, "aborted")
                    yield from self._drain_events(streamed, reported)
                    completed = True
                    return
                self.step()
                # live slots first (mid-flight tokens), then finished
                # requests (their tail + the final event)
                for s in self.active_slots:
                    req = self.slot_req[s]
                    if req is None:
                        # cancelled by the consumer while an earlier slot's
                        # tokens were yielded: _drain_events finishes it
                        continue
                    done = (req.done if isinstance(req, _ResumedRequest)
                            else [])
                    full = list(done) + self.slot_tokens[s]
                    sent = streamed.get(req.req_id, 0)
                    for i in range(sent, len(full)):
                        yield TokenEvent(req.req_id, full[i], i,
                                         self.iteration)
                    streamed[req.req_id] = max(sent, len(full))
                new_reported = len(self.results)
                yield from self._drain_events(streamed, reported)
                reported = new_reported
        except GeneratorExit:
            raise                 # early close: the finally abort applies
        except BaseException:
            # EngineCrashError / EngineStallError / anything else escaping
            # step() is a (simulated) process death, not an early close:
            # re-raise with NO cleanup and NO journal finalization, so the
            # in-flight requests stay recoverable (journal "aborted"
            # finishes here would make --resume skip them forever).
            crashed = True
            raise
        finally:
            gc.callbacks.remove(self._on_gc)
            self.stream_chunks = prev
            if not completed and not crashed:
                # the caller broke out of / close()d the generator
                # mid-stream: finish the in-flight slots honestly
                # ("aborted", tokens-so-far) so the page pool drains and
                # the engine stays reusable for a later submit()+run().
                # No events can be yielded during GeneratorExit — the
                # aborted ServeResults land in self.results instead.
                # Queued requests stay queued, same contract as run().
                for s in list(self.active_slots):
                    self._finish_slot(s, "aborted")

    def _on_gc(self, stage: str, info: dict) -> None:
        """`gc.callbacks` hook that `serve()` installs: each collection is a
        ``gc`` span (generation as metadata) and its pause is added to the
        next `IterStats.gc_s`, wherever in the loop it fell."""
        if stage == "start":
            self._gc_span = phase("gc", self.tracer,
                                  generation=info["generation"]).__enter__()
        elif self._gc_span is not None:
            span, self._gc_span = self._gc_span, None
            span.__exit__(None, None, None)
            self._gc_s += time.perf_counter() - span.start

    def _drain_events(self, streamed: dict[int, int], reported: int):
        """Final-event tail for every result appended since `reported`:
        any not-yet-streamed tokens, then the completion event."""
        for res in self.results[reported:]:
            sent = streamed.pop(res.req_id, 0)
            for i in range(sent, len(res.tokens)):
                yield TokenEvent(res.req_id, res.tokens[i], i,
                                 self.iteration)
            yield TokenEvent(res.req_id, -1, len(res.tokens), self.iteration,
                             finished=True, reason=res.finished_reason,
                             result=res)

    def cancel(self, req_id: int) -> bool:
        """Cancel a queued or in-flight request: it finishes with
        ``finished_reason="cancelled"`` and its tokens-so-far, and its
        pages/reservations drain.  Returns False when no pending request
        carries `req_id` (already finished, or never submitted)."""
        for i, req in enumerate(self.queue):
            if req.req_id == req_id:
                self.queue.pop(i)
                self._journal_cancel(req_id)
                self._emit(req, [], "cancelled")
                return True
        for s in self.active_slots:
            if self.slot_req[s].req_id == req_id:
                self._journal_cancel(req_id)
                self._finish_slot(s, "cancelled")
                return True
        return False

    def _journal_cancel(self, req_id: int) -> None:
        if self.journal is not None:
            self.journal.append("cancel", req_id=req_id, it=self.iteration)

    # ----------------------------------------------------------- durability
    def _journal_commits(self) -> None:
        """End-of-step WAL flush: one commit record (delta tokens, total,
        remaining token budget, remaining deadline) per live slot that
        committed tokens this iteration.  Runs before `serve()` yields the
        step's TokenEvents, so a streamed token is always at least as
        durable as the journal's flush policy."""
        now = self._now()
        for s in self.active_slots:
            req = self.slot_req[s]
            done = req.done if isinstance(req, _ResumedRequest) else []
            full = list(done) + self.slot_tokens[s]
            prev = self._journal_done.get(req.req_id, 0)
            if len(full) <= prev:
                continue
            dl = getattr(req, "deadline_s", None)
            rem_dl = None
            if dl is not None:
                t0 = self._submit_t.get(req.req_id)
                rem_dl = dl if t0 is None else dl - (now - t0)
            self.journal.append(
                "commit", req_id=req.req_id, toks=full[prev:], n=len(full),
                rem=int(self.slot_budget[s]) - len(self.slot_tokens[s]),
                dl=rem_dl, it=self.iteration)
            self._journal_done[req.req_id] = len(full)

    def snapshot(self, path: str | None = None) -> dict:
        """Host-side logical state only — queue order, per-request
        ``(prompt, committed tokens, remaining token budget, remaining
        deadline)``, the admission counter — NEVER device arrays: the KV
        cache, block tables, and jit caches are all recomputable, because
        `restore()` re-admits unfinished work through the `_ResumedRequest`
        path and chunked prefill rebuilds the KV bit-identically.
        Unfinished work is listed in recovery order: in-flight slots
        (oldest admission first), then the queue.  Deadlines are stored as
        the REMAINING monotonic delta so a restart neither resets nor
        instantly expires them.  With `path`, also writes the snapshot
        atomically (see `journal.write_snapshot`)."""
        now = self._now()

        def rem_dl(req):
            dl = getattr(req, "deadline_s", None)
            if dl is None:
                return None
            t0 = self._submit_t.get(req.req_id)
            return dl if t0 is None else dl - (now - t0)

        def entry(req, emitted, rem):
            if isinstance(req, _ResumedRequest):
                prompt = req.prompt[:req.orig_prompt_len]
                plen = req.orig_prompt_len
                done = list(req.done) + list(emitted)
            else:
                prompt, plen = list(req.prompt), len(req.prompt)
                done = list(emitted)
            return {"req_id": req.req_id, "prompt": list(prompt),
                    "done": done, "max_new": int(rem),
                    "deadline_s": rem_dl(req), "orig_prompt_len": plen}

        requests = [entry(self.slot_req[s], self.slot_tokens[s],
                          int(self.slot_budget[s]) - len(self.slot_tokens[s]))
                    for _, s in sorted((self.slot_seq[s], s)
                                       for s in self.active_slots)]
        requests += [entry(req, [], req.max_new_tokens)
                     for req in self.queue]
        all_ids = ([r.req_id for r in self.results]
                   + [e["req_id"] for e in requests])
        state = {
            "papi_snapshot": SNAPSHOT_VERSION,
            "iteration": self.iteration,
            "admit_seq": self._admit_seq,
            "next_req_id": max(all_ids, default=-1) + 1,
            "requests": requests,
            "finished": [{"req_id": r.req_id, "reason": r.finished_reason,
                          "tokens": list(r.tokens)} for r in self.results],
        }
        if path is not None:
            write_snapshot(path, state)
            if self.tracer.enabled:
                self.tracer.emit("journal", self.iteration, op="snapshot",
                                 path=str(path), requests=len(requests))
        return state

    def restore(self, path) -> dict:
        """Re-admit every unfinished request recorded in the snapshot or
        journal at `path` into THIS (freshly constructed) engine, through
        the PR 6 `_ResumedRequest` path: ``prompt + committed tokens``
        re-chunks through prefill bit-identically, so each recovered
        stream continues exactly where the journal left off.  Finished
        requests (including torn-tail cases whose committed prefix already
        exhausted the budget or hit eos) are never re-admitted — finishes
        stay exactly-once.  Deadlines resume with their remaining budget.
        Returns a summary dict (resumed / finished / torn_bytes)."""
        state = recover(path, eos_token=self.eos_token)
        now = self._now()
        for r in state.requests:
            self.queue.append(_ResumedRequest(
                req_id=r.req_id, prompt=list(r.prompt) + list(r.done),
                max_new_tokens=int(r.max_new), deadline_s=r.deadline_s,
                done=list(r.done), orig_prompt_len=r.orig_prompt_len))
            # the deadline survives as a REMAINING monotonic delta: rebase
            # the submit stamp to now so _deadline_expired sees exactly
            # the budget that was left at snapshot/crash time
            self._submit_t[r.req_id] = now
            self.submit_iteration.setdefault(r.req_id, self.iteration)
            self._journal_done[r.req_id] = len(r.done)
            if self.journal is not None:
                self.journal.append(
                    "resume", req_id=r.req_id, prompt=list(r.prompt),
                    done=list(r.done), max_new=int(r.max_new),
                    dl=r.deadline_s, plen=r.orig_prompt_len)
        self._admit_seq = max(self._admit_seq, state.admit_seq)
        summary = {"resumed": len(state.requests),
                   "finished": len(state.finished),
                   "records": state.records,
                   "torn_bytes": state.torn_bytes,
                   "next_req_id": state.next_req_id}
        if self.tracer.enabled:
            self.tracer.emit("recover", self.iteration, path=str(path),
                             **summary)
        log.info("restored %d unfinished request(s) from %s (%d already "
                 "finished, %d torn byte(s) discarded)",
                 summary["resumed"], path, summary["finished"],
                 summary["torn_bytes"])
        return summary

    # ------------------------------------------------------------- internals
    def _cache_shardings(self, cfg: ModelConfig):
        if self.kv is not None:
            return paged_cache_shardings(
                cfg, self.max_slots, self.kv.alloc.num_pages + 1,
                self.kv.page_size, self.kv.max_blocks, self.rules, self.mesh)
        return cache_shardings(cfg, self.max_slots, self.capacity,
                               self.rules, self.mesh)

    def _slot_pos(self, s: int) -> int:
        """Device cache position of live slot s (tokens of KV written).  The
        first output token comes from prefill, so its KV is written by the
        NEXT decode step: pos = prompt + generated - 1."""
        return int(self.slot_prompt[s]) + len(self.slot_tokens[s]) - 1

    def _sync_tables(self) -> None:
        """Push the host block tables into the cache pytrees the jitted
        steps consume.  `BlockTables.device()` caches until a row mutates,
        so this is an identity check + dict store on the no-change path."""
        if self.kv is None:
            return
        tables = self.kv.tables.device()
        if self.cache["block_tables"] is not tables:
            self.cache = dict(self.cache)
            self.cache["block_tables"] = tables
            if self.draft_cache is not None:
                self.draft_cache = dict(self.draft_cache)
                self.draft_cache["block_tables"] = tables

    def _fetch(self, *arrays):
        """Single device->host sync round-trip (counted).  Sharded arrays
        gather here — still one round trip from the host's point of view.
        Runs as the ``engine.fetch`` span: the host waits on the device, and
        the wait is kept out of `IterStats.host_s`."""
        self.host_transfers += 1
        with phase("engine.fetch", self.tracer) as span:
            if self._sanitizer is not None:
                with self._sanitizer.allow_transfers():
                    # papilint: allow-transfer(the engine's single counted sync point)
                    got = jax.device_get(arrays)
            else:
                # papilint: allow-transfer(the engine's single counted sync point)
                got = jax.device_get(arrays)
        self._fetch_s += time.perf_counter() - span.start
        return got[0] if len(arrays) == 1 else got

    def _scope(self):
        """The mesh trace/dispatch scope: installs the logical->mesh rules
        so `shard()` constraints and the shard_map'd kernels resolve.  Every
        compiled entry point must be CALLED under it too (papi_linear and
        the attn hook read it at trace time, and tracing happens lazily on
        the first call of each (kind, tlp, variant) key)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return axis_rules(self.rules, self.mesh)

    def _attn_scope(self):
        """The decode-attention implementation for every compiled entry
        point: the Pallas flash-decode kernels under ``attn_pim=True`` (any
        TLP — plain decode, speculative verify windows, and chunked-prefill
        waves all hit the windowed kernel; the paged XLA page-gather never
        traces), the XLA softmax path otherwise.  Like `_scope`, tracing
        reads this at first call, so every jitted CALL must run under it."""
        return attn_impl("pim" if self.attn_pim else "xla")

    def _jit_key(self, kind: str, tlp: int) -> tuple:
        return (kind, tlp, self.scheduler.fc_assignment, self.pim_interpret,
                self.attn_pim)

    def _call(self, key: tuple, fn, *args,
              span: str | None = "engine.dispatch"):
        """Dispatch one compiled program: the bare asynchronous call, never
        blocked, inside the ``span`` phase (``engine.dispatch`` with the
        jit-cache key as metadata; None when the caller's own phase covers
        it).  Every `_get_*` getter returns its key alongside the
        executable for this label.  A call that grew the function's jit
        cache (a trace, then a compile or a persistent-cache load) counts
        in `IterStats.jit_misses`.  Device time per program comes from the
        profiler trace, under the program's name."""
        before = fn._cache_size()
        if span is None:
            out = fn(*args)
        else:
            with phase(span, self.tracer, key=format_program_key(key)):
                out = fn(*args)
        self._jit_misses += fn._cache_size() > before
        return out

    def _get_decode(self, which: str):
        """Legacy (unfused) per-call decode step."""
        tlp = 1 if which == "draft" else (self.spec_len if which == "verify" else 1)
        key = self._jit_key(which, tlp)
        if key not in self._decode_jit:
            cfg = self.draft_cfg if which == "draft" else self.cfg
            fn = partial(decode_step, cfg)
            self._decode_jit[key] = jax.jit(fn)
        return key, self._decode_jit[key]

    def _get_plain_fused(self):
        """Fused plain decode: decode_step + greedy in one device program, so
        the only host transfer is the [slots] token vector (plus the
        device-side finite-logits flag riding in the same fetch)."""
        key = self._jit_key("plain_fused", 1)
        if key not in self._decode_jit:
            cfg = self.cfg

            def papi_decode_step(params, cache, last, fault):
                logits, cache = decode_step(cfg, params, cache, last[:, None])
                logits = _inject_fault(logits, fault)
                bad = ~jnp.all(jnp.isfinite(logits))
                return greedy(logits[:, -1]), bad, cache

            self._decode_jit[key] = jax.jit(papi_decode_step)
        return key, self._decode_jit[key]

    def _get_spec_fused(self):
        """Fused speculative iteration: the k-step draft loop is a
        `jax.lax.scan`, the verify + accept-longest-prefix + cache rewind are
        vectorized device computation, and the host fetches a single
        (out, accepted, finished_eos) bundle."""
        key = self._jit_key("spec_fused", self.spec_len)
        if key not in self._decode_jit:
            cfg, dcfg = self.cfg, self.draft_cfg
            k, eos = self.spec_len, self.eos_token

            def spec_step(params, draft_params, cache, draft_cache, last,
                          fault):
                # 1) draft proposes autoregressively.  It runs k steps — the
                # extra step writes KV for the window's final token, keeping
                # the two caches in lockstep when the full window is accepted.
                def draft_body(carry, _):
                    dc, tok = carry
                    logits, dc = decode_step(dcfg, draft_params, dc,
                                             tok[:, None])
                    nxt = greedy(logits[:, -1])
                    return (dc, nxt), nxt

                (draft_cache, _), props = jax.lax.scan(
                    draft_body, (draft_cache, last), None, length=k)
                # window rows: [last, props[0], ..., props[k-2]]  -> [slots, k]
                window = jnp.concatenate([last[None], props[:-1]], axis=0).T

                # 2) target verifies the window in ONE decode step (TLP = k)
                logits, cache = decode_step(cfg, params, cache, window)
                logits = _inject_fault(logits, fault)
                bad = ~jnp.all(jnp.isfinite(logits))
                target = greedy(logits)                           # [slots, k]

                # 3) accept longest matching prefix, rewind target cache to
                # the accepted position, resync the draft cache to it
                out, accepted = accept_speculative(window, target)
                cache = dict(cache)
                cache["pos"] = cache["pos"] - (k - accepted)
                draft_cache = dict(draft_cache)
                draft_cache["pos"] = jnp.minimum(draft_cache["pos"],
                                                 cache["pos"])
                in_window = jnp.arange(k)[None, :] < accepted[:, None]
                finished_eos = jnp.any((out == eos) & in_window, axis=1)
                return out, accepted, finished_eos, bad, cache, draft_cache

            self._decode_jit[key] = jax.jit(spec_step)
        return key, self._decode_jit[key]

    def _get_oracle(self, which: str):
        """Degraded-mode decode step: the tested XLA-attention / plain-FC
        oracle path, compiled once per model and NEVER fault-injected.  Its
        jit key is independent of the scheduler's fc assignment — it must
        always be the same executable the correctness suite validates."""
        # papilint: disable=PL003 (oracle pins attn/fc at dispatch; one executable by contract)
        key = ("oracle", which)
        if key not in self._decode_jit:
            cfg = self.draft_cfg if which == "draft" else self.cfg
            self._decode_jit[key] = jax.jit(partial(decode_step, cfg))
        return key, self._decode_jit[key]

    def _fault_code(self):
        """Per-iteration logits-fault code, passed as a TRACED int32 scalar
        so flipping it never retraces the fused programs.  Under
        ``fused=False`` the engine already runs the oracle path, so logits
        faults only apply to the fused programs."""
        if self.faults is None or not self.fused:
            return jnp.asarray(FAULT_NONE, jnp.int32)
        code = self.faults.logits_fault(self.iteration)
        if code != FAULT_NONE and self.tracer.enabled:
            self.tracer.emit("fault", self.iteration,
                             fault="nan" if code == FAULT_NAN else "inf")
        return jnp.asarray(code, jnp.int32)

    def _degraded_step(self):
        """Re-run a poisoned iteration on the oracle path: XLA attention,
        plain-PU linear, speculation clamped to a single plain decode step.
        Runs inside `_decode_all`'s ambient scopes — `attn_impl` and
        `fc_variant` are save/restore context managers, so nesting the
        oracle contexts here is safe.  When speculating, the draft cache
        advances one plain step too, keeping both KVs in lockstep for the
        next (healthy) speculative iteration."""
        self.degraded_steps += 1
        self._degraded_this_step = True
        if self.tracer.enabled:
            self.tracer.emit("degraded", self.iteration, mode="step")
        log.warning("non-finite logits at iteration %d: re-running the "
                    "step on the oracle path", self.iteration)
        last = jnp.asarray(self.slot_last)
        with attn_impl("xla"), fc_variant("pu"):
            okey, ofn = self._get_oracle("main")
            logits, self.cache = self._call(
                okey, ofn, self.params, self.cache, last[:, None])
            if self.spec_len > 1 and self.draft_cfg is not None:
                dkey, dfn = self._get_oracle("draft")
                _, self.draft_cache = self._call(
                    dkey, dfn, self.draft_params, self.draft_cache,
                    last[:, None])
            # papilint: allow-transfer(degraded re-run commits its token)
            nxt_h = self._fetch(greedy(logits[:, -1]))
        return (np.asarray(nxt_h)[:, None].astype(np.int32),
                np.ones(self.max_slots), None)

    def _get_prefill(self, which: str):
        cfg = self.draft_cfg if which == "draft" else self.cfg
        # admission usually runs outside any fc_variant context ("pu"), but
        # papi_linear reads the AMBIENT variant at trace time — key on it so
        # a caller-wrapped engine never reuses a stale executable.  The attn
        # impl is keyed too: chunk waves trace the windowed Pallas kernel
        # under attn_pim.
        key = (which, current_fc_variant(), current_fc_interpret(),
               self.attn_pim)
        if key not in self._prefill_jit:
            fn = prefill_to_pages if self.kv is not None else prefill_to_slots
            self._prefill_jit[key] = jax.jit(
                _named("papi_admit_prefill", partial(fn, cfg)))
        return key, self._prefill_jit[key]

    def _get_chunk(self, which: str):
        """Chunked-prefill continuation step (`models.prefill_chunk`): one
        fixed [max_slots, prefill_len] window through the decode path at
        each slot's running prompt offset.  Layout-agnostic — the cache
        pytree carries the block tables when paged."""
        cfg = self.draft_cfg if which == "draft" else self.cfg
        key = ("chunk_" + which, current_fc_variant(),
               current_fc_interpret(), self.attn_pim)
        if key not in self._prefill_jit:
            self._prefill_jit[key] = jax.jit(
                _named("papi_prefill_chunk", partial(prefill_chunk, cfg)))
        return key, self._prefill_jit[key]

    # --------------------------------------- continuous batching (serve())
    def _get_wave(self, which: str):
        """The serve loop's mixed prefill/decode wave (`models.mixed_step`):
        prefill chunks and single-token decodes share one fixed-shape
        [max_slots, prefill_len] program.  The main wave folds fault
        injection + the finite-logits guard + the greedy argmax in with the
        logits (one fetchable (tokens, bad) bundle); the draft wave only
        advances the draft KV, nothing is fetched from it."""
        cfg = self.draft_cfg if which == "draft" else self.cfg
        key = ("wave_" + which, current_fc_variant(),
               current_fc_interpret(), self.attn_pim)
        if key not in self._prefill_jit:
            if which == "main":
                def papi_mixed_wave(params, cache, toks, lens, pin_mask,
                                    pin_pos, fault):
                    logits, cache = mixed_step(cfg, params, cache, toks,
                                               lens, pin_mask, pin_pos)
                    logits = _inject_fault(logits, fault)
                    bad = ~jnp.all(jnp.isfinite(logits))
                    return greedy(logits), bad, cache
            else:
                def papi_mixed_wave(params, cache, toks, lens, pin_mask,
                                    pin_pos):
                    _, cache = mixed_step(cfg, params, cache, toks, lens,
                                          pin_mask, pin_pos)
                    return cache
            self._prefill_jit[key] = jax.jit(papi_mixed_wave)
        return key, self._prefill_jit[key]

    def _get_oracle_wave(self):
        """Degraded-mode wave: the XLA-attention / plain-FC oracle, never
        fault-injected, keyed independently of the scheduler's assignment
        (same contract as `_get_oracle`)."""
        # papilint: disable=PL003 (oracle pins attn/fc at dispatch; one executable by contract)
        key = ("oracle_wave",)
        if key not in self._prefill_jit:
            cfg = self.cfg

            def wave(params, cache, toks, lens, pin_mask, pin_pos):
                logits, cache = mixed_step(cfg, params, cache, toks, lens,
                                           pin_mask, pin_pos)
                return greedy(logits), cache

            self._prefill_jit[key] = jax.jit(wave)
        return key, self._prefill_jit[key]

    def _get_park(self):
        """Parks inactive slots' cache positions at 1 (see `_park`): one
        fixed-shape [max_slots] mask program serves any inactive set."""
        # papilint: disable=PL003 (a where over positions: no FC or attention to trace)
        key = ("park",)
        if key not in self._decode_jit:
            def papi_park_slots(mask, pos):
                return jnp.where(mask, jnp.ones((), jnp.int32), pos)

            self._decode_jit[key] = jax.jit(papi_park_slots)
        return key, self._decode_jit[key]

    def _prefilling_slots(self) -> list[int]:
        """Slots mid-chunked-prefill (serve() only: offline admission always
        completes a prompt's waves before returning)."""
        return [s for s in self.active_slots
                if int(self.slot_offset[s]) < int(self.slot_prompt[s])]

    def _tokens_written(self, s: int) -> int:
        """KV tokens live slot `s` has actually committed: the chunk
        frontier while mid-prefill, the decode position after."""
        off = int(self.slot_offset[s])
        return off if off < int(self.slot_prompt[s]) else self._slot_pos(s)

    def _wave_rows(self, prefilling: list[int]):
        """Build one chunk wave over the mid-prefill slots: each advances by
        one (ragged-tail-masked) window from its running offset.  Returns
        the host-side row arrays plus the slots whose prompt this wave
        completes (their logits row is the request's first output token).
        ``pin`` re-anchors each prefilling row's cache position to the
        host-tracked offset — mid-prefill slots ride every OTHER dispatched
        program as masked garbage rows whose device `pos` drifts."""
        ctoks = np.zeros((self.max_slots, self.prefill_len), np.int32)
        clens = np.zeros(self.max_slots, np.int32)
        pin = np.zeros(self.max_slots, bool)
        pin_pos = np.zeros(self.max_slots, np.int32)
        finals: list[int] = []
        for s in prefilling:
            req = self.slot_req[s]
            off, plen = int(self.slot_offset[s]), int(self.slot_prompt[s])
            n = min(plen - off, self.prefill_len)
            ctoks[s, :n] = req.prompt[off:off + n]
            clens[s] = n
            pin[s] = True
            pin_pos[s] = off
            if off + n == plen:
                finals.append(s)
        return ctoks, clens, pin, pin_pos, finals

    def _finalize_first_tokens(self, finals: list[int],
                               nxt_h: np.ndarray) -> None:
        """A wave just completed these slots' prompts: commit each first
        output token (same instant-finish semantics as offline admission —
        <eos> or a 1-token budget frees the slot for the next iteration's
        admission)."""
        for s in finals:
            req = self.slot_req[s]
            tok = int(nxt_h[s])
            self._note_first_token(req.req_id)
            self.slot_tokens[s] = [tok]
            self.slot_last[s] = tok
            if tok == self.eos_token or self.slot_budget[s] <= 1:
                reason = "eos" if tok == self.eos_token else "length"
                self._emit(req, [tok], reason, slot=s)
                self.slot_req[s] = None
                self.slot_tokens[s] = []
                self.slot_last[s] = 0
                if self.kv is not None:
                    self.kv.release(s)

    def _ensure_wave_pages(self, prefilling: list[int],
                           clens: np.ndarray) -> None:
        """Map the pages this wave's chunks write (serve() admitted with
        only chunk 0 mapped).  Cannot fail: the admission reservation
        covers the full prompt + budget + window."""
        if self.kv is None:
            return
        for s in prefilling:
            self.kv.ensure(s, int(self.slot_offset[s]) + int(clens[s]))

    def _chunk_wave(self, prefilling: list[int]) -> None:
        """Speculative serve iterations run the prefill chunks as their own
        wave (prefill rows only) and let the decodes ride the fused
        speculative program right after — two dispatches, still zero
        prefill stall.  Runs under the ambient ("pu") FC variant exactly
        like offline admission chunks, so first tokens are bit-identical to
        the offline oracle."""
        ctoks, clens, pin, pin_pos, finals = self._wave_rows(prefilling)
        self._ensure_wave_pages(prefilling, clens)
        self._sync_tables()
        ct, cl = jnp.asarray(ctoks), jnp.asarray(clens)
        pm, pp = jnp.asarray(pin), jnp.asarray(pin_pos)
        with self._scope(), self._attn_scope():
            wkey, wfn = self._get_wave("main")
            nxt, bad, cache2 = self._call(
                wkey, wfn, self.params, self.cache, ct, cl, pm, pp,
                jnp.asarray(FAULT_NONE, jnp.int32))
            self.cache = cache2
            if self.draft_cfg is not None:
                dkey, dfn = self._get_wave("draft")
                self.draft_cache = self._call(
                    dkey, dfn, self.draft_params, self.draft_cache,
                    ct, cl, pm, pp)
        for s in prefilling:
            self.slot_offset[s] += int(clens[s])
        if finals:
            # papilint: allow-transfer(first tokens of finishing chunks)
            nxt_h, _ = self._fetch(nxt, bad)
            self._finalize_first_tokens(finals, np.asarray(nxt_h))

    def _mixed_wave_iteration(self, prefilling: list[int],
                              decoding: list[int]):
        """The tentpole TLP=1 serve iteration: ongoing decodes (chunks of
        length 1 holding each slot's last token) and new requests' prefill
        chunk waves run in ONE fused device program — no prefill stall, one
        dispatch + one fetch per iteration.  Returns the `_decode_all`-shaped
        (out, accepted, finished) bundle for the decoding slots."""
        with phase("engine.prepare", self.tracer):
            ctoks, clens, pin, pin_pos, finals = self._wave_rows(prefilling)
            chunk_lens = clens.copy()    # prefill rows only, for the draft
            for s in decoding:
                ctoks[s, 0] = self.slot_last[s]
                clens[s] = 1
            self._ensure_wave_pages(prefilling, chunk_lens)
            if self.kv is not None:
                for s in decoding:
                    self.kv.ensure(s, self._slot_pos(s) + 1)
            self._sync_tables()
            ct, cl = jnp.asarray(ctoks), jnp.asarray(clens)
            pm, pp = jnp.asarray(pin), jnp.asarray(pin_pos)
            fault = self._fault_code()
        variant = self.scheduler.fc_assignment
        with self._scope(), \
                fc_variant(variant, interpret=self.pim_interpret), \
                self._attn_scope():
            wkey, wfn = self._get_wave("main")
            nxt, bad, cache2 = self._call(
                wkey, wfn, self.params, self.cache, ct, cl, pm, pp, fault)
            if self.draft_cfg is not None and prefilling:
                # the draft's KV covers the prompt positions (chunk rows
                # only — the TLP=1 decode path never advances the draft)
                dkey, dfn = self._get_wave("draft")
                self.draft_cache = self._call(
                    dkey, dfn, self.draft_params, self.draft_cache, ct,
                    jnp.asarray(chunk_lens), pm, pp)
            # papilint: allow-transfer(the wave's one token+fault fetch)
            nxt_h, bad_h = self._fetch(nxt, bad)
            if bad_h:
                # non-finite logits: drop the poisoned wave (cache2 never
                # assigned) and re-run the SAME wave on the oracle path
                out_h = self._degraded_wave(ct, cl, pm, pp)
            else:
                self.cache = cache2
                out_h = np.asarray(nxt_h)
        for s in prefilling:
            self.slot_offset[s] += int(chunk_lens[s])
        self._finalize_first_tokens(finals, out_h)
        return (out_h[:, None].astype(np.int32), np.ones(self.max_slots),
                None)

    def _degraded_wave(self, ct, cl, pm, pp) -> np.ndarray:
        """Oracle re-run of a poisoned mixed wave (the wave twin of
        `_degraded_step`): XLA attention, plain-PU FC, never injected."""
        self.degraded_steps += 1
        self._degraded_this_step = True
        if self.tracer.enabled:
            self.tracer.emit("degraded", self.iteration, mode="wave")
        log.warning("non-finite logits at iteration %d: re-running the "
                    "mixed wave on the oracle path", self.iteration)
        with attn_impl("xla"), fc_variant("pu"):
            okey, ofn = self._get_oracle_wave()
            nxt, self.cache = self._call(
                okey, ofn, self.params, self.cache, ct, cl, pm, pp)
            # papilint: allow-transfer(oracle wave re-run commits tokens)
            return np.asarray(self._fetch(nxt))

    def _admit(self) -> int:
        """Mixed continuous batching: fill free slots from the queue, one
        compiled `prefill_to_slots` call per admission wave (fixed-shape
        batch padded to max_slots, so the call compiles exactly once).  A
        request that finishes instantly at admission (first token is <eos>,
        or a 1-token budget) frees its slot for the NEXT wave, so the queue
        keeps draining within this step exactly like the seed's slot-reuse
        loop did."""
        self._deferred_head = None
        if (self.queue and self.faults is not None
                and self.faults.admission_blocked(self.iteration)):
            # injected allocator admission failure: the whole wave defers
            # (queue order kept) and the deferral-age / preemption /
            # watchdog machinery sees it like genuine pool pressure
            self._deferred_head = self.queue[0].req_id
            if self.tracer.enabled:
                self.tracer.emit("fault", self.iteration, fault="admit",
                                 req_id=self._deferred_head)
            return 0
        admitted = 0
        while True:
            wave_admitted, instant_finish = self._admit_wave()
            admitted += wave_admitted
            if not (instant_finish and self.queue):
                return admitted

    def _reject(self, req: ServeRequest) -> None:
        self._emit(req, [], "rejected")

    # ------------------------------------------------- failure-model helpers
    def _now(self) -> float:
        """Deadline clock (monotonic); tests monkeypatch this to expire
        deadlines without sleeping."""
        return time.monotonic()

    def _note_first_token(self, req_id: int) -> None:
        """TTFT stamp: the request's first output token just materialized.
        setdefault — a preempted request's re-admission produces a
        CONTINUATION token through the same code path, and the original
        first-token stamp must survive it."""
        if req_id not in self._first_tok_t:
            self._first_tok_t[req_id] = self._now()
            self.first_token_iteration.setdefault(req_id, self.iteration)
            if self.tracer.enabled:
                self.tracer.emit("first_token", self.iteration,
                                 req_id=req_id)

    def _latency_fields(self, req_id: int, n_tokens: int) -> dict:
        """Per-request latency bundle for the ServeResult (see
        serving/metrics.py for the metric definitions).  Missing phases
        (never admitted / never produced a token) yield None, not 0 — the
        summary excludes them instead of skewing percentiles."""
        now = self._now()
        t0, i0 = self._submit_t.get(req_id), self.submit_iteration.get(req_id)
        ta, ia = self._admit_t.get(req_id), self.admit_iteration.get(req_id)
        tf = self._first_tok_t.get(req_id)
        i_f = self.first_token_iteration.get(req_id)
        return dict(
            queue_delay_s=(ta - t0) if (t0 is not None and ta is not None)
            else None,
            ttft_s=(tf - t0) if (t0 is not None and tf is not None) else None,
            # no inter-token gap exists below 2 tokens: None (excluded from
            # the summary, which counts contributors per metric), not a
            # fake 0.0 dragging the percentiles down
            tpot_s=(((now - tf) / (n_tokens - 1)) if n_tokens > 1 else None)
            if tf is not None else None,
            queue_delay_iters=(ia - i0)
            if (i0 is not None and ia is not None) else None,
            ttft_iters=(i_f - i0)
            if (i0 is not None and i_f is not None) else None,
        )

    def _emit(self, req, tokens: Sequence[int], reason: str,
              slot: int | None = None) -> None:
        """Append the caller-visible result for `req`.  A preempted request
        re-entered admission as a `_ResumedRequest` whose prompt carries its
        own earlier output — reassemble the original stream here."""
        if isinstance(req, _ResumedRequest):
            toks, plen = req.done + list(tokens), req.orig_prompt_len
        else:
            toks, plen = list(tokens), len(req.prompt)
        if self.journal is not None:
            # WAL discipline: the finish record (carrying the tail since
            # the last commit) goes durable BEFORE the result is
            # externalized, so a durable consumer sees finishes
            # exactly-once across a crash
            prev = self._journal_done.pop(req.req_id, 0)
            self.journal.append("finish", req_id=req.req_id, reason=reason,
                                toks=toks[prev:], n=len(toks),
                                it=self.iteration)
        self.results.append(ServeResult(
            req.req_id, toks, plen, self.iteration, reason,
            **self._latency_fields(req.req_id, len(toks))))
        if self.tracer.enabled:
            self.tracer.emit("finish", self.iteration, req_id=req.req_id,
                             reason=reason, tokens=len(toks), slot=slot)
        if reason not in ("eos", "length"):
            # unhappy finishes (timeout / cancelled / rejected / aborted)
            # are operational signals, not errors — INFO
            log.info("request %d finished: %s (%d tokens)",
                     req.req_id, reason, len(toks))

    def _finish_slot(self, s: int, reason: str) -> None:
        """Finish live slot `s` outside the normal eos/length path (timeout,
        cancel, abort): emit tokens-so-far and drain the slot's pages."""
        self._emit(self.slot_req[s], self.slot_tokens[s], reason, slot=s)
        self.slot_req[s] = None
        self.slot_tokens[s] = []
        self.slot_last[s] = 0
        if self.kv is not None:
            self.kv.release(s)

    def _deadline_expired(self, req) -> bool:
        dl = getattr(req, "deadline_s", None)
        if dl is None:
            return False
        t0 = self._submit_t.get(req.req_id)
        return t0 is not None and self._now() - t0 > dl

    def _expire_deadlines(self) -> None:
        still_queued = [r for r in self.queue if not self._deadline_expired(r)]
        if len(still_queued) != len(self.queue):
            for req in self.queue:
                if self._deadline_expired(req):
                    self._emit(req, [], "timeout")
            self.queue = still_queued
        for s in self.active_slots:
            if self._deadline_expired(self.slot_req[s]):
                self._finish_slot(s, "timeout")

    def _should_preempt(self) -> bool:
        """Pool-pressure trigger: the head has deferred `preempt_after`
        consecutive iterations, or the pool occupancy crossed
        `preempt_watermark` (fraction of usable pages mapped) while a
        deferral is pending.  Dense admission never defers, so preemption
        is a paged-layout mechanism."""
        if self.kv is None or self._defer_age < 1:
            return False
        if self.preempt_after is not None and (
                self._defer_age >= self.preempt_after):
            return True
        if self.preempt_watermark is not None:
            alloc = self.kv.alloc
            return alloc.mapped_count >= self.preempt_watermark * alloc.num_pages
        return False

    def _preempt_one(self) -> bool:
        """Preempt the YOUNGEST in-flight request (highest admission
        sequence number): release its pages and requeue it at the back as
        `prompt + tokens-so-far`, which chunked admission recomputes
        bit-identically.  The oldest in-flight request is never preempted
        — it always runs to completion, so the pool always drains toward
        the deferring head and forward progress is guaranteed (with a
        single in-flight request there is nothing younger, so the head
        simply waits for it to finish)."""
        live = sorted((self.slot_seq[s], s) for s in self.active_slots)
        if len(live) < 2:
            return False
        victim = live[-1][1]
        req = self.slot_req[victim]
        emitted = self.slot_tokens[victim]
        if isinstance(req, _ResumedRequest):
            done = req.done + list(emitted)
            base_prompt = req.prompt[:req.orig_prompt_len]
            plen = req.orig_prompt_len
        else:
            done = list(emitted)
            base_prompt = list(req.prompt)
            plen = len(req.prompt)
        self.queue.append(_ResumedRequest(
            req_id=req.req_id,
            prompt=base_prompt + done,
            max_new_tokens=int(self.slot_budget[victim]) - len(emitted),
            deadline_s=getattr(req, "deadline_s", None),
            done=done,
            orig_prompt_len=plen,
        ))
        self.slot_req[victim] = None
        self.slot_tokens[victim] = []
        self.slot_last[victim] = 0
        if self.kv is not None:
            self.kv.release(victim)
        self.preemptions += 1
        self.preempted_ids.add(req.req_id)
        if self.journal is not None:
            self.journal.append("preempt", req_id=req.req_id,
                                done=len(done), it=self.iteration)
        if self.tracer.enabled:
            self.tracer.emit("preempt", self.iteration, req_id=req.req_id,
                             slot=victim, done=len(done))
        log.info("preempted request %d from slot %d (%d tokens done, "
                 "deferral age %d)", req.req_id, victim, len(done),
                 self._defer_age)
        return True

    def _snapshot(self) -> dict:
        """Diagnostic state bundle carried by the structured errors."""
        snap = {
            "iteration": self.iteration,
            "queue": [r.req_id for r in self.queue],
            "deferred_head": self._defer_head,
            "deferral_age": self._defer_age,
            "active": {s: self.slot_req[s].req_id
                       for s in self.active_slots},
            "slot_budget": {s: int(self.slot_budget[s])
                            for s in self.active_slots},
            "preemptions": self.preemptions,
            "degraded_steps": self.degraded_steps,
            "stalled_iterations": self._stalled,
        }
        if self.kv is not None:
            snap["pool"] = self.kv.alloc.snapshot()
        return snap

    def _watchdog(self, progress: bool) -> None:
        if progress:
            self._stalled = 0
            return
        self._stalled += 1
        if (self.stall_limit is not None
                and (self.queue or self.active_slots)
                and self._stalled >= self.stall_limit):
            snap = self._snapshot()
            # the snapshot rides the trace too, so a post-mortem does not
            # depend on the exception propagating to something that logs it
            if self.tracer.enabled:
                self.tracer.emit("stall", self.iteration, snapshot=snap)
            log.error("engine stalled for %d iterations at iteration %d "
                      "(queue=%s)", self._stalled, self.iteration,
                      snap["queue"])
            raise EngineStallError(
                f"engine made no progress for {self._stalled} consecutive "
                f"iterations at iteration {self.iteration} "
                f"(queue={snap['queue']}, deferral_age={self._defer_age}, "
                f"pool={snap.get('pool')})", snap)

    def _check_invariants(self) -> None:
        if not (self.debug_invariants and self.kv is not None):
            return
        try:
            self.kv.alloc.check()
        except AssertionError as err:
            raise AllocatorInvariantError(
                f"page-pool invariant violated at iteration "
                f"{self.iteration}: {err}", self._snapshot()) from err

    def _mark_admitted(self, slot: int, req) -> None:
        """Admission-order bookkeeping: the preemption victim policy sorts
        on `slot_seq`, and the first-admission iteration feeds the
        admission-delay numbers the --pressure benchmark gates."""
        self._admit_seq += 1
        self.slot_seq[slot] = self._admit_seq
        self.admit_iteration.setdefault(req.req_id, self.iteration)
        self._admit_t.setdefault(req.req_id, self._now())
        if self.journal is not None:
            # the admission-CLAMPED budget: re-admission after recovery
            # clamps the same way preemption does, so replay must see the
            # effective value, not the caller's max_new_tokens
            self.journal.append("admit", req_id=req.req_id, slot=slot,
                                budget=int(self.slot_budget[slot]),
                                it=self.iteration)
        if self.tracer.enabled:
            self.tracer.emit("admit", self.iteration, req_id=req.req_id,
                             slot=slot, prompt_len=len(req.prompt))

    def _admit_wave(self) -> tuple[int, bool]:
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        batch_rows: list[tuple[int, ServeRequest]] = []
        window = max(self.spec_len, 1)
        while self.queue and free:
            req = self.queue[0]
            p = len(req.prompt)        # FULL prompt — never truncated
            if p > self.prefill_len and not self._can_chunk:
                # SSM/hybrid state cannot mask the garbage tail of a chunk
                # window, so stateful families stay single-window; reject
                # honestly instead of silently dropping the prompt head
                self.queue.pop(0)
                self._reject(req)
                continue
            if self.kv is not None:
                # page-budgeted admission: a request enters iff pages for
                # the FULL prompt + max_new_tokens + a speculative window
                # are available.  The prompt's pages are mapped up front
                # (every chunk scatters straight onto them); the rest of
                # the budget is reserved and mapped lazily.  Per-request
                # length is bounded by the POOL, not a per-slot slab.
                cap = self.kv.max_context - p - window
                if cap < 1:
                    self.queue.pop(0)
                    self._reject(req)
                    continue
                want = max(1, min(req.max_new_tokens, cap))
                if not self.kv.can_admit(p + want + window):
                    # pool busy — defer (the queue keeps order) instead of
                    # rejecting.  The deferral is noted so step() can age
                    # it and trigger pool-pressure preemption; absent that,
                    # the reservation math still guarantees this clears
                    # once running requests finish.
                    self._deferred_head = req.req_id
                    break
                self.queue.pop(0)
                slot = free.pop(0)
                # serve() maps only chunk 0's pages up front and lets each
                # later wave map its own chunk (admission under LIVE
                # pressure: the full budget is still reserved — ensure()
                # cannot fail — but the pool watermark follows the prefill
                # frontier instead of spiking to the whole prompt at once)
                initial = (min(p, self.prefill_len) if self.stream_chunks
                           else p)
                self.kv.admit(slot, p + want + window, initial)
                self.slot_budget[slot] = want
                self._mark_admitted(slot, req)
                batch_rows.append((slot, req))
                continue
            self.queue.pop(0)
            # never let a request outgrow its slot's KV capacity: the budget
            # reserves a full speculative window past the last new token.
            # A prompt the slab cannot hold at all is rejected — honestly,
            # not truncated.
            budget = self.capacity - p - window
            if budget < 1:
                # cannot emit even one token without overflowing the slot
                self._reject(req)
                continue
            slot = free.pop(0)
            self.slot_budget[slot] = max(1, min(req.max_new_tokens, budget))
            self._mark_admitted(slot, req)
            batch_rows.append((slot, req))
        if not batch_rows:
            return 0, False
        with phase("engine.admit.prefill", self.tracer, rows=len(batch_rows)):
            return self._prefill_admitted(batch_rows)

    def _prefill_admitted(self, batch_rows) -> tuple[int, bool]:
        """Prefill the requests `_admit_wave` just placed in slots and
        commit their first tokens (the ``engine.admit.prefill`` span)."""
        # ---- chunk 0: the compiled fixed-shape prefill (positions 0..P-1)
        tokens = np.zeros((self.max_slots, self.prefill_len), np.int32)
        lens = np.ones(self.max_slots, np.int32)
        src = np.full(self.max_slots, -1, np.int32)
        for row, (slot, req) in enumerate(batch_rows):
            p0 = min(len(req.prompt), self.prefill_len)
            tokens[row, :p0] = req.prompt[:p0]
            lens[row] = p0
            src[slot] = row
            self.slot_prompt[slot] = len(req.prompt)
        batch = {"tokens": jnp.asarray(tokens),
                 "prompt_lens": jnp.asarray(lens)}
        src_dev = jnp.asarray(src)
        self._sync_tables()   # paged: admitted rows just mapped their pages
        with self._scope(), self._attn_scope():
            pkey, pfn = self._get_prefill("main")
            first, self.cache = self._call(
                pkey, pfn, self.params, batch, self.cache, src_dev)
            if self.draft_cfg is not None:
                dkey, dfn = self._get_prefill("draft")
                _, self.draft_cache = self._call(
                    dkey, dfn, self.draft_params, batch, self.draft_cache,
                    src_dev)
        admitted = 0
        if self.stream_chunks:
            # ---- continuous batching: a prompt longer than the window does
            # NOT stall this admission — the slot enters mid-prefill
            # (slot_offset < slot_prompt) and step() advances it one chunk
            # wave per iteration, MIXED with the ongoing decodes, until its
            # final chunk produces the first token.  Short prompts finalize
            # right here exactly like offline admission.
            long_rows = [(slot, req) for slot, req in batch_rows
                         if len(req.prompt) > self.prefill_len]
            for slot, req in long_rows:
                self.slot_req[slot] = req
                self.slot_tokens[slot] = []
                self.slot_offset[slot] = self.prefill_len
                admitted += 1              # counts toward RLP
            batch_rows = [(slot, req) for slot, req in batch_rows
                          if len(req.prompt) <= self.prefill_len]
            if not batch_rows:
                return admitted, False
            # papilint: allow-transfer(admission wave's first tokens)
            first_h = np.array(self._fetch(first))
        else:
            # ---- chunks 1..: prompts longer than the window continue
            # through the fixed-shape chunk step at their running offsets.
            # Every wave advances each pending slot by one
            # (ragged-tail-masked) window; a slot's first output token comes
            # from its FINAL chunk's logits.  Nothing host-side depends on a
            # wave's result (tokens come from req.prompt), so all waves
            # dispatch back-to-back and the whole admission costs ONE
            # device->host sync at the end.
            pending = {slot: req for slot, req in batch_rows
                       if len(req.prompt) > self.prefill_len}
            offs = {slot: self.prefill_len for slot in pending}
            wave_finals: list[tuple[Any, list[int]]] = []
            while pending:
                ctoks = np.zeros((self.max_slots, self.prefill_len), np.int32)
                clens = np.zeros(self.max_slots, np.int32)
                final: list[int] = []
                for slot, req in list(pending.items()):
                    n = min(len(req.prompt) - offs[slot], self.prefill_len)
                    ctoks[slot, :n] = req.prompt[offs[slot]:offs[slot] + n]
                    clens[slot] = n
                    offs[slot] += n
                    if offs[slot] == len(req.prompt):
                        final.append(slot)
                        del pending[slot]
                ct, cl = jnp.asarray(ctoks), jnp.asarray(clens)
                with self._scope(), self._attn_scope():
                    ckey, cfn = self._get_chunk("main")
                    nxt, self.cache = self._call(
                        ckey, cfn, self.params, self.cache, ct, cl)
                    if self.draft_cfg is not None:
                        # the draft's KV must cover the same prompt positions
                        dkey, dfn = self._get_chunk("draft")
                        _, self.draft_cache = self._call(
                            dkey, dfn, self.draft_params, self.draft_cache,
                            ct, cl)
                if final:
                    wave_finals.append((nxt, final))
            # papilint: allow-transfer(one batched sync for all waves)
            got = self._fetch(first, *(nxt for nxt, _ in wave_finals))
            if wave_finals:
                first_h = np.array(got[0])
                for (_, final), nxt_h in zip(wave_finals, got[1:]):
                    for slot in final:
                        first_h[slot] = int(nxt_h[slot])
            else:
                first_h = np.array(got)

        instant_finish = False
        for slot, req in batch_rows:
            self.slot_offset[slot] = len(req.prompt)
            tok = int(first_h[slot])
            self._note_first_token(req.req_id)
            self.slot_tokens[slot] = [tok]
            self.slot_last[slot] = tok
            # prefill already produced the first output token
            if tok == self.eos_token or self.slot_budget[slot] <= 1:
                reason = "eos" if tok == self.eos_token else "length"
                self._emit(req, [tok], reason, slot=slot)
                self.slot_last[slot] = 0   # slot stays available
                if self.kv is not None:
                    self.kv.release(slot)
                instant_finish = True
            else:
                self.slot_req[slot] = req
                admitted += 1              # counts toward RLP
        return admitted, instant_finish

    def _decode_all(self, decoding: list[int]
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
        """One decoding iteration for the `decoding` slots.  Returns (new
        token matrix [slots, <=tlp], accepted counts [slots], eos-finished
        mask|None)."""
        variant = self.scheduler.fc_assignment
        tlp = self.spec_len
        plain = tlp <= 1 or self.draft_cfg is None
        with phase("engine.prepare", self.tracer):
            if self.kv is not None:
                # map pages for the KV this iteration writes (positions
                # pos..pos+tlp-1).  Cannot fail: the admission reservation
                # covers prompt + max_new + window, and coverage never
                # exceeds it before the request finishes.
                for s in decoding:
                    self.kv.ensure(s, self._slot_pos(s) + (1 if plain
                                                           else tlp))
                self._sync_tables()
            if plain:
                last = jnp.asarray(self.slot_last)
                fault = self._fault_code() if self.fused else None
        with self._scope(), \
                fc_variant(variant, interpret=self.pim_interpret), \
                self._attn_scope():
            if plain:
                if self.fused:
                    fkey, ffn = self._get_plain_fused()
                    nxt, bad, cache2 = self._call(
                        fkey, ffn, self.params, self.cache, last, fault)
                    # papilint: allow-transfer(the iteration's one fetch)
                    nxt_h, bad_h = self._fetch(nxt, bad)
                    if bad_h:
                        # non-finite logits: drop the poisoned step (the
                        # returned cache is never assigned) and re-run on
                        # the oracle path
                        return self._degraded_step()
                    self.cache = cache2
                else:
                    pkey, pfn = self._get_decode("plain")
                    logits, self.cache = self._call(
                        pkey, pfn, self.params, self.cache, last[:, None])
                    # papilint: allow-transfer(legacy unfused per-step fetch)
                    nxt_h = self._fetch(greedy(logits[:, -1]))
                return (np.asarray(nxt_h)[:, None].astype(np.int32),
                        np.ones(self.max_slots), None)
            if self.fused:
                return self._speculative_iteration_fused()
            return self._speculative_iteration_host()

    def _speculative_iteration_fused(self):
        """Device-resident draft/verify/accept: one transfer per iteration."""
        key, fn = self._get_spec_fused()
        out, accepted, fin, bad, cache, draft_cache = self._call(
            key, fn, self.params, self.draft_params, self.cache,
            self.draft_cache, jnp.asarray(self.slot_last), self._fault_code(),
        )
        # papilint: allow-transfer(the spec iteration's one bundle fetch)
        out_h, acc_h, fin_h, bad_h = self._fetch(out, accepted, fin, bad)
        if bad_h:
            # non-finite verify logits: neither cache is assigned (both
            # still hold the pre-step state), and the iteration degrades to
            # a single oracle decode step (spec window clamped to 1)
            return self._degraded_step()
        self.cache, self.draft_cache = cache, draft_cache
        return (np.asarray(out_h), np.asarray(acc_h).astype(np.float64),
                np.asarray(fin_h))

    def _speculative_iteration_host(self):
        """The seed's per-step host loop — the reference implementation the
        fused path is validated against (and the benchmark's baseline)."""
        k = self.spec_len
        draft_key, draft_fn = self._get_decode("draft")
        # 1) draft proposes k-1 tokens autoregressively (k steps: the extra
        # step writes KV for the window's final token)
        proposals = [self.slot_last.copy()]
        last = jnp.asarray(self.slot_last[:, None])
        for _ in range(k):
            logits, self.draft_cache = self._call(
                draft_key, draft_fn, self.draft_params, self.draft_cache,
                last
            )
            nxt = greedy(logits[:, -1])
            # papilint: allow-transfer(legacy host-spec baseline, per draft step)
            proposals.append(np.asarray(self._fetch(nxt)))
            last = nxt[:, None]
        window = np.stack(proposals[:k], axis=1)          # [slots, k]

        # 2) target verifies the window in ONE decode step (TLP = k)
        vkey, vfn = self._get_decode("verify")
        logits, self.cache = self._call(
            vkey, vfn, self.params, self.cache, jnp.asarray(window)
        )
        # papilint: allow-transfer(legacy host-spec verify fetch)
        target = np.asarray(self._fetch(greedy(logits)))  # [slots, k]

        # 3) accept longest matching prefix; roll back caches per slot
        accepted = np.zeros(self.max_slots, np.int64)
        out = np.zeros((self.max_slots, k), np.int32)
        for s in range(self.max_slots):
            n = 0
            while n < k - 1 and window[s, n + 1] == target[s, n]:
                n += 1
            accepted[s] = n + 1                            # +1: free token
            out[s, : n + 1] = target[s, : n + 1]
        # target cache advanced by k for every slot; rewind to accepted
        rewind = jnp.asarray(k - accepted, jnp.int32)
        self.cache["pos"] = self.cache["pos"] - rewind
        # resync draft cache to the target position
        if self.draft_cache is not None:
            self.draft_cache["pos"] = jnp.minimum(
                self.draft_cache["pos"], self.cache["pos"]
            )
        return out, accepted.astype(np.float64), None

    def step(self) -> None:
        if self._sanitizer is None:
            return self._step_impl()
        stats0 = len(self.stats)
        with self._sanitizer.scope(self):
            self._step_impl()
        self._sanitizer.after_step(self, stepped=len(self.stats) > stats0)

    def sanitize_report(self):
        """The sanitizer's accumulated budget/compile counters, or None
        when the engine was built without ``sanitize=True``."""
        return None if self._sanitizer is None else self._sanitizer.report

    def _step_impl(self) -> None:
        """One engine iteration, as the ``engine.step`` span (step number
        = the `IterStats.iteration` it records) around its phases:
        ``engine.admit``, ``engine.prepare``, ``engine.dispatch``,
        ``engine.fetch``, ``engine.commit``, ``engine.park`` and
        ``engine.account`` (see `telemetry.phase`)."""
        t0 = time.perf_counter()
        fetch0 = self._fetch_s
        misses0 = self._jit_misses
        transfers0 = self.host_transfers
        results0 = len(self.results)
        preempted0 = self.preemptions
        self._degraded_this_step = False
        if self.tracer.enabled:
            # events emitted anywhere below (including by the page manager,
            # which doesn't know the iteration) default to this step index
            self.tracer.iteration = self.iteration
        with phase("engine.step", self.tracer, kind="iteration",
                   step=self.iteration + 1) as step:
            if self.faults is not None and self.faults.crash_now(
                    self.iteration):
                # simulated process death: no cleanup, no emission, no
                # journal finalization — exactly what recovery must cope
                # with
                if self.tracer.enabled:
                    self.tracer.emit("fault", self.iteration, fault="crash")
                raise EngineCrashError(
                    f"injected crash at iteration {self.iteration}",
                    self.iteration)
            if self.faults is not None:
                delay = self.faults.step_delay(self.iteration)
                if delay > 0:
                    if self.tracer.enabled:
                        self.tracer.emit("fault", self.iteration,
                                         fault="latency", delay_s=delay)
                    time.sleep(delay)
            with phase("engine.admit", self.tracer) as span:
                admitted = self._admit_phase()
                span.set(admitted=admitted)
            arrived = self._arrived_this_step
            self._arrived_this_step = 0
            active = self.active_slots
            if not active:
                # Still a step: count it, or `run(max_iterations=)` is a
                # dead guard — paged admission deferring with nothing
                # active would spin this loop forever (regression-tested).
                with phase("engine.account", self.tracer):
                    self.scheduler.observe_counts(0, admitted)
                    if self.tracer.enabled:
                        self._trace_scheduler()
                    self.iteration += 1
                    self._watchdog(admitted > 0
                                   or len(self.results) > results0
                                   or self.preemptions > preempted0)
                    self._check_invariants()
                if self.tracer.enabled:
                    step.meta.update(
                        fc_variant=self.scheduler.fc_assignment,
                        rlp=self.scheduler.rlp, tlp=self.scheduler.tlp,
                        ai_estimate=self.scheduler.ai_estimate,
                        new_tokens=0, degraded=0, decode_slots=0,
                        prefill_slots=0, queued=len(self.queue),
                        arrivals=arrived,
                        transfers=self.host_transfers - transfers0,
                        idle=True)
                return

            speculating = self.spec_len > 1 and self.draft_cfg is not None
            prefilling = (self._prefilling_slots() if self.stream_chunks
                          else [])
            chunked = len(prefilling)
            if prefilling and not speculating:
                # TLP=1 continuous batching: decodes + prefill chunks in ONE
                # fused program (the wave handles its own page mapping)
                decoding = [s for s in active if s not in set(prefilling)]
                out, accepted, _fin = self._mixed_wave_iteration(prefilling,
                                                                 decoding)
            else:
                if prefilling:
                    # speculative serve: advance the prefill frontier first
                    # so a slot finishing its prompt this iteration rides the
                    # verify program below, exactly like offline admission
                    self._chunk_wave(prefilling)
                pset = set(prefilling)
                decoding = [s for s in self.active_slots
                            if s not in pset
                            or int(self.slot_offset[s])
                            >= int(self.slot_prompt[s])]
                out = np.zeros((self.max_slots, 1), np.int32)
                accepted = np.zeros(self.max_slots)
                if decoding:
                    # the eos flags in the bundle are a device-side
                    # convenience for callers (launch.serve); the commit
                    # below re-derives finishes anyway since length-based
                    # finishes need per-request budgets
                    out, accepted, _fin = self._decode_all(decoding)

            with phase("engine.commit", self.tracer):
                iter_tokens, finished_flags = self._commit(
                    decoding, out, accepted, speculating)
            with phase("engine.park", self.tracer):
                self._park()
            # the PAPI runtime scheduling step (§5.2.2): the per-slot
            # finished flags go to the scheduler as an array — it sums them
            # itself; then watchdog, invariants, pool statistics, IterStats
            with phase("engine.account", self.tracer):
                self.scheduler.observe_counts(finished_flags, admitted)
                if self.tracer.enabled:
                    self._trace_scheduler()
                self.iteration += 1
                self._watchdog(admitted > 0 or len(iter_tokens) > 0
                               or chunked > 0
                               or len(self.results) > results0
                               or self.preemptions > preempted0)
                self._check_invariants()
                kv_used = kv_free = kv_peak = 0
                kv_frag = 0.0
                if self.kv is not None:
                    live_tokens = sum(self._tokens_written(s)
                                      for s in range(self.max_slots)
                                      if self.slot_req[s] is not None)
                    ps = self.kv.stats(live_tokens)
                    kv_used, kv_free = ps.mapped, ps.free
                    kv_peak, kv_frag = ps.watermark, ps.fragmentation
                wall_s = time.perf_counter() - t0
                gc_s, self._gc_s = self._gc_s, 0.0
                self.stats.append(IterStats(
                    preemptions=self.preemptions - preempted0,
                    deferral_age=self._defer_age,
                    degraded=1 if self._degraded_this_step else 0,
                    iteration=self.iteration,
                    rlp=self.scheduler.rlp,
                    tlp=self.scheduler.tlp,
                    ai_estimate=self.scheduler.ai_estimate,
                    fc_variant=self.scheduler.fc_assignment,
                    new_tokens=len(iter_tokens),
                    accepted=(float(np.mean(accepted[decoding]))
                              if len(decoding) else 0.0),
                    wall_s=wall_s,
                    transfers=self.host_transfers - transfers0,
                    kv_pages_used=kv_used,
                    kv_pages_free=kv_free,
                    kv_page_watermark=kv_peak,
                    kv_fragmentation=kv_frag,
                    arrivals=arrived,
                    admitted=admitted,
                    queued=len(self.queue),
                    prefill_slots=chunked,
                    decode_slots=len(decoding),
                    host_s=wall_s - (self._fetch_s - fetch0),
                    gc_s=gc_s,
                    jit_misses=self._jit_misses - misses0,
                ))
                if self.tracer.enabled:
                    if self.kv is not None:
                        self.tracer.emit("pool", used=kv_used, free=kv_free,
                                         watermark=kv_peak,
                                         fragmentation=kv_frag)
                    st = self.stats[-1]
                    step.meta.update(
                        fc_variant=st.fc_variant, rlp=st.rlp, tlp=st.tlp,
                        ai_estimate=st.ai_estimate, new_tokens=st.new_tokens,
                        degraded=st.degraded, decode_slots=st.decode_slots,
                        prefill_slots=st.prefill_slots, queued=st.queued,
                        arrivals=st.arrivals, transfers=st.transfers)

    def _admit_phase(self) -> int:
        """Deadlines, admission, deferral ageing and pool-pressure
        preemption (the ``engine.admit`` span).  Returns the number of
        requests admitted."""
        self._expire_deadlines()
        admitted = self._admit()
        # deferral-age accounting: consecutive iterations the SAME queue
        # head has been deferred by the pool (slot-limited waits don't
        # count — only can_admit failures / injected admission faults set
        # `_deferred_head`)
        if self._deferred_head is None:
            self._defer_age = 0
            self._defer_head = None
        elif self._deferred_head != self._defer_head:
            self._defer_head = self._deferred_head
            self._defer_age = 1
        else:
            self._defer_age += 1
        if self._deferred_head is not None:
            if self.tracer.enabled:
                self.tracer.emit("defer", self.iteration,
                                 req_id=self._deferred_head,
                                 age=self._defer_age)
            log.debug("queue head %d deferred by the pool (age %d)",
                      self._deferred_head, self._defer_age)
        if self._defer_age and self._should_preempt() and self._preempt_one():
            # pages freed — retry admission immediately so the head's
            # admission delay is bounded by K, not K + another deferral
            admitted += self._admit()
            if self._deferred_head is None:
                self._defer_age = 0
        return admitted

    def _commit(self, decoding: list[int], out, accepted,
                speculating: bool) -> tuple[list[int], np.ndarray]:
        """Host-side bookkeeping of the step's tokens (the ``engine.commit``
        span): append them, detect eos / length, return pages, and journal
        the commits.  Returns (the tokens committed, finished flags)."""
        iter_tokens: list[int] = []
        finished_flags = np.zeros(self.max_slots, bool)
        for s in decoding:
            req = self.slot_req[s]
            if req is None:      # instant-finished by this iteration's wave
                continue
            n_acc = int(accepted[s])
            for j in range(n_acc):
                tok = int(out[s, j])
                self.slot_tokens[s].append(tok)
                iter_tokens.append(tok)
                if tok == self.eos_token or (
                    len(self.slot_tokens[s]) >= self.slot_budget[s]
                ):
                    reason = "eos" if tok == self.eos_token else "length"
                    self._emit(req, self.slot_tokens[s], reason, slot=s)
                    self.slot_req[s] = None
                    finished_flags[s] = True
                    break
            else:
                self.slot_last[s] = self.slot_tokens[s][-1]
                if self.kv is not None and speculating and (
                        n_acc < self.spec_len):
                    # speculative rollback returned the cache position to
                    # the accepted prefix; pages past it hold only the
                    # rejected window tail — return them to the pool (the
                    # admission reservation keeps them claimable, so next
                    # iteration's ensure() re-maps without risk)
                    self.kv.rewind(s, self._slot_pos(s))
                continue
            # slot freed: park its position on a safe nonzero value
            self.slot_last[s] = 0
            if self.kv is not None:
                self.kv.release(s)

        if self.journal is not None:
            self._journal_commits()
        return iter_tokens, finished_flags

    def _park(self) -> None:
        """Park inactive slots at pos=1 so their garbage decode can't creep
        past the cache capacity (they are masked from outputs anyway), with
        `papi_park_slots` (the ``engine.park`` span)."""
        inactive = np.array([r is None for r in self.slot_req])
        if inactive.any():
            mask = jnp.asarray(inactive)
            pkey, pfn = self._get_park()
            self.cache["pos"] = self._call(pkey, pfn, mask, self.cache["pos"],
                                           span=None)
            if self.draft_cache is not None:
                self.draft_cache["pos"] = self._call(
                    pkey, pfn, mask, self.draft_cache["pos"], span=None)

    def _trace_scheduler(self) -> None:
        """Emit this iteration's scheduling decision with its INPUTS (the
        AI estimate and the alpha threshold it was compared against), not
        just the chosen variant — the flip timeline in a trace must show
        why each decision went the way it did."""
        ev = self.scheduler.events[-1]
        self.tracer.emit("scheduler", self.iteration,
                         ai_estimate=ev.ai_estimate, alpha=ev.alpha,
                         assignment=ev.assignment, flipped=ev.rescheduled,
                         rlp=ev.rlp, tlp=ev.tlp)

    def set_spec_len(self, tlp: int) -> None:
        """Host updates the TLP register (dynamic speculation length).

        Both layouts budget admission for `prompt + max_new + window`, so
        widening the window mid-flight must re-check every LIVE slot or the
        verify step's KV writes overrun what admission reserved:

        * paged — the admission reservation covered the OLD window's pages;
          widening re-budgets live slots' reservations and clamps the
          window to what the free pool (and block-table width) can cover,
          or the per-iteration `ensure()` could exhaust the pool mid-flight;
        * dense — a live slot's slab holds `prompt + budget + OLD window`
          tokens; a wider window would make the verify step's
          dynamic_update_slice run past `cache_capacity`, where it CLAMPS
          downward and silently corrupts earlier live KV.  The window is
          clamped to the smallest live slot's headroom instead.

        Narrower is always affordable; on clamp the scheduler simply gets a
        smaller TLP than it asked for this cycle.
        """
        if tlp != self.spec_len:
            tlp = (self._rebudget_spec_window(tlp) if self.kv is not None
                   else self._clamp_spec_window_dense(tlp))
        self.spec_len = tlp
        self.scheduler.set_tlp(tlp)

    def _clamp_spec_window_dense(self, tlp: int) -> int:
        """Dense layout: admission guaranteed `prompt + budget + old_window
        <= cache_capacity` per live slot, so the widest window every live
        slot can hold is its remaining slab headroom."""
        want = max(tlp, 1)
        live = [s for s in range(self.max_slots)
                if self.slot_req[s] is not None]
        for s in live:
            headroom = (self.capacity - int(self.slot_prompt[s])
                        - int(self.slot_budget[s]))
            want = min(want, max(headroom, 1))
        return want if want != max(tlp, 1) else tlp

    def _rebudget_spec_window(self, tlp: int) -> int:
        """Adjust live slots' page reservations from the current speculative
        window to `tlp`'s; returns the (possibly clamped) window every live
        slot can actually hold — bounded by BOTH the free pool and the
        block-table width (a slot admitted near `max_blocks * page_size`
        tokens has no table rows left for a wider window)."""
        old_win = max(self.spec_len, 1)
        live = [s for s in range(self.max_slots)
                if self.slot_req[s] is not None]

        def budget(s: int, win: int) -> int:
            base = int(self.slot_prompt[s]) + int(self.slot_budget[s])
            return self.kv.pages_for(base + win)

        def delta(s: int, new_win: int) -> int:
            return budget(s, new_win) - budget(s, old_win)

        want = max(tlp, 1)
        while want > old_win and (
                sum(delta(s, want) for s in live) > self.kv.alloc.available
                or any(budget(s, want) > self.kv.max_blocks for s in live)):
            want -= 1
        for s in live:
            self.kv.alloc.reserve_more(s, delta(s, want))
        return want if want != max(tlp, 1) else tlp
