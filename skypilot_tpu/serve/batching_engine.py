"""Continuous batching engine for the model server.

vLLM-style scheduling, rebuilt TPU-first (no reference equivalent —
SkyPilot ships no serving internals): a FIXED pool of KV-cache slots is
the batch dimension, so every jit'd shape is static.  Requests join a
running batch the moment a slot frees (no wait for the batch to drain),
and one `models.decode.paged_engine_step` call advances every active
slot a token per engine tick — new arrivals ride along with half-finished
generations.

This module is the compatibility FACADE over the engine's three parts
(split per ROADMAP before the page pool landed):

- `serve/scheduler.py`  — Request handles, the bounded/TTL'd admission
  queue (QueueFull -> 429, QueueExpired -> 503), slot bookkeeping.
- `serve/cache_manager.py` — the paged-KV host side: page pool
  allocator (refcounts/pins/COW, null page), chain-hashed prefix
  cache with LRU eviction, per-slot page ownership.
- `serve/sampler.py`    — submit-side sampling validation + the jitted
  per-slot admission staging.

Existing imports keep working: `batching_engine.QueueFull`,
`batching_engine._Request`, `ContinuousBatchingEngine`, ... are all
re-exported here.

The KV cache is a pool of `kv_pages` pages `[L, N, h_kv, page_size, d]`
with per-slot block tables (`models/decode.paged_engine_step` reads
pages by table index inside the jitted tick).  Memory is bounded by
the tokens a request can actually touch, decoupling slot count from
max_len; admission allocates `ceil((prompt + max_new - 1) /
page_size)` pages and BACKPRESSURES (QueueFull/429 + Retry-After) on
pool exhaustion instead of failing the engine.  Pages free on
completion, cancel, and TTL expiry.  `kv_pages=None` sizes the pool so
that every slot can hold a request of max_len at once
(`PagedKVManager.pool_pages`).  `quantize_kv=True` stores pages as int8
with per-page-per-head scales (~2x more tokens per byte; dequant fuses
into the attention einsum).  `prefix_caching=True` registers every FULL
prefilled prompt page under a chain hash, so requests sharing a system
prompt adopt the cached pages instead of re-prefilling — TTFT on a
prefix hit collapses to the tail chunks.  Sessions diverging mid-page
stop matching at the divergence page and each writes its own copy
(full pages are immutable once written, so shared pages are never
mutated).

Decode hot loop (the device never waits on Python):
- Token selection happens ON DEVICE inside the jitted step — greedy
  argmax plus per-slot temperature/top-k sampling, stop-set matching,
  and max_new_tokens countdown all live in the jitted tick, so
  tick t+1's input IS tick t's output with zero host transfer.
- Ticks are PIPELINED one deep: the worker dispatches tick t+1 before
  fetching tick t's tokens and reads results one tick behind for
  stream/stop bookkeeping, so host work overlaps device compute.  A
  slot that stops at tick t is already inactive on device when tick
  t+1 runs — the pipeline never decodes past a stop.
- Prompt prefill is CHUNKED: an admission splits a long prompt into
  fixed-size chunks, at most one an iteration of the loop, so the worst
  ITL stall any admission can impose on running requests is one chunk's
  compute, not one prompt's.
- A chunk RIDES the tick: an iteration that has a chunk to run
  dispatches one program (`decode.paged_engine_step_with_chunk`) that
  is the live slots' tick and the chunk together, every layer's weights
  read once for both, where a chunk between two ticks would stream the
  weights the tick has just streamed.  Whether a chunk is pending, its
  kind (first or later) and its width choose the program, nothing else:
  a pending chunk takes the fused step also when no slot is live (the
  frozen slots' results are not read), so the programs a warm-up
  compiles are the ones traffic meets.  The speculative engine, whose
  verify ticks are synchronous, the slice engine and `export_prefill`
  run the standalone programs (`decode.prefill`, `prefill_chunk`).

Self-speculative decoding (`spec_tokens=k > 0`): a per-slot host-side
n-gram/prompt-lookup drafter (`serve/sampler.NgramDrafter`) proposes
k tokens, ONE batched verify tick (`decode.paged_spec_engine_step`)
scores all of them against the paged cache, and each slot emits its longest exactly-matching draft
prefix plus the verified bonus token.  Token streams are byte-identical
to spec-off — greedy AND seeded sampling — because every emitted token
is the engine's own verified choice; drafts only decide how many land
per dispatch.  Rejected drafts' KV writes land beyond the slot's
advanced length (overwritten by later ticks before any query attends
them) or, past the block table, in the pool's reserved null page.
Spec mode runs ticks SYNCHRONOUSLY (the drafter needs the tokens a
tick just emitted), trading the one-deep pipeline for up to k+1 tokens
per dispatch.  The paged attention inside every tick runs the Pallas
paged-attention kernel where it can (`SKYTPU_DECODE_KERNEL=
pallas|gather`, ops/paged_attention.py) with the jnp gather fallback
elsewhere — both parity-pinned against `decode.generate`.

Exact-prefill trick for static shapes: the prompt's
first n-1 tokens are prefilled PADDED to a power-of-two bucket
(bounding compile count), the slot is inserted at length n-1, and the
LAST real prompt token is fed through the next batched step — it
overwrites the first pad position and attends only real keys, so
logits match unpadded decode exactly (tests pin this against
decode.generate).  Chunk 0 keeps that flash-prefill path; chunks at
index > 0 run `decode.prefill_chunk` (per-position causal mask), which
preserves the same n-1/last-token trick per chunk.  A prefix-cache hit
replaces chunk 0: the cached pages seed the private prefill cache and
only the unmatched tail chunks run.  Expert models take the same path:
their layer drops no token (models/moe.py), so a token's result does
not depend on its neighbours and chunks may be padded, split and
served from cached pages like any other model's.  Their ticks return
the expert layers' counts beside `finished`, read one tick behind
(stats()['moe']).  A looped stack (`cfg.loop_passes` > 1: the layers
run several times a token) takes the same path too: a page holds its
tokens' keys of every cache layer (`cfg.cache_layers`, one a pass and
layer), a tick still yields one token a slot, and returns the exit
gate's mass by pass the same way (stats()['loop']).  A model with
mamba layers takes it too: each slot's states sit beside the pool and
the tick advances them in place; a prefix hit restores them from a
store of snapshots saved at chosen page boundaries, and goes only as
deep as a snapshot does (stats()['ssm']).

Admission is BOUNDED: `max_queue` rejects new submits when the backlog
is full (`QueueFull` -> HTTP 429) and `queue_ttl` expires requests
that waited too long queued (`QueueExpired` -> HTTP 503), so a load
spike degrades with fast, honest rejections instead of unbounded TTFT.
"""
from __future__ import annotations

import collections
import math
import os
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

from skypilot_tpu import sky_logging
from skypilot_tpu.observability import logs as logs_lib
from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.observability import profiling
from skypilot_tpu.observability import tracing
from skypilot_tpu.serve import cache_manager
from skypilot_tpu.serve import handoff as handoff_lib
from skypilot_tpu.serve import sampler as sampler_lib
from skypilot_tpu.serve import scheduler

logger = sky_logging.init_logger(__name__)

# ------------------------------------------------- compatibility facade
QueueFull = scheduler.QueueFull
QueueExpired = scheduler.QueueExpired
DeadlineExceeded = scheduler.DeadlineExceeded
PagesExhausted = cache_manager.PagesExhausted
HandoffError = handoff_lib.HandoffError
HandoffRejected = handoff_lib.HandoffRejected
_Request = scheduler.Request
_Slot = scheduler.Slot
_PendingPrefill = scheduler.PendingPrefill
_WAIT_BUCKETS = scheduler.WAIT_BUCKETS
# Full public surface of the three parts, same names (the facade
# contract `sky lint` pins: facade-missing/facade-stale findings when
# this drifts — see analysis/passes/facade_surface.py).
AdmissionQueue = scheduler.AdmissionQueue
PendingPrefill = scheduler.PendingPrefill
Request = scheduler.Request
RoleBudget = scheduler.RoleBudget
Slot = scheduler.Slot
WAIT_BUCKETS = scheduler.WAIT_BUCKETS
AdmissionPlan = cache_manager.AdmissionPlan
NULL_PAGE = cache_manager.NULL_PAGE
PagePool = cache_manager.PagePool
PagedKVManager = cache_manager.PagedKVManager
PrefixCache = cache_manager.PrefixCache
StateSnapshots = cache_manager.StateSnapshots
chunk_hashes = cache_manager.chunk_hashes
NgramDrafter = sampler_lib.NgramDrafter
SlotSampler = sampler_lib.SlotSampler
validate_sampling = sampler_lib.validate_sampling
validate_stop_ids = sampler_lib.validate_stop_ids

_PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
# The least width of a chunk that rides the tick (where `prefill_chunk`
# allows it).  Every width is a program of the tick's own size, and a
# serving host spends about a second of set-up on each even when the
# compile cache holds it (tracing and lowering, four times a standalone
# chunk's: chip runs, PR 36), while rows under the one read of the
# weights cost the products next to nothing until some 240 of them
# reach a v5e's ridge.  So every piece up to this width shares one
# program a kind, which is told how many of its rows are the prompt's
# and leaves the attention of the rest undone.
_FUSED_MIN_WIDTH = 256

# Process-global registry instruments (observability/metrics.py) —
# what `GET /metrics` on the serving fronts exposes.  Counters are
# process-cumulative (Prometheus semantics: rates come from deltas);
# the per-ENGINE view lives in stats().  Gauges describe the most
# recently constructed engine — one engine per serving process.
# Queue/admission instruments live in serve/scheduler.py; page-pool
# and prefix-cache instruments in serve/cache_manager.py.
_M_TICKS = metrics_lib.counter(
    'skytpu_engine_ticks_total', 'Decode engine ticks dispatched.')
_M_TOKENS = metrics_lib.counter(
    'skytpu_engine_decode_tokens_total',
    'Tokens generated across all requests.')
_M_PREFILL_CHUNKS = metrics_lib.counter(
    'skytpu_engine_prefill_chunks_total',
    'Prompt prefill chunks executed.')
_M_PREFILL_CHUNKS_FUSED = metrics_lib.counter(
    'skytpu_engine_prefill_chunks_fused_total',
    'Prompt prefill chunks that shared their read of the weights with '
    'the decode tick of at least one live slot (one program for both).')
_M_CONV_SNAPSHOTS_SAVED = metrics_lib.counter(
    'skytpu_engine_conv_snapshots_saved_total',
    'Prompt pages published to the prefix cache with the conv tails at '
    'their end (models with conv layers).')
_M_CONV_SNAPSHOTS_RESTORED = metrics_lib.counter(
    'skytpu_engine_conv_snapshots_restored_total',
    'Prefix hits whose conv tails came from the snapshot of their last '
    'cached page (models with conv layers).')
_M_SSM_SNAPSHOTS_SAVED = metrics_lib.counter(
    'skytpu_engine_ssm_snapshots_saved_total',
    'Snapshots of every mamba layer\'s state saved to the store at a '
    'prompt\'s page boundary (models with mamba layers).')
_M_SSM_SNAPSHOTS_RESTORED = metrics_lib.counter(
    'skytpu_engine_ssm_snapshots_restored_total',
    'Prefix hits whose mamba states came from a snapshot in the store.')
_M_SSM_PROMPT_TOKENS = metrics_lib.counter(
    'skytpu_engine_ssm_prompt_tokens_total',
    'Prompt tokens admitted (models with mamba layers).')
_M_SSM_RESTORED_TOKENS = metrics_lib.counter(
    'skytpu_engine_ssm_restored_tokens_total',
    'Prompt tokens whose pages and mamba states a prefix hit restored.')
_M_SSM_RECOMPUTED_TOKENS = metrics_lib.counter(
    'skytpu_engine_ssm_recomputed_tokens_total',
    'Prompt tokens whose pages were cached but whose mamba states were '
    'not (no snapshot that deep): prefilled again.')
_M_SSM_STATE_BYTES = metrics_lib.gauge(
    'skytpu_engine_ssm_state_bytes',
    'Bytes of the slots\' mamba states held beside the page pool.')
_M_SSM_SNAPSHOT_ROWS = metrics_lib.gauge(
    'skytpu_engine_ssm_snapshot_rows',
    'Entries of the store of mamba state snapshots (each every mamba '
    'layer\'s state of one prefix).')
_M_BUSY_SLOTS = metrics_lib.gauge(
    'skytpu_engine_busy_slots', 'KV slots currently decoding.')
_M_SLOTS = metrics_lib.gauge(
    'skytpu_engine_slots', 'Total KV slots in the pool.')
_M_DECODE_RATE = metrics_lib.gauge(
    'skytpu_engine_decode_tokens_per_s',
    'Decode tokens/s over the trailing 10s window.')
_M_HANDOFF_EXPORTS = metrics_lib.counter(
    'skytpu_engine_handoff_exports_total',
    'KV page exports served (the prefill side of a handoff).')
_M_HANDOFF_IMPORTS = metrics_lib.counter(
    'skytpu_engine_handoff_imports_total',
    'KV page imports (the decode side of a handoff), by result.',
    ('result',))
_M_DEADLINE_REAPED = metrics_lib.counter(
    'skytpu_engine_deadline_reaped_total',
    'Decoding requests cancelled mid-generation because their '
    'X-SkyTPU-Deadline-Ms passed (slot and KV pages freed).')
_M_SPEC_PROPOSED = metrics_lib.counter(
    'skytpu_engine_spec_proposed_tokens_total',
    'Draft tokens proposed to speculative verify ticks (k per live '
    'slot per tick).')
_M_SPEC_ACCEPTED = metrics_lib.counter(
    'skytpu_engine_spec_accepted_tokens_total',
    'Draft tokens accepted by speculative verify ticks (the emitted '
    'base token per tick is not counted).')
_M_SPEC_ACCEPT_LEN = metrics_lib.histogram(
    'skytpu_engine_spec_accept_len_tokens',
    'Tokens emitted per slot per speculative verify tick (1 = every '
    'draft rejected; k+1 = all accepted plus the bonus token).',
    buckets=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0))
_M_KERNEL_LIVE_SHARE = metrics_lib.gauge(
    'skytpu_engine_paged_kernel_live_page_share',
    'Share of the block tables\' rows (slots x rows) that the last '
    'paged decode tick\'s live contexts reached: the pages the decode '
    'kernel walks over the pages a table-sized grid would visit.')
_M_KV_KERNEL_WRITES = metrics_lib.counter(
    'skytpu_engine_kv_kernel_writes_total',
    'Cache-layer writes of a tick\'s new K/V rows that the paged decode '
    'kernel made itself, with no scatter (one a cache layer a tick).')
_M_KV_HEADS_PER_ROW = metrics_lib.gauge(
    'skytpu_engine_kv_heads_per_row',
    'KV heads one row of the page pool holds side by side: 1, or 128 / '
    'head_dim where heads narrower than a lane tile share a row.')
_M_KERNEL_PALLAS = metrics_lib.gauge(
    'skytpu_engine_decode_kernel_pallas',
    'Whether the paged decode attention runs the Pallas kernel '
    '(1) or the jnp gather fallback (0).')


def _device_memory_left(device) -> Optional[int]:
    """Bytes `device` has left for new arrays (its limit less what is
    in use now); None where the backend reports no memory statistics
    (the CPU)."""
    stats = device.memory_stats() or {}
    if 'bytes_limit' not in stats or 'bytes_in_use' not in stats:
        return None
    return int(stats['bytes_limit']) - int(stats['bytes_in_use'])


# The share of the device memory left, with weights, pool and slot
# states resident, that a model with mamba layers gives its store of
# state snapshots; the rest is for private prefill caches and the
# programs' temporaries.
_SNAPSHOT_SHARE = 0.25


def _snapshot_entries(cfg, slots: int, pool_bytes: int, device) -> int:
    """Entries of the store of state snapshots (a model with mamba
    layers): `_SNAPSHOT_SHARE` of what `device` has left once the pool
    (`pool_bytes`) and the slots' states are placed, at least one, at
    most two a slot; two a slot where the backend reports no memory
    statistics."""
    import jax.numpy as jnp  # pylint: disable=import-outside-toplevel
    one = cfg.mamba_layers * (
        4 * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state +
        (cfg.conv_kernel - 1) * cfg.ssm_conv_dim *
        jnp.dtype(cfg.dtype).itemsize)
    left = _device_memory_left(device)
    if left is None:
        return 2 * slots
    left -= pool_bytes + slots * one
    return max(1, min(2 * slots, int(left * _SNAPSHOT_SHARE) // one))


def _prefill_bound(slots: int, private: int, pool) -> int:
    """How many prompts may be mid-prefill at once, each holding a
    private cache of `private` bytes beside the page pool (`pool`: its
    k/v leaves, already on the device like the weights).

    Two bounds, the smaller holds, never under 1.  By the pool: a burst
    of admissions may hold as many bytes beside the pool as the pool
    holds itself (a pool of slots x max_len: every slot may; a smaller
    one: fewer).  By the device: what it has left with weights and
    pool resident, less one private cache more as room for the
    programs' temporaries (the program that makes or moves a private
    cache may hold a second one while it runs), divided among private
    caches; they are placed as the pool is, so on a mesh a device holds
    the pool's share of each.  A backend that reports no memory
    statistics leaves the first bound alone."""
    import jax  # pylint: disable=import-outside-toplevel
    leaves = jax.tree.leaves(pool)
    held = sum(leaf.nbytes for leaf in leaves)
    bound = min(slots, held // private)
    shards = [leaf.addressable_shards[0] for leaf in leaves]
    left = _device_memory_left(shards[0].device)
    if left is not None:
        share = sum(s.data.nbytes for s in shards) / held
        bound = min(bound, int(left // (private * share)) - 1)
    return max(1, bound)


def _maybe_page_journal():
    """Journal page alloc/free events only when someone is watching:
    the `serve.page_pool` chaos site is armed (scenarios replay the
    journal to prove alloc/free balance) or SKYTPU_SERVE_PAGE_EVENTS
    is set.  Production admissions stay I/O-free."""
    from skypilot_tpu.chaos import injector as chaos_injector  # pylint: disable=import-outside-toplevel
    if not (os.environ.get('SKYTPU_SERVE_PAGE_EVENTS') or
            chaos_injector.site_armed('serve.page_pool')):
        return None
    from skypilot_tpu.observability import events as events_lib  # pylint: disable=import-outside-toplevel
    return events_lib.get_journal(
        os.path.join(events_lib.journal_root(), 'serve.jsonl'))


class ContinuousBatchingEngine:
    """Submit() from any thread; one worker thread owns the device."""

    # Whether a prefill chunk rides the decode tick, one program for
    # both.  An engine whose tick is not `_step` alone keeps the
    # standalone chunk programs: the slice engine (its ranks run the
    # tick on a broadcast command) and any engine with `spec_tokens`.
    _FUSES_CHUNKS = True

    def __init__(self, cfg, params, *, max_len: int = 512,
                 slots: int = 4, prefill_chunk: int = 512,
                 max_queue: int = 0,
                 queue_ttl: Optional[float] = None,
                 max_top_k: int = 64, max_stop_ids: int = 16,
                 mesh=None,
                 kv_pages: Optional[int] = None, page_size: int = 16,
                 quantize_kv: bool = False,
                 prefix_caching: bool = True,
                 spec_tokens: int = 0) -> None:
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.models import decode
        from skypilot_tpu.ops import paged_attention as paged_attention_lib

        self.cfg = cfg
        # The engine's own view of the weights: the caller's tree with
        # the q/k/v projection kernels in the form the layer scan's
        # product reads (`decode.serving_params`; every other leaf is
        # shared, the caller's tree is left as it was).
        self.params = decode.serving_params(cfg, params)
        # Live weight swap (POST /weights_swap): bumped by swap_params()
        # ON THE WORKER THREAD between ticks; read anywhere (int loads
        # are atomic under the GIL).  Epoch 0 = the params the engine
        # booted with.
        self._weight_epoch = 0
        self.max_len = max_len
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.max_queue = int(max_queue)          # 0 = unbounded
        self.queue_ttl = queue_ttl               # None = no expiry
        self.max_top_k = int(max_top_k)
        self.max_stop_ids = int(max_stop_ids)
        self._jnp = jnp
        self._jax = jax
        self._slots = [scheduler.Slot() for _ in range(slots)]
        self._queue = scheduler.AdmissionQueue(
            max_queue=max_queue, queue_ttl=queue_ttl,
            drain_estimate=self._drain_estimate)
        self._cond = self._queue.cond
        self._stop = threading.Event()
        self._sampler = sampler_lib.SlotSampler(self.max_top_k,
                                                self.max_stop_ids)
        self.quantize_kv = bool(quantize_kv)
        # Host ops the worker runs between ticks (KV handoff imports
        # mutate self._cache, which only the worker may touch); each
        # entry is a no-raise closure that reports through its own
        # result holder.
        self._host_ops: Deque[Any] = collections.deque()
        self._host_ops_lock = threading.Lock()
        # Exports materialize a private prefill cache each; bound the
        # concurrent ones so a handoff stampede can't blow memory.
        self._export_sem = threading.BoundedSemaphore(2)

        self.spec_tokens = int(spec_tokens)
        if self.spec_tokens < 0:
            raise ValueError(
                f'spec_tokens must be >= 0, got {spec_tokens}')
        if ((cfg.conv_layers or cfg.mamba_layers) and
                (self.spec_tokens or mesh is not None)):
            raise ValueError(
                'a model with recurrent state (conv or mamba layers) is '
                'served without speculation (a rejected draft\'s rows '
                'would stay in the state) and on one device (the state '
                'has no placement over a mesh)')
        page_size = int(page_size)
        n_pages = cache_manager.PagedKVManager.pool_pages(
            kv_pages, slots, max_len, page_size)
        self._kv = cache_manager.PagedKVManager(
            n_pages, page_size, slots, prefix_caching=prefix_caching,
            journal=_maybe_page_journal())
        # Which attention path the paged tick runs — resolved ONCE here
        # (env SKYTPU_DECODE_KERNEL, defaulting to the Pallas kernel
        # wherever it can run) and baked into the jitted partials below
        # as a closure constant, so the hot loop never re-reads the
        # environment.
        self.decode_kernel = paged_attention_lib.decode_kernel_choice()
        # A model with mamba layers: a store of snapshots of the slots'
        # states, sized from the memory left (`_snapshot_entries`).
        snapshots = 0
        if cfg.mamba_layers:
            snapshots = _snapshot_entries(
                cfg, slots, 2 * cfg.cache_layers * n_pages * page_size *
                cfg.n_kv_heads * cfg.head_dim *
                jnp.dtype(cfg.dtype).itemsize,
                next(iter(jax.tree.leaves(self.params)[0].devices())))
        self._snapshots = (cache_manager.StateSnapshots(snapshots)
                           if cfg.mamba_layers else None)
        # On a TPU the kernel copies whole 128-lane rows: a narrower
        # head shares its row with its neighbours where it can, and is
        # padded where it cannot (an int8 pool, a mesh of more than one
        # device; `init_paged_cache`).
        self._cache = decode.init_paged_cache(
            cfg, n_pages, page_size, slots, max_len // page_size,
            quantize_kv=quantize_kv,
            head_lanes=paged_attention_lib.pool_head_lanes(
                self.decode_kernel),
            sharded_heads=mesh is not None and mesh.size > 1,
            snapshots=snapshots)
        self._heads_per_row = decode.heads_per_row(cfg, self._cache['k'])
        _M_KV_HEADS_PER_ROW.set(self._heads_per_row)
        # Prompts that may be mid-prefill at once.  Each holds a private
        # cache of max_len (over every cache layer) until it joins the
        # engine's cache; past the bound a request waits in the queue
        # for a prefill to finish.
        # (A model with conv layers: and its tails, and those at the end
        # of each of its pages; with mamba layers: its states and one
        # snapshot of them.)
        private = (2 * cfg.cache_layers * cfg.n_kv_heads * cfg.head_dim *
                   max_len + cfg.conv_layers * (max_len // page_size + 1) *
                   (cfg.conv_kernel - 1) * cfg.d_model
                   ) * jnp.dtype(cfg.dtype).itemsize + 2 * sum(
                       leaf.nbytes // slots for key in ('ssm', 'xbc')
                       if key in self._cache
                       for leaf in (self._cache[key],))
        self._max_prefills = _prefill_bound(
            slots, private, tuple(self._cache[key] for key in
                                  ('k', 'v', 'conv_snap')
                                  if key in self._cache))
        _M_KERNEL_PALLAS.set(
            1 if self.decode_kernel == 'pallas' else 0)
        self._state = decode.init_engine_state(slots, max_stop_ids)
        self._mesh = mesh
        if mesh is not None:
            # Tensor-sharded serving: place the KV pool and the tiny
            # per-slot state explicitly (kv_heads on 'tensor', state
            # replicated) instead of leaving GSPMD to guess from the
            # first donated step.
            from skypilot_tpu.parallel import sharding as sharding_lib
            self._cache = jax.device_put(
                self._cache, sharding_lib.paged_cache_sharding(
                    mesh, quantized=quantize_kv))
            self._state = jax.device_put(
                self._state, sharding_lib.engine_state_sharding(mesh))

        # Every jitted entry is a function under its own name
        # (`decode.bind`, never a partial or a lambda): the name is the
        # program's in a device trace (`jit_paged_engine_step`), and the
        # benchmark's readers find the tick and the prefill programs by
        # it.
        self._step = jax.jit(
            decode.bind(decode.paged_engine_step, cfg,
                        max_top_k=self.max_top_k,
                        kernel=self.decode_kernel, mesh=mesh),
            donate_argnums=(2,))
        # Speculative verify tick: same donated-pool discipline as
        # the plain tick, plus the [slots, k] draft batch; the
        # kernel choice is a closure constant, so both ticks hit
        # the same attention path.
        self._spec_step = jax.jit(
            decode.bind(decode.paged_spec_engine_step, cfg,
                        max_top_k=self.max_top_k,
                        kernel=self.decode_kernel, mesh=mesh),
            donate_argnums=(2,))
        # Block-table surgery: donated so XLA patches the pool's
        # tiny int32 tables in place.
        self._admit_paged = jax.jit(decode.paged_admit_slot,
                                    donate_argnums=(0,))
        self._release_paged = jax.jit(decode.paged_release_slot,
                                      donate_argnums=(0,))
        # Private-prefill -> pool page scatter (quantizing when the
        # pool is int8); the pool is donated (in-place patch), the
        # private cache is not (its [L,1,h,T,d] layout cannot alias
        # the page-major pool output — donating it just warns).
        self._insert_pages = jax.jit(
            decode.insert_prefill_pages,
            static_argnames=('first_page',), donate_argnums=(0,))
        # Prefix-hit seeding: cached pages -> the leading positions
        # of a fresh private cache (pool read-only, NOT donated).
        self._seed_private = jax.jit(
            decode.bind(decode.paged_seed_private, cfg),
            static_argnames=('priv_len',))
        # KV handoff adoption: imported page contents -> pool pages
        # (quantizing when the pool is int8); pool donated.  The
        # quantized variant lands int8 wire bytes verbatim — the
        # import path's hot case never dequantizes.
        self._write_pages = jax.jit(decode.write_pages,
                                    donate_argnums=(0,))
        self._write_pages_q = jax.jit(decode.write_pages_quantized,
                                      donate_argnums=(0,))
        # A prefill's snapshot of its mamba states -> an entry of the
        # store (pool donated: written in place).
        self._save_snapshot = jax.jit(decode.save_snapshot,
                                      donate_argnums=(0,))
        # Jitted prefill: one compile per prompt-length bucket (the
        # whole point of the bucket padding), not eager per-op dispatch
        # per admission.
        self._prefill = jax.jit(
            decode.bind(decode.prefill, cfg, max_len=max_len, mesh=mesh))
        # Chunk continuation at index > 0 (masked per-position causal
        # path): one compile per chunk width; the private prefill cache
        # is donated so XLA extends it in place.
        self._prefill_chunk = jax.jit(
            decode.bind(decode.prefill_chunk, cfg), donate_argnums=(2,))
        # The tick with a chunk riding it (first chunk: no private
        # cache goes in; later: the prompt's, donated like the pool).
        # One compile per chunk kind and width, as the two above.
        self._chunk_step = None
        if self._FUSES_CHUNKS and not self.spec_tokens:
            self._chunk_step = jax.jit(
                decode.bind(decode.paged_engine_step_with_chunk, cfg,
                            max_len=max_len, max_top_k=self.max_top_k,
                            kernel=self.decode_kernel, mesh=mesh),
                donate_argnums=(2, 4))
        # ---- continuous profiling plane (observability/profiling.py).
        # Tick-phase spans + recompile sentinel; both collapse to no-ops
        # under SKYTPU_PROFILE_DISABLE.  Every resolved jit entry above
        # (incl. the Pallas kernel path, a closure constant of _step)
        # gets the sentinel's O(1) cache-size probe so a steady-state
        # recompile is counted and journaled instead of silently
        # stalling ticks.
        self._profiler = profiling.TickProfiler()
        self._sentinel = profiling.RecompileSentinel()
        for attr in ('_step', '_spec_step', '_admit_paged',
                     '_release_paged', '_insert_pages', '_seed_private',
                     '_write_pages', '_write_pages_q', '_prefill',
                     '_prefill_chunk', '_chunk_step', '_save_snapshot'):
            setattr(self, attr, self._sentinel.wrap(
                attr.lstrip('_'), getattr(self, attr)))
        self._failed: Optional[Exception] = None

        # ---- metrics (updated under _metrics_lock; read by stats()).
        # These are the per-ENGINE view; every update is mirrored into
        # the process-global registry instruments above (what
        # GET /metrics exposes).
        self._metrics_lock = threading.Lock()
        self._tokens_generated = 0
        self._ticks = 0
        self._kernel_live_pages = 0
        self._kernel_table_pages = 0
        self._kernel_walked_pages = 0
        self._kernel_calls = 0
        # Cache-layer writes of the ticks' new rows, by route: the
        # decode kernel's own, or a scatter before it
        # (`decode.kernel_writes_rows`: the verify tick, int8 pools, a
        # mesh and the gather path scatter).
        self._kv_writes = {'kernel_writes': 0, 'scatter_writes': 0}
        self._rows_in_kernel = {
            s_q: decode.kernel_writes_rows(self.decode_kernel, quantize_kv,
                                           s_q, mesh)
            for s_q in (1, self.spec_tokens + 1)}
        # window (0 = none) -> how many layers have it: what
        # `_count_kernel_pages` needs of the layer pattern.
        self._layers_by_window = collections.Counter(
            w for _, w in (cfg.layer_kinds() or
                           ((True, 0),) * cfg.n_layers) * cfg.loop_passes)
        # Expert layers' counts, summed over ticks and layers: rows
        # routed, (row, held expert) pairs, the fullest expert's rows.
        # None until a tick returns some (a model without experts
        # never does).
        self._moe_counts: Optional[List[int]] = None
        # A model with conv layers: its tails' snapshots saved with
        # prompt pages and restored by prefix hits, the prompt tokens a
        # hit restored (pages and tails alike), the prompt tokens
        # admitted.
        self._conv_counts = dict.fromkeys(
            ('snapshots_saved', 'snapshots_restored', 'restored_tokens',
             'prompt_tokens'), 0)
        # A model with mamba layers: snapshots of the states saved to
        # the store, restored by prefix hits; the prompt tokens
        # admitted, those a hit restored (pages and states), and those
        # whose pages were cached but whose states were not.
        self._ssm_counts = dict.fromkeys(
            ('snapshots_saved', 'snapshots_restored', 'prompt_tokens',
             'restored_tokens', 'recomputed_tokens'), 0)
        if cfg.mamba_layers:
            _M_SSM_STATE_BYTES.set(self._cache['ssm'].nbytes +
                                   self._cache['xbc'].nbytes)
            _M_SSM_SNAPSHOT_ROWS.set(snapshots)
        # A looped stack's (cfg.loop_passes > 1) exit mass by pass,
        # summed over the ticks read.
        self._exit_mass = [0.0] * cfg.loop_passes
        self._prefill_chunks = 0
        self._prefill_chunks_fused = 0
        self._page_deferrals = 0
        self._spec_ticks = 0
        self._spec_slot_ticks = 0   # (live slot, verify tick) pairs
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._rate_window: Deque[Tuple[float, int]] = collections.deque()
        # Finished per-request spans (queue/prefill/TTFT/ITL/total),
        # bounded; surfaced via stats()['recent_spans'] and span().
        self._spans = tracing.SpanStore()
        _M_SLOTS.set(slots)
        _M_BUSY_SLOTS.set(0)

        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------ public

    def submit(self, prompt_ids: List[int], max_new_tokens: int,
               stop_token=None, sampling=None,
               request_id: Optional[str] = None,
               route_meta: Optional[Dict[str, Any]] = None,
               deadline_ms: Optional[float] = None,
               qos_class: Optional[str] = None
               ) -> scheduler.Request:
        """stop_token: None, one id, or an iterable of ids — the
        request finishes at the FIRST generated member of the set
        (multi-EOS: model-level EOS + chat turn-end markers).

        sampling: optional models.decode.SamplingConfig.  temperature
        <= 0 decodes greedily (the deterministic serving default);
        temperature > 0 samples on device with per-request top_k/seed —
        deterministic for a given seed (the slot's key chain splits
        once per generated token, independent of other traffic).

        request_id: the propagated X-SkyTPU-Request-Id (generated when
        absent); names the request's span record and timeline events.

        deadline_ms: total time budget from submission (the propagated
        X-SkyTPU-Deadline-Ms).  Queued past it -> DeadlineExceeded at
        pop; mid-decode past it -> the worker reaps the slot and frees
        its KV pages on the next tick.

        qos_class: the propagated X-SkyTPU-QoS-Class.  The scheduler
        clamps max_new_tokens to the class token budget, applies the
        class deadline default when deadline_ms is None, and pops
        queued work in smooth-weighted class order."""
        if not prompt_ids:
            raise ValueError('empty prompt')
        if max_new_tokens < 1:
            raise ValueError(
                f'max_new_tokens must be >= 1, got {max_new_tokens}')
        if len(prompt_ids) + max_new_tokens > self.max_len:
            raise ValueError(
                f'prompt {len(prompt_ids)} + new {max_new_tokens} '
                f'exceeds max_len {self.max_len}')
        temperature, top_k, seed = sampler_lib.validate_sampling(
            sampling, max_top_k=self.max_top_k)
        request = scheduler.Request(prompt_ids, max_new_tokens,
                                    stop_token, temperature=temperature,
                                    top_k=top_k, seed=seed,
                                    request_id=request_id,
                                    route_meta=route_meta,
                                    deadline_ms=deadline_ms,
                                    qos_class=qos_class)
        request._span_store = self._spans  # pylint: disable=protected-access
        # The epoch in force AT SUBMIT: a swap landing mid-decode still
        # attributes this request to the weights that prefilled it.
        request.span.weight_epoch = self._weight_epoch
        sampler_lib.validate_stop_ids(request.stop_ids,
                                      self.max_stop_ids)
        if self._stop.is_set() or self._failed is not None:
            raise RuntimeError('batching engine is stopped'
                               if self._failed is None else
                               f'batching engine failed: {self._failed}')
        # Admission is page-aware: a request that could NEVER fit is a
        # caller error; a pool too busy RIGHT NOW while a backlog
        # already waits is backpressure (429 + Retry-After) — the
        # honest degraded mode for an exhausted pool.
        need = self._kv.pages_needed(len(prompt_ids), max_new_tokens)
        if need > self._kv.pool.capacity:
            raise ValueError(
                f'request needs {need} KV pages > pool capacity '
                f'{self._kv.pool.capacity} (pool of '
                f'{self._kv.pool.capacity} pages x '
                f'{self._kv.page_size} tokens)')
        if len(self._queue) > 0 and not self._pool_has_room(
                prompt_ids, need):
            raise self._queue.reject(
                'pages_exhausted',
                f'KV page pool exhausted ({need} page(s) needed, '
                f'{self._kv.pool.free_count} free); retry later')
        self._queue.submit(request)
        if self._stop.is_set():
            # Lost the race with stop(): its drain may have already run,
            # so fail this request directly (idempotent via the event).
            if not request.done.is_set():
                request._finish(  # pylint: disable=protected-access
                    RuntimeError('batching engine stopped'))
        return request

    def _pool_has_room(self, prompt_ids: List[int], need: int) -> bool:
        """Could the pool cover this request right now: `need` pages,
        less those of its prompt that the prefix cache already holds
        (a request on a long cached document needs its tail only; the
        whole row would read as exhaustion whenever the other slots'
        tails are long).  The match is only made where the whole row
        does not fit."""
        if self._kv.can_admit(need):
            return True
        if not self._kv.prefix_caching:
            return False
        cached = self._kv.import_prefix_depth(cache_manager.chunk_hashes(
            prompt_ids[:-1], self._kv.page_size))
        return cached > 0 and self._kv.can_admit(need - cached)

    def generate(self, prompt_ids: List[int], max_new_tokens: int,
                 stop_token=None, sampling=None,
                 timeout: float = 600.0) -> List[int]:
        return self.submit(prompt_ids, max_new_tokens, stop_token,
                           sampling=sampling).result(timeout)

    # ------------------------------------------------------- KV handoff

    def export_prefill(self, prompt_ids: List[int],
                       page_size: Optional[int] = None,
                       binary: bool = False) -> Any:
        """Prefill a prompt and export its FULL KV pages for another
        replica to adopt (the prefill side of a disaggregated handoff).

        Runs the same chunked-prefill path an admission would, but into
        a private cache that never touches this engine's page pool —
        a prefill replica can export for many decode replicas without
        competing with its own admissions.  Returns the
        serve/handoff.py wire payload: the prompt's full pages in
        page-major layout (int8 + scales when this engine quantizes
        KV), plus the chain hashes the importer registers them under.
        The sub-page tail of the prompt is the importer's to prefill
        (it is < one page and rides the normal partial-prefix path).

        binary=True returns the `application/octet-stream` frame
        (handoff.encode_binary) instead of the JSON/base64 dict — same
        fields, raw array bytes, ~25% less on the wire.
        """
        import numpy as np  # pylint: disable=import-outside-toplevel

        from skypilot_tpu.models import decode  # pylint: disable=import-outside-toplevel
        handoff_lib.check_model(self.cfg)
        if self._stop.is_set() or self._failed is not None:
            raise RuntimeError('batching engine is stopped'
                               if self._failed is None else
                               f'batching engine failed: {self._failed}')
        ps = int(page_size) if page_size else self._kv.page_size
        n = len(prompt_ids)
        if n < 2:
            raise HandoffError('prompt too short to export')
        if n > self.max_len:
            raise HandoffError(
                f'prompt {n} exceeds this replica\'s max_len '
                f'{self.max_len}')
        full = (n - 1) // ps     # full pages inside the prefilled [0, n-1)
        if full < 1:
            raise HandoffError(
                f'prompt {n} holds no full {ps}-token page to export')
        hashes = cache_manager.chunk_hashes(prompt_ids[:n - 1], ps)
        n_target = n - 1
        encode = (handoff_lib.encode_binary if binary
                  else handoff_lib.encode_payload)
        with self._export_sem:
            cache = self._prefill_private(prompt_ids, n_target)
            if self.quantize_kv:
                kq, vq, ks, vs = decode.export_private_pages(
                    cache, full, ps, quantize=True)
                payload = encode(
                    hashes[:full], ps, np.asarray(kq), np.asarray(vq),
                    np.asarray(ks), np.asarray(vs))
            else:
                k, v = decode.export_private_pages(cache, full, ps)
                payload = encode(
                    hashes[:full], ps, np.asarray(k), np.asarray(v))
        _M_HANDOFF_EXPORTS.inc()
        return payload

    def _prefill_private(self, prompt_ids: List[int],
                         n_target: int) -> Dict[str, Any]:
        """Prefill tokens [0, n_target) into a FRESH private cache
        ([L, 1, h_kv, max_len, d]) without touching the page pool:
        chunk 0 through the bucketed flash path, then masked chunk
        continuations — the same compile cache the admission path
        uses.  The slice engine overrides this with a one-shot
        sequence-parallel prefill for long prompts."""
        import numpy as np  # pylint: disable=import-outside-toplevel
        jnp = self._jnp
        chunk = self.prefill_chunk
        take = min(n_target, chunk)
        bucket = min(self._bucket(take), self.max_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :take] = prompt_ids[:take]
        _, cache = self._prefill(self.params, jnp.asarray(padded))
        cache = dict(cache, index=jnp.asarray(take, jnp.int32))
        consumed = take
        while consumed < n_target:
            take = min(n_target - consumed, chunk)
            width = min(self._bucket(take), chunk,
                        self.max_len - consumed)
            piece = np.zeros((1, width), np.int32)
            piece[0, :take] = prompt_ids[consumed:consumed + take]
            _, cache = self._prefill_chunk(self.params,
                                           jnp.asarray(piece), cache)
            cache = dict(cache,
                         index=jnp.asarray(consumed + take, jnp.int32))
            consumed += take
        return cache

    def import_pages(self, hashes: List[int], page_size: int,
                     k_pages, v_pages, k_scale=None,
                     v_scale=None) -> Tuple[int, int]:
        """Adopt exported KV pages into this engine's pool + prefix
        cache (the decode side of a handoff).  Returns
        (pages_imported, pages_already_cached).

        The pages are published exactly like locally prefilled ones:
        registered in the prefix cache under their chain hashes, so
        the follow-up submit() adopts them as a prefix hit (and so do
        later requests sharing the prompt).  Pool exhaustion raises
        QueueFull (reason pages_exhausted -> HTTP 429 + Retry-After);
        any structural mismatch raises HandoffError — the router falls
        back to local prefill, the request is never lost.
        """
        import numpy as np  # pylint: disable=import-outside-toplevel
        from skypilot_tpu.chaos import injector  # pylint: disable=import-outside-toplevel
        handoff_lib.check_model(self.cfg)
        if not self._kv.prefix_caching:
            raise HandoffError('KV import needs the prefix cache '
                               '(imports publish pages through it)')
        if int(page_size) != self._kv.page_size:
            raise HandoffError(
                f'page_size mismatch: payload {page_size}, '
                f'pool {self._kv.page_size}')
        if len(hashes) > self._kv.pool.capacity:
            raise HandoffError(
                f'{len(hashes)} pages exceed pool capacity '
                f'{self._kv.pool.capacity}')
        if (getattr(k_pages, 'dtype', None) is not None and
                str(k_pages.dtype) == 'int8' and k_scale is None):
            raise HandoffError('int8 pages need their scales')
        # Chaos: deny -> the decode replica refuses the handoff (the
        # router must fall back to local prefill); delay -> handoff
        # latency (runs on the HTTP thread, never stalls the ticks).
        if injector.inject('serve.kv_handoff',
                           pages=len(hashes)) is injector.DENY:
            _M_HANDOFF_IMPORTS.labels(result='denied').inc()
            raise HandoffRejected(
                'chaos: KV handoff import denied')
        if self._stop.is_set() or self._failed is not None:
            raise RuntimeError('batching engine is stopped'
                               if self._failed is None else
                               f'batching engine failed: {self._failed}')
        holder: Dict[str, Any] = {}
        done = threading.Event()

        def op() -> None:
            # Runs ON THE WORKER THREAD: self._cache and the prefix
            # cache are worker-owned; every outcome lands in `holder`.
            try:
                if self._stop.is_set():
                    raise RuntimeError('batching engine stopped')
                cached = self._kv.import_prefix_depth(hashes)
                fresh_hashes = hashes[cached:]
                if not fresh_hashes:
                    holder['result'] = (0, cached)
                    return
                fresh = self._kv.alloc_pages(len(fresh_hashes))
                try:
                    jnp = self._jnp
                    ids = np.asarray(fresh, np.int32)
                    if k_scale is not None and self.quantize_kv:
                        # int8 wire -> int8 pool: scatter q/scale
                        # verbatim (no dequant/requant on the decode
                        # replica's critical path).
                        self._cache = self._write_pages_q(
                            self._cache,
                            jnp.asarray(k_pages[:, cached:]),
                            jnp.asarray(v_pages[:, cached:]),
                            jnp.asarray(k_scale[:, cached:]),
                            jnp.asarray(v_scale[:, cached:]), ids)
                    elif k_scale is not None:
                        # int8 wire -> float pool: dequantize once.
                        self._cache = self._write_pages(
                            self._cache,
                            jnp.asarray(
                                k_pages[:, cached:].astype(np.float32)
                                * k_scale[:, cached:, ..., None]),
                            jnp.asarray(
                                v_pages[:, cached:].astype(np.float32)
                                * v_scale[:, cached:, ..., None]),
                            ids)
                    else:
                        self._cache = self._write_pages(
                            self._cache,
                            jnp.asarray(k_pages[:, cached:]),
                            jnp.asarray(v_pages[:, cached:]), ids)
                    self._kv.prefix.register(fresh_hashes, fresh)
                finally:
                    # register() pinned the published pages; dropping
                    # the import's alloc ref leaves them pin-held (and
                    # frees them outright if anything above raised).
                    self._kv.pool.decref(fresh)
                holder['result'] = (len(fresh_hashes), cached)
            except BaseException as e:  # pylint: disable=broad-except
                holder['error'] = e
            finally:
                done.set()

        with self._host_ops_lock:
            self._host_ops.append(op)
        with self._cond:
            self._cond.notify_all()
        if not done.wait(timeout=60):
            _M_HANDOFF_IMPORTS.labels(result='timeout').inc()
            raise HandoffError('KV import timed out waiting for the '
                               'engine worker')
        if 'error' in holder:
            error = holder['error']
            if isinstance(error, cache_manager.PagesExhausted):
                _M_HANDOFF_IMPORTS.labels(
                    result='pages_exhausted').inc()
                raise self._queue.reject(
                    'pages_exhausted',
                    f'KV page pool exhausted for handoff import '
                    f'({len(hashes)} page(s) needed); retry later')
            _M_HANDOFF_IMPORTS.labels(result='error').inc()
            raise error
        _M_HANDOFF_IMPORTS.labels(result='ok').inc()
        return holder['result']

    def export_prefix_pages(self, max_pages: int = 64,
                            binary: bool = True) -> Any:
        """Export the hottest prefix-cache pages as a handoff payload
        (the drain-time sibling handoff: a retiring replica ships its
        still-pinned session prefixes to a same-role survivor so those
        sessions don't cold-start).  Unlike export_prefill this reads
        the POOL pages the prefix cache pins — no prefill runs.

        Returns the binary octet-stream frame (binary=True) or the
        JSON/base64 dict; raises HandoffError when this engine has no
        exportable prefixes (prefix caching off, empty cache)."""
        import numpy as np  # pylint: disable=import-outside-toplevel

        from skypilot_tpu.models import decode  # pylint: disable=import-outside-toplevel
        handoff_lib.check_model(self.cfg)
        if not self._kv.prefix_caching:
            raise HandoffError('prefix export needs the prefix cache')
        if self._stop.is_set() or self._failed is not None:
            raise RuntimeError('batching engine is stopped'
                               if self._failed is None else
                               f'batching engine failed: {self._failed}')
        holder: Dict[str, Any] = {}
        done = threading.Event()
        encode = (handoff_lib.encode_binary if binary
                  else handoff_lib.encode_payload)

        def op() -> None:
            # Worker thread: the pool cache and prefix cache are
            # worker-owned; the gather below reads pages no tick
            # mutates (full prefix pages are immutable once written).
            try:
                if self._stop.is_set():
                    raise RuntimeError('batching engine stopped')
                entries = self._kv.prefix.hot_entries(int(max_pages))
                if not entries:
                    raise HandoffError('no cached prefixes to export')
                hashes = [h for h, _ in entries]
                ids = np.asarray([p for _, p in entries], np.int32)
                k = self._cache['k']
                v = self._cache['v']
                # The wire holds [L, n, h_kv, ps, d] whatever the
                # pool's rows are (`decode.from_rows`).
                heads = lambda pages: decode.from_rows(pages, self.cfg, 2)
                if self.quantize_kv:
                    payload = encode(
                        hashes, self._kv.page_size,
                        np.asarray(heads(k['q'][:, ids])),
                        np.asarray(heads(v['q'][:, ids])),
                        np.asarray(k['scale'][:, ids]),
                        np.asarray(v['scale'][:, ids]))
                else:
                    payload = encode(
                        hashes, self._kv.page_size,
                        np.asarray(heads(k[:, ids]), np.float32),
                        np.asarray(heads(v[:, ids]), np.float32))
                holder['result'] = payload
            except BaseException as e:  # pylint: disable=broad-except
                holder['error'] = e
            finally:
                done.set()

        with self._host_ops_lock:
            self._host_ops.append(op)
        with self._cond:
            self._cond.notify_all()
        if not done.wait(timeout=60):
            raise HandoffError('prefix export timed out waiting for '
                               'the engine worker')
        if 'error' in holder:
            raise holder['error']
        _M_HANDOFF_EXPORTS.inc()
        return holder['result']

    def swap_params(self, new_params) -> int:
        """Swap the serving weights in place WITHOUT dropping the KV
        page pool or any in-flight request — the live half of
        `POST /weights_swap`.

        Runs as a host op ON THE WORKER THREAD between ticks: that IS
        the scoped tick pause — no tick can be mid-flight while
        self.params is reassigned, and the jitted steps take params as
        an argument (never donated), so the next tick simply decodes
        with the new weights against the same cache.  In-flight
        requests keep their KV pages; requests submitted after the
        swap are span-stamped with the new epoch.  Returns the new
        weight epoch.

        Callers are responsible for device placement (the server
        restores the checkpoint with the engine's shardings before
        calling) and pass the training layout, as to the constructor;
        the q/k/v kernels are re-formed here, on the caller's thread
        before the op is queued, so the pause stays the epoch-ordered
        assignment."""
        if self._stop.is_set() or self._failed is not None:
            raise RuntimeError('batching engine is stopped'
                               if self._failed is None else
                               f'batching engine failed: {self._failed}')
        from skypilot_tpu.models import decode  # pylint: disable=import-outside-toplevel
        new_params = decode.serving_params(self.cfg, new_params)
        holder: Dict[str, Any] = {}
        done = threading.Event()

        def op() -> None:
            # Worker thread: between ticks by construction.
            try:
                if self._stop.is_set():
                    raise RuntimeError('batching engine stopped')
                self.params = new_params
                self._weight_epoch += 1
                holder['result'] = self._weight_epoch
            except BaseException as e:  # pylint: disable=broad-except
                holder['error'] = e
            finally:
                done.set()

        with self._host_ops_lock:
            self._host_ops.append(op)
        with self._cond:
            self._cond.notify_all()
        if not done.wait(timeout=60):
            raise RuntimeError('weight swap timed out waiting for the '
                               'engine worker')
        if 'error' in holder:
            raise holder['error']
        return holder['result']

    @property
    def weight_epoch(self) -> int:
        return self._weight_epoch

    def _drain_host_ops(self) -> int:
        ran = 0
        while True:
            with self._host_ops_lock:
                if not self._host_ops:
                    return ran
                op = self._host_ops.popleft()
            op()   # no-raise by construction
            ran += 1

    def _drain_estimate(self) -> float:
        """Rough seconds until one queue position frees: backlog size
        over the recent decode rate (floor 1s — it feeds Retry-After)."""
        rate = self._decode_rate()
        if rate <= 0:
            return 1.0
        avg_new = 32.0  # no per-request oracle; a slot's typical budget
        return max(1.0, len(self._queue) * avg_new /
                   (rate * max(1, len(self._slots))))

    def _decode_rate(self) -> float:
        with self._metrics_lock:
            if not self._rate_window:
                return 0.0
            t0 = self._rate_window[0][0]
            span = time.monotonic() - t0
            total = sum(n for _, n in self._rate_window)
        return total / max(span, 1e-3)

    def stats(self) -> Dict[str, Any]:
        """Live scheduling + decode-saturation stats (surfaced via the
        server's /health): queue depth and slot occupancy are the
        scale-out signals, decode_tokens_per_s and the queue-wait
        histogram say whether the replica is decode-bound rather than
        merely popular (serve/autoscalers.py consumes busy/slots as
        replica load).  The page-pool view:
        kv_pages_{total,used,free,pinned}, prefix-cache entry/hit/miss
        counts, pages_exhausted_deferrals, and paged_kernel (the pages
        the decode ticks' live contexts held beside the rows of every
        block table: how much of `max_len` the traffic uses; and
        `walked_pages`, the pages the decode kernel is given to walk
        summed over the layers, a window layer's being those that hold
        its last `sliding_window` keys; `calls`, the kernel's calls, one
        a cache layer a tick; `kernel_writes` and `scatter_writes`, the
        ticks' cache-layer writes of their new K/V rows made by the
        kernel itself or by a scatter before it).  Expert models add
        `moe`: the expert layers' counts summed over ticks and layers
        (`touched`: held experts that a live token was routed to,
        summed over the ticks and layers).  A model with conv layers adds `conv`: `layers`,
        `state_bytes` (the slots' tails), `snapshots_saved` (prompt
        pages published with the tails at their end),
        `snapshots_restored` (prefix hits that took their tails from
        their last page's), `restored_tokens` (the prompt tokens those
        hits restored, pages and tails) and `prompt_tokens` (admitted).
        A model with mamba layers adds `ssm`: `state_bytes` (the slots'
        states), `snapshot_rows` (entries of the snapshot store),
        `snapshots_saved`, `snapshots_restored`, `snapshots_evicted`,
        `prompt_tokens` (admitted), `restored_tokens` (those a hit
        restored, pages and states) and `recomputed_tokens` (those whose
        pages were cached but whose states were not: prefilled again).
        A looped stack adds `loop`: `steps` (passes over the stack a token),
        `cache_layers` (the pool's: layers x steps), `passes` (stack
        passes run by the ticks read, `steps` a tick) and `exit_mass`
        (by pass, the share of each token decoded by a live slot that
        left after that pass, summed: it adds up to the tokens)."""
        from skypilot_tpu.models import decode  # pylint: disable=import-outside-toplevel
        busy = sum(1 for s in self._slots if s.active)
        with self._metrics_lock:
            stats = {
                'slots': len(self._slots),
                'busy_slots': busy,
                'tokens_generated': self._tokens_generated,
                'failed': self._failed is not None,
                'ticks': self._ticks,
                'prefill_chunks': self._prefill_chunks,
                # ... of which rode the tick of at least one live slot.
                'prefill_chunks_fused': self._prefill_chunks_fused,
                'prefill_chunk': self.prefill_chunk,
                'decode_kernel': self.decode_kernel,
                'spec_tokens': self.spec_tokens,
                'weight_epoch': self._weight_epoch,
                # Bytes of q/k/v projection kernels (and biases) held
                # in the serving form; 0: the layer scan copies each
                # layer's kernels out of the stack before the product.
                'weights': {'reformed_bytes': decode.serving_form_bytes(
                    self.cfg, self.params)},
            }
            if self.spec_tokens:
                stats['spec_ticks'] = self._spec_ticks
                stats['spec_proposed_tokens'] = self._spec_proposed
                stats['spec_accepted_tokens'] = self._spec_accepted
                # Mean tokens per slot per verify tick: accepted
                # drafts plus the always-emitted verified base token.
                stats['spec_accept_len_mean'] = (
                    round((self._spec_accepted +
                           self._spec_slot_ticks) /
                          self._spec_slot_ticks, 3)
                    if self._spec_slot_ticks else None)
            if self._moe_counts is not None:
                stats['moe'] = dict(zip(
                    ('tokens', 'held_pairs', 'max_expert_tokens',
                     'touched'), self._moe_counts))
            if self.cfg.conv_layers:
                stats['conv'] = dict(
                    self._conv_counts, layers=self.cfg.conv_layers,
                    state_bytes=self._cache['conv'].nbytes)
            if self._snapshots is not None:
                stats['ssm'] = dict(
                    self._ssm_counts,
                    state_bytes=(self._cache['ssm'].nbytes +
                                 self._cache['xbc'].nbytes),
                    snapshot_rows=self._snapshots.entries,
                    snapshots_evicted=self._snapshots.evicted)
            if self.cfg.loop_passes > 1:
                stats['loop'] = {
                    'steps': self.cfg.loop_passes,
                    'cache_layers': self.cfg.cache_layers,
                    'passes': self.cfg.loop_passes * self._ticks,
                    'exit_mass': list(self._exit_mass)}
        stats.update(self._queue.stats())
        stats.update(self._kv.stats())
        with self._metrics_lock:
            stats['pages_exhausted_deferrals'] = self._page_deferrals
            # Cumulative over ticks: what the decode kernel walked of
            # what the block tables have rows for.
            stats['paged_kernel'] = {
                'live_pages': self._kernel_live_pages,
                'table_pages': self._kernel_table_pages,
                'walked_pages': self._kernel_walked_pages,
                'calls': self._kernel_calls,
                'heads_per_row': self._heads_per_row, **self._kv_writes}
        rate = round(self._decode_rate(), 3)
        stats['decode_tokens_per_s'] = rate
        # The worker loop's cumulative totals (iterations, seconds by
        # phase, the starvation probe): monotone, so a reader takes a
        # difference.  `starved_s` is an estimate; the ring stays in
        # profile().
        stats['tick_loop'] = self._profiler.tick_loop()
        # Per-request phase traces (newest first) — the "why was THIS
        # request slow" answer, keyed by X-SkyTPU-Request-Id.
        stats['recent_spans'] = self._spans.recent()
        # Freshen the scrape-time gauges so /metrics agrees with
        # /health no matter which is polled.
        _M_SLOTS.set(stats['slots'])
        _M_BUSY_SLOTS.set(busy)
        _M_DECODE_RATE.set(rate)
        return stats

    def span(self, request_id: str) -> Optional[Dict[str, Any]]:
        """The finished span record for a request id (None while the
        request is still running or once it aged out of the store)."""
        return self._spans.get(request_id)

    def profile(self) -> Dict[str, Any]:
        """Continuous-profiling snapshot (what `GET /profile` serves):
        the tick-phase ring with per-phase quantiles, the cumulative
        `tick_loop` totals, the device-memory watermark (read now, on
        this thread), the profiler's modeled self-overhead, and the
        recompile sentinel's per-jit-entry compile counts."""
        snap = self._profiler.snapshot()
        snap['recompiles'] = self._sentinel.snapshot()
        return snap

    def set_role_budget(
            self, budget: Optional[scheduler.RoleBudget]) -> bool:
        """Swap the fractional-role budget in place — warm weights and
        page pool untouched; the next tick's admission gate and prefill
        chunk clamp pick it up.  Version-ordered: a stale push (lower
        version than the one in force) is dropped and False returned.
        None removes the clamp entirely."""
        return self._queue.set_role_budget(budget)

    @property
    def role_budget(self) -> Optional[scheduler.RoleBudget]:
        return self._queue.role_budget

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        self._thread.join(timeout=10)
        # Fail fast for anything still queued or in flight — callers
        # must not sit out their full result() timeout at shutdown.
        self._queue.drain(
            lambda: RuntimeError('batching engine stopped'))
        for slot in self._slots:
            if slot.request is not None:
                slot.request._finish(  # pylint: disable=protected-access
                    RuntimeError('batching engine stopped'))
                slot.request = None
            slot.drafter = None
        # Host-side accounting only (the device is going away): every
        # slot- and prefix-held page returns to the pool, so the
        # alloc/free journal balances.
        self._kv.release_all()
        # Handoff imports still queued never ran; unblock their waiters.
        self._drain_host_ops()

    # ------------------------------------------------------------ metrics

    def _record_tokens(self, n: int) -> None:
        now = time.monotonic()
        with self._metrics_lock:
            self._tokens_generated += n
            self._rate_window.append((now, n))
            while (self._rate_window and
                   now - self._rate_window[0][0] > 10.0):
                self._rate_window.popleft()
        _M_TOKENS.inc(n)
        _M_DECODE_RATE.set(round(self._decode_rate(), 3))

    def _count_conv(self, **counts: int) -> None:
        """Add to stats()['conv'] (a model with conv layers only)."""
        if not self.cfg.conv_layers:
            return
        with self._metrics_lock:
            for key, n in counts.items():
                self._conv_counts[key] += n
        _M_CONV_SNAPSHOTS_SAVED.inc(counts.get('snapshots_saved', 0))
        _M_CONV_SNAPSHOTS_RESTORED.inc(counts.get('snapshots_restored', 0))

    def _count_ssm(self, **counts: int) -> None:
        """Add to stats()['ssm'] (a model with mamba layers only)."""
        with self._metrics_lock:
            for key, n in counts.items():
                self._ssm_counts[key] += n
        for key, metric in (('snapshots_saved', _M_SSM_SNAPSHOTS_SAVED),
                            ('snapshots_restored', _M_SSM_SNAPSHOTS_RESTORED),
                            ('prompt_tokens', _M_SSM_PROMPT_TOKENS),
                            ('restored_tokens', _M_SSM_RESTORED_TOKENS),
                            ('recomputed_tokens', _M_SSM_RECOMPUTED_TOKENS)):
            metric.inc(counts.get(key, 0))

    def _record_chunk(self, fused: bool = False) -> None:
        _M_PREFILL_CHUNKS.inc()
        if fused:
            _M_PREFILL_CHUNKS_FUSED.inc()
        with self._metrics_lock:
            self._prefill_chunks += 1
            self._prefill_chunks_fused += fused

    # ------------------------------------------------------------ worker

    def _bucket(self, n: int) -> int:
        for b in _PREFILL_BUCKETS:
            if n <= b:
                return b
        return n

    def _chunk_width(self, take: int, chunk: int) -> int:
        """The width `take` prompt tokens are padded to for one chunk
        of at most `chunk`: their power-of-two bucket, and no less than
        `_FUSED_MIN_WIDTH` where the chunk rides the tick."""
        width = self._bucket(take)
        if self._chunk_step is not None:
            width = max(width, min(_FUSED_MIN_WIDTH, self._bucket(chunk)))
        return width

    # --------------------------------------------------------- admission

    def _pad_row(self, row: List[int]):
        import numpy as np  # pylint: disable=import-outside-toplevel
        padded = np.zeros((self.max_len // self._kv.page_size,),
                          np.int32)
        padded[:len(row)] = row
        return self._jnp.asarray(padded)

    def _start_admission(self, slot_id: int,
                         request: scheduler.Request
                         ) -> Optional[scheduler.PendingPrefill]:
        """Begin admitting `request` into `slot_id`.  Returns a
        PendingPrefill when chunks remain, None when the slot is live
        (or the request finished at admission).  Raises PagesExhausted
        (pool backpressure) BEFORE touching any state — the caller
        requeues the request at the head."""
        slot = self._slots[slot_id]
        prompt = request.prompt_ids
        n = len(prompt)
        # Match the prefix cache and allocate the request's pages (a
        # model with mamba layers: a hit as deep as a state snapshot).
        plan = self._kv.plan_admission(    # may raise PagesExhausted
            prompt, request.max_new_tokens,
            hit_depth=(None if self._snapshots is None
                       else self._snapshots.depth))
        request.span.prefix_hit_pages = (plan.n_reuse_tokens //
                                         self._kv.page_size)
        self._kv.commit(slot_id, plan)
        self._queue.record_admission(request, self._profiler.iteration)
        # A model with conv layers: the slot's tails come from the
        # snapshot of the hit's last page (the null page's: none).
        tails = {}
        if self.cfg.conv_layers:
            tails['snap_page'] = (plan.reuse_pages or
                                  [cache_manager.NULL_PAGE])[-1]
        self._count_conv(prompt_tokens=n)
        # A model with mamba layers: the hit's states come from its
        # snapshot (pinned until the restore is dispatched), and the
        # boundaries the prefill will save snapshots at.
        restore, save = None, None
        if self._snapshots is not None:
            restore, save = self._plan_snapshots(plan, n)
            if restore is not None:
                tails['snapshot'] = restore[1]
        if n <= 1:
            # Single-token prompt: empty slot; stale keys are masked
            # (per-position causal mask) and position 0 is overwritten
            # by the first step's write.
            self._cache = self._admit_paged(
                self._cache, slot_id, self._pad_row(plan.row), 0, **tails)
            slot.request = request
            self._activate(slot_id, request, int(prompt[-1]), 0,
                           remaining=request.max_new_tokens,
                           key=self._jax.random.PRNGKey(request.seed))
            return None
        if plan.n_reuse_tokens >= n - 1:
            # Full prefix hit (the prefilled region [0, n-1) is page-
            # aligned and entirely cached): no prefill at all — the
            # slot joins the next tick and TTFT collapses to one step.
            self._cache = self._admit_paged(
                self._cache, slot_id, self._pad_row(plan.row), n - 1,
                **tails)
            self._count_conv(snapshots_restored=1,
                             restored_tokens=plan.n_reuse_tokens)
            self._restored(restore)
            slot.request = request
            self._activate(slot_id, request, int(prompt[-1]), n - 1,
                           remaining=request.max_new_tokens,
                           key=self._jax.random.PRNGKey(request.seed))
            return None
        # Prefill tokens [0, n-1) in chunks; the last REAL
        # prompt token is fed through the first batched step (it
        # overwrites the first pad position and attends only real
        # keys, so logits match unpadded decode exactly).
        slot.request = request
        pending = scheduler.PendingPrefill(slot_id, request, n - 1, plan)
        pending.restore, pending.save = restore, save
        return pending

    def _plan_snapshots(self, plan, n: int):
        """A model with mamba layers: (the snapshot a hit restores, as
        (prefix hash, store entry) pinned, or None; the page boundary,
        as (tokens, prefix hash), at which the prefill saves the state,
        or None).  Saved: the branch point, the deepest boundary the
        prompt shares with the prefix cache, where the hit stops short
        of it for want of a snapshot; and a prompt that shares nothing
        has the state at its deepest boundary that is a whole number of
        prefill chunks (and pages) saved.  A prompt that shares nothing
        cannot tell where the part a later prompt will share ends (a
        document, a system prompt, the turns so far): a branch inside
        its last chunk resumes from there and recomputes less than a
        chunk, and the branch point's own snapshot is saved then.
        Counts the prompt's tokens."""
        ps = self._kv.page_size
        hashes = plan.page_hashes
        depth, shared = plan.n_reuse_tokens // ps, len(plan.reuse_pages)
        restore = None
        if depth:
            restore = (hashes[depth - 1],
                       self._snapshots.acquire(hashes[depth - 1]))
        save = None
        if shared > depth:
            save = (shared * ps, hashes[shared - 1])
        elif not shared and self._kv.prefix_caching:
            align = math.lcm(self.prefill_chunk, ps) // ps   # pages
            at = len(hashes) // align * align
            if at:
                save = (at * ps, hashes[at - 1])
        self._count_ssm(prompt_tokens=n, restored_tokens=depth * ps,
                        recomputed_tokens=(shared - depth) * ps)
        return restore, save

    def _restored(self, restore) -> None:
        """A hit's snapshot was read (its restore is dispatched)."""
        if restore is not None:
            self._snapshots.release(*restore)
            self._count_ssm(snapshots_restored=1)

    def _advance_prefill(self, pending: scheduler.PendingPrefill,
                         riders: int = 0
                         ) -> Tuple[bool, Optional[Tuple[Any, ...]]]:
        """Run ONE chunk of a pending prefill (this is the whole point:
        an admission stalls running decodes by at most one chunk).
        Returns (done, tick): done when the prefill completed and the
        slot went live (or its request was dropped and the slot freed);
        tick, where the chunk rode the decode tick (`_dispatch_chunk`),
        that tick's (state, finished, counts, exit mass), the tick of
        the `riders` slots live at the call; None where no tick ran.
        """
        jnp = self._jnp
        request = pending.request
        if request.cancelled or request.deadline_exceeded():
            if request.cancelled:
                request._finish()  # pylint: disable=protected-access
            else:
                _M_DEADLINE_REAPED.inc()
                request._finish(  # pylint: disable=protected-access
                    scheduler.DeadlineExceeded(
                        'request deadline passed mid-prefill'))
            self._slots[pending.slot_id].request = None
            self._release_slot_pages(pending.slot_id)
            if pending.cache is None and pending.restore is not None:
                # Its snapshot was never read: unpin it.
                self._snapshots.release(*pending.restore)
            return True, None  # pending is finished (slot freed)
        import numpy as np  # pylint: disable=import-outside-toplevel
        n_target = pending.n_target
        # Fractional-role clamp: a decode-heavy budget shrinks the
        # per-tick piece (floor 1 — prefill slows, never stalls).
        chunk = self._queue.prefill_tokens_per_tick(self.prefill_chunk)
        plan = pending.plan
        reuse_tokens = plan.n_reuse_tokens
        seeding = pending.cache is None and reuse_tokens > 0
        tick = None
        # The phase and `span.prefill_s` time the host's DISPATCH of
        # the program (asynchronous), not the program.
        with self._profiler.phase(
                'prefill-chunk', request_id=request.request_id) as phase:
            t_chunk0 = time.monotonic()
            if seeding:
                # Prefix hit: seed the private cache from the cached
                # pages — positions [0, reuse_tokens) appear exactly as
                # if they had been prefilled here; only the tail chunks
                # run (count 0: no prompt token is computed).
                snapshot = ()
                if self._snapshots is not None:
                    snapshot = (pending.restore[1],)
                pending.cache = self._seed_private(
                    self._cache,
                    np.asarray(plan.reuse_pages[
                        :reuse_tokens // self._kv.page_size], np.int32),
                    *snapshot, priv_len=self.max_len)
                pending.consumed = reuse_tokens
                self._count_conv(snapshots_restored=1,
                                 restored_tokens=reuse_tokens)
                if self._snapshots is not None:
                    self._restored(pending.restore)
                phase.count = 0
            elif pending.cache is None:
                # Chunk 0: flash prefill from index 0 into a fresh
                # private cache.  Width = the bucket of min(n_target,
                # chunk) so short prompts keep today's bucket-bounded
                # compile count; pad keys land at positions >= the real
                # length where the causal mask hides them (and the
                # first one is overwritten by the real last token's
                # step).  Padding is staged in NUMPY: eager
                # `.at[:n].set` would compile a tiny scatter per
                # distinct prompt length, right on the admission path.
                take = min(n_target, chunk)
                snap = self._snapshot_in(pending, 0, take)
                bucket = min(self._chunk_width(take, chunk), self.max_len)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :take] = request.prompt_ids[:take]
                pending.cache, tick = self._dispatch_chunk(
                    jnp.asarray(padded), None, take, riders, snap)
                self._save_snapshot_of(pending, snap)
                # The padded flash cache advanced index to `bucket`;
                # chunk continuations must write at the REAL consumed
                # length.
                pending.cache = dict(pending.cache,
                                     index=jnp.asarray(take, jnp.int32))
                pending.consumed = take
                phase.count = bucket
            else:
                # Chunk i>0: masked per-position-causal continuation at
                # index = consumed.  Width is the POWER-OF-TWO BUCKET
                # of the remaining tail capped at `chunk` (bounded
                # compile count) AND at max_len - start: the write must
                # fit the private cache — a wider piece would make
                # dynamic_update_slice clamp its start index and
                # silently overwrite already-prefilled positions
                # (reachable when chunk does not divide max_len, and on
                # every prefix-hit seed whose tail is shorter than one
                # chunk).  Pad positions are beyond every real query's
                # causal horizon and each is overwritten by the decode
                # step that reaches it.
                start = pending.consumed
                take = min(n_target - start, chunk)
                snap = self._snapshot_in(pending, start, take)
                width = min(self._chunk_width(take, chunk), chunk,
                            self.max_len - start)
                piece = np.zeros((1, width), np.int32)
                piece[0, :take] = request.prompt_ids[start:start + take]
                pending.cache, tick = self._dispatch_chunk(
                    jnp.asarray(piece), pending.cache, take, riders, snap)
                self._save_snapshot_of(pending, snap)
                pending.cache = dict(
                    pending.cache,
                    index=jnp.asarray(start + take, jnp.int32))
                pending.consumed = start + take
                phase.count = width
            request.span.mark_prefill_chunk(time.monotonic() - t_chunk0)
        if seeding:
            return False, None
        self._record_chunk(fused=tick is not None and riders > 0)
        if pending.consumed < n_target:
            return False, tick
        return self._finish_prefill(pending), tick

    @staticmethod
    def _snapshot_in(pending, start: int, take: int):
        """A model with mamba layers: the prompt's save boundary as (rows
        into the chunk, prefix hash) where it falls inside the chunk's
        (start, start + take], else None."""
        if pending.save is None or not start < pending.save[0] <= (
                start + take):
            return None
        return pending.save[0] - start, pending.save[1]

    def _save_snapshot_of(self, pending, snap) -> None:
        """The chunk just dispatched computed the prompt's state at a
        save boundary (`snap`): into a store entry, if one is free or
        can be freed."""
        if snap is None:
            return
        _, h = snap
        pending.save = None
        entry = self._snapshots.reserve(h)
        if entry is None:
            return
        self._cache = self._save_snapshot(self._cache,
                                          pending.cache['snap'], entry)
        self._count_ssm(snapshots_saved=1)

    def _dispatch_chunk(self, piece, cache, take: int, riders: int,
                        snap=None):
        """Dispatch one prefill chunk, `piece` [1, width] holding `take`
        prompt tokens, of the prompt whose private cache is `cache`
        (None: its first chunk) -> (the private cache with the chunk in
        it, tick).  Where the engine
        fuses, the chunk rides the decode tick, one program for both
        (`decode.paged_engine_step_with_chunk`), and tick is that
        tick's (state, finished, counts, exit mass); it runs whether or
        not a slot is live (`riders` 0: over frozen slots, a tick
        nobody reads), so which program a (kind, width) of chunk takes
        never hangs on what else the engine is doing.  Otherwise the
        standalone programs run and tick is None."""
        if self._chunk_step is None:
            if cache is None:
                return self._prefill(self.params, piece)[1], None
            return self._prefill_chunk(self.params, piece, cache)[1], None
        # `decode-step` inside the chunk's own phase: this dispatch is
        # the iteration's tick too.
        extra = ()
        if self._snapshots is not None:
            # The states after the first `snap_at` rows come back as
            # the cache's snapshot (the chunk's end where no boundary
            # falls inside it).
            extra = (self._jnp.asarray(take if snap is None else snap[0],
                                       self._jnp.int32),)
        with self._profiler.phase('decode-step', count=riders):
            (self._state, self._cache, finished, moe, exit_mass,
             cache) = self._chunk_step(
                 self.params, self._state, self._cache, piece, cache,
                 self._jnp.asarray(take, self._jnp.int32), *extra)
        return cache, (self._state, finished, moe, exit_mass)

    def _finish_prefill(self, pending: scheduler.PendingPrefill) -> bool:
        """All chunks in: adopt the private cache into the page pool
        and join the next decode tick at length n-1 with the last REAL
        prompt token as input.  Split out of `_advance_prefill` so the
        slice engine's sequence-parallel prefill (one shot instead of
        chunks) lands through the same adoption path."""
        import numpy as np  # pylint: disable=import-outside-toplevel
        request = pending.request
        n_target = pending.n_target
        plan = pending.plan
        # Cache adoption (page scatter) + activation: its own phase so
        # prefill compute and pool surgery separate.
        with self._profiler.phase('page-scatter',
                                  request_id=request.request_id) as phase:
            # Scatter only the FRESH pages (the reused prefix already
            # lives in the pool — rewriting pages another slot shares,
            # even with identical values, is what this skips), then
            # point the block table at the full row and publish the
            # fresh full pages for the next prefix hit.
            ps = self._kv.page_size
            r = len(plan.reuse_pages)
            n_prompt_pages = -(-n_target // ps)
            phase.count = n_prompt_pages - r
            self._cache = self._insert_pages(
                self._cache, pending.cache,
                np.asarray(plan.row[r:n_prompt_pages], np.int32),
                first_page=r)
            # The slot's conv tails (or mamba states) are the prompt's,
            # after its last prefilled token.
            tails = ({'tail': pending.cache['conv'][:, 0]}
                     if self.cfg.conv_layers else {})
            if self._snapshots is not None:
                tails['states'] = {key: pending.cache[key]
                                   for key in ('ssm', 'xbc')}
            pending.cache = None   # donated to the scatter
            self._cache = self._admit_paged(
                self._cache, pending.slot_id,
                self._pad_row(plan.row), n_target, **tails)
            self._kv.register_prefix(plan)
            if self._kv.prefix_caching:
                self._count_conv(snapshots_saved=max(
                    0, len(plan.page_hashes) - r))
            self._activate(pending.slot_id, request,
                           int(request.prompt_ids[-1]), n_target,
                           remaining=request.max_new_tokens,
                           key=self._jax.random.PRNGKey(request.seed))
        return True

    def _activate(self, slot_id: int, request: scheduler.Request,
                  token: int, length: int, *, remaining: int,
                  key) -> None:
        """Flip a slot live in the device state (one jitted dispatch)."""
        # The device's cache lengths are set by the admission paths;
        # the host keeps its own count for stats()['paged_kernel'].
        self._slots[slot_id].depth = length
        if self.spec_tokens:
            # Seed the slot's drafter with everything decoded so far:
            # the history must END with the token the next tick feeds
            # (prompt[-1]) so the n-gram tail predicts continuations of
            # it.
            self._slots[slot_id].drafter = sampler_lib.NgramDrafter(
                list(request.prompt_ids) + list(request.tokens))
        self._state = self._sampler.admit(
            self._state, slot_id, token, remaining, request.stop_ids,
            key, request.temperature, request.top_k)
        request.span.mark_live(self._profiler.iteration)

    def _deactivate(self, slot_ids: List[int]) -> None:
        """Host-forced slot shutdown (cancel): flip active off so the
        next tick freezes the slot."""
        active = self._state['active']
        for i in slot_ids:
            active = active.at[i].set(False)
        self._state = dict(self._state, active=active)

    def _release_slot_pages(self, slot_id: int) -> None:
        """Park the slot's block table on the null page (stale
        in-flight writes land in garbage, never in recycled pages),
        THEN return its pages to the pool."""
        self._cache = self._release_paged(self._cache, slot_id)
        self._kv.release(slot_id)

    def _count_kernel_pages(self, live, s_q: int) -> None:
        """Add one tick to stats()['paged_kernel']: the pages
        that hold each live slot's cache and the tick's `s_q` new
        tokens (`live_pages`) beside the rows of every slot's block
        table, the pages the decode kernel is given to walk summed
        over the cache's layers (`walked_pages`): a window layer's walk
        starts at the page of the first query's first key; the
        kernel's calls, one a cache layer; and the cache layers'
        writes of the new rows, by the route the tick's program was
        built with (`decode.kernel_writes_rows`).  From the host's own
        depth of each slot, no device read."""
        ps = self._kv.page_size
        held = walked = 0
        for i in live:
            depth = self._slots[i].depth
            pages = -(-(depth + s_q) // ps)
            held += pages
            walked += sum(
                n * (pages - (max(depth - w + 1, 0) // ps if w else 0))
                for w, n in self._layers_by_window.items())
        rows = len(self._slots) * (self.max_len // ps)
        in_kernel = self._rows_in_kernel[s_q]
        with self._metrics_lock:
            self._kernel_live_pages += held
            self._kernel_table_pages += rows
            self._kernel_walked_pages += walked
            self._kernel_calls += self.cfg.cache_layers
            self._kv_writes['kernel_writes' if in_kernel else
                            'scatter_writes'] += self.cfg.cache_layers
        if in_kernel:
            _M_KV_KERNEL_WRITES.inc(self.cfg.cache_layers)
        _M_KERNEL_LIVE_SHARE.set(held / rows)

    def _count_tick(self, moe, exit_mass) -> None:
        """Add what one tick counted on the device (its outputs beside
        `finished`, read with it) to stats(): the expert layers' counts
        ('moe') and a looped stack's exit mass by pass ('loop'); each
        None for a model without."""
        if moe is None and exit_mass is None:
            return
        import numpy as np  # pylint: disable=import-outside-toplevel
        with self._metrics_lock:
            if moe is not None:
                if self._moe_counts is None:
                    self._moe_counts = [0] * len(moe)
                for j, c in enumerate(np.asarray(moe)):
                    self._moe_counts[j] += int(c)
            if exit_mass is not None:
                for j, m in enumerate(np.asarray(exit_mass)):
                    self._exit_mass[j] += float(m)

    def _dispatch_step(self):
        """Dispatch one jitted engine tick.  The slice engine
        (serve/slice_replica.py) overrides this to broadcast the tick
        through its rank coordinator first — every host of a multi-host
        replica must dispatch the same SPMD step in lockstep."""
        return self._step(self.params, self._state, self._cache)

    def _dispatch_spec_step(self, drafts):
        """Dispatch one jitted speculative verify tick (the slice
        engine broadcasts it through its rank coordinator, exactly
        like `_dispatch_step`)."""
        return self._spec_step(self.params, self._state, self._cache,
                               drafts)

    def _spec_tick(self, live: Dict[int, scheduler.Request]) -> None:
        """One SYNCHRONOUS speculative tick: host drafters propose k
        tokens per live slot, ONE batched verify dispatch scores all of
        them against the paged cache, and each slot emits its longest
        exactly-matching prefix plus the verified bonus token.

        Spec mode gives up the one-deep tick pipeline on purpose: the
        drafter needs the tokens a tick just emitted before it can
        propose the next batch, so tick t+1's input depends on a host
        read of tick t.  What it buys back is up to k+1 tokens per
        dispatch — on repetitive text the dispatch count (the per-token
        floor on ITL) drops by the mean acceptance length.  Token
        streams are byte-identical to spec-off by construction: every
        emitted token is the engine's own verified choice, drafts only
        decide how many land per dispatch.
        """
        import numpy as np  # pylint: disable=import-outside-toplevel
        prof = self._profiler
        k = self.spec_tokens
        n_live = len(live)
        with prof.phase('spec-verify', count=n_live):
            drafts = np.zeros((len(self._slots), k), np.int32)
            for slot_id in live:
                drafter = self._slots[slot_id].drafter
                if drafter is not None:
                    drafts[slot_id] = drafter.propose(k)
            self._count_kernel_pages(live, k + 1)
            drafts_dev = self._jnp.asarray(drafts)
            if self._mesh is not None:
                from skypilot_tpu.parallel import sharding as sharding_lib  # pylint: disable=import-outside-toplevel
                drafts_dev = self._jax.device_put(
                    drafts_dev,
                    sharding_lib.spec_drafts_sharding(self._mesh))
            (self._state, self._cache, finished, toks_d, counts_d,
             moe_d, exit_d) = self._dispatch_spec_step(drafts_dev)
        with prof.device_wait(finished, count=n_live):
            toks = np.asarray(toks_d)
            counts = np.asarray(counts_d)
            fins = np.asarray(finished)
            self._count_tick(moe_d, exit_d)
        with prof.phase('sample') as phase:
            pushed = 0
            accepted = 0
            slot_ticks = 0
            for slot_id, request in list(live.items()):
                if request.done.is_set():
                    continue
                slot_ticks += 1
                c = int(counts[slot_id])
                self._slots[slot_id].depth += c
                emitted = [int(t) for t in toks[slot_id, :c]]
                drafter = self._slots[slot_id].drafter
                if drafter is not None and emitted:
                    drafter.observe(emitted)
                for token in emitted:
                    request._push(token)  # pylint: disable=protected-access
                pushed += c
                accepted += max(c - 1, 0)
                span = request.span
                span.spec_steps += 1
                span.spec_proposed += k
                span.spec_accepted += max(c - 1, 0)
                _M_SPEC_ACCEPT_LEN.observe(float(max(c, 1)))
                if fins[slot_id]:
                    live.pop(slot_id, None)
                    self._slots[slot_id].request = None
                    self._slots[slot_id].drafter = None
                    self._release_slot_pages(slot_id)
                    request._finish()  # pylint: disable=protected-access
            if pushed:
                self._record_tokens(pushed)
            with self._metrics_lock:
                self._ticks += 1
                self._spec_ticks += 1
                self._spec_slot_ticks += slot_ticks
                self._spec_proposed += k * n_live
                self._spec_accepted += accepted
            _M_TICKS.inc()
            _M_SPEC_PROPOSED.inc(k * n_live)
            _M_SPEC_ACCEPTED.inc(accepted)
            _M_BUSY_SLOTS.set(sum(1 for s in self._slots if s.active))
            phase.count = pushed

    # ------------------------------------------------------------ worker

    def _run(self) -> None:
        # Profiling lifecycle: one start/end pair brackets the worker's
        # whole run so journal replay can attribute the ring's ticks to
        # an engine incarnation (and see whether it died or drained).
        # The profiler's collector hook lives exactly as long.
        prof = self._profiler
        try:
            journal = profiling.serve_journal()
        except Exception:  # pylint: disable=broad-except
            journal = None
        if journal is not None:
            journal.append('tick_profile_start',
                           ring_ticks=prof.ring_ticks,
                           enabled=not prof.disabled)
        prof.start()
        try:
            self._run_pipelined(prof)
        finally:
            prof.close()
            if journal is not None:
                journal.append(
                    'tick_profile_end',
                    status='error' if self._failed is not None else 'ok',
                    ticks=prof.ticks)

    def _run_pipelined(self, prof: profiling.TickProfiler) -> None:
        import numpy as np  # pylint: disable=import-outside-toplevel
        # One in-flight tick: (state_handles, finished_handle,
        # [(slot_id, request), ...], the expert layers' counts or
        # None, a looped stack's exit mass or None) — read one tick
        # behind.
        inflight: Optional[Tuple[Any, Any, List[Tuple[int, Any]],
                                 Any, Any]] = None
        pending_prefills: Deque[scheduler.PendingPrefill] = (
            collections.deque())
        live: Dict[int, scheduler.Request] = {}  # slot -> decoding req
        while not self._stop.is_set():
            try:
                prof.begin_tick()
                self._queue.expire_stale()
                # Host ops (KV handoff imports) run between ticks: they
                # mutate self._cache, which only this thread owns.
                if self._host_ops:
                    with prof.phase('handoff') as phase:
                        phase.count = self._drain_host_ops()
                # Cancelled or deadline-expired live requests: freeze
                # their slots on device before the next dispatch, free
                # them (and their KV pages) for admission.  Deadline
                # reaps finish with DeadlineExceeded so the HTTP front
                # answers 504 instead of a silent truncation.  (Making
                # room is part of admitting: the phase is `admit`.)
                now = time.monotonic()
                reaped = [(i, r.cancelled) for i, r in live.items()
                          if r.cancelled or r.deadline_exceeded(now)]
                if reaped:
                    with prof.phase('admit', count=len(reaped)):
                        self._deactivate([i for i, _ in reaped])
                        for i, was_cancel in reaped:
                            request = live.pop(i)
                            self._slots[i].request = None
                            self._slots[i].drafter = None
                            self._release_slot_pages(i)
                            if was_cancel:
                                request._finish()  # pylint: disable=protected-access
                            else:
                                _M_DEADLINE_REAPED.inc()
                                request._finish(  # pylint: disable=protected-access
                                    scheduler.DeadlineExceeded(
                                        'request deadline passed '
                                        'mid-generation'))
                # Admissions: hand free slots to queued requests, one
                # `admit` phase each.  The prompt's chunks run
                # interleaved with ticks below.  Page-pool exhaustion
                # DEFERS (the request goes back to the queue head and
                # waits for pages to free or its TTL) — it must never
                # fail the engine.
                deferred = False
                free = [i for i, s in enumerate(self._slots)
                        if not s.active]
                occupied = len(self._slots) - len(free)
                for slot_id in free:
                    # Fractional-role decode budget: stop admitting
                    # once occupied slots reach the decode-token cap
                    # (queued requests keep their WRR order; running
                    # decodes always finish).
                    if not self._queue.admission_allowed(occupied):
                        break
                    if len(pending_prefills) >= self._max_prefills:
                        break
                    request = self._queue.pop()
                    if request is None:
                        break
                    try:
                        # Bind request identity so engine-worker log
                        # lines land in the structured ring under the
                        # request that triggered them (the worker
                        # thread never sees the HTTP front's context).
                        with prof.phase('admit',
                                        request_id=request.request_id,
                                        count=len(request.prompt_ids)), \
                                logs_lib.bind(
                                    request_id=request.request_id,
                                    **(getattr(self, 'log_identity',
                                               None) or {})):
                            pending = self._start_admission(
                                slot_id, request)
                    except cache_manager.PagesExhausted:
                        self._queue.requeue_front(request)
                        with self._metrics_lock:
                            self._page_deferrals += 1
                        deferred = True
                        break
                    if pending is not None:
                        pending_prefills.append(pending)
                        occupied += 1
                    elif self._slots[slot_id].request is not None:
                        live[slot_id] = request
                        occupied += 1
                # Starvation probe, right before this iteration's first
                # dispatch: a tick in flight that has already finished
                # left the device with nothing queued.
                if inflight is not None and (pending_prefills or live):
                    prof.probe_starved(inflight[1])
                # At most ONE prefill chunk an iteration — the bound
                # on the ITL stall an admission can impose.  Where it
                # rides the tick (`_dispatch_chunk`), `tick` is the tick
                # of the `riders`, the slots live before it; a prompt
                # it finishes is adopted behind it and joins the next.
                tick, riders = None, []
                if pending_prefills:
                    riders = list(live.items())
                    pending = pending_prefills.popleft()
                    done, tick = self._advance_prefill(pending,
                                                       len(riders))
                    if done:
                        if self._slots[pending.slot_id].request is not None:
                            live[pending.slot_id] = pending.request
                    else:
                        pending_prefills.append(pending)
                # Dispatch tick t+1 BEFORE reading tick t: the host's
                # token fetch and stream bookkeeping below overlap the
                # device's compute of this new step.  `decode-step`
                # times that (asynchronous) dispatch, not the tick.
                dispatched = None
                if live and self.spec_tokens:
                    # Speculative mode: synchronous multi-token verify
                    # ticks (see _spec_tick); `inflight` stays empty.
                    self._spec_tick(live)
                elif tick is not None and riders:
                    # The chunk's step was this iteration's tick.
                    state, finished, moe, exit_mass = tick
                    dispatched = (state, finished, riders, moe, exit_mass)
                elif live:
                    # (Also behind a chunk that rode over frozen slots
                    # only: a slot it made live gets its tick now.)
                    with prof.phase('decode-step', count=len(live)):
                        (self._state, self._cache, finished, moe,
                         exit_mass) = self._dispatch_step()
                    dispatched = (self._state, finished,
                                  list(live.items()), moe, exit_mass)
                if dispatched is not None:
                    ticked = [slot_id for slot_id, _ in dispatched[2]]
                    self._count_kernel_pages(ticked, 1)
                    for slot_id in ticked:
                        self._slots[slot_id].depth += 1
                if inflight is not None:
                    state_t, finished_t, snapshot, moe_t, exit_t = inflight
                    # The one place the host waits for the device: the
                    # blocking read of the tick in flight, nothing else.
                    with prof.device_wait(finished_t,
                                          count=len(snapshot)):
                        toks = np.asarray(state_t['tokens'])
                        fins = np.asarray(finished_t)
                        self._count_tick(moe_t, exit_t)
                    with prof.phase('sample') as phase:
                        pushed = 0
                        for slot_id, request in snapshot:
                            if request.done.is_set():
                                # Finished in an earlier tick (device
                                # froze the slot); this tick's value is
                                # a repeat.
                                continue
                            request._push(int(toks[slot_id]))  # pylint: disable=protected-access
                            pushed += 1
                            if fins[slot_id]:
                                live.pop(slot_id, None)
                                self._slots[slot_id].request = None
                                self._release_slot_pages(slot_id)
                                request._finish()  # pylint: disable=protected-access
                        if pushed:
                            self._record_tokens(pushed)
                        with self._metrics_lock:
                            self._ticks += 1
                        _M_TICKS.inc()
                        _M_BUSY_SLOTS.set(
                            sum(1 for s in self._slots if s.active))
                        phase.count = pushed
                inflight = dispatched
                prof.end_tick()
                if (inflight is None and not live and
                        not pending_prefills):
                    if deferred:
                        # Pool exhausted and nothing running to free
                        # pages soon: throttle the retry loop (TTL
                        # expiry / cancel / submit backpressure are
                        # what resolve this state).
                        with prof.idle('pages'):
                            time.sleep(0.005)
                    else:
                        with self._cond:
                            with self._host_ops_lock:
                                ops_waiting = bool(self._host_ops)
                            if (not len(self._queue) and
                                    not ops_waiting and
                                    not self._stop.is_set()):
                                with prof.idle('empty'):
                                    self._cond.wait(timeout=0.05)
            except Exception as e:  # pylint: disable=broad-except
                logger.exception('batching engine tick failed')
                # The jit'd step donates the page pool — after a
                # failure mid-step the cache buffers may be invalid, so
                # the engine CANNOT safely continue: fail everything in
                # flight, mark failed (submit() rejects from now on),
                # and exit the worker.
                self._fail_everything(e)
                return

    # ------------------------------------------------------------ failure

    def _fail_everything(self, e: Exception) -> None:
        self._failed = e
        self._stop.set()
        for slot in self._slots:
            if slot.request is not None:
                slot.request._finish(RuntimeError(  # pylint: disable=protected-access
                    f'batching engine failed: {e}'))
                slot.request = None
            slot.drafter = None
        self._queue.drain(
            lambda: RuntimeError(f'batching engine failed: {e}'))
        self._kv.release_all()
        self._drain_host_ops()  # stop is set: pending imports error out
