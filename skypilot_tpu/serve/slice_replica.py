"""Slice-serving runtime: one replica = one gang-scheduled multi-host
slice.

ROADMAP item 3, the last pillar of the serving story.  Training
already treats a TPU pod slice as the unit of compute (gang supervisor,
`parallel/mesh.py`, fsdp/tp sharding); serving replicas were single
processes.  This module makes "replica" mean "slice":

- **Mesh.**  `build_slice_mesh(num_hosts, cfg)` lays the slice out as
  `sequence x tensor` over its hosts (emulated hosts = one virtual
  device each; real hosts contribute their local chips).  The tensor
  factor takes as many hosts as the config's head/ff/vocab counts
  divide — weights shard per `parallel/sharding.py`'s SpecLayout
  (heads/mlp/vocab on 'tensor', embed on 'fsdp'), so a model too big
  for one host spreads across the slice; the remainder lands on
  'sequence' for long-context prefill.  The paged KV pool shards
  through the existing `page_pool_sharding` (kv heads on 'tensor').
- **Gang.**  :class:`SliceReplicaEngine` wraps the continuous-batching
  engine with a rank protocol (`serve/coordinator.py`): rank 0 owns
  the HTTP front (the LB keeps talking to ONE url) and broadcasts
  every host-side scheduling decision — admit, prefill, tick — so all
  ranks dispatch identical SPMD steps.  One dead rank fails the
  replica AS A UNIT: the engine fails everything in flight, `/health`
  turns 503 with ``slice.degraded``, the controller retires and
  replaces the replica, and the LB re-routes to survivors (chaos
  scenario ``replica_rank_death`` proves zero lost requests).
- **Sequence-parallel prefill.**  Prompts at/above ``sp_threshold``
  tokens skip the chunked-prefill ladder and run ONE
  `models/decode.prefill_sp` shot: ring attention
  (`ops/ring_attention.py`) splits the quadratic attention and its
  activations across the slice's sequence axis, so a 100k-token
  context that would OOM (or stall) one host prefills in ~1/hosts
  the time.

Emulated vs real:

- *Emulated* (tests): all `num_hosts` virtual devices live
  in this process (`xla_force_host_platform_device_count`); follower
  ranks are `LocalRank` threads that execute the command log (and its
  `serve.rank_exec` chaos site) while rank 0's dispatch covers every
  device.
- *Real slices*: each TPU-VM worker runs ``python -m
  skypilot_tpu.serve.slice_replica`` under the gang supervisor.
  Rank 0 (`SKYTPU_HOST_RANK=0`) initializes `jax.distributed`, accepts
  follower connections on the coordinator port, and serves HTTP; ranks
  > 0 connect and execute each broadcast command by dispatching the
  same jitted step on their local devices (`follower_serve`).
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu import sky_logging
from skypilot_tpu.serve import batching_engine as batching_engine_lib
from skypilot_tpu.serve import coordinator as coordinator_lib

logger = sky_logging.init_logger(__name__)

# Port offset from the JAX coordinator for the serve rank protocol
# (real slices; the gang env contract pins the jax.distributed port).
SLICE_COORD_PORT_OFFSET = 17


def sp_threshold_default() -> int:
    """Prompt tokens at which a slice replica prefills sequence-
    parallel instead of chunked (env SKYTPU_SLICE_SP_THRESHOLD)."""
    return int(os.environ.get('SKYTPU_SLICE_SP_THRESHOLD', '1024'))


def slice_axes(num_hosts: int, cfg,
               tensor: Optional[int] = None,
               sequence: Optional[int] = None) -> Dict[str, int]:
    """Factor a slice's hosts into (sequence, tensor) mesh axes.

    Default policy: tensor takes the LARGEST divisor of num_hosts the
    config's shapes support (n_heads, n_kv_heads, d_ff, vocab_size all
    divisible) — weight sharding is why the model needs a slice at all
    — and the remainder rides 'sequence' for long-context prefill.
    Either factor can be pinned explicitly (``--slice-sequence`` /
    ``--slice-tensor``); they must multiply to num_hosts.
    """
    if num_hosts < 1:
        raise ValueError(f'num_hosts must be >= 1, got {num_hosts}')
    if tensor is not None and sequence is not None:
        if tensor * sequence != num_hosts:
            raise ValueError(
                f'sequence ({sequence}) x tensor ({tensor}) must equal '
                f'num_hosts ({num_hosts})')
        return {'sequence': int(sequence), 'tensor': int(tensor)}
    if sequence is not None:
        if num_hosts % sequence:
            raise ValueError(f'sequence ({sequence}) must divide '
                             f'num_hosts ({num_hosts})')
        return {'sequence': int(sequence),
                'tensor': num_hosts // int(sequence)}
    if tensor is None:
        tensor = 1
        for d in range(1, num_hosts + 1):
            if num_hosts % d:
                continue
            if (cfg.n_heads % d or cfg.n_kv_heads % d or
                    cfg.d_ff % d or cfg.vocab_size % d):
                continue
            tensor = d
    if num_hosts % tensor:
        raise ValueError(f'tensor ({tensor}) must divide num_hosts '
                         f'({num_hosts})')
    for dim, value in (('n_heads', cfg.n_heads),
                       ('n_kv_heads', cfg.n_kv_heads),
                       ('d_ff', cfg.d_ff),
                       ('vocab_size', cfg.vocab_size)):
        if value % tensor:
            raise ValueError(
                f'tensor={tensor} must divide {dim} ({value}); pin '
                f'--slice-sequence to keep more hosts on the sequence '
                f'axis')
    return {'sequence': num_hosts // int(tensor), 'tensor': int(tensor)}


def build_slice_mesh(num_hosts: int, cfg, *, devices=None,
                     tensor: Optional[int] = None,
                     sequence: Optional[int] = None):
    """jax.sharding.Mesh for one slice replica: `sequence x tensor`
    over the slice's devices (emulated host = one virtual device)."""
    import jax  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.parallel import mesh as mesh_lib  # pylint: disable=import-outside-toplevel
    axes = slice_axes(num_hosts, cfg, tensor=tensor, sequence=sequence)
    if devices is None:
        devices = jax.devices()
    if len(devices) < num_hosts:
        raise ValueError(
            f'num_hosts={num_hosts} needs {num_hosts} devices; have '
            f'{len(devices)} (emulated hosts ride '
            f'xla_force_host_platform_device_count on CPU)')
    return mesh_lib.build_mesh(
        mesh_lib.MeshConfig(sequence=axes['sequence'],
                            tensor=axes['tensor']),
        devices=devices[:num_hosts])


class SliceReplicaEngine(batching_engine_lib.ContinuousBatchingEngine):
    """Continuous-batching engine whose replica is a multi-host slice.

    Extends the base engine with (a) the slice mesh — weights, KV pool
    and engine state land sharded/replicated per parallel/sharding.py;
    (b) the rank protocol — every tick/admission broadcasts through the
    SliceCoordinator before the SPMD dispatch, and a dead rank fails
    the replica as a unit; (c) sequence-parallel prefill for prompts at
    or above `sp_threshold` tokens."""

    # The ranks run a tick on the coordinator's TICK command, so a
    # prefill chunk stays a program of its own between two ticks.
    _FUSES_CHUNKS = False

    def __init__(self, cfg, params, *, num_hosts: int,
                 sp_threshold: Optional[int] = None,
                 sequence: Optional[int] = None,
                 tensor: Optional[int] = None,
                 mesh=None,
                 rank_channels: Optional[List[Any]] = None,
                 **kwargs) -> None:
        import jax  # pylint: disable=import-outside-toplevel

        from skypilot_tpu.models import decode  # pylint: disable=import-outside-toplevel
        self.num_hosts = int(num_hosts)
        self.sp_threshold = (sp_threshold_default()
                             if sp_threshold is None
                             else int(sp_threshold))
        if mesh is None:
            mesh = build_slice_mesh(self.num_hosts, cfg,
                                    sequence=sequence, tensor=tensor)
        self._slice_mesh = mesh
        self._sp_degree = int(mesh.shape.get('sequence', 1))
        self._coordinator = coordinator_lib.SliceCoordinator(
            self.num_hosts, channels=rank_channels)
        self._sp_prefills = 0
        # One compile per padded prompt width (the bucket ladder bounds
        # the count, same as the chunked path).
        self._sp_prefill_jit = jax.jit(decode.bind(
            decode.prefill_sp, cfg, mesh=mesh,
            max_len=kwargs.get('max_len', 512)))
        super().__init__(cfg, params, mesh=mesh, **kwargs)
        # The SP prefill entry is created before the base engine builds
        # the recompile sentinel; enroll it now.
        self._sp_prefill_jit = self._sentinel.wrap('sp_prefill',
                                                   self._sp_prefill_jit)

    # --------------------------------------------------- gang protocol

    def _dispatch_step(self):
        """Coordinated tick: rank 0 broadcasts TICK and waits for every
        rank's ack (the `slice_sync_ms` overhead), then dispatches the
        SPMD step.  RankDead propagates to the worker loop, which fails
        the replica as a unit — a half-dead slice must never keep
        half-serving."""
        with self._profiler.phase('slice-sync'):
            self._coordinator.tick()
        return super()._dispatch_step()

    def _dispatch_spec_step(self, drafts):
        """Coordinated speculative verify tick: the draft batch rides
        the TICK payload so real followers (`FollowerExecutor`) dispatch
        the identical spec step — drafts are rank 0's host-side
        decision, exactly like admissions."""
        import numpy as np  # pylint: disable=import-outside-toplevel
        with self._profiler.phase('slice-sync'):
            self._coordinator.broadcast(
                coordinator_lib.CMD_TICK,
                spec=np.asarray(drafts).tolist())
        return super()._dispatch_spec_step(drafts)

    def _activate(self, slot_id, request, token, length, *,
                  remaining, key) -> None:
        """Slot activation broadcasts the FULL admission so follower
        ranks can mirror it against their local shard: the prompt (the
        follower re-runs the prefill — on real hardware each host must
        compute its shard of every step anyway), the page row rank 0's
        planner allocated, and the per-slot decode state (token,
        budget, stop set, key chain seed, sampling params)."""
        import numpy as np  # pylint: disable=import-outside-toplevel
        self._coordinator.broadcast(
            coordinator_lib.CMD_ADMIT, slot=slot_id,
            tokens=len(request.prompt_ids),
            prompt=[int(t) for t in request.prompt_ids],
            length=int(length), token=int(token),
            remaining=int(remaining),
            stop_ids=sorted(int(s) for s in request.stop_ids),
            key=np.asarray(key).tolist(),
            temperature=float(request.temperature),
            top_k=int(request.top_k), row=self._kv.slot_row(slot_id),
            request_id=request.request_id)
        request.span.slice_sync_ms = round(
            self._coordinator.sync_ms_mean(), 4)
        super()._activate(slot_id, request, token, length,
                          remaining=remaining, key=key)

    def _release_slot_pages(self, slot_id) -> None:
        """Slot release is a coordinated command too: followers park
        the slot's block table on the null page exactly when rank 0
        does, so stale in-flight writes land in garbage on EVERY
        host."""
        self._coordinator.broadcast(
            coordinator_lib.CMD_RELEASE, slot=slot_id)
        super()._release_slot_pages(slot_id)

    # ------------------------------------------------------ SP prefill

    def _sp_padded_width(self, n_target: int) -> Optional[int]:
        """Padded prompt width for the one-shot SP prefill: the bucket
        of n_target, rounded up to a multiple of the sequence degree,
        capped at max_len.  None = does not fit; use the chunked
        path."""
        sp = self._sp_degree
        width = min(self._bucket(n_target), self.max_len)
        width = -(-width // sp) * sp
        if width > self.max_len:
            width = -(-n_target // sp) * sp
        if width > self.max_len:
            return None
        return width

    def _try_sp_prefill(self, prompt_ids: List[int],
                        n_target: int) -> Optional[Dict[str, Any]]:
        """One-shot sequence-parallel prefill of [0, n_target), or None
        when the prompt should take the chunked path (below threshold,
        a layer pattern, parallel block, post-norms or more than one
        pass, or padding does not fit)."""
        import numpy as np  # pylint: disable=import-outside-toplevel
        cfg = self.cfg
        if (n_target < self.sp_threshold or cfg.layer_pattern or
                cfg.parallel_block or cfg.post_norms or
                cfg.loop_passes != 1):
            return None
        width = self._sp_padded_width(n_target)
        if width is None:
            return None
        jnp = self._jnp
        padded = np.zeros((1, width), np.int32)
        padded[0, :n_target] = prompt_ids[:n_target]
        cache = self._sp_prefill_jit(self.params, jnp.asarray(padded))
        with self._metrics_lock:
            self._sp_prefills += 1
        return dict(cache, index=jnp.asarray(n_target, jnp.int32))

    def _advance_prefill(self, pending, riders: int = 0):
        request = pending.request
        if (pending.cache is None and
                pending.plan.n_reuse_tokens == 0 and
                not request.cancelled):
            with self._profiler.phase(
                    'prefill-chunk', request_id=request.request_id,
                    count=pending.n_target) as phase:
                t0 = time.monotonic()
                cache = self._try_sp_prefill(request.prompt_ids,
                                             pending.n_target)
                # Below the threshold: the chunked path records its own.
                phase.record = cache is not None
            if cache is not None:
                pending.cache = cache
                pending.consumed = pending.n_target
                request.span.mark_prefill_chunk(time.monotonic() - t0)
                self._record_chunk()
                self._coordinator.broadcast(
                    coordinator_lib.CMD_PREFILL,
                    slot=pending.slot_id, tokens=pending.n_target,
                    sp=self._sp_degree)
                return self._finish_prefill(pending), None
        return super()._advance_prefill(pending, riders)

    def _prefill_private(self, prompt_ids: List[int],
                         n_target: int) -> Dict[str, Any]:
        """Export-side prefill (`export_prefill`): long prompts go
        sequence-parallel here too — a prefill-role slice exports
        100k-token KV without the chunk ladder."""
        cache = self._try_sp_prefill(prompt_ids, n_target)
        if cache is not None:
            return cache
        return super()._prefill_private(prompt_ids, n_target)

    # ----------------------------------------------------------- stats

    def stats(self) -> Dict[str, Any]:
        stats = super().stats()
        slice_stats = self._coordinator.stats()
        with self._metrics_lock:
            slice_stats['sp_prefills'] = self._sp_prefills
        slice_stats['sp_degree'] = self._sp_degree
        slice_stats['tensor_degree'] = int(
            self._slice_mesh.shape.get('tensor', 1))
        slice_stats['sp_threshold'] = self.sp_threshold
        stats['num_hosts'] = self.num_hosts
        stats['slice'] = slice_stats
        return stats

    def stop(self) -> None:
        super().stop()
        self._coordinator.close()


# ----------------------------------------------------------- real slices


class FollowerExecutor:
    """Execute the rank-0 command log against REAL local devices.

    A follower rank of a real slice holds the same weights and the
    same engine geometry as rank 0; every broadcast command carries
    rank 0's host-side scheduling decision (which slot, which pages,
    which drafts), so replaying the log with the SAME jitted functions
    reproduces rank 0's device state bit-for-bit — that is the whole
    gang contract: identical SPMD dispatches in identical order.

    Command semantics:

    - ``TICK``: one jitted engine step; a ``spec`` payload (the draft
      batch rank 0's n-gram drafters proposed) selects the speculative
      verify tick instead — same attention kernel either way.
    - ``ADMIT``: replay the chunked prefill of prompt positions
      ``[0, length)`` into a private cache, scatter it into the page
      row rank 0's planner allocated, point the
      slot's block table at the row, and arm the sampler state
      (token/budget/stop set/key chain/sampling params).  Prefix
      reuse needs no special case: rewriting a reused page lands the
      identical KV bytes (causal KV at position i depends only on
      tokens [0..i], and both prefill paths are deterministic).
    - ``RELEASE``: park the slot's table on the null page, exactly
      when rank 0 does.
    - ``PREFILL``: informational (the SP one-shot); the ADMIT replay
      covers the KV, so nothing to do here.
    - ``SHUTDOWN``: handled by `follower_serve` (closes the loop).

    The executor keeps per-follower throughput honest: all heavy work
    goes through jits compiled once per shape bucket, mirroring the
    engine's compile-count discipline.
    """

    def __init__(self, cfg, params, *, max_len: int = 512,
                 slots: int = 4, prefill_chunk: int = 512,
                 kv_pages: Optional[int] = None, page_size: int = 16,
                 quantize_kv: bool = False,
                 max_top_k: int = 64, max_stop_ids: int = 16) -> None:
        import jax  # pylint: disable=import-outside-toplevel
        import jax.numpy as jnp  # pylint: disable=import-outside-toplevel

        from skypilot_tpu.models import decode  # pylint: disable=import-outside-toplevel
        from skypilot_tpu.ops import paged_attention as paged_attention_lib  # pylint: disable=import-outside-toplevel
        from skypilot_tpu.serve import sampler as sampler_lib  # pylint: disable=import-outside-toplevel
        self.cfg = cfg
        # The leader's engine re-forms the q/k/v kernels it is given;
        # so does its follower, and both lower one program.
        self.params = decode.serving_params(cfg, params)
        self.max_len = int(max_len)
        self.prefill_chunk = int(prefill_chunk)
        self._jnp = jnp
        self._sampler = sampler_lib.SlotSampler(int(max_top_k),
                                                int(max_stop_ids))
        self._page_size = int(page_size)
        self._commands = 0
        kernel = paged_attention_lib.decode_kernel_choice()
        self._step = jax.jit(
            decode.bind(decode.paged_engine_step, cfg,
                        max_top_k=int(max_top_k), kernel=kernel),
            donate_argnums=(2,))
        self._spec_step = jax.jit(
            decode.bind(decode.paged_spec_engine_step, cfg,
                        max_top_k=int(max_top_k), kernel=kernel),
            donate_argnums=(2,))
        self._admit_paged = jax.jit(decode.paged_admit_slot,
                                    donate_argnums=(0,))
        self._release_paged = jax.jit(decode.paged_release_slot,
                                      donate_argnums=(0,))
        self._insert_pages = jax.jit(
            decode.insert_prefill_pages,
            static_argnames=('first_page',), donate_argnums=(0,))
        # The pool rank 0's engine builds from the same geometry.
        self._cache = decode.init_paged_cache(
            cfg, batching_engine_lib.PagedKVManager.pool_pages(
                kv_pages, int(slots), self.max_len, self._page_size),
            self._page_size, int(slots),
            self.max_len // self._page_size,
            quantize_kv=bool(quantize_kv))
        self._state = decode.init_engine_state(int(slots),
                                               int(max_stop_ids))
        self._prefill = jax.jit(
            decode.bind(decode.prefill, cfg, max_len=self.max_len))
        self._prefill_chunk_jit = jax.jit(
            decode.bind(decode.prefill_chunk, cfg), donate_argnums=(2,))

    def _bucket(self, n: int) -> int:
        for b in batching_engine_lib._PREFILL_BUCKETS:  # pylint: disable=protected-access
            if n <= b:
                return b
        return n

    def _replay_prefill(self, prompt: List[int], length: int):
        """Chunked prefill of prompt positions [0, length) — the same
        bucket ladder the engine runs, so follower compile counts stay
        bounded by the same buckets."""
        import numpy as np  # pylint: disable=import-outside-toplevel
        jnp = self._jnp
        chunk = self.prefill_chunk
        take = min(length, chunk)
        bucket = min(self._bucket(take), self.max_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :take] = prompt[:take]
        _, cache = self._prefill(self.params, jnp.asarray(padded))
        cache = dict(cache, index=jnp.asarray(take, jnp.int32))
        consumed = take
        while consumed < length:
            take = min(length - consumed, chunk)
            width = min(self._bucket(take), chunk,
                        self.max_len - consumed)
            piece = np.zeros((1, width), np.int32)
            piece[0, :take] = prompt[consumed:consumed + take]
            _, cache = self._prefill_chunk_jit(self.params,
                                               jnp.asarray(piece),
                                               cache)
            cache = dict(cache,
                         index=jnp.asarray(consumed + take, jnp.int32))
            consumed += take
        return cache

    def _pad_row(self, row: List[int]):
        import numpy as np  # pylint: disable=import-outside-toplevel
        padded = np.zeros((self.max_len // self._page_size,), np.int32)
        padded[:len(row)] = row
        return self._jnp.asarray(padded)

    def _admit(self, payload: Dict[str, Any]) -> None:
        import numpy as np  # pylint: disable=import-outside-toplevel
        jnp = self._jnp
        slot = int(payload['slot'])
        length = int(payload['length'])
        prompt = payload['prompt']
        row = payload.get('row')
        if length > 0:
            pre = self._replay_prefill(prompt, length)
            n_pages = -(-length // self._page_size)
            self._cache = self._insert_pages(
                self._cache, pre,
                np.asarray(row[:n_pages], np.int32), first_page=0)
        self._cache = self._admit_paged(
            self._cache, slot, self._pad_row(row), length)
        self._state = self._sampler.admit(
            self._state, slot, int(payload['token']),
            int(payload['remaining']),
            frozenset(payload['stop_ids']),
            jnp.asarray(payload['key'], jnp.uint32),
            float(payload['temperature']), int(payload['top_k']))

    def __call__(self, cmd) -> None:
        payload = cmd.payload
        self._commands += 1
        if cmd.kind == coordinator_lib.CMD_TICK:
            drafts = payload.get('spec') if payload else None
            if drafts is not None:
                out = self._spec_step(
                    self.params, self._state, self._cache,
                    self._jnp.asarray(drafts, self._jnp.int32))
                self._state, self._cache = out[0], out[1]
            else:
                out = self._step(self.params, self._state, self._cache)
                self._state, self._cache = out[0], out[1]
        elif cmd.kind == coordinator_lib.CMD_ADMIT:
            # Pre-follower-executor ADMITs carried only slot/tokens;
            # tolerate them so mixed-version logs replay (state just
            # won't mirror — the emulated tier).
            if payload and 'prompt' in payload:
                self._admit(payload)
        elif cmd.kind == coordinator_lib.CMD_RELEASE:
            self._cache = self._release_paged(self._cache,
                                              int(payload['slot']))
        # CMD_PREFILL: SP one-shot notification — the ADMIT replay
        # writes the same KV, nothing to mirror here.


def follower_main(rank: int, coordinator_address: str,
                  executor: Optional[FollowerExecutor] = None) -> None:
    """Rank > 0 of a REAL slice: connect to rank 0's rank-protocol
    port and execute the command log.  With an executor (built from
    the same model/geometry flags as rank 0), every command dispatches
    the matching jitted step on this host's local devices; without
    one, the process just holds the gang together (the emulated tier,
    where all virtual devices live on rank 0)."""
    sock = coordinator_lib.follower_connect(coordinator_address, rank)
    logger.info(f'slice follower rank {rank} connected to '
                f'{coordinator_address}')
    coordinator_lib.follower_serve(sock, rank, executor)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--num-hosts', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_NUM_HOSTS', '1')))
    parser.add_argument('--rank', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_HOST_RANK', '0')))
    parser.add_argument('--coordinator',
                        default=os.environ.get(
                            'SKYTPU_COORDINATOR_ADDRESS'))
    parser.add_argument('--model', default='tiny')
    parser.add_argument('--max-len', type=int, default=512)
    parser.add_argument('--max-batch', type=int, default=8)
    parser.add_argument('--prefill-chunk', type=int, default=512)
    args, extra = parser.parse_known_args()
    from skypilot_tpu import compile_cache  # pylint: disable=import-outside-toplevel
    compile_cache.enable()
    if args.rank > 0:
        # Follower rank of a real slice: the rank-protocol port is the
        # JAX coordinator's + a fixed offset.  The executor mirrors
        # rank 0's engine geometry: model/max-len/max-batch/prefill-
        # chunk from the (gang-identical) CLI, KV pool shape from the
        # SKYTPU_SERVE_* env the task YAML exports to every worker.
        if not args.coordinator:
            raise SystemExit('rank > 0 needs --coordinator (or the '
                             'gang env contract)')
        import flax.linen as nn  # pylint: disable=import-outside-toplevel
        import jax  # pylint: disable=import-outside-toplevel
        import jax.numpy as jnp  # pylint: disable=import-outside-toplevel

        from skypilot_tpu.models import configs  # pylint: disable=import-outside-toplevel
        from skypilot_tpu.models.transformer import Transformer  # pylint: disable=import-outside-toplevel
        cfg = configs.get_config(args.model)
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))['params'])
        kv_pages_env = os.environ.get('SKYTPU_SERVE_KV_PAGES')
        executor = FollowerExecutor(
            cfg, params, max_len=args.max_len, slots=args.max_batch,
            prefill_chunk=args.prefill_chunk,
            kv_pages=(int(kv_pages_env) if kv_pages_env else None),
            page_size=int(os.environ.get('SKYTPU_SERVE_PAGE_SIZE',
                                         '16')),
            quantize_kv=os.environ.get('SKYTPU_SERVE_KV_INT8',
                                       '') == '1')
        host, _, port = args.coordinator.rpartition(':')
        follower_main(args.rank,
                      f'{host}:{int(port) + SLICE_COORD_PORT_OFFSET}',
                      executor)
        return
    # Rank 0: hand over to the model server CLI with num_hosts set —
    # one entrypoint for `run: python -m skypilot_tpu.serve.
    # slice_replica` task YAMLs.
    import sys  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.serve import model_server  # pylint: disable=import-outside-toplevel
    sys.argv = ([sys.argv[0], '--num-hosts', str(args.num_hosts),
                 '--model', args.model,
                 '--max-len', str(args.max_len),
                 '--max-batch', str(args.max_batch),
                 '--prefill-chunk', str(args.prefill_chunk),
                 '--continuous-batching'] +
                list(extra))
    model_server.main()


if __name__ == '__main__':
    main()
