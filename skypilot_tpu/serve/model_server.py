"""Model server: the TPU inference path behind an HTTP endpoint.

The reference serves whatever container the user brings; this framework
also ships a native replica server wired to its own compute layer
(models/decode.py — flash-kernel prefill + jit'd KV-cache decode), so
`sky serve up` of a model is one YAML:

    run: python -m skypilot_tpu.serve.model_server --model tiny \
            --port $SKYTPU_SERVE_REPLICA_PORT

Endpoints:
  GET  /                 -> health + engine stats (readiness probe;
                            includes recent request spans)
  GET  /metrics          -> Prometheus text exposition (observability/
                            metrics.py process-global registry: engine
                            ticks, decode tokens/s, queue-wait + TTFT +
                            ITL histograms, admission rejections)
  POST /generate         -> {"prompt_ids": [[..]], "max_new_tokens": N,
                             "temperature": T, "top_k": K, "seed": S}
                            => {"tokens": [[..]], "latency_ms": ..}
                            (sampling params work under continuous
                            batching too — selection runs on device in
                            the engine tick, seeded per request; a full
                            admission queue answers 429 + Retry-After,
                            an expired queued request 503.)
  POST /generate_stream  -> SSE: data: {"token": N} per token, then
                            data: [DONE]  (continuous batching only)
  POST /generate_text    -> {"prompt": "...", "max_new_tokens": N}
                            => {"completion": "...", ...} via the
                            checkpoint's real tokenizer
                            (models/tokenizer.py) or the byte-level
                            fallback; {"stream": true} upgrades the
                            response to SSE data: {"text": "<delta>"}
                            events with UTF-8-safe incremental decode
                            (continuous batching only).

Real checkpoints: point --checkpoint-dir at a converted HF checkpoint
(models/import_weights.py) — --model auto reads its model_config.json
and the tokenizer files sitting next to it, so one directory serves
Llama/Gemma/Qwen/Mixtral releases end to end.  Without tokenizer
files the byte-level convention (UTF-8 bytes are ids, NUL is EOS)
keeps the server dependency-free.
"""
from __future__ import annotations

import argparse
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

from skypilot_tpu import sky_logging
from skypilot_tpu.observability import logs as logs_lib
from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.observability import tracing
from skypilot_tpu.serve import batching_engine as batching_engine_lib
from skypilot_tpu.serve import handoff as handoff_lib
from skypilot_tpu.serve import http_protocol
from skypilot_tpu.serve import qos as qos_lib
from skypilot_tpu.serve import roles as roles_lib
from skypilot_tpu.serve import router as router_lib

logger = sky_logging.init_logger(__name__)

# Requests routed by role (the LB's X-SkyTPU-Routed-Role /
# X-SkyTPU-Affinity headers) — the replica-side view of the router's
# decisions, scraped by `serve status --metrics` for the AFFINITY
# column.
_M_ROUTED = metrics_lib.counter(
    'skytpu_engine_routed_total',
    'LB-routed requests served, by routed role and affinity outcome.',
    ('role', 'affinity'))
_M_DRAIN_REJECTED = metrics_lib.counter(
    'skytpu_serve_drain_rejected_total',
    'Generation requests answered 503 because the replica is '
    'draining (the LB retries them on a sibling).')
# Process identity marker: always 1; its labels (via the registry's
# constant labels when SKYTPU_SERVE_REPLICA_ID is set) name this
# replica, so scrapers can join any series to the replica it came
# from even without target labels.
_M_PROCESS_INFO = metrics_lib.gauge(
    'skytpu_process_info',
    'Constant 1 carrying this process\'s identity labels '
    '(replica_id / role / num_hosts on serving replicas).')
# Forward-pass FLOPs per generated token: the fleet aggregator
# multiplies this by decode tokens/s and divides by the chip roofline
# for the per-replica skytpu_mfu_estimate gauge.
_M_FLOPS_PER_TOKEN = metrics_lib.gauge(
    'skytpu_engine_model_flops_per_token',
    'Approximate forward FLOPs per generated token (2 x parameter '
    'count plus the context-dependent attention term) of the model '
    'this replica serves.')
# Live weight swap + bulk inference (sky batch-infer): the replica-side
# series the fleet aggregator folds into its batch section for
# `sky serve top`.
_M_WEIGHT_SWAPS = metrics_lib.counter(
    'skytpu_batch_weight_swaps_total',
    'Live weight swaps attempted on this replica (POST /weights_swap), '
    'by outcome.', ('status',))
_M_WEIGHT_EPOCH = metrics_lib.gauge(
    'skytpu_batch_weight_epoch',
    'Weight epoch currently serving (0 = boot weights; each '
    'successful live swap bumps it).')
_M_BATCH_ROWS = metrics_lib.counter(
    'skytpu_batch_rows_served_total',
    'Generation rows served under QoS class batch — the replica-side '
    'progress signal of a bulk-inference run.')


def model_flops_per_token(cfg, n_params: int, max_len: int) -> float:
    """Forward FLOPs per generated token for the MFU roofline.

    Matmul work is ~2 x params (one multiply-add per parameter per
    token); a looped stack (`cfg.loop_passes`) runs every parameter
    but the embedding's and the head's once a pass.  On top of that,
    attention reads the KV cache: per cache layer (`cfg.cache_layers`:
    one a pass and layer) and cached position, QK^T and attn x V each
    cost 2 x n_heads x head_dim FLOPs; at the mean decode context
    (max_len / 2) that adds 2 x cache_layers x n_heads x head_dim x
    max_len.  `SKYTPU_MODEL_FLOPS_PER_TOKEN` overrides the whole
    estimate for imported models whose param tree misleads the count
    (quantized or partially-frozen checkpoints)."""
    override = os.environ.get('SKYTPU_MODEL_FLOPS_PER_TOKEN')
    if override:
        try:
            return float(override)
        except ValueError:
            logger.warning('Ignoring non-numeric '
                           f'SKYTPU_MODEL_FLOPS_PER_TOKEN={override!r}')
    attn = (2.0 * cfg.cache_layers * cfg.n_heads * cfg.head_dim
            * float(max_len))
    outside = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings
                                              else 2)
    again = (cfg.loop_passes - 1) * max(0.0, float(n_params) - outside)
    return 2.0 * (float(n_params) + again) + attn


class ClientDisconnected(RuntimeError):
    """The client hung up while its request was in flight: the engine
    slot was cancelled and its KV pages freed; no response is owed."""


def default_deadline_ms() -> Optional[float]:
    """Replica-side default request deadline (ms) for requests that
    carry no X-SkyTPU-Deadline-Ms header; None = no deadline."""
    value = os.environ.get('SKYTPU_SERVE_DEFAULT_DEADLINE_MS')
    if not value:
        return None
    try:
        ms = float(value)
    except ValueError:
        return None
    return ms if ms > 0 else None


def _attempt_header(raw: Optional[str]) -> Optional[int]:
    """Parse the LB's X-SkyTPU-Attempt header value (None when absent
    or malformed — spans then read as attempt 0)."""
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        return None


# `GET /spans` query parsing lives with the span stores; both HTTP
# fronts and the LB control plane share it.
parse_span_query = tracing.parse_span_query


def _maybe_journal_request(event: str, **fields) -> None:
    """Journal request execution only while someone is watching (the
    `serve.kv_handoff` / `serve.rank_exec` chaos sites armed, or
    SKYTPU_SERVE_HANDOFF_EVENTS set): the handoff_consistency
    invariant replays these to prove no request is lost or
    double-executed across a handoff failure OR a slice-rank death."""
    import os  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.chaos import injector as chaos_injector  # pylint: disable=import-outside-toplevel
    if not (os.environ.get('SKYTPU_SERVE_HANDOFF_EVENTS') or
            chaos_injector.site_armed('serve.kv_handoff') or
            chaos_injector.site_armed('serve.rank_exec') or
            chaos_injector.site_armed('serve.controller_tick')):
        return
    from skypilot_tpu.observability import events as events_lib  # pylint: disable=import-outside-toplevel
    try:
        events_lib.get_journal(
            os.path.join(events_lib.journal_root(),
                         'serve.jsonl')).append(event, **fields)
    except Exception:  # pylint: disable=broad-except
        pass  # recording must never break the serving path


def _maybe_journal_batch(event: str, **fields) -> None:
    """Journal the weight-swap lifecycle only while someone is watching
    (the `batch.shard_write` chaos site armed, or SKYTPU_BATCH_EVENTS
    set): the batch_exactly_once invariant replays these alongside the
    batch driver's shard/row events."""
    import os  # pylint: disable=import-outside-toplevel

    from skypilot_tpu.chaos import injector as chaos_injector  # pylint: disable=import-outside-toplevel
    if not (os.environ.get('SKYTPU_BATCH_EVENTS') or
            chaos_injector.site_armed('batch.shard_write')):
        return
    from skypilot_tpu.observability import events as events_lib  # pylint: disable=import-outside-toplevel
    try:
        events_lib.get_journal(
            os.path.join(events_lib.journal_root(),
                         'serve.jsonl')).append(event, **fields)
    except Exception:  # pylint: disable=broad-except
        pass  # recording must never break the serving path


class ModelServer:

    def __init__(self, model: str, *, checkpoint_dir: Optional[str] = None,
                 max_len: int = 512, max_batch: int = 8,
                 seed: int = 0, quantize: Optional[str] = None,
                 continuous_batching: bool = False,
                 tensor: int = 1,
                 tokenizer_path: Optional[str] = None,
                 max_queue: int = 0,
                 queue_ttl: Optional[float] = None,
                 prefill_chunk: int = 512,
                 default_temperature: float = 0.0,
                 default_top_k: int = 0,
                 default_seed: int = 0,
                 kv_pages: Optional[int] = None,
                 page_size: int = 16,
                 quantize_kv: bool = False,
                 prefix_caching: bool = True,
                 spec_tokens: int = 0,
                 role: str = router_lib.DEFAULT_ROLE,
                 num_hosts: int = 1,
                 sp_threshold: Optional[int] = None,
                 slice_sequence: Optional[int] = None,
                 slice_tensor: Optional[int] = None,
                 replica_id: Optional[int] = None) -> None:
        import jax
        import flax.linen as nn

        from skypilot_tpu.models import configs
        from skypilot_tpu.models.transformer import Transformer
        from skypilot_tpu.ops import attention as attention_ops

        # What this process runs on, as JAX reports it — served on
        # GET / so a prober can name the device without touching JAX
        # itself (a chip belongs to one process).  interpret_mode()
        # refuses to start an interpreted server on a non-CPU backend.
        dev = jax.devices()[0]
        self.runtime: Dict[str, Any] = {
            'device': {'platform': dev.platform, 'kind': dev.device_kind,
                       'count': jax.device_count()},
            'jax_version': jax.__version__,
            'pallas_interpret': attention_ops.interpret_mode(),
        }
        if quantize not in (None, 'int8'):
            # Validate BEFORE the (potentially minutes-long) checkpoint
            # restore, not after.
            raise ValueError(f'Unknown quantize mode {quantize!r}; '
                             "have 'int8'.")
        if tensor > 1 and quantize:
            raise ValueError(
                'quantize + tensor sharding is not supported yet '
                '(quantized leaves change the param pytree the '
                'shardings were computed for).')
        self.num_hosts = int(num_hosts)
        self.sp_threshold = sp_threshold
        if self.num_hosts > 1:
            if tensor > 1:
                raise ValueError(
                    '--num-hosts subsumes --tensor: the slice mesh '
                    'lays out sequence x tensor itself '
                    '(--slice-tensor pins the factor).')
            if quantize:
                raise ValueError(
                    'quantize + multi-host sharding is not supported '
                    'yet (quantized leaves change the param pytree '
                    'the shardings were computed for).')
            if not continuous_batching:
                raise ValueError('--num-hosts > 1 requires '
                                 '--continuous-batching (the slice '
                                 'engine IS the batching engine)')
        if model == 'auto':
            # Converted checkpoints carry their own ModelConfig
            # (import_weights writes model_config.json next to the
            # orbax step) — no preset needed for real releases.
            from skypilot_tpu.models import import_weights
            cfg = (import_weights.load_model_config(checkpoint_dir)
                   if checkpoint_dir else None)
            if cfg is None:
                raise ValueError(
                    "--model auto needs --checkpoint-dir pointing at a "
                    "converted checkpoint (with model_config.json); "
                    "see python -m skypilot_tpu.models.import_weights.")
            self.cfg = cfg
        else:
            self.cfg = configs.get_config(model)
        # Real tokenizer when the checkpoint ships one (converted
        # checkpoints do); byte-level fallback otherwise.
        from skypilot_tpu.models import tokenizer as tokenizer_lib
        self.tokenizer = tokenizer_lib.load_tokenizer(
            tokenizer_path or checkpoint_dir)
        if self.tokenizer.eos_id is None:
            # stop_token=None means every request runs to
            # max_new_tokens, holding batching slots; say so once at
            # startup instead of silently degrading throughput.
            logger.warning(
                'Tokenizer has no EOS id (missing/incomplete '
                'tokenizer_config.json?): generation cannot stop '
                'early and will always run to max_new_tokens.')
        self.max_len = max_len
        self.max_batch = max_batch
        # Disaggregated serving role (prefill / decode / mixed):
        # advertised via /health so the controller and LB can dispatch
        # by role; the engine itself is role-agnostic — a prefill
        # replica mostly serves /prefill_export, a decode replica
        # mostly /kv_import + generation, and either can do both.
        if role not in router_lib.ROLES:
            raise ValueError(f'Unknown replica role {role!r}; one of '
                             f'{router_lib.ROLES}')
        self.role = role
        # Graceful drain: once set (POST /drain, from the controller's
        # retirement path), new generation work is refused with 503 +
        # Retry-After while in-flight decodes run to completion.
        self.draining = False
        # Process identity for fleet telemetry: which replica this is.
        # Explicit kwarg (tests run several servers per process), else
        # the controller-set env var (real replica processes).
        env_rid = os.environ.get('SKYTPU_SERVE_REPLICA_ID')
        if replica_id is not None:
            self.replica_id: Optional[int] = int(replica_id)
        elif env_rid and env_rid.isdigit():
            self.replica_id = int(env_rid)
        else:
            self.replica_id = None
        if env_rid and env_rid.isdigit():
            # Constant identity labels on EVERY exposed series: the
            # controller's aggregator keys its time-series store by
            # the full label set, so replicas must not expose
            # indistinguishable series.  Env-gated: only a real
            # replica process (one server per process) owns the
            # process-global registry's identity.
            metrics_lib.REGISTRY.set_const_labels({
                'replica_id': env_rid, 'role': role,
                'num_hosts': int(num_hosts)})
            # Same ownership rule for the log plane's process-level
            # identity fallback (per-request contextvar binds win).
            logs_lib.set_process_identity(
                'replica', replica_id=int(env_rid), role=role)
        _M_PROCESS_INFO.set(1)
        # Trace segments for non-engine legs of a request's life (the
        # /prefill_export and /kv_import handoff endpoints record
        # here); exported with the engine spans via GET /spans.
        self.trace_segments = tracing.SegmentStore()
        model_mod = Transformer(self.cfg)
        init_tokens = jax.numpy.zeros((1, 8), jax.numpy.int32)
        key = jax.random.PRNGKey(seed)

        # Tensor-sharded serving (models too big for one chip): params
        # carry NamedShardings over a tensor mesh; GSPMD partitions the
        # decode einsums and inserts the collectives — the decode code
        # is unchanged.
        # Request-side sampling defaults (the CLI's --temperature /
        # --top-k / --seed): applied when a request omits the field.
        self.default_temperature = float(default_temperature)
        self.default_top_k = int(default_top_k)
        self.default_seed = int(default_seed)
        self._shardings = None
        self._mesh = None
        if self.num_hosts > 1:
            # Slice replica: one mesh (sequence x tensor) over the
            # slice's hosts; weights shard per the same SpecLayout the
            # tensor path uses (heads/mlp/vocab on 'tensor', embed on
            # 'fsdp' — trivial axes resolve to replication).
            from skypilot_tpu.parallel.sharding import LOGICAL_AXIS_RULES
            from skypilot_tpu.serve import slice_replica as slice_lib
            mesh = slice_lib.build_slice_mesh(
                self.num_hosts, self.cfg, sequence=slice_sequence,
                tensor=slice_tensor)
            self._mesh = mesh
            abstract = jax.eval_shape(
                lambda rng: model_mod.init(rng, init_tokens)['params'],
                key)
            specs = nn.get_partition_spec(abstract)
            self._shardings = nn.meta.unbox(nn.logical_to_mesh_sharding(
                specs, mesh, LOGICAL_AXIS_RULES))
        elif tensor > 1:
            from skypilot_tpu.parallel import MeshConfig, build_mesh
            from skypilot_tpu.parallel.sharding import LOGICAL_AXIS_RULES
            if len(jax.devices()) < tensor:
                raise ValueError(
                    f'tensor={tensor} needs {tensor} devices; have '
                    f'{len(jax.devices())}.')
            for dim, value in (('n_kv_heads', self.cfg.n_kv_heads),
                               ('n_heads', self.cfg.n_heads),
                               ('d_ff', self.cfg.d_ff),
                               ('vocab_size', self.cfg.vocab_size)):
                if value % tensor:
                    raise ValueError(
                        f'tensor={tensor} must divide {dim} ({value}) '
                        f'for {model!r}; pick a smaller degree.')
            mesh = build_mesh(MeshConfig(tensor=tensor),
                              devices=jax.devices()[:tensor])
            self._mesh = mesh
            abstract = jax.eval_shape(
                lambda rng: model_mod.init(rng, init_tokens)['params'],
                key)
            specs = nn.get_partition_spec(abstract)
            self._shardings = nn.meta.unbox(nn.logical_to_mesh_sharding(
                specs, mesh, LOGICAL_AXIS_RULES))

        def _init(rng):
            return nn.meta.unbox(
                model_mod.init(rng, init_tokens)['params'])

        from skypilot_tpu.data import checkpoints
        if (checkpoint_dir and
                checkpoints.latest_step(checkpoint_dir) is not None):
            # Restore straight from checkpoint metadata: random weights
            # are never materialised just to be overwritten (for an 8B
            # model that would double peak memory and add minutes of
            # startup), and optimizer moments are never read at all.
            # With tensor sharding, shards stream straight to their
            # devices — the unsharded tree never exists on one chip.
            params = checkpoints.restore_params(
                checkpoint_dir, None, shardings=self._shardings)
        else:
            if checkpoint_dir:
                logger.warning(
                    f'No checkpoint under {checkpoint_dir}; serving '
                    'FRESH random-init weights.')
            else:
                logger.warning('No --checkpoint-dir given; serving '
                               'FRESH random-init weights.')
            # Init deterministically UNSHARDED, then place: generating
            # the random weights under GSPMD partitioning changes the
            # values with the mesh layout (the partitioned RNG lowers
            # differently), so a sharded replica would not be
            # weight-identical to a single-process one.  Checkpoints —
            # the real serving path — stream sharded regardless.
            params = jax.jit(_init)(key)
            if self._shardings is not None:
                params = jax.device_put(params, self._shardings)
        if quantize:
            from skypilot_tpu.models import quantize as quantize_lib
            # quantize_params computes on the host; place the result
            # once, or every jitted call uploads the weights again.
            params = jax.device_put(quantize_lib.quantize_params(params))
            report = quantize_lib.quantization_report(params)
            logger.info(
                f'int8 weight-only quantization: '
                f'{report["quantized_bytes"] / 1e6:.1f} MB '
                f'({report["ratio"]:.2f}x of f32)')
        self.params = params
        # Live weight swap (POST /weights_swap): the epoch now serving
        # (0 = boot weights; mirrors the engine's counter) and how to
        # re-quantize swapped checkpoints when this server quantizes.
        self.weight_version = 0
        self._quantize = quantize
        # Serving roofline input: forward FLOPs per generated token.
        # The controller's aggregator turns this + decode tokens/s
        # into the per-replica skytpu_mfu_estimate gauge.
        n_params = sum(int(p.size)
                       for p in jax.tree_util.tree_leaves(params))
        self.flops_per_token = model_flops_per_token(
            self.cfg, n_params, max_len)
        _M_FLOPS_PER_TOKEN.set(self.flops_per_token)
        # One generation at a time: KV caches are sized per call and
        # the chip is exclusive anyway; the HTTP layer queues.
        self._lock = threading.Lock()
        self._engine = None
        if continuous_batching:
            # Requests join a running batch as slots free; token
            # selection (greedy or per-request temperature/top-k) runs
            # on device inside the pipelined tick.
            if self.num_hosts > 1:
                # Slice replica: coordinated ticks across the gang +
                # sequence-parallel long-context prefill.
                from skypilot_tpu.serve import slice_replica as slice_lib
                self._engine = slice_lib.SliceReplicaEngine(
                    self.cfg, self.params, num_hosts=self.num_hosts,
                    sp_threshold=sp_threshold, mesh=self._mesh,
                    max_len=max_len, slots=max_batch,
                    max_queue=max_queue, queue_ttl=queue_ttl,
                    prefill_chunk=prefill_chunk, kv_pages=kv_pages,
                    page_size=page_size, quantize_kv=quantize_kv,
                    prefix_caching=prefix_caching,
                    spec_tokens=spec_tokens)
            else:
                self._engine = batching_engine_lib.ContinuousBatchingEngine(
                    self.cfg, self.params, max_len=max_len,
                    slots=max_batch, max_queue=max_queue,
                    queue_ttl=queue_ttl, prefill_chunk=prefill_chunk,
                    mesh=self._mesh, kv_pages=kv_pages,
                    page_size=page_size, quantize_kv=quantize_kv,
                    prefix_caching=prefix_caching,
                    spec_tokens=spec_tokens)
        # 'dense': the lock-step generate path (flash prefill + masked
        # decode) of a server without an engine.
        self.runtime['decode_kernel'] = (
            self._engine.decode_kernel if self._engine is not None
            else 'dense')
        if self._engine is not None:
            # The engine worker thread emits records outside any HTTP
            # request context; it stamps this identity (plus the
            # request id it re-binds around each admission) so the log
            # plane can attribute worker-side lines in-process too.
            self._engine.log_identity = {
                'process': 'replica', 'replica_id': self.replica_id,
                'role': self.role}

    def close(self) -> None:
        """Release background resources (the batching engine's worker
        thread + slot KV cache); safe to call twice."""
        if self._engine is not None:
            self._engine.stop()
            self._engine = None

    def drain(self) -> Dict[str, Any]:
        """Enter draining: refuse new generates (503 + Retry-After)
        while the engine finishes what it holds.  Idempotent; returns
        the in-flight snapshot the controller's drain monitor reads."""
        self.draining = True
        return {'draining': True, 'inflight': self.inflight()}

    def apply_role_budget(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """POST /role_budget: controller rebalance push or role-morph
        commit.  Swaps the engine's fractional-role budget IN PLACE
        (warm weights and page pool untouched) and, when the payload
        names a different role, flips the advertised role and clears
        draining — the morph's scoped drain is over and the replica
        re-opens under its new role.  Version-ordered: a stale push
        (older `version` than the budget in force) is dropped so a
        rebalance racing a morph cannot resurrect the old split."""
        engine = self._engine
        if engine is None:
            raise ValueError('role budgets require --continuous-batching')
        new_role = roles_lib.normalize(req.get('role') or self.role)
        version = int(req.get('version', 0))
        split = req.get('split')
        if (req.get('prefill_tokens') is not None and
                req.get('decode_tokens') is not None):
            budget = batching_engine_lib.RoleBudget(
                prefill_tokens=int(req['prefill_tokens']),
                decode_tokens=int(req['decode_tokens']),
                role=new_role,
                split=float(split) if split is not None
                else roles_lib.DEFAULT_SPLITS[new_role],
                version=version)
        elif split is not None:
            budget = batching_engine_lib.RoleBudget.from_split(
                float(split), slots=self.max_batch,
                prefill_chunk=engine.prefill_chunk, role=new_role,
                version=version)
        else:
            budget = batching_engine_lib.RoleBudget.for_role(
                new_role, slots=self.max_batch,
                prefill_chunk=engine.prefill_chunk, version=version)
        applied = engine.set_role_budget(budget)
        morphed = applied and new_role != self.role
        if morphed:
            self.role = new_role
            self.draining = False
        elif applied and req.get('resume'):
            # Aborted morph rollback: re-open under the same role.
            self.draining = False
        return {'applied': applied, 'morphed': morphed,
                'role': self.role, 'draining': self.draining,
                'budget': budget.as_dict()}

    def weights_swap(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """POST /weights_swap: live checkpoint swap — restore the
        latest orbax checkpoint under `checkpoint_dir` and swap it
        into the running engine WITHOUT dropping the KV page pool or
        any in-flight request (the engine assigns the new tree between
        ticks — the scoped pause; see
        ContinuousBatchingEngine.swap_params).  The bumped weight
        epoch lands in /health, every later request's span, and every
        generate response, so batch output rows record which weights
        produced them."""
        from skypilot_tpu.data import checkpoints  # pylint: disable=import-outside-toplevel
        engine = self._engine
        if engine is None:
            raise ValueError('live weight swap requires '
                             '--continuous-batching')
        checkpoint_dir = req.get('checkpoint_dir')
        if not checkpoint_dir or not isinstance(checkpoint_dir, str):
            raise ValueError('weights_swap needs a checkpoint_dir')
        step = checkpoints.latest_step(checkpoint_dir)
        if step is None:
            raise ValueError(f'no checkpoint under {checkpoint_dir}')
        _maybe_journal_batch('weight_swap_start',
                             replica_id=self.replica_id,
                             checkpoint_dir=checkpoint_dir, step=step)
        t0 = time.perf_counter()
        status = 'error'
        epoch: Optional[int] = None
        try:
            params = checkpoints.restore_params(
                checkpoint_dir, None, shardings=self._shardings)
            if self._quantize:
                import jax  # pylint: disable=import-outside-toplevel

                from skypilot_tpu.models import quantize as quantize_lib  # pylint: disable=import-outside-toplevel
                params = jax.device_put(
                    quantize_lib.quantize_params(params))
            epoch = engine.swap_params(params)
            self.params = params
            self.weight_version = epoch
            status = 'ok'
        finally:
            _M_WEIGHT_SWAPS.labels(status=status).inc()
            if epoch is not None:
                _M_WEIGHT_EPOCH.set(epoch)
            _maybe_journal_batch('weight_swap_end',
                                 replica_id=self.replica_id,
                                 status=status, weight_epoch=epoch)
        return {'weight_version': epoch, 'step': step,
                'restore_ms': round(
                    (time.perf_counter() - t0) * 1e3, 1)}

    def inflight(self) -> int:
        """Busy slots + queued admissions (0 without an engine): the
        occupancy signal a drain waits on, per the concurrency-limits
        framing — slot/page occupancy, not wall-clock guesses."""
        engine = self._engine
        if engine is None:
            return 0
        stats = engine.stats()
        return (int(stats.get('busy_slots', 0)) +
                int(stats.get('queued_requests', 0)))

    def identity(self) -> Dict[str, Any]:
        """Trace-segment identity tags for this replica's exports."""
        return {'process': 'replica', 'replica_id': self.replica_id,
                'role': self.role, 'num_hosts': self.num_hosts}

    def export_spans(self, since: Optional[float] = None,
                     request_id: Optional[str] = None,
                     limit: Optional[int] = None) -> Dict[str, Any]:
        """The `GET /spans` payload: engine request spans + the
        handoff-endpoint segments, identity-tagged, oldest first."""
        segments = self.trace_segments.export(
            since=since, request_id=request_id)
        engine = self._engine
        if engine is not None:
            segments.extend(engine._spans.export(  # pylint: disable=protected-access
                self.identity(), since=since, request_id=request_id))
        segments.sort(key=lambda s: s.get('start') or 0.0)
        if limit is not None:
            segments = segments[-int(limit):]
        return {'segments': segments}

    def export_profile(self) -> Dict[str, Any]:
        """The `GET /profile` payload: the engine's tick-phase ring +
        recompile-sentinel snapshot, identity-tagged so `sky serve
        profile` can stitch a fleet view."""
        payload = self.identity()
        engine = self._engine
        payload['profile'] = (engine.profile() if engine is not None
                              else None)
        return payload

    def record_handoff_segment(self, name: str, request_id: str,
                               start: float, duration_ms: float,
                               attempt: Optional[int] = None,
                               **fields: Any) -> None:
        """One non-engine leg of a request's life (the prefill
        replica's /prefill_export, the decode replica's /kv_import)
        as a trace segment — without this, `sky serve trace` of a
        disaggregated request would miss the prefill replica
        entirely (exports never create an engine span)."""
        seg = self.identity()
        seg.update({'name': name, 'request_id': request_id,
                    'start': start,
                    'duration_ms': round(duration_ms, 3),
                    'attempt': int(attempt or 0), 'phases': []})
        seg.update(fields)
        self.trace_segments.add(seg)

    def generate(self, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0, top_k: int = 0,
                 stop_token=None, seed: int = 0,
                 request_id: Optional[str] = None,
                 route_meta: Optional[Dict[str, Any]] = None,
                 deadline_ms: Optional[float] = None,
                 qos_class: Optional[str] = None,
                 on_submit=None, disconnect_probe=None) -> Any:
        """stop_token: None, a single id, or an iterable of ids (the
        tokenizer's multi-EOS stop set).

        request_id: propagated X-SkyTPU-Request-Id; under continuous
        batching it names the request's span record (multi-row batches
        suffix `-1`, `-2`, ... on rows after the first).

        deadline_ms: per-request time budget (X-SkyTPU-Deadline-Ms);
        the engine reaps the slot(s) past it -> DeadlineExceeded.

        on_submit: called with the engine request handles right after
        submission (async front's disconnect watchdog cancels through
        them).  disconnect_probe: polled while waiting; returning True
        means the client hung up — every handle is cancelled and
        ClientDisconnected raised (threaded front, MSG_PEEK probe)."""
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.models import decode
        prompt = jnp.asarray(prompt_ids, jnp.int32)
        if prompt.ndim != 2:
            raise ValueError('prompt_ids must be [batch, seq]')
        if prompt.shape[0] > self.max_batch:
            raise ValueError(
                f'batch {prompt.shape[0]} > max_batch {self.max_batch}')
        if prompt.shape[1] + max_new_tokens > self.max_len:
            raise ValueError(
                f'prompt {prompt.shape[1]} + new {max_new_tokens} '
                f'exceeds max_len {self.max_len}')
        sampling = decode.SamplingConfig(temperature=temperature,
                                         top_k=top_k, seed=seed)
        if self._engine is not None:
            # Each row is its own request: they decode TOGETHER with
            # whatever else is in flight (no lock — that is the point).
            # Sampling runs ON DEVICE inside the engine tick, seeded
            # per request.
            requests = [
                self._engine.submit([int(t) for t in row],
                                    max_new_tokens,
                                    stop_token=stop_token,
                                    sampling=sampling,
                                    request_id=(
                                        None if request_id is None
                                        else (request_id if i == 0 else
                                              f'{request_id}-{i}')),
                                    route_meta=route_meta,
                                    deadline_ms=deadline_ms,
                                    qos_class=qos_class)
                for i, row in enumerate(prompt_ids)
            ]
            if on_submit is not None:
                on_submit(requests)
            if disconnect_probe is not None:
                # Poll the connection while waiting: a client that hung
                # up must free its slots NOW, not after max_new_tokens.
                wait_until = time.monotonic() + 600
                while True:
                    pending = next((r for r in requests
                                    if not r.done.is_set()), None)
                    if pending is None:
                        break
                    if disconnect_probe():
                        for r in requests:
                            r.cancel()
                        raise ClientDisconnected(
                            'client disconnected mid-generation')
                    if time.monotonic() > wait_until:
                        raise TimeoutError('generation timed out')
                    pending.done.wait(0.1)
            return [r.result(timeout=600) for r in requests]
        with self._lock:
            tokens, new = decode.generate(
                self.cfg, self.params, prompt,
                max_new_tokens=max_new_tokens, max_len=self.max_len,
                sampling=sampling, rng=jax.random.PRNGKey(seed),
                mesh=self._mesh)
        del tokens
        return new.tolist()


def _make_handler(server: ModelServer):

    class Handler(BaseHTTPRequestHandler):
        protocol_version = 'HTTP/1.1'

        def log_message(self, *args):
            del args

        def send_response(self, code, message=None):
            # Remember the status for the access log/counter (the
            # last send_response of the exchange wins, matching what
            # actually went on the wire).
            self._status = code
            super().send_response(code, message)

        def _read_body(self) -> bytes:
            length = int(self.headers.get('Content-Length', 0))
            return self.rfile.read(length)

        def _read_json(self) -> Dict[str, Any]:
            return json.loads(self._read_body() or b'{}')

        def _reply(self, code: int, payload: Dict[str, Any],
                   headers: Optional[Dict[str, str]] = None) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header('Content-Type', 'application/json')
            self.send_header('Content-Length', str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _reply_backpressure(self, e: Exception) -> bool:
            """Admission-control errors become honest HTTP status +
            Retry-After instead of a generic 500: 429 when the queue is
            full, 503 when the request expired waiting (the client
            should hit another replica / back off, not time out), 504
            when the request's own deadline passed."""
            if isinstance(e, batching_engine_lib.QueueFull):
                self._reply(429, {'error': str(e)},
                            {'Retry-After': str(int(e.retry_after))})
                return True
            if isinstance(e, batching_engine_lib.QueueExpired):
                self._reply(503, {'error': str(e)},
                            {'Retry-After': str(int(e.retry_after))})
                return True
            if isinstance(e, batching_engine_lib.DeadlineExceeded):
                self._reply(504, {'error': str(e),
                                  'reason': 'deadline_exceeded'})
                return True
            return False

        def _reject_if_draining(self) -> bool:
            """503 + Retry-After for new generation work on a draining
            replica — the LB's same-role retry lands it on a sibling
            (this is what makes a drain invisible to clients)."""
            if not server.draining:
                return False
            # Consume the request body first: replying with unread
            # body bytes on the socket would desync a keep-alive
            # connection's framing for the next request.
            self._read_body()
            _M_DRAIN_REJECTED.inc()
            self._reply(503, {'error': 'replica is draining',
                              'reason': 'draining'},
                        {'Retry-After': '5'})
            return True

        def _deadline_ms(self) -> Optional[float]:
            """The request's X-SkyTPU-Deadline-Ms, else the replica's
            env default (SKYTPU_SERVE_DEFAULT_DEADLINE_MS)."""
            raw = self.headers.get(router_lib.DEADLINE_HEADER)
            if raw:
                try:
                    ms = float(raw)
                    return ms if ms > 0 else None
                except ValueError:
                    pass
            return default_deadline_ms()

        def _qos_class(self) -> str:
            """The request's X-SkyTPU-QoS-Class, clamped to a known
            class (absent -> the env default class)."""
            return qos_lib.normalize(
                self.headers.get(router_lib.QOS_CLASS_HEADER))

        def _disconnect_probe(self):
            """True once the client socket is closed.  MSG_PEEK never
            consumes pipelined bytes: data waiting reads as 'still
            connected', only a clean EOF (or a dead socket) as gone."""
            import select  # pylint: disable=import-outside-toplevel
            import socket as socket_lib  # pylint: disable=import-outside-toplevel
            sock = self.connection

            def probe() -> bool:
                try:
                    readable, _, _ = select.select([sock], [], [], 0)
                    if not readable:
                        return False
                    return sock.recv(1, socket_lib.MSG_PEEK) == b''
                except (OSError, ValueError):
                    return True
            return probe

        def _sampling(self, req: Dict[str, Any]):
            """(temperature, top_k, seed) — request fields, falling
            back to the server's CLI defaults."""
            return (float(req.get('temperature',
                                  server.default_temperature)),
                    int(req.get('top_k', server.default_top_k)),
                    int(req.get('seed', server.default_seed)))

        def _request_id(self) -> str:
            """The propagated X-SkyTPU-Request-Id, or a fresh id when
            this server is the outermost layer that saw the request."""
            return (self.headers.get(tracing.REQUEST_ID_HEADER) or
                    tracing.new_request_id())

        def _route_meta(self) -> Optional[Dict[str, Any]]:
            """Routing facts the LB forwarded; None for direct hits.
            Counting happens here so the replica's /metrics carries
            the per-role/affinity view the CLI table shows."""
            role = self.headers.get(router_lib.ROUTED_ROLE_HEADER)
            affinity = self.headers.get(router_lib.AFFINITY_HEADER)
            handoff_ms = self.headers.get(router_lib.HANDOFF_MS_HEADER)
            if not (role or affinity or handoff_ms):
                return None
            _M_ROUTED.labels(role=role or 'unknown',
                             affinity=affinity or 'none').inc()
            try:
                ms = float(handoff_ms) if handoff_ms else None
            except ValueError:
                ms = None
            return {'routed_role': role,
                    'affinity_hit': (affinity == 'hit'
                                     if affinity else None),
                    'handoff_ms': ms,
                    'attempt': _attempt_header(
                        self.headers.get(router_lib.ATTEMPT_HEADER))}

        def do_GET(self):
            path, _, query = self.path.partition('?')
            route = (path if path in http_protocol.REPLICA_PATHS
                     else logs_lib.HEALTH_ROUTE)
            self._status = 0
            # Request-scoped log context: every record emitted while
            # handling this request carries the propagated id + this
            # replica's identity.  Probe/scrape access lines log at
            # DEBUG (logs_lib.PROBE_ROUTES) so the ring isn't
            # wall-to-wall controller scrape noise.
            with logs_lib.bind(
                    request_id=self.headers.get(
                        tracing.REQUEST_ID_HEADER),
                    attempt=_attempt_header(
                        self.headers.get(router_lib.ATTEMPT_HEADER)),
                    process='replica', replica_id=server.replica_id,
                    role=server.role):
                try:
                    self._get(path, query)
                finally:
                    logs_lib.access_log(logger, 'GET', route,
                                        self._status)

        def _get(self, path, query):
            if path == http_protocol.METRICS:
                engine = server._engine  # pylint: disable=protected-access
                if engine is not None:
                    engine.stats()  # freshen the scrape-time gauges
                body = metrics_lib.expose().encode()
                self.send_response(200)
                self.send_header('Content-Type',
                                 metrics_lib.CONTENT_TYPE)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if path == http_protocol.SPANS:
                # Trace-segment export: this replica's leg of each
                # request's life, for cross-process assembly
                # (sky serve trace / the controller aggregator).
                self._reply(200, server.export_spans(
                    **parse_span_query(query)))
                return
            if path == http_protocol.PROFILE:
                # Continuous-profiling export: tick-phase ring +
                # recompile sentinel (sky serve profile).
                self._reply(200, server.export_profile())
                return
            if path == http_protocol.LOGS:
                # Structured log-ring export (sky serve logs): this
                # process's recent records, seq-cursor paginated.
                self._reply(200, {'records': logs_lib.get_ring().export(
                    **logs_lib.parse_log_query(query))})
                return
            payload = {'status': 'ok',
                       'model': f'{server.cfg.d_model}x'
                                f'{server.cfg.n_layers}',
                       'role': server.role,
                       'num_hosts': server.num_hosts,
                       'draining': server.draining,
                       'weight_version': server.weight_version,
                       **server.runtime}
            engine = server._engine  # pylint: disable=protected-access
            code = 200
            if engine is not None:  # local bind: close() may race
                stats = engine.stats()
                payload['engine'] = stats
                if 'slice' in stats:
                    # Slice replicas surface gang health top-level so
                    # the controller's probe can tell "rank died, tear
                    # down and replace" from a transient flap.
                    payload['slice'] = stats['slice']
                if stats['failed']:
                    # A dead engine must fail the readiness probe or
                    # the LB keeps routing to a black hole.
                    payload['status'] = 'engine_failed'
                    code = 503
            self._reply(code, payload)

        def _generate_text(self):
            """Text in, text out through the checkpoint's tokenizer
            (models/tokenizer.py: real tokenizer.json / .model when
            present, byte-level fallback otherwise).  With
            {"stream": true} the response is SSE {"text": delta}
            events, decoded incrementally UTF-8-safe."""
            if self._reject_if_draining():
                return
            try:
                tok = server.tokenizer
                if server.cfg.vocab_size < tok.vocab_size:
                    raise ValueError(
                        f'model vocab {server.cfg.vocab_size} < '
                        f'tokenizer vocab {tok.vocab_size}: checkpoint '
                        'and tokenizer do not match')
                req = self._read_json()
                text = req['prompt']
                if not isinstance(text, str) or not text:
                    raise ValueError('prompt must be a non-empty string')
                ids = tok.encode(text, add_bos=True)
                if not ids:
                    raise ValueError('prompt tokenized to nothing')
                rid = self._request_id()
                if req.get('stream'):
                    self._stream_text(tok, ids, req, rid)
                    return
                t0 = time.perf_counter()
                # The engine stops AT the tokenizer's EOS (freeing the
                # slot); the lock-step scan is fixed-length, so the
                # truncation below applies either way.
                temperature, top_k, seed = self._sampling(req)
                tokens = server.generate(
                    [ids], int(req.get('max_new_tokens', 64)),
                    temperature, top_k,
                    stop_token=tok.eos_ids or None, seed=seed,
                    request_id=rid,
                    route_meta=self._route_meta(),
                    deadline_ms=self._deadline_ms(),
                    qos_class=self._qos_class(),
                    disconnect_probe=self._disconnect_probe())[0]
                _maybe_journal_request('serve_request_done',
                                       request_id=rid, status='ok',
                                       tokens=len(tokens))
                stops = [i for i, t in enumerate(tokens)
                         if t in tok.eos_ids]
                if stops:
                    tokens = tokens[:stops[0]]
                self._reply(200, {
                    'completion': tok.decode(tokens),
                    'tokens': tokens,
                    'weight_version': server.weight_version,
                    'latency_ms': round(
                        (time.perf_counter() - t0) * 1e3, 1),
                }, {tracing.REQUEST_ID_HEADER: rid})
            except ClientDisconnected:
                return  # nobody is owed a reply; the slot is freed
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._reply(400, {'error': str(e)})
            except Exception as e:  # pylint: disable=broad-except
                if not self._reply_backpressure(e):
                    self._reply(500, {'error': f'{type(e).__name__}: {e}'})

        def _stream_text(self, tok, ids, req, rid):
            """SSE text deltas: data: {"text": "..."} per decode step
            (skipping steps buffered inside a multi-byte sequence),
            then data: [DONE].  Needs --continuous-batching."""
            from skypilot_tpu.models import decode
            from skypilot_tpu.models.tokenizer import StreamDecoder
            if server._engine is None:  # pylint: disable=protected-access
                self._reply(400, {'error': 'streaming requires '
                                           '--continuous-batching'})
                return
            temperature, top_k, seed = self._sampling(req)
            request = server._engine.submit(  # pylint: disable=protected-access
                ids, int(req.get('max_new_tokens', 64)),
                stop_token=tok.eos_ids or None,
                sampling=decode.SamplingConfig(
                    temperature=temperature, top_k=top_k, seed=seed),
                request_id=rid, route_meta=self._route_meta(),
                deadline_ms=self._deadline_ms(),
                qos_class=self._qos_class())
            self._start_sse(rid)
            decoder = StreamDecoder(tok)
            try:
                for token in request.stream(timeout=600):
                    if token in tok.eos_ids:
                        break
                    delta = decoder.push(token)
                    if delta:
                        self._sse_chunk(json.dumps({'text': delta}))
                tail = decoder.finish()
                if tail:
                    self._sse_chunk(json.dumps({'text': tail}))
                self._sse_chunk('[DONE]')
                self.wfile.write(b'0\r\n\r\n')
            except (BrokenPipeError, ConnectionResetError):
                request.cancel()
            except Exception as e:  # pylint: disable=broad-except
                request.cancel()
                try:
                    self._sse_chunk(json.dumps(
                        {'error': f'{type(e).__name__}: {e}'}))
                    self.wfile.write(b'0\r\n\r\n')
                except (BrokenPipeError, ConnectionResetError, OSError):
                    pass

        def _generate_stream(self):
            """SSE token stream: `data: {"token": N}` per token, then
            `data: [DONE]`.  Requires --continuous-batching (the engine
            produces tokens one step at a time); single prompt only.
            The LB relays these chunks unbuffered end-to-end."""
            if self._reject_if_draining():
                return
            try:
                req = self._read_json()
                prompt = req['prompt_ids']
                if (isinstance(prompt, list) and prompt and
                        isinstance(prompt[0], list)):
                    if len(prompt) != 1:
                        raise ValueError(
                            'streaming serves one prompt per request')
                    prompt = prompt[0]
                if server._engine is None:  # pylint: disable=protected-access
                    self._reply(400, {
                        'error': 'streaming requires '
                                 '--continuous-batching'})
                    return
                from skypilot_tpu.models import decode
                temperature, top_k, seed = self._sampling(req)
                rid = self._request_id()
                request = server._engine.submit(  # pylint: disable=protected-access
                    [int(t) for t in prompt],
                    int(req.get('max_new_tokens', 16)),
                    stop_token=req.get('stop_token'),
                    sampling=decode.SamplingConfig(
                        temperature=temperature, top_k=top_k,
                        seed=seed),
                    request_id=rid, route_meta=self._route_meta(),
                    deadline_ms=self._deadline_ms(),
                    qos_class=self._qos_class())
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._reply(400, {'error': str(e)})
                return
            except Exception as e:  # pylint: disable=broad-except
                # Stopped/failed engine (503) or a full admission
                # queue (429 + Retry-After): an HTTP error, not a
                # dropped connection.
                if not self._reply_backpressure(e):
                    self._reply(503,
                                {'error': f'{type(e).__name__}: {e}'})
                return
            self._start_sse(rid)
            try:
                for token in request.stream(timeout=600):
                    self._sse_chunk(json.dumps({'token': token}))
                self._sse_chunk('[DONE]')
                self.wfile.write(b'0\r\n\r\n')
                _maybe_journal_request('serve_request_done',
                                       request_id=rid, status='ok',
                                       tokens=len(request.tokens))
            except (BrokenPipeError, ConnectionResetError):
                # Client went away: free the slot instead of decoding
                # the rest of max_new_tokens for nobody.
                request.cancel()
            except Exception as e:  # pylint: disable=broad-except
                # Same slot-leak logic for every other failure (stalled
                # stream timeout, other socket errors): nobody is
                # reading this request anymore.
                request.cancel()
                try:
                    self._sse_chunk(json.dumps(
                        {'error': f'{type(e).__name__}: {e}'}))
                    self.wfile.write(b'0\r\n\r\n')
                except (BrokenPipeError, ConnectionResetError, OSError):
                    pass

        def _start_sse(self, rid: Optional[str] = None) -> None:
            self.send_response(200)
            self.send_header('Content-Type', 'text/event-stream')
            self.send_header('Cache-Control', 'no-cache')
            self.send_header('Transfer-Encoding', 'chunked')
            if rid is not None:
                self.send_header(tracing.REQUEST_ID_HEADER, rid)
            self.end_headers()

        def _sse_chunk(self, data: str) -> None:
            payload = f'data: {data}\n\n'.encode()
            self.wfile.write(f'{len(payload):x}\r\n'.encode() +
                             payload + b'\r\n')
            self.wfile.flush()

        def _prefill_export(self):
            """KV handoff, prefill side: prefill the prompt and return
            its full pages as a serve/handoff.py wire payload — the
            router imports it on a decode replica and then forwards the
            request there (where it lands as a prefix hit).  A request
            carrying {"wire": "binary"} (or Accept: application/
            octet-stream) gets the raw binary frame instead of
            JSON/base64."""
            engine = server._engine  # pylint: disable=protected-access
            if engine is None:
                self._reply(400, {'error': 'KV handoff requires '
                                           '--continuous-batching'})
                return
            if self._reject_if_draining():
                return
            try:
                req = self._read_json()
                prompt = req['prompt_ids']
                if (isinstance(prompt, list) and prompt and
                        isinstance(prompt[0], list)):
                    if len(prompt) != 1:
                        raise ValueError(
                            'export serves one prompt per request')
                    prompt = prompt[0]
                binary = (req.get('wire') == 'binary' or
                          handoff_lib.CONTENT_TYPE_BINARY in
                          (self.headers.get('Accept') or ''))
                t0, wall0 = time.perf_counter(), time.time()
                payload = engine.export_prefill(
                    [int(t) for t in prompt],
                    page_size=req.get('page_size'), binary=binary)
                server.record_handoff_segment(
                    'prefill_export', self._request_id(), wall0,
                    (time.perf_counter() - t0) * 1e3,
                    attempt=_attempt_header(self.headers.get(
                        router_lib.ATTEMPT_HEADER)),
                    tokens=len(prompt))
                if binary:
                    self.send_response(200)
                    self.send_header('Content-Type',
                                     handoff_lib.CONTENT_TYPE_BINARY)
                    self.send_header('Content-Length',
                                     str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                else:
                    self._reply(200, payload)
            except (handoff_lib.HandoffError, KeyError, ValueError,
                    TypeError, json.JSONDecodeError) as e:
                self._reply(400, {'error': str(e)})
            except Exception as e:  # pylint: disable=broad-except
                if not self._reply_backpressure(e):
                    self._reply(500,
                                {'error': f'{type(e).__name__}: {e}'})

        def _kv_import(self):
            """KV handoff, decode side: adopt exported pages into the
            pool + prefix cache.  Accepts the JSON/base64 payload OR
            the binary frame (Content-Type: application/octet-stream).
            429 pages_exhausted when the pool cannot hold them right
            now; 503 when the import is refused (chaos deny /
            shedding) — the router falls back to local prefill either
            way."""
            engine = server._engine  # pylint: disable=protected-access
            if engine is None:
                self._reply(400, {'error': 'KV handoff requires '
                                           '--continuous-batching'})
                return
            if self._reject_if_draining():
                # Imported pages would die with this replica anyway.
                return
            try:
                ctype = self.headers.get('Content-Type') or ''
                if handoff_lib.CONTENT_TYPE_BINARY in ctype:
                    decoded = handoff_lib.decode_binary(
                        self._read_body())
                else:
                    decoded = handoff_lib.decode_payload(
                        self._read_json())
                t0, wall0 = time.perf_counter(), time.time()
                imported, cached = engine.import_pages(
                    decoded['hashes'], decoded['page_size'],
                    decoded['k'], decoded['v'],
                    k_scale=decoded.get('k_scale'),
                    v_scale=decoded.get('v_scale'))
                server.record_handoff_segment(
                    'kv_import', self._request_id(), wall0,
                    (time.perf_counter() - t0) * 1e3,
                    attempt=_attempt_header(self.headers.get(
                        router_lib.ATTEMPT_HEADER)),
                    imported_pages=imported, cached_pages=cached)
                self._reply(200, {'imported_pages': imported,
                                  'cached_pages': cached})
            except handoff_lib.HandoffRejected as e:
                self._reply(503, {'error': str(e),
                                  'reason': 'kv_handoff_denied'})
            except (handoff_lib.HandoffError, KeyError, ValueError,
                    TypeError, json.JSONDecodeError) as e:
                self._reply(400, {'error': str(e)})
            except Exception as e:  # pylint: disable=broad-except
                if not self._reply_backpressure(e):
                    self._reply(500,
                                {'error': f'{type(e).__name__}: {e}'})

        def _drain(self):
            """Controller retirement path: flip the replica to
            draining (new generates 503 while in-flight work
            finishes) and report the occupancy the drain waits on."""
            self._reply(200, server.drain())

        def _role_budget(self):
            """Rebalance push / morph commit: swap the fractional-role
            budget in place (see ModelServer.apply_role_budget).
            Allowed while draining — a morph drains, then commits."""
            try:
                self._reply(200,
                            server.apply_role_budget(self._read_json()))
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._reply(400, {'error': str(e)})
            except Exception as e:  # pylint: disable=broad-except
                self._reply(500, {'error': f'{type(e).__name__}: {e}'})

        def _weights_swap(self):
            """Live checkpoint swap (see ModelServer.weights_swap).
            Allowed while draining — a fleet can pre-stage fresh
            weights on replicas it is about to re-open."""
            try:
                self._reply(200,
                            server.weights_swap(self._read_json()))
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._reply(400, {'error': str(e)})
            except Exception as e:  # pylint: disable=broad-except
                self._reply(500, {'error': f'{type(e).__name__}: {e}'})

        def _prefix_export(self):
            """Drain-time sibling handoff: export the hottest prefix-
            cache pages (POOL pages — no prefill runs) so a surviving
            replica inherits the pinned sessions.  Allowed while
            draining — that is the point."""
            engine = server._engine  # pylint: disable=protected-access
            if engine is None:
                self._reply(400, {'error': 'prefix export requires '
                                           '--continuous-batching'})
                return
            try:
                req = self._read_json()
                binary = (req.get('wire') == 'binary' or
                          handoff_lib.CONTENT_TYPE_BINARY in
                          (self.headers.get('Accept') or ''))
                payload = engine.export_prefix_pages(
                    max_pages=int(req.get('max_pages', 64)),
                    binary=binary)
                if binary:
                    self.send_response(200)
                    self.send_header('Content-Type',
                                     handoff_lib.CONTENT_TYPE_BINARY)
                    self.send_header('Content-Length',
                                     str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                else:
                    self._reply(200, payload)
            except (handoff_lib.HandoffError, KeyError, ValueError,
                    TypeError, json.JSONDecodeError) as e:
                self._reply(404, {'error': str(e)})
            except Exception as e:  # pylint: disable=broad-except
                if not self._reply_backpressure(e):
                    self._reply(500,
                                {'error': f'{type(e).__name__}: {e}'})

        def do_POST(self):
            path = self.path.partition('?')[0]
            route = (path if path in http_protocol.REPLICA_PATHS
                     else 'unknown')
            self._status = 0
            with logs_lib.bind(
                    request_id=self.headers.get(
                        tracing.REQUEST_ID_HEADER),
                    attempt=_attempt_header(
                        self.headers.get(router_lib.ATTEMPT_HEADER)),
                    process='replica', replica_id=server.replica_id,
                    role=server.role):
                try:
                    self._post()
                finally:
                    logs_lib.access_log(logger, 'POST', route,
                                        self._status)

        def _post(self):
            if self.path == http_protocol.GENERATE_STREAM:
                self._generate_stream()
                return
            if self.path == http_protocol.GENERATE_TEXT:
                self._generate_text()
                return
            if self.path == http_protocol.PREFILL_EXPORT:
                self._prefill_export()
                return
            if self.path == http_protocol.KV_IMPORT:
                self._kv_import()
                return
            if self.path == http_protocol.DRAIN:
                self._drain()
                return
            if self.path == http_protocol.PREFIX_EXPORT:
                self._prefix_export()
                return
            if self.path == http_protocol.ROLE_BUDGET:
                self._role_budget()
                return
            if self.path == http_protocol.WEIGHTS_SWAP:
                self._weights_swap()
                return
            if self.path != http_protocol.GENERATE:
                self._reply(404, {'error': 'unknown path'})
                return
            if self._reject_if_draining():
                return
            try:
                req = self._read_json()
                t0 = time.perf_counter()
                temperature, top_k, seed = self._sampling(req)
                rid = self._request_id()
                qos_class = self._qos_class()
                tokens = server.generate(
                    req['prompt_ids'],
                    int(req.get('max_new_tokens', 16)),
                    temperature, top_k, seed=seed, request_id=rid,
                    route_meta=self._route_meta(),
                    deadline_ms=self._deadline_ms(),
                    qos_class=qos_class,
                    disconnect_probe=self._disconnect_probe())
                if qos_class == qos_lib.BATCH:
                    _M_BATCH_ROWS.inc(len(tokens))
                _maybe_journal_request(
                    'serve_request_done', request_id=rid, status='ok',
                    tokens=sum(len(t) for t in tokens))
                self._reply(200, {
                    'tokens': tokens,
                    'weight_version': server.weight_version,
                    'latency_ms': round(
                        (time.perf_counter() - t0) * 1e3, 1),
                }, {tracing.REQUEST_ID_HEADER: rid})
            except ClientDisconnected:
                return  # nobody is owed a reply; the slots are freed
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._reply(400, {'error': str(e)})
            except Exception as e:  # pylint: disable=broad-except
                # Engine failures (stopped engine, tick error, result
                # timeout) must reach the client as an HTTP error —
                # and admission-control pushback as 429/503 with
                # Retry-After — not a dropped connection.
                if not self._reply_backpressure(e):
                    self._reply(500, {'error': f'{type(e).__name__}: {e}'})

    return Handler


def serve_forever(server: ModelServer, port: int = 0) -> int:
    httpd = ThreadingHTTPServer(('0.0.0.0', port),
                                _make_handler(server))
    port = httpd.server_port
    logger.info(f'model server on :{port}')
    try:
        httpd.serve_forever()
    finally:
        server.close()
    return port


def start_background(server: ModelServer, port: int = 0):
    """Tests: start the server on a daemon thread; returns (port,
    shutdown_fn)."""
    httpd = ThreadingHTTPServer(('0.0.0.0', port),
                                _make_handler(server))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    def stop() -> None:
        httpd.shutdown()
        # Close the listening socket too: a stopped replica must
        # REFUSE connections (so an LB retries a sibling fast), not
        # strand them in the accept backlog.
        httpd.server_close()

    return httpd.server_port, stop


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='tiny',
                        help="Preset name, or 'auto' to read "
                             'model_config.json from --checkpoint-dir '
                             '(converted real checkpoints).')
    parser.add_argument('--port', type=int, default=8080)
    parser.add_argument('--max-len', type=int, default=512)
    parser.add_argument('--max-batch', type=int, default=8)
    parser.add_argument('--checkpoint-dir', default=None)
    parser.add_argument('--tokenizer', default=None,
                        help='Tokenizer file/dir (default: tokenizer '
                             'files next to --checkpoint-dir, else the '
                             'byte-level fallback).')
    parser.add_argument('--quantize', default=None, choices=['int8'],
                        help='Weight-only quantization: ~2x less HBM '
                             'traffic per decoded token vs bf16.')
    parser.add_argument('--continuous-batching', action='store_true',
                        help='Slot-pool scheduling: requests join a '
                             'running batch as slots free '
                             '(max_batch = slot count); pipelined '
                             'decode ticks with on-device sampling.')
    parser.add_argument('--max-queue', type=int, default=0,
                        help='Bound the admission queue: submits '
                             'beyond this many waiting requests get '
                             'HTTP 429 + Retry-After (0 = unbounded).')
    parser.add_argument('--queue-ttl', type=float, default=None,
                        help='Seconds a request may wait queued before '
                             'it expires with HTTP 503 + Retry-After.')
    parser.add_argument('--prefill-chunk', type=int, default=512,
                        help='Chunked prefill width: long prompts '
                             'prefill in chunks interleaved with '
                             'decode ticks, bounding the ITL stall an '
                             'admission imposes on running requests.')
    import os as _os
    parser.add_argument('--kv-pages', type=int,
                        default=(int(_os.environ['SKYTPU_SERVE_KV_PAGES'])
                                 if _os.environ.get(
                                     'SKYTPU_SERVE_KV_PAGES')
                                 else None),
                        help='Size of the KV page pool: N pages with '
                             'per-slot block tables — slot count '
                             'decouples from --max-len, pool '
                             'exhaustion backpressures (429). '
                             'Default: what every slot needs to hold '
                             '--max-len at once, max_batch * max_len '
                             '/ page_size + 1 '
                             '(env SKYTPU_SERVE_KV_PAGES).')
    parser.add_argument('--page-size', type=int,
                        default=int(_os.environ.get(
                            'SKYTPU_SERVE_PAGE_SIZE', '16')),
                        help='Tokens per KV page (--max-len must be '
                             'a multiple; env '
                             'SKYTPU_SERVE_PAGE_SIZE).')
    parser.add_argument('--quantize-kv', action='store_true',
                        default=_os.environ.get(
                            'SKYTPU_SERVE_KV_INT8', '') == '1',
                        help='Store KV pages as int8 with per-page-'
                             'per-head scales: ~2x tokens per byte of '
                             'cache (env SKYTPU_SERVE_KV_INT8=1).')
    parser.add_argument('--spec-tokens', type=int,
                        default=int(_os.environ.get(
                            'SKYTPU_SERVE_SPEC_TOKENS', '0')),
                        help='Self-speculative decoding: propose N '
                             'draft tokens per slot from an n-gram '
                             'prompt-lookup drafter and verify them '
                             'all in one batched tick — token streams '
                             'stay byte-identical, ITL drops by the '
                             'acceptance length on repetitive text '
                             '(0 = off; env '
                             'SKYTPU_SERVE_SPEC_TOKENS).')
    parser.add_argument('--no-prefix-cache', action='store_true',
                        default=_os.environ.get(
                            'SKYTPU_SERVE_PREFIX_CACHE', '1') == '0',
                        help='Disable prompt prefix reuse across '
                             'requests (env '
                             'SKYTPU_SERVE_PREFIX_CACHE=0).')
    parser.add_argument('--temperature', type=float, default=0.0,
                        help='Default sampling temperature for '
                             'requests that omit it (0 = greedy).')
    parser.add_argument('--top-k', type=int, default=0,
                        help='Default top-k filter for requests that '
                             'omit it (0 = off).')
    parser.add_argument('--seed', type=int, default=0,
                        help='Default sampling seed for requests that '
                             'omit it.')
    parser.add_argument('--tensor', type=int, default=1,
                        help='Tensor-shard the model over N local '
                             'devices (models too big for one chip); '
                             'GSPMD partitions the decode einsums.')
    parser.add_argument('--num-hosts', type=int,
                        default=int(_os.environ.get(
                            'SKYTPU_SERVE_REPLICA_NUM_HOSTS', '1')),
                        help='Serve this replica as a multi-host SLICE '
                             'of N gang-scheduled hosts: weights '
                             'tensor/fsdp-sharded over the slice mesh, '
                             'paged KV pool sharded with them, ticks '
                             'coordinated across ranks, long prompts '
                             'prefilled sequence-parallel (ring '
                             'attention).  Emulated hosts = virtual '
                             'devices; env '
                             'SKYTPU_SERVE_REPLICA_NUM_HOSTS — set by '
                             'the controller from the role pool\'s '
                             'num_hosts:.  Requires '
                             '--continuous-batching.')
    parser.add_argument('--sp-threshold', type=int,
                        default=(int(_os.environ[
                            'SKYTPU_SLICE_SP_THRESHOLD'])
                                 if _os.environ.get(
                                     'SKYTPU_SLICE_SP_THRESHOLD')
                                 else None),
                        help='Prompt tokens at which a multi-host '
                             'replica prefills sequence-parallel in '
                             'one shot instead of chunked (default '
                             '1024; env SKYTPU_SLICE_SP_THRESHOLD).')
    parser.add_argument('--slice-sequence', type=int, default=None,
                        help='Pin the sequence-axis factor of the '
                             'slice mesh (default: hosts left over '
                             'after the tensor factor).')
    parser.add_argument('--slice-tensor', type=int, default=None,
                        help='Pin the tensor-axis factor of the slice '
                             'mesh (default: the largest divisor of '
                             '--num-hosts the model shapes support).')
    parser.add_argument('--role',
                        default=_os.environ.get(
                            'SKYTPU_SERVE_REPLICA_ROLE', 'mixed'),
                        choices=list(router_lib.ROLES),
                        help='Disaggregated-serving role this replica '
                             'advertises: prefill (serves '
                             '/prefill_export for KV handoff), decode '
                             '(receives handoffs + streams tokens), or '
                             'mixed (both; the default).  Env '
                             'SKYTPU_SERVE_REPLICA_ROLE — set by the '
                             'controller per role pool.')
    parser.add_argument('--http-server', default='async',
                        choices=['async', 'threaded'],
                        help='Connection front end: one asyncio event '
                             'loop (default; N concurrent SSE streams '
                             'without a thread per connection) or the '
                             'legacy thread-per-connection server.')
    args = parser.parse_args()
    from skypilot_tpu import compile_cache  # pylint: disable=import-outside-toplevel
    compile_cache.enable()
    server = ModelServer(args.model, checkpoint_dir=args.checkpoint_dir,
                         max_len=args.max_len, max_batch=args.max_batch,
                         quantize=args.quantize,
                         continuous_batching=args.continuous_batching,
                         tensor=args.tensor,
                         tokenizer_path=args.tokenizer,
                         max_queue=args.max_queue,
                         queue_ttl=args.queue_ttl,
                         prefill_chunk=args.prefill_chunk,
                         default_temperature=args.temperature,
                         default_top_k=args.top_k,
                         default_seed=args.seed,
                         kv_pages=args.kv_pages,
                         page_size=args.page_size,
                         quantize_kv=args.quantize_kv,
                         prefix_caching=not args.no_prefix_cache,
                         spec_tokens=args.spec_tokens,
                         role=args.role,
                         num_hosts=args.num_hosts,
                         sp_threshold=args.sp_threshold,
                         slice_sequence=args.slice_sequence,
                         slice_tensor=args.slice_tensor)
    if args.http_server == 'async':
        from skypilot_tpu.serve import async_server  # pylint: disable=import-outside-toplevel
        async_server.serve_forever(server, args.port)
    else:
        serve_forever(server, args.port)


if __name__ == '__main__':
    main()
