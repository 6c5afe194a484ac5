"""First-class checkpoint/resume contract.

The reference leaves checkpointing entirely to user code (SURVEY.md §5:
"not in the framework" — users mount a bucket and hand-roll resume).
Here it is a framework contract:

- Managed jobs (and `launch --checkpoint-bucket`) auto-create a bucket
  mount at CHECKPOINT_PATH and export SKYTPU_CHECKPOINT_DIR
  (skylet/constants.py:42) keyed by task id.
- User code calls `checkpoint_manager()` to get an orbax
  CheckpointManager rooted there, and `latest_step()` /
  `restore_or_init()` for the resume-on-recovery convention.
"""
from __future__ import annotations

import itertools
import os
import queue
import threading
import time
from typing import Any, Optional, Tuple

from skypilot_tpu import sky_logging
from skypilot_tpu.chaos import injector as chaos_injector
from skypilot_tpu.observability import events as events_lib
from skypilot_tpu.skylet import constants

logger = sky_logging.init_logger(__name__)

# Where the checkpoint bucket is mounted on cluster hosts.
CHECKPOINT_PATH = '/checkpoint'


def default_bucket_name(user_hash: str) -> str:
    return f'skytpu-checkpoints-{user_hash}'


def checkpoint_dir() -> Optional[str]:
    """The directory user code should checkpoint into (None when the
    task was launched without the checkpoint contract)."""
    return os.environ.get(constants.ENV_CHECKPOINT_DIR)


def checkpoint_manager(directory: Optional[str] = None,
                       *,
                       max_to_keep: int = 3,
                       save_interval_steps: int = 1) -> Any:
    """An orbax CheckpointManager rooted at the task's checkpoint dir."""
    import orbax.checkpoint as ocp  # pylint: disable=import-outside-toplevel
    directory = directory or checkpoint_dir()
    if directory is None:
        raise RuntimeError(
            'No checkpoint dir: set SKYTPU_CHECKPOINT_DIR or pass '
            'directory=.')
    options = ocp.CheckpointManagerOptions(
        max_to_keep=max_to_keep,
        save_interval_steps=save_interval_steps,
        create=True)
    return ocp.CheckpointManager(directory, options=options)


def latest_step(directory: Optional[str] = None) -> Optional[int]:
    """Latest saved step in the checkpoint dir, or None."""
    import orbax.checkpoint as ocp  # pylint: disable=import-outside-toplevel
    directory = directory or checkpoint_dir()
    if directory is None or not os.path.isdir(str(directory)):
        return None
    mgr = ocp.CheckpointManager(directory)
    return mgr.latest_step()


def restore_params(directory: str,
                   params_template: Any = None,
                   shardings: Any = None) -> Any:
    """Restore just the PARAMS from the newest training checkpoint.

    Inference-side counterpart of restore_or_init: training saves the
    full TrainState (params + Adam moments ~= 3x the weight bytes);
    servers only want weights, so only the 'params' subtree is read
    from disk (every other leaf is an orbax PLACEHOLDER, skipped
    entirely).  The restore template comes from the checkpoint's own
    metadata; `params_template` is only the no-checkpoint fallback
    return value (callers handle fresh-weight init).  The leaves come
    back as device arrays in their stored dtype: on `shardings` when
    given, else on the default device.
    """
    import jax  # pylint: disable=import-outside-toplevel
    import orbax.checkpoint as ocp  # pylint: disable=import-outside-toplevel
    step = latest_step(directory)
    if step is None:
        logger.warning(f'No checkpoint under {directory}; returning '
                       'the template unchanged.')
        return params_template
    mgr = ocp.CheckpointManager(
        directory, item_handlers=ocp.PyTreeCheckpointHandler())
    # Template comes from the CHECKPOINT's own metadata (no structure
    # assumptions about the caller's tree); every leaf outside the
    # 'params' subtree becomes PLACEHOLDER, which orbax skips entirely
    # — optimizer moments never touch disk or RAM.
    meta = mgr.item_metadata(step)

    # Every leaf is restored straight onto a device placement: each
    # leaf's ShapeDtypeStruct carries its target sharding, so orbax
    # streams every shard to its device and the full tree never
    # materializes on one chip (the whole point of tensor-sharded
    # serving).  The shardings tree is the UNBOXED param structure; the
    # checkpoint's is boxed ({'value': leaf}), but boxing preserves
    # leaf traversal order, so leaves pair up 1:1.
    if shardings is None:
        # One chip: the default device.  Left to orbax, a checkpoint
        # written from host arrays — every converted checkpoint — comes
        # back as numpy, and a server holding numpy weights uploads all
        # of them again on every jitted call (on the chip: 3.85 GB, over
        # a second, per decode tick).
        placements = itertools.repeat(
            jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    else:
        sharding_leaves = jax.tree_util.tree_leaves(
            shardings,
            is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
        # Validate counts up front: a mismatched shardings tree used to
        # exhaust the iterator mid-traversal and die with a bare
        # StopIteration from inside tree_map_with_path.
        num_params = sum(
            1 for path, _ in
            jax.tree_util.tree_flatten_with_path(meta)[0]
            if getattr(path[0], 'key', None) == 'params')
        if len(sharding_leaves) != num_params:
            raise ValueError(
                f'shardings tree has {len(sharding_leaves)} leaves but '
                f'the checkpoint\'s params subtree has {num_params} — '
                f'wrong model config for this checkpoint?')
        placements = iter(sharding_leaves)

    def _leaf(path, leaf):
        if getattr(path[0], 'key', None) != 'params':
            return ocp.PLACEHOLDER
        return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype,
                                    sharding=next(placements))

    template = jax.tree_util.tree_map_with_path(_leaf, meta)

    # PyTreeRestore only honors a target sharding via explicit
    # restore_args; build them from the template's annotations.
    def _restore_arg(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return ocp.ArrayRestoreArgs(sharding=leaf.sharding,
                                        global_shape=leaf.shape,
                                        dtype=leaf.dtype)
        return ocp.RestoreArgs()

    restore_args = jax.tree_util.tree_map(
        _restore_arg, template,
        is_leaf=lambda x: x is ocp.PLACEHOLDER or
        isinstance(x, jax.ShapeDtypeStruct))
    restored = mgr.restore(
        step, args=ocp.args.PyTreeRestore(item=template,
                                          restore_args=restore_args))
    logger.info(f'Restored params from step {step} of {directory}')
    return _strip_partition_boxes(restored['params'])


def _strip_partition_boxes(tree: Any) -> Any:
    """Collapse flax partitioning-box levels in a restored tree.

    Training saves boxed params (nn.with_logical_partitioning wraps
    each leaf in a node that serializes as {'value': leaf}); inference
    wants the plain arrays.
    """
    if isinstance(tree, dict):
        if set(tree) == {'value'}:
            return _strip_partition_boxes(tree['value'])
        return {k: _strip_partition_boxes(v) for k, v in tree.items()}
    return tree


def restore_or_init(mgr: Any, state: Any) -> tuple:
    """(state, start_step): restore latest checkpoint if one exists.

    The auto-resume convention managed jobs rely on after preemption
    recovery: relaunched tasks call this and continue from where the
    evicted run left off.
    """
    import orbax.checkpoint as ocp  # pylint: disable=import-outside-toplevel
    step = mgr.latest_step()
    if step is None:
        return state, 0
    restored = mgr.restore(step, args=ocp.args.StandardRestore(state))
    logger.info(f'Restored checkpoint at step {step}')
    return restored, step + 1


def restore_sharded(directory: str, abstract_state: Any,
                    shardings: Any) -> Tuple[Optional[Any], int]:
    """(state, start_step): restore the newest checkpoint ONTO
    `shardings` — which may live on a different (smaller or larger)
    mesh than the one that saved it.

    The elastic-recovery restore: after a partial preemption shrinks
    the gang, the surviving hosts rebuild a smaller mesh and every
    checkpoint shard streams straight to its new device placement —
    orbax reshards on read, so the full tree never materializes on one
    chip and no resharding pass runs afterwards.

    `abstract_state` is an eval_shape'd tree (models/train.py
    abstract_train_state); `shardings` is its matching tree of
    NamedShardings.  Leaves pair by traversal order (flax partitioning
    boxes preserve it — the same invariant restore_params relies on).
    Returns (None, 0) when the directory holds no checkpoint.
    """
    import jax  # pylint: disable=import-outside-toplevel
    import orbax.checkpoint as ocp  # pylint: disable=import-outside-toplevel
    step = latest_step(directory)
    if step is None:
        return None, 0
    abstract_leaves, treedef = jax.tree_util.tree_flatten(abstract_state)
    sharding_leaves = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
    if len(abstract_leaves) != len(sharding_leaves):
        raise ValueError(
            f'abstract state has {len(abstract_leaves)} leaves but the '
            f'shardings tree has {len(sharding_leaves)}')
    template = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype, sharding=s)
        for leaf, s in zip(abstract_leaves, sharding_leaves)
    ])
    mgr = ocp.CheckpointManager(directory)
    restored = mgr.restore(step, args=ocp.args.StandardRestore(template))
    logger.info(f'Sharded-restored step {step} of {directory} onto '
                f'{len(set().union(*(s.device_set for s in sharding_leaves)))}'
                f' device(s)')
    return restored, step + 1


# ------------------------------------------------------- async checkpointing


class AsyncCheckpointManager:
    """Checkpoint saves off the step critical path.

    The step loop calls :meth:`save`; the device->host snapshot happens
    on the caller thread (cheap), the durable write (orbax save — the
    bucket I/O that used to stall the step for its full duration) runs
    on a background writer thread.  Contract:

    - **Bounded in-flight saves**: at most `max_in_flight` snapshots
      are queued or being written; when the bound is hit, `save`
      blocks until a slot frees.  Blocked time is journaled on the
      start event and accumulated in
      ``skytpu_checkpoint_blocked_seconds_total`` — nonzero means the
      save interval is shorter than the write takes.
    - **Retry with backoff**: a failed write (bucket flake) retries up
      to `max_retries` times with exponential backoff; exhaustion
      journals ``status=<error>`` and training continues — a flaky
      bucket must degrade checkpoint freshness, never kill the run.
    - **Wait-on-exit**: :meth:`wait_until_finished` / :meth:`close`
      drain every queued save before returning, so an orderly exit
      (or a pre-resize finalize) never abandons an in-flight write.
    - Every save is journaled ``checkpoint_save_start/_end`` (status,
      attempts, duration_s) and timed into
      ``skytpu_checkpoint_save_seconds``; the write path is a
      ``checkpoint.save`` chaos site, so fault storms are testable.

    `async_save=False` degrades to the legacy blocking behavior (same
    journal/retry semantics on the caller thread) — the A/B the bench
    pins the <10% overhead claim against.
    """

    def __init__(self,
                 directory: Optional[str] = None,
                 *,
                 max_to_keep: int = 3,
                 save_interval_steps: int = 1,
                 max_in_flight: int = 1,
                 max_retries: int = 3,
                 retry_backoff_s: float = 0.1,
                 async_save: bool = True,
                 journal: Optional[Any] = None) -> None:
        directory = directory or checkpoint_dir()
        if directory is None:
            raise RuntimeError(
                'No checkpoint dir: set SKYTPU_CHECKPOINT_DIR or pass '
                'directory=.')
        self.directory = str(directory)
        self.save_interval_steps = max(1, int(save_interval_steps))
        self.max_in_flight = max(1, int(max_in_flight))
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff_s = retry_backoff_s
        self.async_save = async_save
        self._journal = (journal if journal is not None
                         else events_lib.training_journal())
        # Interval filtering is ours (skipping a save must also skip
        # the snapshot); the underlying manager saves unconditionally.
        self._mgr = checkpoint_manager(self.directory,
                                       max_to_keep=max_to_keep,
                                       save_interval_steps=1)
        self._slots = threading.Semaphore(self.max_in_flight)
        self._queue: 'queue.Queue[Optional[Tuple[int, Any, float]]]' = (
            queue.Queue())
        self._idle = threading.Event()
        self._idle.set()
        self._pending = 0
        self._pending_lock = threading.Lock()
        self._closed = False
        self.saves_ok = 0
        self.saves_failed = 0
        self.blocked_seconds = 0.0
        self.last_error: Optional[BaseException] = None
        self._writer: Optional[threading.Thread] = None
        if self.async_save:
            self._writer = threading.Thread(target=self._writer_loop,
                                            name='skytpu-ckpt-writer',
                                            daemon=True)
            self._writer.start()

    # ------------------------------------------------------------- public

    def save(self, step: int, state: Any) -> bool:
        """Snapshot `state` and schedule its durable write; returns
        whether a save was scheduled (False off the save interval)."""
        if self._closed:
            raise RuntimeError('AsyncCheckpointManager is closed')
        if step % self.save_interval_steps != 0:
            return False
        snapshot = self._snapshot(state)
        if not self.async_save:
            self._write(step, snapshot, blocked_s=0.0)
            return True
        t0 = time.monotonic()
        self._slots.acquire()  # bounded in-flight: block when full
        blocked_s = time.monotonic() - t0
        if blocked_s > 0.001:
            self.blocked_seconds += blocked_s
            events_lib.checkpoint_blocked_counter().inc(blocked_s)
        with self._pending_lock:
            self._pending += 1
            self._idle.clear()
        self._queue.put((step, snapshot, blocked_s))
        return True

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def restore_or_init(self, state: Any) -> tuple:
        """The resume-on-recovery convention (module-level
        restore_or_init) against this manager's directory."""
        return restore_or_init(self._mgr, state)

    def wait_until_finished(self) -> None:
        """Block until every scheduled save has reached a terminal
        status (written, or failed after retries)."""
        if self.async_save:
            self._idle.wait()
        self._mgr.wait_until_finished()

    def close(self) -> None:
        """Drain and stop the writer (wait-on-exit semantics)."""
        if self._closed:
            return
        self.wait_until_finished()
        self._closed = True
        if self._writer is not None:
            self._queue.put(None)
            self._writer.join(timeout=60)

    def __enter__(self) -> 'AsyncCheckpointManager':
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        del exc_type, exc, tb
        self.close()

    # ------------------------------------------------------------ internal

    @staticmethod
    def _snapshot(state: Any) -> Any:
        """Device->host copy on the caller thread, so the background
        write never races the step loop donating/overwriting device
        buffers."""
        import jax  # pylint: disable=import-outside-toplevel
        return jax.device_get(state)

    def _writer_loop(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            step, snapshot, blocked_s = item
            try:
                self._write(step, snapshot, blocked_s=blocked_s)
            finally:
                self._slots.release()
                with self._pending_lock:
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.set()

    def _write(self, step: int, snapshot: Any, *,
               blocked_s: float) -> None:
        import orbax.checkpoint as ocp  # pylint: disable=import-outside-toplevel
        self._journal.append('checkpoint_save_start', step=step,
                             directory=self.directory,
                             blocked_s=round(blocked_s, 6))
        t0 = time.monotonic()
        attempts = 0
        backoff = self.retry_backoff_s
        # 'interrupted' survives only when something non-retryable
        # (worker shutdown, KeyboardInterrupt) escapes the loop: the
        # finally below still closes the checkpoint_save lifecycle, so
        # an abandoned in-flight save is diagnosable from the journal.
        status = 'interrupted'
        try:
            while True:
                attempts += 1
                try:
                    # Chaos site: a raise here is a bucket-write flake;
                    # the retry loop below is the code under test.
                    chaos_injector.inject('checkpoint.save', step=step,
                                          attempt=attempts,
                                          directory=self.directory)
                    self._mgr.save(step,
                                   args=ocp.args.StandardSave(snapshot),
                                   force=True)
                    self._mgr.wait_until_finished()
                    self.saves_ok += 1
                    status = 'ok'
                    break
                except Exception as e:  # pylint: disable=broad-except
                    if attempts > self.max_retries:
                        status = type(e).__name__
                        self.last_error = e
                        self.saves_failed += 1
                        logger.warning(
                            f'checkpoint save at step {step} failed '
                            f'after {attempts} attempt(s): {e}')
                        break
                    time.sleep(backoff)
                    backoff *= 2
        finally:
            duration = time.monotonic() - t0
            events_lib.checkpoint_save_hist().observe(duration)
            self._journal.append('checkpoint_save_end', step=step,
                                 status=status, attempts=attempts,
                                 duration_s=round(duration, 6))
