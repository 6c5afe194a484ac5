"""Per-request sampling plumbing for the batching engine.

Split out of `serve/batching_engine.py` (the facade re-exports what
callers need): submit-side validation of sampling parameters against
the engine's compiled limits, and the jitted host->device staging that
flips a slot live — token selection itself runs ON DEVICE inside the
engine tick (`models/decode.batched_sample`), so this module is the
thin, recompile-safe edge around it:

- temperature is TRACED (client floats must not trigger a compile
  storm); top_k rides a static `max_top_k` table, so requested values
  are validated here against the engine's compiled ceiling;
- a request's stop set becomes a fixed-width, -1-padded device row
  (`max_stop_ids` wide — the multi-EOS stop sets of instruct
  checkpoints);
- `admit_state` writes a whole slot admission in ONE jitted dispatch
  instead of seven eager scatters on the hot path;
- :class:`NgramDrafter` — the per-slot host-side draft proposer for
  self-speculative decoding (lives next to the sampling state it
  shares a slot with; the engine verifies its drafts on device in one
  batched tick).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Iterable, List, Optional, Tuple


class NgramDrafter:
    """Prompt-lookup / n-gram draft proposer for self-speculative
    decoding: model-free and host-side.

    The draft for the next k tokens is the continuation of the most
    recent EARLIER occurrence of the current tail n-gram in the
    request's (prompt + generated) history, longest n first (n down
    from `max_ngram`).  Repetitive text — code, templated JSON,
    retrieval quotes, degenerate greedy cycles — makes these drafts
    mostly right, collapsing ITL by the acceptance length; random text
    makes them mostly wrong, which costs nothing beyond the
    already-batched verify tick.  Misses pad with the request's last
    token: pads must be VALID vocab ids because the verify forward
    embeds them before rejecting them.
    """

    def __init__(self, prompt_ids: Iterable[int], *,
                 max_ngram: int = 3) -> None:
        self.history: List[int] = [int(t) for t in prompt_ids]
        self.max_ngram = int(max_ngram)

    def observe(self, tokens: Iterable[int]) -> None:
        """Record tokens the engine actually emitted for this slot."""
        self.history.extend(int(t) for t in tokens)

    def propose(self, k: int) -> List[int]:
        """k draft tokens continuing the current history."""
        hist = self.history
        out: List[int] = []
        for n in range(min(self.max_ngram, len(hist) - 1), 0, -1):
            tail = hist[-n:]
            for i in range(len(hist) - n - 1, -1, -1):
                if hist[i:i + n] == tail:
                    out = hist[i + n:i + n + k]
                    break
            if out:
                break
        pad = hist[-1] if hist else 0
        out = out[:k]
        out.extend([pad] * (k - len(out)))
        return out


def validate_sampling(sampling: Optional[Any], *, max_top_k: int
                      ) -> Tuple[float, int, int]:
    """-> (temperature, top_k, seed), raising ValueError on parameters
    the engine's compiled graphs cannot honor."""
    temperature, top_k, seed = 0.0, 0, 0
    if sampling is not None:
        temperature = float(sampling.temperature)
        top_k = int(sampling.top_k)
        seed = int(getattr(sampling, 'seed', 0))
    if top_k > max_top_k:
        raise ValueError(
            f'top_k {top_k} > engine max_top_k {max_top_k}')
    return temperature, top_k, seed


def validate_stop_ids(stop_ids: Iterable[int],
                      max_stop_ids: int) -> None:
    n = len(tuple(stop_ids))
    if n > max_stop_ids:
        raise ValueError(
            f'{n} stop ids > engine max_stop_ids {max_stop_ids}')


class SlotSampler:
    """Jitted per-slot sampling/admission helpers bound to one engine
    configuration (max_top_k shapes the on-device top-k table;
    max_stop_ids the stop rows)."""

    def __init__(self, max_top_k: int, max_stop_ids: int) -> None:
        import jax

        from skypilot_tpu.models import decode

        self.max_top_k = int(max_top_k)
        self.max_stop_ids = int(max_stop_ids)
        self._jax = jax
        # One dispatch per admission for the whole per-slot state write
        # (NOT donated: the previous tick's token buffer may still be
        # pending its one-tick-behind host read).
        self._admit_state = jax.jit(decode.admit_slot_state)
        self._sample_one = jax.jit(
            functools.partial(decode.batched_sample,
                              max_top_k=self.max_top_k))

    def key(self, seed: int):
        return self._jax.random.PRNGKey(seed)

    def sample_one(self, logits, key, temperature: float,
                   top_k: int) -> int:
        """Select one token from single-row logits with the same math
        a tick uses (MoE first-token-from-prefill path)."""
        import jax.numpy as jnp
        return int(self._sample_one(
            logits, key[None],
            jnp.asarray([temperature], jnp.float32),
            jnp.asarray([top_k], jnp.int32))[0])

    def stop_row(self, stop_ids: Iterable[int]):
        row = [-1] * self.max_stop_ids
        for i, sid in enumerate(sorted(stop_ids)):
            row[i] = sid
        return row

    def admit(self, state: Dict[str, Any], slot_id: int, token: int,
              remaining: int, stop_ids: Iterable[int], key,
              temperature: float, top_k: int) -> Dict[str, Any]:
        """Flip a slot live in the device state (one jitted dispatch)."""
        import jax.numpy as jnp
        return self._admit_state(
            state, slot_id, token, remaining,
            jnp.asarray(self.stop_row(stop_ids), jnp.int32), key,
            temperature, top_k)
