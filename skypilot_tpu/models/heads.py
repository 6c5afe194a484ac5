"""Output-head helpers shared by the raw-param forward paths.

The flax Transformer handles its own unembedding in-module; the decode
(inference) and pipeline (manual PP) paths operate on the plain param
dict and share this one implementation, so tied/untied dispatch can
never drift between them.
"""
from __future__ import annotations

import jax.numpy as jnp

from skypilot_tpu.models.quantize import maybe_dequant


def unembed(x, params, cfg):
    """[b, s, d] -> logits [b, s, V], always RETURNED in f32 (CE/
    sampling numerics) with the matmul itself in f32 or the activation
    dtype per cfg.logits_in_f32 — the same contract as the flax
    Transformer's in-module unembedding; `cfg.logit_scale` multiplies
    the logits where a model states one."""
    mm_dtype = jnp.float32 if cfg.logits_in_f32 else cfg.dtype
    if cfg.tie_embeddings:
        kernel = params['embed']['embedding'].astype(mm_dtype).T  # [d, V]
    else:
        kernel = maybe_dequant(params['lm_head']['kernel'], mm_dtype)
    logits = jnp.einsum('bsd,dv->bsv', x.astype(mm_dtype),
                        kernel).astype(jnp.float32)
    if cfg.logit_scale != 1.0:
        logits = logits * cfg.logit_scale
    return logits
