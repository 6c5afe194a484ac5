"""Operations, bytes and peaks: the arithmetic the yardstick rests on.

Everything here is computed from a configuration's published sizes (the
`configs/<name>.json` keys), never from what an implementation happens
to touch: a kernel that reads padded pages reads more than is counted
here and scores lower.  A later PR may change the program; it may not
change this file.
"""
from __future__ import annotations

from typing import Any, Dict

# Published per-chip peaks, keyed by a substring of `device_kind`, most
# specific first.  Source: Google Cloud TPU documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s), "TPU v5p" (459 TFLOP/s,
# 2765 GB/s), "TPU v6e" (918 TFLOP/s, 1640 GB/s), "TPU v4" (275 TFLOP/s,
# 1200 GB/s).  The FLOP/s column is copied from bench.py `_peak_flops`.
# A device that is not here is an error, never a default.
_PEAKS = (
    ('v6 lite', {'flops_bf16': 918e12, 'hbm_bytes_per_s': 1640e9}),
    ('v6e', {'flops_bf16': 918e12, 'hbm_bytes_per_s': 1640e9}),
    ('v5 lite', {'flops_bf16': 197e12, 'hbm_bytes_per_s': 819e9}),
    ('v5litepod', {'flops_bf16': 197e12, 'hbm_bytes_per_s': 819e9}),
    ('v5e', {'flops_bf16': 197e12, 'hbm_bytes_per_s': 819e9}),
    ('v5p', {'flops_bf16': 459e12, 'hbm_bytes_per_s': 2765e9}),
    ('v4', {'flops_bf16': 275e12, 'hbm_bytes_per_s': 1200e9}),
)

_DTYPE_BYTES = {'bfloat16': 2, 'float16': 2, 'float32': 4, 'int8': 1}


def peaks(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    for key, row in _PEAKS:
        if key in kind:
            return dict(row)
    raise ValueError(
        f'no published peaks on record for device_kind {device_kind!r}; '
        f'add a row with its source to benchmarks/cost.py')


def head_dim(model: Dict[str, Any]) -> int:
    return int(model.get('head_dim') or
               model['hidden_size'] // model['num_attention_heads'])


def param_counts(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the dense Llama-style block both configurations
    use: q/k/v/o projections, SwiGLU (gate, up, down), two RMSNorm
    scales a layer; embedding table, final norm, untied head."""
    d = model['hidden_size']
    hd = head_dim(model)
    h_q = model['num_attention_heads']
    h_kv = model['num_key_value_heads']
    f = model['intermediate_size']
    v = model['vocab_size']
    attn = d * hd * (h_q + 2 * h_kv) + h_q * hd * d
    mlp = 3 * d * f
    layer = attn + mlp + 2 * d
    head = 0 if model.get('tie_word_embeddings') else d * v
    total = layer * model['num_hidden_layers'] + v * d + d + head
    return {'layer': layer, 'layer_matmul': attn + mlp, 'embedding': v * d,
            'head': d * v, 'total': total}


def weight_bytes(model: Dict[str, Any]) -> int:
    return param_counts(model)['total'] * _DTYPE_BYTES[model['torch_dtype']]


def kv_bytes_per_token(model: Dict[str, Any], kv_dtype: str) -> int:
    """K and V of one position over every layer, at the pool's dtype."""
    return (2 * model['num_key_value_heads'] * head_dim(model) *
            _DTYPE_BYTES[kv_dtype] * model['num_hidden_layers'])


def _attn_flops_per_key(model: Dict[str, Any]) -> int:
    # q.k and p.v: 2 multiply-adds per (query head, key, channel).
    return (4 * model['num_attention_heads'] * head_dim(model) *
            model['num_hidden_layers'])


def decode_flops(model: Dict[str, Any], context: int) -> float:
    """FLOPs the model needs to produce one token whose query attends
    `context` keys: every layer matmul and the head once, plus
    attention over the context."""
    pc = param_counts(model)
    matmul = 2 * (pc['layer_matmul'] * model['num_hidden_layers'] +
                  pc['head'])
    return float(matmul + _attn_flops_per_key(model) * context)


def prefill_flops(model: Dict[str, Any], start: int, n_new: int) -> float:
    """FLOPs to prefill positions [start, start + n_new) causally
    (cached positions [0, start) need none of their own).  No head: the
    first token's logits are a decode step's."""
    pc = param_counts(model)
    matmul = 2 * pc['layer_matmul'] * model['num_hidden_layers'] * n_new
    keys = n_new * start + n_new * (n_new + 1) // 2
    return float(matmul + _attn_flops_per_key(model) * keys)


def paged_attention_floor_s(model: Dict[str, Any], contexts_sum: int,
                            kv_dtype: str, peak: Dict[str, float]
                            ) -> Dict[str, Any]:
    """Least time for decode attention over contexts summing to
    `contexts_sum` keys: K and V read once from HBM, or the FLOPs at the
    compute peak, whichever is longer; says which bound it was."""
    by_bytes = (kv_bytes_per_token(model, kv_dtype) * contexts_sum /
                peak['hbm_bytes_per_s'])
    by_flops = (_attn_flops_per_key(model) * contexts_sum /
                peak['flops_bf16'])
    return {'seconds': max(by_bytes, by_flops),
            'bound': 'hbm' if by_bytes >= by_flops else 'flops'}
