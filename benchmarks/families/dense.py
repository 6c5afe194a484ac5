"""Family `dense`: the Llama-style decoder block, everything the
yardstick knows of it (see `families/__init__.py` for the entries).

RMSNorm, rotary embedding, grouped-query causal attention, SwiGLU,
untied head, as the Mistral and InternLM2 model cards describe them.
All of it is computed from a configuration's published sizes (the
`configs/<name>.json` keys), never from what an implementation happens
to touch: a kernel that reads padded pages reads more than is counted
here and scores lower.  A later PR may change the program; it may not
change this file.

**The plain reference** (`logits`) is the block in float32, in
straightforward `jax.numpy`: no kernel, no cache, no batching, and
nothing imported from the program.  Matrix products run at "highest"
precision (on a TPU a float32 product otherwise runs in bf16 passes).
One layer's weights are cast to float32 at a time, so the served bf16
weights and the reference fit one chip together.

Departure from the sources, noted once: rotary pairs are adjacent
channels (2i, 2i+1), as this repository's block rotates them, where the
Hugging Face code pairs channel i with i + d/2.  With seeded random
weights the two differ by a fixed permutation of q/k output channels
and give the same distribution of logits.

`precision='int8'` is the control of "How `correct` is decided": the
same forward with both operands of every matrix product rounded to
int8 (symmetric, one scale a row), the nearest precision below the
configurations' bfloat16.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks import cost

# HF config key -> the program's ModelConfig field.
_TO_PROGRAM = {
    'hidden_size': 'd_model', 'num_hidden_layers': 'n_layers',
    'num_attention_heads': 'n_heads', 'num_key_value_heads': 'n_kv_heads',
    'intermediate_size': 'd_ff', 'vocab_size': 'vocab_size',
    'rope_theta': 'rope_theta', 'rms_norm_eps': 'norm_eps',
    'hidden_act': 'mlp_act', 'tie_word_embeddings': 'tie_embeddings',
    'torch_dtype': 'dtype',
}


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's published keys as the program's
    `ModelConfig`, through `config_from_json_dict`."""
    from skypilot_tpu.models import configs
    d = {ours: model[theirs] for theirs, ours in _TO_PROGRAM.items()}
    d.update(param_dtype=model['torch_dtype'], max_seq_len=max_len,
             remat=False)
    derived = model['hidden_size'] // model['num_attention_heads']
    if model.get('head_dim') not in (None, derived):
        d['head_dim_override'] = model['head_dim']
    return configs.config_from_json_dict(d)


def head_dim(model: Dict[str, Any]) -> int:
    return int(model.get('head_dim') or
               model['hidden_size'] // model['num_attention_heads'])


def shapes(model: Dict[str, Any]) -> Dict[str, Any]:
    """leaf path -> (shape, fan_in or None for a norm scale): the tree
    `models/decode.py` reads with `scan_layers` (a leading layer axis);
    `tests/test_rehearsal.py` pins it against `Transformer.init`."""
    d = model['hidden_size']
    hd = head_dim(model)
    h_q = model['num_attention_heads']
    h_kv = model['num_key_value_heads']
    f = model['intermediate_size']
    v = model['vocab_size']
    n = model['num_hidden_layers']
    return {
        ('embed', 'embedding'): ((v, d), 2500),     # std 0.02
        ('final_norm', 'scale'): ((d,), None),
        ('lm_head', 'kernel'): ((d, v), d),
        ('layers', 'layer', 'attn_norm', 'scale'): ((n, d), None),
        ('layers', 'layer', 'mlp_norm', 'scale'): ((n, d), None),
        ('layers', 'layer', 'attn', 'q_proj', 'kernel'):
            ((n, d, h_q, hd), d),
        ('layers', 'layer', 'attn', 'k_proj', 'kernel'):
            ((n, d, h_kv, hd), d),
        ('layers', 'layer', 'attn', 'v_proj', 'kernel'):
            ((n, d, h_kv, hd), d),
        ('layers', 'layer', 'attn', 'o_proj', 'kernel'):
            ((n, h_q, hd, d), h_q * hd),
        ('layers', 'layer', 'mlp', 'gate_proj', 'kernel'): ((n, d, f), d),
        ('layers', 'layer', 'mlp', 'up_proj', 'kernel'): ((n, d, f), d),
        ('layers', 'layer', 'mlp', 'down_proj', 'kernel'): ((n, f, d), f),
    }


def param_counts(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the block: q/k/v/o projections, SwiGLU (gate, up,
    down), two RMSNorm scales a layer; embedding table, final norm,
    untied head."""
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


def decode_cache_bytes(model: Dict[str, Any], context: int,
                       kv_dtype: str) -> int:
    """What one decoded token at `context` has to read from the caches:
    K and V of every position over every layer, at the pool's dtype."""
    return (2 * model['num_key_value_heads'] * head_dim(model) *
            cost.DTYPE_BYTES[kv_dtype] * model['num_hidden_layers'] *
            context)


def decode_attention_flops(model: Dict[str, Any], context: int) -> int:
    """And what it computes over them: q.k and p.v, 2 multiply-adds per
    (query head, key, channel)."""
    return (4 * model['num_attention_heads'] * head_dim(model) *
            model['num_hidden_layers'] * context)


def decode_flops(model: Dict[str, Any], context: int) -> float:
    """FLOPs the model needs to produce one token whose query attends
    `context` keys: every layer matmul and the head once, plus
    attention over the context."""
    pc = param_counts(model)
    matmul = 2 * (pc['layer_matmul'] * model['num_hidden_layers'] +
                  pc['head'])
    return float(matmul + decode_attention_flops(model, context))


def prefill_flops(model: Dict[str, Any], start: int, n_new: int) -> float:
    """FLOPs to prefill positions [start, start + n_new) causally
    (cached positions [0, start) need none of their own).  No head: the
    first token's logits are a decode step's."""
    pc = param_counts(model)
    matmul = 2 * pc['layer_matmul'] * model['num_hidden_layers'] * n_new
    keys = n_new * start + n_new * (n_new + 1) // 2
    return float(matmul + decode_attention_flops(model, keys))


# ------------------------------------------------- the plain reference


def _q8(x):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _mm(x, w, precision: str):
    """x [..., k] @ w [k, n]."""
    if precision == 'int8':
        x = _q8(x)
        w = _q8(w.T).T      # one scale an output column
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) +
                             eps) * scale


def _rope(x, theta: float):
    """x [s, h, d] at positions 0..s-1; adjacent-channel pairs."""
    s, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _layer(x, lp, *, h_q: int, h_kv: int, hd: int, theta: float,
           eps: float, precision: str):
    s, d = x.shape
    w = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    h = _rms(x, w['attn_norm']['scale'], eps)
    q = _mm(h, w['attn']['q_proj']['kernel'].reshape(d, h_q * hd),
            precision).reshape(s, h_q, hd)
    k = _mm(h, w['attn']['k_proj']['kernel'].reshape(d, h_kv * hd),
            precision).reshape(s, h_kv, hd)
    v = _mm(h, w['attn']['v_proj']['kernel'].reshape(d, h_kv * hd),
            precision).reshape(s, h_kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = h_q // h_kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum('qhd,khd->hqk', q, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum('hqk,khd->qhd', probs, v,
                     precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(out.reshape(s, h_q * hd),
                w['attn']['o_proj']['kernel'].reshape(h_q * hd, d),
                precision)
    h = _rms(x, w['mlp_norm']['scale'], eps)
    gate = _mm(h, w['mlp']['gate_proj']['kernel'], precision)
    up = _mm(h, w['mlp']['up_proj']['kernel'], precision)
    return x + _mm(jax.nn.silu(gate) * up,
                   w['mlp']['down_proj']['kernel'], precision)


_layer_jit = jax.jit(_layer, static_argnames=('h_q', 'h_kv', 'hd', 'theta',
                                              'eps', 'precision'))


@jax.jit
def _take_layer(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def _head(x, first, scale, kernel, *, rows, eps, precision):
    x = jax.lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    return _mm(_rms(x, scale.astype(jnp.float32), eps),
               kernel.astype(jnp.float32), precision)


_head_jit = jax.jit(_head, static_argnames=('rows', 'eps', 'precision'))


def logits(model: Dict[str, Any], params, tokens, first: int, rows: int,
           precision: str = 'float32'):
    """Logits [rows, vocab]: row j is the model's output at position
    first + j of `tokens`, one sequence (a prompt followed by its
    served tokens).  The caller pads `tokens` to a bucket of lengths so
    that few shapes compile: the block is causal, so padding behind a
    position cannot reach it."""
    if model.get('hidden_act', 'silu') != 'silu':
        raise ValueError('the reference block is SwiGLU (silu) only')
    if model.get('tie_word_embeddings'):
        raise ValueError('the reference block has an untied head')
    if first < 0 or first + rows > len(tokens):
        raise ValueError(f'rows [{first}, {first + rows}) outside '
                         f'{len(tokens)} tokens')
    ids = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params['embed']['embedding'], ids,
                 axis=0).astype(jnp.float32)
    kw = dict(h_q=model['num_attention_heads'],
              h_kv=model['num_key_value_heads'],
              hd=head_dim(model), theta=float(model['rope_theta']),
              eps=float(model['rms_norm_eps']), precision=precision)
    stacked = params['layers']['layer']
    for i in range(model['num_hidden_layers']):
        x = _layer_jit(x, _take_layer(stacked, i), **kw)
    return _head_jit(x, first, params['final_norm']['scale'],
                     params['lm_head']['kernel'], rows=rows,
                     eps=kw['eps'], precision=precision)
