"""Family `ouro`: a looped stack of sandwich-normed dense layers with
an exit gate, everything the yardstick knows of it (see
`families/__init__.py` for the entries).

The model, as `Ouro-2.6B`'s config.json states its sizes
(`total_ut_steps` T, `early_exit_threshold`) and as its published
`modeling_ouro.py` and paper ("Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741) give the block; what config.json
does not itself state is listed under the configuration file's
`assumed`.  For a sequence `x_0 = Emb[tokens]`, L layers, and
`RMS(z; g) = z / sqrt(mean(z^2) + eps) * g`:

    h = x_0
    for t in 0 .. T-1:                   the same L layers' weights in every pass
      for l in 0 .. L-1:
        a = RMS(h; g1_l)
        q, k, v = a Wq_l, a Wk_l, a Wv_l            no bias, no q/k norm
        q, k rotated (theta, all channels, adjacent pairs)
        o = softmax(q k^T * hd^-0.5, causal) v      keys of THIS pass
        h = h + RMS(o Wo_l; g2_l)                   sandwich: the output is normed
        m = RMS(h; g3_l)
        h = h + RMS((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)
      h = RMS(h; g_final)                H_t; it is ALSO the input of pass t+1
      lam_t = sigmoid(H_t w_gate + b_gate)
    p_t = lam_t * prod_{j<t} (1 - lam_j)  for t < T-1;  p_{T-1} = prod_{j<T-1} (1 - lam_j)
    e = the first t with sum_{j<=t} p_j >= early_exit_threshold, else T-1
    logits = H_e W_head                  per position; untied head

Every pass runs for every position whatever `e` is: the threshold
chooses which pass's hidden state the head reads.  A served system
keeps a position's keys and values once a (pass, layer): T x L cache
layers.

Counted from the configuration's published sizes, never from what an
implementation happens to touch.  A later PR may change the program;
it may not change this file.

**The plain reference** (`logits`) is the equations above in float32
`jax.numpy` at "highest" matmul precision: no kernel, no cache, no
batching, nothing imported from the program.  One layer's weights are
cast to float32 at a time.  `precision='int8'` is the control: the
same forward with both operands of every matrix product (projections,
FFN, gate, head) rounded to int8, one scale a row, the nearest
precision below the configuration's bfloat16.

**How far a bfloat16 forward lies from this reference, and why the
cell's `logit_gap_max` is wide.**  With seeded weights the looped stack
amplifies a rounding: each of the 384 sub-layer applications adds a
unit-sized, freshly normed output to a stream that the pass's end norms
back to unit size, so a pass forgets most of its input's size and keeps
its direction, errors included, and the next pass starts from them.
Measured at hidden 256, 48 layers x 4 passes, on the CPU (PERF.md, PR
35): the program computing in float32 on the same bfloat16 weights
reads 0.0000 from this file's logits, so the distance is rounding and
not another function; the program in bfloat16 lies 0.43 from them in
the root mean square (logits of standard deviation 1.0), the int8
control 0.98, which is as far as unrelated logits lie.  So on the chip
four served tokens in ten are not this reference's best, the widest gap
of a run reads 0.8 to 1.9 for the program and 3.2 to 4.8 for the
control, and the limit lies between: it tells bfloat16 from int8 and a
served token from an altered one, and it cannot tell a small error of
the program from rounding.  What pins the mathematics is tier-1's
comparison in float32 on the CPU twin (`tests/unit/test_looped.py`,
2e-5 on logits of the same size), where a dropped norm scale or a pass
on another pass's keys reads 1e-2 or more.  A trained checkpoint, whose
passes refine a state and do not scramble it, would read closer; its
files are not in the repository.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks import cost

_HIGHEST = jax.lax.Precision.HIGHEST


def _check(model: Dict[str, Any]) -> None:
    """What the equations above assume of the configuration."""
    want = {'hidden_act': 'silu', 'tie_word_embeddings': False,
            'rope_scaling': None, 'use_sliding_window': False}
    bad = {k: model.get(k) for k, v in want.items() if model.get(k) != v}
    if bad:
        raise ValueError(f'family ouro is written for {want}; the '
                         f'configuration has {bad}')
    if set(model['layer_types'][:model['num_hidden_layers']]) != {
            'full_attention'}:
        raise ValueError('family ouro has full attention in every layer')
    if passes(model) < 2:
        raise ValueError('family ouro is a looped stack: '
                         'total_ut_steps >= 2')


def passes(model: Dict[str, Any]) -> int:
    """Times the stack runs a token."""
    return int(model['total_ut_steps'])


def cache_layers(model: Dict[str, Any]) -> int:
    """Layers of keys and values a position keeps: one a (pass, layer)."""
    return passes(model) * model['num_hidden_layers']


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's published keys as the program's
    `ModelConfig`."""
    from skypilot_tpu.models import configs
    _check(model)
    d = dict(
        vocab_size=model['vocab_size'], d_model=model['hidden_size'],
        n_layers=model['num_hidden_layers'],
        n_heads=model['num_attention_heads'],
        n_kv_heads=model['num_key_value_heads'],
        d_ff=model['intermediate_size'],
        rope_theta=float(model['rope_theta']),
        norm_eps=float(model['rms_norm_eps']),
        mlp_act=model['hidden_act'], tie_embeddings=False,
        loop_passes=passes(model),
        exit_threshold=float(model['early_exit_threshold']),
        post_norms=True,
        dtype=model['torch_dtype'], param_dtype=model['torch_dtype'],
        max_seq_len=max_len, remat=False)
    if model['head_dim'] != d['d_model'] // d['n_heads']:
        d['head_dim_override'] = model['head_dim']
    return configs.config_from_json_dict(d)


def shapes(model: Dict[str, Any]) -> Dict[str, Any]:
    """leaf path -> (shape, fan_in or None for a norm scale): the tree
    `models/decode.py` reads with `scan_layers` (a leading layer axis).
    Four norm scales a layer; the gate's kernel and bias (its bias is
    seeded too, standard normal, so that a dropped bias shows)."""
    d = model['hidden_size']
    hd = model['head_dim']
    h_q = model['num_attention_heads']
    h_kv = model['num_key_value_heads']
    f = model['intermediate_size']
    v = model['vocab_size']
    n = model['num_hidden_layers']
    layer = ('layers', 'layer')
    out = {
        ('embed', 'embedding'): ((v, d), 2500),     # std 0.02
        ('final_norm', 'scale'): ((d,), None),
        ('exit_gate', 'kernel'): ((d, 1), d),
        ('exit_gate', 'bias'): ((1,), 1),
        ('lm_head', 'kernel'): ((d, v), d),
        layer + ('attn', 'q_proj', 'kernel'): ((n, d, h_q, hd), d),
        layer + ('attn', 'k_proj', 'kernel'): ((n, d, h_kv, hd), d),
        layer + ('attn', 'v_proj', 'kernel'): ((n, d, h_kv, hd), d),
        layer + ('attn', 'o_proj', 'kernel'): ((n, h_q, hd, d), h_q * hd),
        layer + ('mlp', 'gate_proj', 'kernel'): ((n, d, f), d),
        layer + ('mlp', 'up_proj', 'kernel'): ((n, d, f), d),
        layer + ('mlp', 'down_proj', 'kernel'): ((n, f, d), f),
    }
    for name in ('attn_norm', 'attn_post_norm', 'mlp_norm',
                 'mlp_post_norm'):
        out[layer + (name, 'scale')] = ((n, d), None)
    return out


def param_counts(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameters: q/k/v/o projections, SwiGLU (gate, up, down), four
    RMSNorm scales a layer; embedding, final norm, the exit gate
    (kernel and bias), untied head.  Each counted once, however often
    it is run."""
    d = model['hidden_size']
    hd = model['head_dim']
    h_q = model['num_attention_heads']
    h_kv = model['num_key_value_heads']
    v = model['vocab_size']
    attn = d * hd * (h_q + 2 * h_kv) + h_q * hd * d
    mlp = 3 * d * model['intermediate_size']
    layer = attn + mlp + 4 * d
    total = (layer * model['num_hidden_layers'] + v * d + d + (d + 1) +
             d * v)
    return {'layer': layer, 'layer_matmul': attn + mlp, 'embedding': v * d,
            'gate': d + 1, 'head': d * v, 'total': total}


def loop_stack_bytes(model: Dict[str, Any]) -> int:
    """Bytes of the layers' matrices at the served dtype: what one pass
    over the stack has to read."""
    return (param_counts(model)['layer_matmul'] *
            model['num_hidden_layers'] *
            cost.DTYPE_BYTES[model['torch_dtype']])


def decode_cache_bytes(model: Dict[str, Any], context: int,
                       kv_dtype: str) -> int:
    """What one decoded token at `context` has to read from the caches:
    K and V of every position over every cache layer (each pass
    attends its own), at the pool's dtype."""
    return (2 * model['num_key_value_heads'] * model['head_dim'] *
            cost.DTYPE_BYTES[kv_dtype] * cache_layers(model) * context)


def decode_attention_flops(model: Dict[str, Any], context: int) -> int:
    """And what it computes over them: q.k and p.v, 2 multiply-adds per
    (query head, key, channel), in every pass."""
    return (4 * model['num_attention_heads'] * model['head_dim'] *
            cache_layers(model) * context)


def decode_flops(model: Dict[str, Any], context: int) -> float:
    """FLOPs the model needs to produce one token whose query attends
    `context` keys: every layer matmul and the gate once a pass, the
    head once, attention over the context in every pass."""
    pc = param_counts(model)
    matmul = 2 * (passes(model) * (pc['layer_matmul'] *
                                   model['num_hidden_layers'] +
                                   model['hidden_size']) + pc['head'])
    return float(matmul + decode_attention_flops(model, context))


def prefill_flops(model: Dict[str, Any], start: int, n_new: int) -> float:
    """FLOPs to prefill positions [start, start + n_new) causally
    (cached positions [0, start) need none of their own): every pass
    runs for every position.  No gate, no head: the first token's
    logits are a decode step's."""
    pc = param_counts(model)
    matmul = (2 * passes(model) * pc['layer_matmul'] *
              model['num_hidden_layers'] * n_new)
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
    return jnp.matmul(x, w, precision=_HIGHEST)


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
    a = _rms(x, w['attn_norm']['scale'], eps)
    q = _mm(a, w['attn']['q_proj']['kernel'].reshape(d, h_q * hd),
            precision).reshape(s, h_q, hd)
    k = _mm(a, w['attn']['k_proj']['kernel'].reshape(d, h_kv * hd),
            precision).reshape(s, h_kv, hd)
    v = _mm(a, w['attn']['v_proj']['kernel'].reshape(d, h_kv * hd),
            precision).reshape(s, h_kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = h_q // h_kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum('qhd,khd->hqk', q, k,
                        precision=_HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(scores, axis=-1), v,
                     precision=_HIGHEST)
    x = x + _rms(_mm(out.reshape(s, h_q * hd),
                     w['attn']['o_proj']['kernel'].reshape(h_q * hd, d),
                     precision), w['attn_post_norm']['scale'], eps)
    m = _rms(x, w['mlp_norm']['scale'], eps)
    gate = _mm(m, w['mlp']['gate_proj']['kernel'], precision)
    up = _mm(m, w['mlp']['up_proj']['kernel'], precision)
    return x + _rms(_mm(jax.nn.silu(gate) * up,
                        w['mlp']['down_proj']['kernel'], precision),
                    w['mlp_post_norm']['scale'], eps)


_layer_jit = jax.jit(_layer, static_argnames=('h_q', 'h_kv', 'hd', 'theta',
                                              'eps', 'precision'))


@jax.jit
def _take_layer(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def _end_pass(x, first, scale, *, rows, eps):
    """The final norm over every position (the next pass's input) and
    its `rows` rows from `first` (what the gate and the head read)."""
    x = _rms(x, scale.astype(jnp.float32), eps)
    return x, jax.lax.dynamic_slice_in_dim(x, first, rows, axis=0)


_end_pass_jit = jax.jit(_end_pass, static_argnames=('rows', 'eps'))


def _exit_and_head(hs, gate, head, *, threshold, precision):
    """hs [T, rows, d]: every pass's normed output.  -> (logits of the
    selected pass [rows, vocab], e [rows], p [T, rows])."""
    last = hs.shape[0] - 1
    w = gate['kernel'].astype(jnp.float32)
    b = gate['bias'].astype(jnp.float32)
    lam = [jax.nn.sigmoid(_mm(hs[t], w, precision)[:, 0] + b[0])
           for t in range(last)]
    stay = jnp.ones_like(lam[0])
    total = jnp.zeros_like(stay)
    e = jnp.full(stay.shape, last, jnp.int32)
    found = jnp.zeros(stay.shape, bool)
    p = []
    for t in range(last):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
        total = total + p[-1]
        here = (total >= threshold) & ~found
        e = jnp.where(here, t, e)
        found = found | here
    p.append(stay)
    h = jnp.take_along_axis(hs, e[None, :, None], axis=0)[0]
    return (_mm(h, head.astype(jnp.float32), precision), e,
            jnp.stack(p))


_exit_and_head_jit = jax.jit(_exit_and_head,
                             static_argnames=('threshold', 'precision'))


def forward(model: Dict[str, Any], params, tokens, first: int, rows: int,
            precision: str = 'float32'):
    """(logits [rows, vocab], e [rows], p [T, rows]): the logits of
    the pass each position's head reads, which pass that is, and the
    exit mass of every pass, for positions first .. first + rows - 1."""
    _check(model)
    if first < 0 or first + rows > len(tokens):
        raise ValueError(f'rows [{first}, {first + rows}) outside '
                         f'{len(tokens)} tokens')
    ids = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params['embed']['embedding'], ids,
                 axis=0).astype(jnp.float32)
    eps = float(model['rms_norm_eps'])
    kw = dict(h_q=model['num_attention_heads'],
              h_kv=model['num_key_value_heads'], hd=model['head_dim'],
              theta=float(model['rope_theta']), eps=eps,
              precision=precision)
    stacked = params['layers']['layer']
    hs = []
    for _ in range(passes(model)):
        for i in range(model['num_hidden_layers']):
            x = _layer_jit(x, _take_layer(stacked, i), **kw)
        x, picked = _end_pass_jit(x, first, params['final_norm']['scale'],
                                  rows=rows, eps=eps)
        hs.append(picked)
    return _exit_and_head_jit(
        jnp.stack(hs), params['exit_gate'], params['lm_head']['kernel'],
        threshold=float(model['early_exit_threshold']),
        precision=precision)


def logits(model: Dict[str, Any], params, tokens, first: int, rows: int,
           precision: str = 'float32'):
    """Logits [rows, vocab]: row j is the model's output at position
    first + j of `tokens`, one sequence (a prompt followed by its
    served tokens).  The caller pads `tokens` to a bucket of lengths so
    that few shapes compile: the block is causal, so padding behind a
    position cannot reach it."""
    return forward(model, params, tokens, first, rows, precision)[0]
