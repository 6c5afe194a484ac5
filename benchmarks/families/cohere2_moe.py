"""Family `cohere2_moe`: a parallel block of window and position-free
full attention beside sigmoid-routed experts and averaged shared
experts, everything the yardstick knows of it (see
`families/__init__.py` for the entries).

The layer, as `command-a-plus-05-2026`'s config.json and the catalog's
`described_as` give it.  For hidden `x` at position `p` of layer `i`
(0-based; `layer_types[i]` says `sliding_attention` or
`full_attention`):

    h    = (x - mean(x)) / sqrt(var(x) + eps) * w        one LayerNorm a
                                                         layer, no bias
    q, k, v = h W_q, h W_k, h W_v                        no bias, no q/k
                                                         norm; scale hd^-0.5
    window layer: q, k rotated (theta, all channels, adjacent pairs:
                  `rope_gptj`); a query at p sees keys p - window + 1 .. p
    full layer:   no rotation at all; keys 0 .. p
    attn = softmax(q k^T * scale) v W_o
    s    = sigmoid(h W_r)        float32, over the PUBLISHED expert count
    T    = the num_experts_per_tok largest;  g_e = s_e / sum_{j in T} s_j
    E(h) = (silu(h G) * (h U)) D      a routed expert E_e and a shared
                                      expert S_k alike, width intermediate_size
    y    = x + attn + sum_{e in T, e held} g_e E_e(h)
             + (1 / n_shared) sum_k S_k(h)
    logits = LN(y_last) Emb^T * logit_scale              tied head

**The chip's share.**  The router keeps its published width
(`published.num_experts`) and its experts per token; the routed sum
runs over the experts of T that are held here (`experts_held` = [lo,
hi), `num_experts` of them), with `g` still normalised over all of T.
What the absent experts would add is left out, and that partial `y`
goes on to the next layer: in the program and here alike.  The
vocabulary is the `vocab_size` rows held.

Counted from the configuration's published sizes, never from what an
implementation happens to touch.  A later PR may change the program;
it may not change this file.

**The plain reference** (`logits`) is the equations above in float32
`jax.numpy` at "highest" matmul precision: no kernel, no cache, no
batching, nothing imported from the program.  It has to fit one chip
beside the served bf16 weights at 8,704 positions, so one expert is
cast to float32 at a time and attention runs in blocks of query rows.
`precision='int8'` is the control: the same forward with both operands
of every matrix product (projections, router, experts, head) rounded to
int8, one scale a row, the nearest precision below the configuration's
bfloat16.

**Where the reference abstains.**  Which experts a token takes is a
step in the router's scores: where a held expert's score lies within a
few roundings of the configuration's stated dtype of the edge of the
top-k, no forward in that dtype can tell on which side it falls, and
the two sides differ by that expert's whole output (here, with 7 of a
token's 8 experts on other chips, a quarter of the layer's).  Both are
the model's output at that precision.  So the float32 `logits` gives
such a row no preference (all zeros): the yardstick's gap reads 0 there
for any token, and the row is not judged.  `undecided_margin(model)` is
the width, in router logits; `forward` returns the plain logits and each
row's margin for whoever wants both.  The control is not spared by it:
it misplaces experts at margins ten times wider.  A float32
configuration (the CPU tests' twin) has a width of 3.6e-7 and abstains
nowhere.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks import cost

_HIGHEST = jax.lax.Precision.HIGHEST
# Query rows one attention block holds: at 128 heads and 8,704 keys a
# block's scores are 0.57 GB in float32.
_ATTN_ROWS = 128
_KINDS = {'sliding_attention': 'window', 'full_attention': 'full_nope'}
# A routing decision is undecided where a held expert's router logit is
# within this many machine epsilons of the configuration's dtype of the
# top-k's edge (the normed input has unit variance and a router column
# unit norm, so a logit is of order one).  On the chip every gap of
# the bfloat16 program over 0.035 sat on a margin under 1.2 epsilons
# (9,165 rows; PERF.md, finding 25); the int8 control misplaces experts
# at margins ten times that.
_UNDECIDED_EPS = 3.0


def _check(model: Dict[str, Any]) -> None:
    """What the equations above assume of the configuration."""
    want = {'hidden_act': 'silu', 'expert_selection_fn': 'sigmoid',
            'norm_topk_prob': True, 'use_parallel_block': True,
            'use_qk_norm': False, 'attention_bias': False,
            'tie_word_embeddings': True, 'rotary_pct': 1,
            'position_embedding_type': 'rope_gptj',
            'shared_expert_combination_strategy': 'average',
            'first_k_dense_replace': 0}
    bad = {k: model.get(k) for k, v in want.items() if model.get(k) != v}
    if bad:
        raise ValueError(f'family cohere2_moe is written for {want}; '
                         f'the configuration has {bad}')
    if set(layer_types(model)) - set(_KINDS):
        raise ValueError(f'layer_types outside {sorted(_KINDS)}')


def layer_types(model: Dict[str, Any]):
    """The kinds of the layers that are run: the published list's
    first `num_hidden_layers` (depth is cut by whole periods)."""
    return list(model['layer_types'][:model['num_hidden_layers']])


def router_width(model: Dict[str, Any]) -> int:
    """Experts the router scores: the published count, whatever is
    held here."""
    return int(model.get('published', {}).get('num_experts',
                                              model['num_experts']))


def experts_held(model: Dict[str, Any]) -> Tuple[int, int]:
    """(lo, n): the routed experts this chip holds."""
    lo, hi = model.get('experts_held', (0, model['num_experts']))
    if hi - lo != model['num_experts']:
        raise ValueError('experts_held does not span num_experts')
    return int(lo), int(hi - lo)


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's published keys as the program's
    `ModelConfig`."""
    from skypilot_tpu.models import configs
    _check(model)
    period = int(model['layer_switch'])
    return configs.config_from_json_dict(dict(
        vocab_size=model['vocab_size'], d_model=model['hidden_size'],
        n_layers=model['num_hidden_layers'],
        n_heads=model['num_attention_heads'],
        n_kv_heads=model['num_key_value_heads'],
        head_dim_override=model['head_dim'],
        d_ff=model['intermediate_size'],
        rope_theta=float(model['rope_theta']),
        norm_eps=float(model['layer_norm_eps']), norm_type='layernorm',
        mlp_act=model['hidden_act'], tie_embeddings=True,
        logit_scale=float(model['logit_scale']),
        parallel_block=True,
        layer_pattern=[_KINDS[t] for t in layer_types(model)[:period]],
        sliding_window=model['sliding_window'],
        n_experts=router_width(model),
        expert_top_k=model['num_experts_per_tok'],
        experts_held=list(experts_held(model)),
        expert_score_fn=model['expert_selection_fn'],
        n_shared_experts=model['num_shared_experts'],
        shared_expert_combine=model['shared_expert_combination_strategy'],
        dtype=model['torch_dtype'], param_dtype=model['torch_dtype'],
        max_seq_len=max_len, remat=False))


def shapes(model: Dict[str, Any]) -> Dict[str, Any]:
    """leaf path -> (shape, fan_in or None for a norm scale): the tree
    `models/decode.py` reads with `scan_layers` (a leading layer axis).
    One norm a layer; the router at its published width; the held
    routed experts and the shared experts as stacks."""
    d = model['hidden_size']
    hd = model['head_dim']
    h_q = model['num_attention_heads']
    h_kv = model['num_key_value_heads']
    f = model['intermediate_size']
    n = model['num_hidden_layers']
    held = model['num_experts']
    shared = model['num_shared_experts']
    layer = ('layers', 'layer')
    moe = layer + ('moe_mlp',)
    out = {
        ('embed', 'embedding'): ((model['vocab_size'], d), 2500),
        ('final_norm', 'scale'): ((d,), None),
        layer + ('attn_norm', 'scale'): ((n, d), None),
        layer + ('attn', 'q_proj', 'kernel'): ((n, d, h_q, hd), d),
        layer + ('attn', 'k_proj', 'kernel'): ((n, d, h_kv, hd), d),
        layer + ('attn', 'v_proj', 'kernel'): ((n, d, h_kv, hd), d),
        layer + ('attn', 'o_proj', 'kernel'): ((n, h_q, hd, d), h_q * hd),
        moe + ('router', 'kernel'): ((n, d, router_width(model)), d),
    }
    for prefix, e in (('', held), ('shared_', shared)):
        out[moe + (f'{prefix}gate_proj',)] = ((n, e, d, f), d)
        out[moe + (f'{prefix}up_proj',)] = ((n, e, d, f), d)
        out[moe + (f'{prefix}down_proj',)] = ((n, e, f, d), f)
    return out


def param_counts(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameters held here: q/k/v/o projections, the router, the held
    routed experts and the shared experts (three matrices each), one
    norm scale a layer; the tied embedding, the final norm.
    `layer_active` is what one token's matmuls touch in expectation:
    attention, router, shared experts and `num_experts_per_tok` x held
    / published routed experts."""
    d = model['hidden_size']
    hd = model['head_dim']
    h_q = model['num_attention_heads']
    h_kv = model['num_key_value_heads']
    expert = 3 * d * model['intermediate_size']
    attn = d * hd * (h_q + 2 * h_kv) + h_q * hd * d
    router = d * router_width(model)
    shared = model['num_shared_experts'] * expert
    routed = model['num_experts'] * expert
    routed_a_token = (model['num_experts_per_tok'] * model['num_experts'] /
                      router_width(model))
    layer = attn + router + shared + routed + d
    v = model['vocab_size']
    return {'layer': layer, 'layer_matmul': attn + router + shared + routed,
            'layer_active': attn + router + shared + routed_a_token * expert,
            'embedding': v * d, 'head': d * v,
            'total': layer * model['num_hidden_layers'] + v * d + d}


def _keys_seen(model: Dict[str, Any], context: int) -> int:
    """Keys a query with `context` keys before and at it attends,
    summed over the layers: a window layer's last `sliding_window`."""
    w = model['sliding_window']
    return sum(min(context, w) if t == 'sliding_attention' else context
               for t in layer_types(model))


def decode_cache_bytes(model: Dict[str, Any], context: int,
                       kv_dtype: str) -> int:
    """What one decoded token at `context` has to read from the caches:
    K and V of the positions each layer's query sees, at the pool's
    dtype."""
    return (2 * model['num_key_value_heads'] * model['head_dim'] *
            cost.DTYPE_BYTES[kv_dtype] * _keys_seen(model, context))


def decode_attention_flops(model: Dict[str, Any], context: int) -> int:
    """And what it computes over them: q.k and p.v, 2 multiply-adds per
    (query head, key, channel)."""
    return (4 * model['num_attention_heads'] * model['head_dim'] *
            _keys_seen(model, context))


def decode_flops(model: Dict[str, Any], context: int) -> float:
    """FLOPs the model needs to produce one token whose query attends
    `context` keys: attention projections, router, shared experts and
    the expected share of a token's routed experts that is held here
    (`layer_active`), the head over the rows held, attention over what
    each layer sees."""
    pc = param_counts(model)
    matmul = 2 * (pc['layer_active'] * model['num_hidden_layers'] +
                  pc['head'])
    return float(matmul + decode_attention_flops(model, context))


def prefill_flops(model: Dict[str, Any], start: int, n_new: int) -> float:
    """FLOPs to prefill positions [start, start + n_new) causally.  No
    head: the first token's logits are a decode step's."""
    pc = param_counts(model)
    matmul = 2 * pc['layer_active'] * model['num_hidden_layers'] * n_new
    attention = sum(decode_attention_flops(model, start + j + 1)
                    for j in range(n_new))
    return float(matmul + attention)


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


def _layer_norm(x, scale, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
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


def _attention(q, k, v, window: int):
    """q [s, h_q, d], k/v [s, h_kv, d] -> [s, h_q, d]; a query at p
    sees keys max(0, p - window + 1) .. p (`window` 0: all of 0 .. p).
    In blocks of `_ATTN_ROWS` query rows."""
    s, h_q, d = q.shape
    h_kv = k.shape[1]
    blk = min(_ATTN_ROWS, s)
    n_blk = -(-s // blk)
    qg = jnp.pad(q, ((0, n_blk * blk - s), (0, 0), (0, 0))).reshape(
        n_blk, blk, h_kv, h_q // h_kv, d)
    kpos = jnp.arange(s)

    def block(args):
        qb, start = args
        qpos = start + jnp.arange(blk)
        scores = jnp.einsum('qgrd,kgd->grqk', qb, k,
                            precision=_HIGHEST) * d ** -0.5
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen = seen & (kpos[None, :] > qpos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum('grqk,kgd->qgrd', probs, v, precision=_HIGHEST)

    out = jax.lax.map(block, (qg, jnp.arange(n_blk) * blk))
    return out.reshape(n_blk * blk, h_q, d)[:s]


def _experts(h, mp, prefix: str, i, weights, precision: str):
    """sum_e weights[:, e] * E_e(h) over layer i's experts of the
    stacks `<prefix>gate_proj` [L, E, d, f], ..., one expert cast to
    float32 at a time (a whole layer's would be 4.6 GB at the
    configuration's sizes).  h [s, d]; weights [s, E]."""
    w_gate, w_up, w_down = (mp[f'{prefix}{name}_proj']
                            for name in ('gate', 'up', 'down'))

    def one(e, acc):
        g = _mm(h, w_gate[i, e].astype(jnp.float32), precision)
        u = _mm(h, w_up[i, e].astype(jnp.float32), precision)
        y = _mm(jax.nn.silu(g) * u, w_down[i, e].astype(jnp.float32),
                precision)
        return acc + weights[:, e, None] * y

    return jax.lax.fori_loop(0, w_gate.shape[1], one, jnp.zeros_like(h))


def _gates(h, w_router, *, top_k: int, lo: int, n_held: int,
           precision: str):
    """([s, n_held], [s]): g_e of the held experts, zero where e is not
    among the token's top-k, normalised over the whole top-k; and the
    row's margin, the least distance in router logits by which a held
    expert is inside or outside the top-k."""
    z = _mm(h, w_router.astype(jnp.float32), precision)
    top_z, idx = jax.lax.top_k(z, top_k + 1)
    top = jax.nn.sigmoid(top_z[:, :top_k])
    g = top / jnp.sum(top, axis=-1, keepdims=True)
    held = (idx[:, :top_k, None] == lo + jnp.arange(n_held)[None, None, :])
    z_held = z[:, lo:lo + n_held]
    last_in, first_out = top_z[:, top_k - 1, None], top_z[:, top_k, None]
    margin = jnp.where(z_held >= last_in, z_held - first_out,
                       last_in - z_held)
    return jnp.sum(g[:, :, None] * held, axis=1), jnp.min(margin, axis=-1)


@functools.partial(jax.jit, static_argnames=(
    'rotate', 'window', 'theta', 'eps', 'top_k', 'lo', 'precision'))
def _layer(x, stacked, i, *, rotate: bool, window: int, theta: float,
           eps: float, top_k: int, lo: int, precision: str):
    """Layer i of the stacked weights over x [s, d] float32 -> (y, each
    row's routing margin).  Each weight is sliced out of its stack
    where it is used, so no copy of the layer is made."""
    s, d = x.shape
    wq, wk, wv, wo = (stacked['attn'][name]['kernel'][i].astype(jnp.float32)
                      for name in ('q_proj', 'k_proj', 'v_proj', 'o_proj'))
    h_q, hd = wq.shape[1:]
    h_kv = wk.shape[1]
    h = _layer_norm(x, stacked['attn_norm']['scale'][i].astype(jnp.float32),
                    eps)
    q = _mm(h, wq.reshape(d, h_q * hd), precision).reshape(s, h_q, hd)
    k = _mm(h, wk.reshape(d, h_kv * hd), precision).reshape(s, h_kv, hd)
    v = _mm(h, wv.reshape(d, h_kv * hd), precision).reshape(s, h_kv, hd)
    if rotate:
        q, k = _rope(q, theta), _rope(k, theta)
    attn = _mm(_attention(q, k, v, window).reshape(s, h_q * hd),
               wo.reshape(h_q * hd, d), precision)
    mp = stacked['moe_mlp']
    gates, margin = _gates(h, mp['router']['kernel'][i], top_k=top_k, lo=lo,
                           n_held=mp['gate_proj'].shape[1],
                           precision=precision)
    routed = _experts(h, mp, '', i, gates, precision)
    n_shared = mp['shared_gate_proj'].shape[1]
    shared = _experts(
        h, mp, 'shared_', i,
        jnp.full((s, n_shared), 1.0 / n_shared, jnp.float32), precision)
    return x + attn + routed + shared, margin


def layer(model: Dict[str, Any], stacked, i: int, x,
          precision: str = 'float32'):
    """The reference's layer `i` alone: x [s, d] float32 at positions
    0 .. s-1 -> y [s, d] (the CPU test that the shares of the experts
    add up reads it)."""
    return _layer_of(model, stacked, i, x, precision)[0]


def _layer_of(model, stacked, i, x, precision):
    kind = layer_types(model)[i]
    return _layer(
        x, stacked, i, rotate=kind == 'sliding_attention',
        window=model['sliding_window'] if kind == 'sliding_attention'
        else 0, theta=float(model['rope_theta']),
        eps=float(model['layer_norm_eps']),
        top_k=model['num_experts_per_tok'], lo=experts_held(model)[0],
        precision=precision)


@functools.partial(jax.jit, static_argnames=('rows', 'eps', 'scale',
                                             'precision'))
def _head(x, first, norm_scale, embedding, *, rows, eps, scale,
          precision):
    x = jax.lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    return _mm(_layer_norm(x, norm_scale.astype(jnp.float32), eps),
               embedding.astype(jnp.float32).T, precision) * scale


def undecided_margin(model: Dict[str, Any]) -> float:
    """The width, in router logits, inside which the configuration's
    stated dtype cannot place a held expert in or out of the top-k."""
    return _UNDECIDED_EPS * float(jnp.finfo(model['torch_dtype']).eps)


def forward(model: Dict[str, Any], params, tokens, first: int, rows: int,
            precision: str = 'float32'):
    """(logits [rows, vocab], margin [rows]): row j is the model's
    output at position first + j of `tokens`, one sequence (a prompt
    followed by its served tokens), and the least routing margin of
    that position over the layers.  The caller pads `tokens` to a
    bucket of lengths so that few shapes compile: the block is causal,
    so padding behind a position cannot reach it."""
    _check(model)
    if first < 0 or first + rows > len(tokens):
        raise ValueError(f'rows [{first}, {first + rows}) outside '
                         f'{len(tokens)} tokens')
    ids = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params['embed']['embedding'], ids,
                 axis=0).astype(jnp.float32)
    stacked = params['layers']['layer']
    margin = jnp.full((rows,), jnp.inf, jnp.float32)
    for i in range(model['num_hidden_layers']):
        x, m = _layer_of(model, stacked, i, x, precision)
        margin = jnp.minimum(margin, m[first:first + rows])
    out = _head(x, first, params['final_norm']['scale'],
                params['embed']['embedding'], rows=rows,
                eps=float(model['layer_norm_eps']),
                scale=float(model['logit_scale']), precision=precision)
    return out, margin


def logits(model: Dict[str, Any], params, tokens, first: int, rows: int,
           precision: str = 'float32'):
    """`forward`'s logits; in float32, the judge's precision, a row
    whose routing is undecided is all zeros (see the module's
    docstring).  The control answers every row, as the program does."""
    out, margin = forward(model, params, tokens, first, rows, precision)
    if precision != 'float32':
        return out
    return jnp.where((margin < undecided_margin(model))[:, None], 0.0, out)
