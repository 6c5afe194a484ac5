"""Family `nemotron_h`: Mamba-2 layers, expert layers of non-gated relu^2
experts beside a shared one, and a few position-free attention layers,
each layer one sub-block alone, everything the yardstick knows of it
(see `families/__init__.py` for the entries).

The model, as `NVIDIA-Nemotron-3-Nano-30B-A3B`'s config.json states its
sizes and the public `nemotron_h` modeling code its equations.
`hybrid_override_pattern` names each layer: M (Mamba-2), E (experts) or
* (attention).  For hidden `h` at position `t`, with RMSNorm(x; w) =
w * x / sqrt(mean(x^2) + eps):

    u = RMSNorm(h; w_l)                       each layer's one norm
    M:  [z | xBC | dt] = u W_in               z: H*P, xBC: H*P + 2*G*N,
                                              dt: H (mamba_num_heads H,
                                              mamba_head_dim P, n_groups G,
                                              ssm_state_size N)
        xBC = silu(sum_j k_j xBC_{t-K+1+j} + b)     depthwise, K = conv_kernel;
                                              xBC before position 0 is 0
        [x | B | C] = xBC;  head h reads group h // (H / G)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      [P, N] a head, S_{-1} = 0
        y_t = S_t C_t + D x_t
        m = RMSNorm_G(y * silu(z); w_norm) W_out   RMSNorm over each of G
                                              groups of H*P/G channels
    E:  s = sigmoid(u W_r)                    float32, over all n_routed_experts
        T = the num_experts_per_tok largest of s + b   b: e_score_correction_bias;
                                              the bias picks, it never weights
        g_e = s_e / (sum_{j in T} s_j + 1e-20) * routed_scaling_factor
        m = sum_{e in T} g_e relu(u U_e)^2 D_e + relu(u U_s)^2 D_s
                                              width moe_intermediate_size;
                                              the shared expert's its own
    *:  q, k, v = u W_q, u W_k, u W_v          GQA, head_dim; no rotation
        m = softmax(q k^T / sqrt(head_dim), causal) v W_o
    h' = h + m
    logits = RMSNorm(h_L; w_final) W_head      untied head

**The chip's share.**  The router keeps its published width
(`published.n_routed_experts`) and its experts per token; the routed
sum runs over the experts of T that are held here (`experts_held` =
[lo, hi), `n_routed_experts` of them), with `g` still normalised over
all of T.  What the absent experts would add is left out, and that
partial `h'` goes on to the next layer: in the program and here alike.
The vocabulary is the `vocab_size` rows held, of the embedding and of
the head.

Counted from the configuration's sizes, never from what an
implementation happens to touch.  A later PR may change the program;
it may not change this file.

**The plain reference** (`logits`) is the equations above in float32
`jax.numpy` at "highest" matmul precision: no kernel, no cache, no
batching, no chunked scan, nothing imported from the program.  The
Mamba recurrence is the sequential scan over positions, one position
a step.  It has to fit one chip beside the served bf16 weights at
8,704 positions, so one expert is cast to float32 at a time and
attention runs in blocks of query rows.  `precision='int8'` is the
control: the same forward with both operands of every matrix product
(projections, router, experts, head) rounded to int8, one scale a row,
the nearest precision below the configuration's bfloat16.
`precision='bf16_state'` keeps everything float32 but rounds each
head's state S to bfloat16 after every position: the program with its
state in the configuration's dtype.  The served-token comparison
does not refuse it on most seeds (PERF.md finding 56): what keeps the
state in float32 is a routing-free check of the state itself
(`tests/unit/test_mamba.py`), which this comparison has no place for.

**Where the reference abstains.**  As `cohere2_moe` and `lfm2_moe`: a
row any of whose expert layers places a pair of experts across the
top-k's edge, one of them held here, within `undecided_margin(model)`
router logits of each other gets no preference (all zeros), so its gap
reads 0.  Over 23 routing layers that is ~97% of the rows.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks import cost

_HIGHEST = jax.lax.Precision.HIGHEST
# Query rows one attention block holds: at 32 heads and 8,704 keys a
# block's scores are 0.29 GB in float32.
_ATTN_ROWS = 256
_KINDS = {'M': 'mamba', 'E': 'experts', '*': 'attn_nope'}
# A routing decision is undecided where a pair of experts across the
# top-k's edge is within this many machine epsilons of the
# configuration's dtype, in router logits, of trading places
# (`cohere2_moe` holds 3, `lfm2_moe` 2).  Here 23 layers route on a
# residual stream rounded to bfloat16 at each of 52 layers, and the
# bfloat16 program's own flips reach past 3: a gap of 1.13 at a row
# whose closest pair lay 3.25-3.5 epsilons apart (PERF.md finding 56).
# 3.5 leaves ~3% of the rows judged, so the cell's limits file
# samples 4,800 served tokens.
_UNDECIDED_EPS = 3.5
# The gates' epsilon (the modeling code adds it to the sum of the
# chosen scores).
_GATE_EPS = 1e-20
# `e_score_correction_bias` is seeded N(0, 1 / _BIAS_FAN_IN): sigma
# 0.05, the scale of the gaps between the top sigmoid scores, so the
# bias changes most tokens' top-6 sets.
_BIAS_FAN_IN = 400
# Mamba-2's initialisation of the decay rates: A drawn uniform in
# [1, 16] (A_log its log).
_A_RANGE = (1.0, 16.0)


def _check(model: Dict[str, Any]) -> None:
    """What the equations above assume of the configuration."""
    want = {'model_type': 'nemotron_h', 'mamba_hidden_act': 'silu',
            'mlp_hidden_act': 'relu2', 'n_group': 1, 'topk_group': 1,
            'norm_topk_prob': True, 'use_conv_bias': True,
            'mamba_proj_bias': False, 'use_bias': False,
            'attention_bias': False, 'mlp_bias': False,
            'tie_word_embeddings': False, 'n_shared_experts': 1,
            'residual_in_fp32': False}
    bad = {k: model.get(k) for k, v in want.items() if model.get(k) != v}
    if bad:
        raise ValueError(f'family nemotron_h is written for {want}; '
                         f'the configuration has {bad}')
    if set(model['hybrid_override_pattern']) - set(_KINDS):
        raise ValueError(f'hybrid_override_pattern outside {sorted(_KINDS)}')
    if len(model['hybrid_override_pattern']) != model['num_hidden_layers']:
        raise ValueError('hybrid_override_pattern is not one letter a layer')


def layer_types(model: Dict[str, Any]) -> List[str]:
    """One letter a layer: 'M', 'E' or '*'."""
    return list(model['hybrid_override_pattern'])


def router_width(model: Dict[str, Any]) -> int:
    """Experts the router scores: the published count, whatever is
    held here."""
    return int(model.get('published', {}).get('n_routed_experts',
                                              model['n_routed_experts']))


def experts_held(model: Dict[str, Any]) -> Tuple[int, int]:
    """(lo, n): the routed experts this chip holds."""
    lo, hi = model.get('experts_held', (0, model['n_routed_experts']))
    if hi - lo != model['n_routed_experts']:
        raise ValueError('experts_held does not span n_routed_experts')
    return int(lo), int(hi - lo)


def _inner(model: Dict[str, Any]) -> int:
    return model['mamba_num_heads'] * model['mamba_head_dim']


def _conv_dim(model: Dict[str, Any]) -> int:
    return _inner(model) + 2 * model['n_groups'] * model['ssm_state_size']


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's keys as the program's `ModelConfig`."""
    from skypilot_tpu.models import configs
    _check(model)
    return configs.config_from_json_dict(dict(
        vocab_size=model['vocab_size'], d_model=model['hidden_size'],
        n_layers=model['num_hidden_layers'],
        n_heads=model['num_attention_heads'],
        n_kv_heads=model['num_key_value_heads'],
        head_dim_override=model['head_dim'],
        d_ff=model['moe_intermediate_size'],
        shared_d_ff=model['moe_shared_expert_intermediate_size'],
        layer_pattern=[_KINDS[letter] for letter in layer_types(model)],
        conv_kernel=model['conv_kernel'],
        ssm_heads=model['mamba_num_heads'],
        ssm_head_dim=model['mamba_head_dim'],
        ssm_groups=model['n_groups'], ssm_state=model['ssm_state_size'],
        ssm_chunk=model['chunk_size'],
        norm_eps=float(model['norm_eps']), mlp_act='relu2',
        tie_embeddings=False, n_experts=router_width(model),
        expert_top_k=model['num_experts_per_tok'],
        experts_held=list(experts_held(model)),
        expert_score_fn='sigmoid', expert_bias=True,
        expert_gate_eps=_GATE_EPS,
        expert_scale=float(model['routed_scaling_factor']),
        n_shared_experts=model['n_shared_experts'],
        shared_expert_combine='sum',
        dtype=model['torch_dtype'], param_dtype=model['torch_dtype'],
        max_seq_len=max_len, remat=False))


def shapes(model: Dict[str, Any]) -> Dict[str, Any]:
    """leaf path -> (shape, fan_in or None for a leaf seeded near 1 in
    float32): the tree `models/decode.py` reads for a model stacked by
    kind, one group 'layers' with the mamba layers' stack under
    'mamba', the attention layers' under 'attn' and the expert layers'
    under 'ffn'.  A_log and dt_bias are given their values by
    `reseed`."""
    d, hd = model['hidden_size'], model['head_dim']
    h_q, h_kv = model['num_attention_heads'], model['num_key_value_heads']
    heads, inner, conv = (model['mamba_num_heads'], _inner(model),
                          _conv_dim(model))
    width = model['conv_kernel']
    kinds = layer_types(model)
    n_m, n_a, n_e = kinds.count('M'), kinds.count('*'), kinds.count('E')
    _, held = experts_held(model)
    f, fs = (model['moe_intermediate_size'],
             model['moe_shared_expert_intermediate_size'])
    v = model['vocab_size']
    m = ('layers', 'mamba')
    a = ('layers', 'attn')
    e = ('layers', 'ffn')
    out = {('embed', 'embedding'): ((v, d), 2500),
           ('lm_head', 'kernel'): ((d, v), d),
           ('final_norm', 'scale'): ((d,), None),
           m + ('mamba_norm', 'scale'): ((n_m, d), None),
           m + ('mamba', 'in_proj', 'kernel'): ((n_m, d, inner + conv + heads),
                                                d),
           m + ('mamba', 'conv_kernel'): ((n_m, width, conv), width),
           m + ('mamba', 'conv_bias'): ((n_m, conv), width),
           m + ('mamba', 'A_log'): ((n_m, heads), None),
           m + ('mamba', 'dt_bias'): ((n_m, heads), None),
           m + ('mamba', 'D'): ((n_m, heads), None),
           m + ('mamba', 'norm', 'scale'): ((n_m, inner), None),
           m + ('mamba', 'out_proj', 'kernel'): ((n_m, inner, d), inner),
           a + ('attn_norm', 'scale'): ((n_a, d), None),
           a + ('attn', 'q_proj', 'kernel'): ((n_a, d, h_q, hd), d),
           a + ('attn', 'k_proj', 'kernel'): ((n_a, d, h_kv, hd), d),
           a + ('attn', 'v_proj', 'kernel'): ((n_a, d, h_kv, hd), d),
           a + ('attn', 'o_proj', 'kernel'): ((n_a, h_q, hd, d), h_q * hd),
           e + ('mlp_norm', 'scale'): ((n_e, d), None),
           e + ('moe_mlp', 'router', 'kernel'): ((n_e, d, router_width(model)),
                                                 d),
           e + ('moe_mlp', 'expert_bias'): ((n_e, router_width(model)),
                                            _BIAS_FAN_IN),
           e + ('moe_mlp', 'up_proj'): ((n_e, held, d, f), d),
           e + ('moe_mlp', 'down_proj'): ((n_e, held, f, d), f),
           e + ('moe_mlp', 'shared_up_proj'): ((n_e, 1, d, fs), d),
           e + ('moe_mlp', 'shared_down_proj'): ((n_e, 1, fs, d), fs)}
    return out


def reseed(model: Dict[str, Any], params, key):
    """`params` with the mamba layers' A_log and dt_bias given Mamba-2's
    initial values (`layouts/single_seeded.py` calls it): A uniform in
    [1, 16], dt = exp(uniform in [log time_step_min, log time_step_max])
    floored at time_step_floor, dt_bias its inverse softplus, so that
    the states hold what they read for tens to thousands of positions
    as a trained model's do."""
    mixer = params['layers']['mamba']['mamba']
    shape = mixer['A_log'].shape
    k_a, k_dt = jax.random.split(key)
    a = jax.random.uniform(k_a, shape, jnp.float32, *_A_RANGE)
    lo, hi = (jnp.log(model['time_step_min']),
              jnp.log(model['time_step_max']))
    dt = jnp.exp(jax.random.uniform(k_dt, shape, jnp.float32, lo, hi))
    dt = jnp.maximum(dt, model['time_step_floor'])
    mixer = dict(mixer, A_log=jnp.log(a).astype(mixer['A_log'].dtype),
                 dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(
                     mixer['dt_bias'].dtype))
    layers = dict(params['layers'],
                  mamba=dict(params['layers']['mamba'], mamba=mixer))
    return dict(params, layers=layers)


# ------------------------------------------------------------- counts


def _mamba_params(model: Dict[str, Any]) -> int:
    """A mamba layer: in_proj, the conv kernel and bias, A_log, dt_bias,
    D, the gated norm, out_proj, the layer's norm."""
    d, heads = model['hidden_size'], model['mamba_num_heads']
    inner, conv = _inner(model), _conv_dim(model)
    return (d * (inner + conv + heads) + model['conv_kernel'] * conv +
            conv + 3 * heads + inner + inner * d + d)


def _attn_params(model: Dict[str, Any]) -> int:
    d, hd = model['hidden_size'], model['head_dim']
    return (d * hd * (model['num_attention_heads'] +
                      2 * model['num_key_value_heads']) +
            model['num_attention_heads'] * hd * d + d)


def expert_bytes(model: Dict[str, Any]) -> int:
    """What one routed expert's two matrices hold at the served dtype:
    what an expert layer reads for each expert a token took."""
    return (2 * model['hidden_size'] * model['moe_intermediate_size'] *
            cost.DTYPE_BYTES[model['torch_dtype']])


def param_counts(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameters: per layer its one sub-block (mamba, attention, or the
    router, the bias, the held experts and the shared expert) and its
    norm; the embedding, the head, the final norm.  `active` is what
    one token's matrix products touch: the mixers, the routers, the
    shared experts and `num_experts_per_tok` experts a layer (of the
    held ones, as many as the share of held experts gives)."""
    d = model['hidden_size']
    kinds = layer_types(model)
    e, k = router_width(model), model['num_experts_per_tok']
    held = model['n_routed_experts']
    expert = 2 * d * model['moe_intermediate_size']
    shared = 2 * d * model['moe_shared_expert_intermediate_size']
    moe = d * e + e + held * expert + shared + d
    moe_active = d * e + k * expert + shared
    total = (kinds.count('M') * _mamba_params(model) +
             kinds.count('*') * _attn_params(model) + kinds.count('E') * moe)
    active = (kinds.count('M') * _mamba_params(model) +
              kinds.count('*') * _attn_params(model) +
              kinds.count('E') * moe_active)
    v = model['vocab_size']
    return {'layers': total, 'active': active, 'expert': expert,
            'mamba': _mamba_params(model), 'attention': _attn_params(model),
            'moe_layer': moe, 'embedding': v * d, 'head': d * v,
            'total': total + 2 * v * d + d}


def attention_layers(model: Dict[str, Any]) -> int:
    """Layers with keys in the page pool."""
    return layer_types(model).count('*')


def decode_cache_bytes(model: Dict[str, Any], context: int,
                       kv_dtype: str) -> int:
    """What one decoded token at `context` has to read from the caches:
    K and V of every position of each attention layer, at the pool's
    dtype (what `%paged_decode_attention` reads; the mamba states are
    `ssm_step_bytes`, whatever the context)."""
    return (2 * model['num_key_value_heads'] * model['head_dim'] *
            cost.DTYPE_BYTES[kv_dtype] * attention_layers(model) * context)


def decode_attention_flops(model: Dict[str, Any], context: int) -> int:
    """And what it computes over them: q.k and p.v, 2 multiply-adds per
    (query head, key, channel) of each attention layer."""
    return (4 * model['num_attention_heads'] * model['head_dim'] *
            attention_layers(model) * context)


def ssm_step_bytes(model: Dict[str, Any]) -> int:
    """What one decoded token's state update has to move, over the mamba
    layers: each head's float32 S read and written once, and the
    update's operands and result (dt x and y of every head's channels,
    exp(dt A) of each head, B and C of each group), float32: what
    `%ssm_decode_step` moves at the least."""
    heads, p = model['mamba_num_heads'], model['mamba_head_dim']
    n, g = model['ssm_state_size'], model['n_groups']
    per_layer = 4 * (2 * heads * p * n + 2 * heads * p + heads + 2 * g * n)
    return layer_types(model).count('M') * per_layer


def _ssm_flops(model: Dict[str, Any]) -> int:
    """A token's element-wise work at the mamba layers: the conv taps,
    the state's decay, outer product and read-out."""
    heads, p = model['mamba_num_heads'], model['mamba_head_dim']
    n = model['ssm_state_size']
    return layer_types(model).count('M') * (
        2 * model['conv_kernel'] * _conv_dim(model) + 6 * heads * p * n)


def decode_flops(model: Dict[str, Any], context: int) -> float:
    """FLOPs the model needs to produce one token whose query attends
    `context` keys: the products of the 6 chosen experts a layer (not
    the held ones that a batched product computes), the shared expert,
    the mixers, the routers, the head; the state update; attention."""
    pc = param_counts(model)
    return float(2 * (pc['active'] + pc['head']) + _ssm_flops(model) +
                 decode_attention_flops(model, context))


def prefill_flops(model: Dict[str, Any], start: int, n_new: int) -> float:
    """FLOPs to prefill positions [start, start + n_new) causally.  No
    head: the first token's logits are a decode step's."""
    pc = param_counts(model)
    attention = sum(decode_attention_flops(model, start + j + 1)
                    for j in range(n_new))
    return float((2 * pc['active'] + _ssm_flops(model)) * n_new +
                 attention)


def decode_state_bytes(model: Dict[str, Any]) -> int:
    """What a sequence carries at the mamba layers: S of every head
    (float32) and the last K - 1 rows of xBC (the served dtype)."""
    heads, p = model['mamba_num_heads'], model['mamba_head_dim']
    n = model['ssm_state_size']
    return layer_types(model).count('M') * (
        4 * heads * p * n + (model['conv_kernel'] - 1) * _conv_dim(model) *
        cost.DTYPE_BYTES[model['torch_dtype']])


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
                             eps) * scale.astype(jnp.float32)


def _attention(q, k, v):
    """q [s, h_q, d], k/v [s, h_kv, d] -> [s, h_q, d], causal; in
    blocks of `_ATTN_ROWS` query rows."""
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
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum('grqk,kgd->qgrd', probs, v, precision=_HIGHEST)

    out = jax.lax.map(block, (qg, jnp.arange(n_blk) * blk))
    return out.reshape(n_blk * blk, h_q, d)[:s]


def _at(tree, i):
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), tree)


def _scan(x, dt, a, b, c, *, groups: int, bf16_state: bool):
    """The recurrence one position a step: x [s, H, P], dt [s, H], a
    [H], b and c [s, G, N] -> y [s, H, P] (without D x)."""
    s, heads, p = x.shape
    rep = heads // groups

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, rep, axis=0)                   # [H, N]
        c_h = jnp.repeat(c_t, rep, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state +
                 (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        if bf16_state:
            # Not a cast there and back: XLA may drop such a pair.
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.einsum('hpn,hn->hp', state, c_h,
                                 precision=_HIGHEST)

    init = jnp.zeros((heads, p, b.shape[-1]), jnp.float32)
    return jax.lax.scan(step, init, (x, dt, b, c))[1]


@functools.partial(jax.jit, static_argnames=('eps', 'heads', 'groups',
                                             'size', 'precision'))
def _mamba_mixer(x, stack, i, *, eps, heads, groups, size, precision):
    """The mamba layer over x [s, d]: x + m."""
    lp = _at(stack, i)
    mp = lp['mamba']
    s = x.shape[0]
    u = _rms(x, lp['mamba_norm']['scale'], eps)
    proj = _mm(u, mp['in_proj']['kernel'], precision)
    inner = mp['out_proj']['kernel'].shape[0]
    conv_dim = mp['conv_kernel'].shape[1]
    z, xbc, dt = jnp.split(proj, [inner, inner + conv_dim], axis=-1)
    taps = mp['conv_kernel']                          # [K, C]
    width = taps.shape[0]
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(width)) +
                      mp['conv_bias'])
    xs, b, c = jnp.split(xbc, [inner, inner + groups * size], axis=-1)
    xs = xs.reshape(s, heads, inner // heads)
    dt = jax.nn.softplus(dt + mp['dt_bias'])
    y = _scan(xs, dt, -jnp.exp(mp['A_log']), b.reshape(s, groups, size),
              c.reshape(s, groups, size), groups=groups,
              bf16_state=precision == 'bf16_state')
    y = (y + mp['D'][:, None] * xs).reshape(s, inner)
    g = (y * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return x + _mm(g.reshape(s, inner) * mp['norm']['scale'],
                   mp['out_proj']['kernel'], precision)


@functools.partial(jax.jit, static_argnames=('eps', 'precision'))
def _attn_mixer(x, stack, i, *, eps, precision):
    """The attention layer over x [s, d]: x + m."""
    lp = _at(stack, i)
    s, d = x.shape
    u = _rms(x, lp['attn_norm']['scale'], eps)
    a = lp['attn']
    _, h_q, hd = a['q_proj']['kernel'].shape
    h_kv = a['k_proj']['kernel'].shape[1]
    q = _mm(u, a['q_proj']['kernel'].reshape(d, h_q * hd),
            precision).reshape(s, h_q, hd)
    k = _mm(u, a['k_proj']['kernel'].reshape(d, h_kv * hd),
            precision).reshape(s, h_kv, hd)
    v = _mm(u, a['v_proj']['kernel'].reshape(d, h_kv * hd),
            precision).reshape(s, h_kv, hd)
    out = _attention(q, k, v).reshape(s, h_q * hd)
    return x + _mm(out, a['o_proj']['kernel'].reshape(h_q * hd, d),
                   precision)


def _gates(f, w_router, bias, *, top_k: int, lo: int, n_held: int,
           scale: float, precision: str):
    """([s, n_held] g_e of the held experts, zero where e is not among
    the token's top-k, normalised over the whole top-k; [s] the row's
    margin: the least, over pairs of an expert in the top-k and one out
    of it of which at least one is held here, of how far their router
    logits must move against each other to trade places).  A pair of
    which neither is held moves nothing here but the gates' common
    denominator, by the pair's score gap over the chosen scores' sum."""
    z = _mm(f, w_router, precision)
    s = jax.nn.sigmoid(z)
    picked = s + bias
    _, idx = jax.lax.top_k(picked, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, z.shape[-1], dtype=jnp.float32),
                     axis=1)                        # [s, E] 0/1
    g = s * chosen
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + _GATE_EPS) * scale
    slope = s * (1.0 - s)
    v_in = jnp.take_along_axis(picked, idx, axis=1)          # [s, k]
    slope_in = jnp.take_along_axis(slope, idx, axis=1)
    pair = ((v_in[:, :, None] - picked[:, None, :]) /
            (slope_in[:, :, None] + slope[:, None, :]))
    held = (jnp.arange(z.shape[-1]) >= lo) & (jnp.arange(z.shape[-1]) <
                                              lo + n_held)
    counts = (held[idx][:, :, None] | held[None, None, :]) & (
        chosen[:, None, :] == 0)
    pair = jnp.where(counts, pair, jnp.inf)
    return g[:, lo:lo + n_held], jnp.min(pair, axis=(1, 2))


@functools.partial(jax.jit, static_argnames=('eps', 'top_k', 'lo',
                                             'scale', 'precision'))
def _expert_ffn(x, stack, i, *, eps, top_k, lo, scale, precision):
    """The expert layer over x [s, d]: (x + m, each row's margin).  The
    experts are cast to float32 one at a time."""
    f = _rms(x, stack['mlp_norm']['scale'][i].astype(jnp.float32), eps)
    mp = stack['moe_mlp']
    n_held = mp['up_proj'].shape[1]
    g, margin = _gates(f, mp['router']['kernel'][i].astype(jnp.float32),
                       mp['expert_bias'][i].astype(jnp.float32),
                       top_k=top_k, lo=lo, n_held=n_held, scale=scale,
                       precision=precision)

    def expert(prefix, e):
        up = mp[f'{prefix}up_proj'][i, e].astype(jnp.float32)
        down = mp[f'{prefix}down_proj'][i, e].astype(jnp.float32)
        return _mm(jnp.square(jax.nn.relu(_mm(f, up, precision))), down,
                   precision)

    def one(e, acc):
        return acc + g[:, e, None] * expert('', e)

    y = jax.lax.fori_loop(0, n_held, one, jnp.zeros_like(x))
    return x + y + expert('shared_', 0), margin


@functools.partial(jax.jit, static_argnames=('rows', 'eps', 'precision'))
def _head(x, first, norm_scale, head, *, rows, eps, precision):
    x = jax.lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    return _mm(_rms(x, norm_scale, eps), head.astype(jnp.float32),
               precision)


def undecided_margin(model: Dict[str, Any]) -> float:
    """The width, in router logits, inside which the configuration's
    stated dtype cannot place an expert in or out of the top-k."""
    return _UNDECIDED_EPS * float(jnp.finfo(model['torch_dtype']).eps)


def layer(model: Dict[str, Any], params, i: int, x,
          precision: str = 'float32'):
    """The reference's layer `i` alone: x [s, d] float32 at positions
    0 .. s-1 -> (y [s, d], each row's routing margin: inf where the
    layer routes nothing)."""
    eps = float(model['norm_eps'])
    kind = layer_types(model)[i]
    at = layer_types(model)[:i].count(kind)
    stacks = params['layers']
    inf = jnp.full((x.shape[0],), jnp.inf, jnp.float32)
    if kind == 'M':
        return _mamba_mixer(
            x, stacks['mamba'], at, eps=eps,
            heads=model['mamba_num_heads'], groups=model['n_groups'],
            size=model['ssm_state_size'], precision=precision), inf
    if kind == '*':
        return _attn_mixer(x, stacks['attn'], at, eps=eps,
                           precision=precision), inf
    return _expert_ffn(x, stacks['ffn'], at, eps=eps,
                       top_k=model['num_experts_per_tok'],
                       lo=experts_held(model)[0],
                       scale=float(model['routed_scaling_factor']),
                       precision=precision)


def forward(model: Dict[str, Any], params, tokens, first: int, rows: int,
            precision: str = 'float32'):
    """(logits [rows, vocab], margin [rows]): row j is the model's
    output at position first + j of `tokens`, one sequence (a prompt
    followed by its served tokens), and the least routing margin of
    that position over the expert layers.  The caller pads `tokens` to
    a bucket of lengths so that few shapes compile: the model is
    causal, so padding behind a position cannot reach it."""
    _check(model)
    if first < 0 or first + rows > len(tokens):
        raise ValueError(f'rows [{first}, {first + rows}) outside '
                         f'{len(tokens)} tokens')
    ids = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params['embed']['embedding'], ids,
                 axis=0).astype(jnp.float32)
    margin = jnp.full((rows,), jnp.inf, jnp.float32)
    for i in range(model['num_hidden_layers']):
        x, m = layer(model, params, i, x, precision)
        margin = jnp.minimum(margin, m[first:first + rows])
    out = _head(x, first, params['final_norm']['scale'],
                params['lm_head']['kernel'], rows=rows,
                eps=float(model['norm_eps']), precision=precision)
    return out, margin


def logits(model: Dict[str, Any], params, tokens, first: int, rows: int,
           precision: str = 'float32'):
    """`forward`'s logits; in float32, the judge's precision, a row
    whose routing is undecided is all zeros (see the module's
    docstring).  A control answers every row, as the program does."""
    out, margin = forward(model, params, tokens, first, rows, precision)
    if precision != 'float32':
        return out
    return jnp.where((margin < undecided_margin(model))[:, None], 0.0, out)
