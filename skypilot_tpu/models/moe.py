"""Mixture-of-Experts layer without dropped tokens.

One pure function, `moe_apply`, is the expert layer for the training
module below, for prefill chunks and for decode ticks
(models/decode.py): the router scores every token over ALL
`cfg.n_experts` (the published width), the top `expert_top_k` are
kept and their gates renormalised, and every held expert's SwiGLU runs
over every token as one batched product, each token's result weighted
by its gate (zero where the token did not choose the expert).  No
capacity, no drops: a token's result does not depend on its
neighbours, so a chunk may be padded, split or batched with other
requests and a shared prefix has shared KV.

The layer is told which experts it holds (`cfg.experts_held` = (lo, n);
None = all): the expert stacks are `[n, ...]` and the routed sum runs
over the top-k experts that fall in `[lo, lo + n)`, with the gates
still normalised over the whole top-k.  What the absent experts would
add is another chip's part of the result.  Shared experts
(`cfg.n_shared_experts`) see every token and are added as their sum or
their average.

Why a plain batched product: at the sizes served (64 tokens a tick or
a few hundred a chunk, 16 held experts of 4096 x 4096) the products
over all held experts cost less MXU time than reading their weights
once takes, so the layer is bound by the bytes whichever way it is
grouped.  Weights are read in the dtype they are stored in,
activations are `cfg.dtype`, products accumulate in float32; the
router alone runs in float32.  Which experts a token takes is a
discontinuous function of the stream: at bfloat16 the last place of a
top-k of near-equal scores can differ from a float32 reference's, and
that token's output then differs by a whole expert (PERF.md, finding
21).  With the experts sharded over the 'expert' mesh axis (params
annotated ('expert', 'embed', 'mlp')) GSPMD splits the batched product
by expert and reduces the combine.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.quantize import maybe_dequant


def route(tokens, router_kernel, cfg: ModelConfig, bias=None
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scores [N, E] (float32, over the published width), and the
    top-k's renormalised gates [N, k] and expert ids [N, k].  The
    router stays in float32 whatever the weights' dtype: which experts
    a token takes is decided here.  With a selection `bias` [E]
    (`cfg.expert_bias`) the top-k is of score + bias and the gates are
    the chosen experts' scores: the bias picks, it never weights."""
    logits = jnp.einsum('nd,de->ne', tokens.astype(jnp.float32),
                        router_kernel.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.expert_score_fn == 'sigmoid':
        scores = jax.nn.sigmoid(logits)
    elif cfg.expert_score_fn == 'softmax':
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(
            f'Unknown expert_score_fn {cfg.expert_score_fn!r}; '
            "have 'softmax', 'sigmoid'.")
    if bias is None:
        gate_vals, gate_idx = jax.lax.top_k(scores, cfg.expert_top_k)
    else:
        _, gate_idx = jax.lax.top_k(
            scores + bias.astype(jnp.float32), cfg.expert_top_k)
        gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
    total = jnp.sum(gate_vals, axis=-1, keepdims=True)
    if cfg.expert_gate_eps:
        total = total + cfg.expert_gate_eps
    gate_vals = gate_vals / total
    if cfg.expert_scale != 1.0:
        gate_vals = gate_vals * cfg.expert_scale
    return scores, gate_vals, gate_idx


# An expert's activation by `cfg.mlp_act`.  'relu2' (Nemotron-H's
# experts) is relu(h)^2 of an expert without a gate matrix.
ACTIVATIONS = {'silu': jax.nn.silu, 'gelu': jax.nn.gelu,
               'relu2': lambda h: jnp.square(jax.nn.relu(h))}


def _expert_products(x, gates, w_gate, w_up, w_down, cfg: ModelConfig):
    """sum_e gates[n, e] * (act(x G_e) * (x U_e)) D_e for stacks
    [E, d, f], [E, d, f], [E, f, d]: x [N, d], gates [N, E] float32 ->
    [N, d] float32; without a gate stack (`w_gate` None: a non-gated
    expert) act(x U_e) D_e.  The hidden activation is x's dtype.  The
    gate scales the hidden activation, so the down projection contracts
    expert and width at once and no [E, N, d] tensor is built."""
    act = ACTIVATIONS[cfg.mlp_act]
    kw = dict(preferred_element_type=jnp.float32)
    u = None
    if w_gate is not None:
        g = jnp.einsum('nd,edf->enf', x, maybe_dequant(w_gate, x.dtype),
                       **kw)
        u = jnp.einsum('nd,edf->enf', x, maybe_dequant(w_up, x.dtype),
                       **kw)
        hidden = act(g) * u
    else:
        hidden = act(jnp.einsum('nd,edf->enf', x,
                                maybe_dequant(w_up, x.dtype), **kw))
    h = nn.with_logical_constraint(
        (hidden * gates.T[:, :, None]).astype(x.dtype),
        ('expert', None, 'mlp'))
    return jnp.einsum('enf,efd->nd', h, maybe_dequant(w_down, x.dtype),
                      **kw)


def moe_apply(tokens, mp: Dict[str, Any], cfg: ModelConfig,
              row_mask: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The expert layer on [N, d] tokens.  `mp` holds `router/kernel`
    [d, n_experts], the held experts' `gate_proj`, `up_proj` [n_held,
    d, f] and `down_proj` [n_held, f, d] (int8 leaves pass through
    `maybe_dequant`) and, with shared experts, `shared_gate_proj`,
    `shared_up_proj`, `shared_down_proj` over [n_shared, ...] (of their
    own width).  `cfg.mlp_act` 'relu2' experts are not gated and have
    no `gate_proj` leaves: act(x U) D.

    Returns (out [N, d] float32, the load-balancing auxiliary loss,
    counts int32 [4]): rows routed, (row, held expert) pairs computed,
    the fullest held expert's rows, and the held experts that any row
    was routed to (`touched`: those whose weights the rows needed),
    over the rows `row_mask` [N] marks (all where None).  With
    `cfg.expert_bias` `mp` holds the selection bias `expert_bias`
    [n_experts] beside the router.  Pure, so training, prefill and
    decode share the one implementation."""
    lo, n_held = cfg.held_experts
    x = tokens.astype(cfg.dtype)
    with jax.named_scope('moe_router'):
        scores, gate_vals, gate_idx = route(
            tokens, mp['router']['kernel'], cfg,
            mp['expert_bias'] if cfg.expert_bias else None)
        # [N, k, n_held]: one-hot of the held experts among the top-k;
        # an expert outside [lo, lo + n_held) gives a zero row.
        chosen = jax.nn.one_hot(gate_idx - lo, n_held, dtype=jnp.float32)
        gates = jnp.einsum('nk,nke->ne', gate_vals, chosen)
        picked = jnp.sum(chosen, axis=1)                 # [N, n_held] 0/1
        if row_mask is not None:
            picked = picked * row_mask.astype(jnp.float32)[:, None]
        per_expert = jnp.sum(picked, axis=0)
        counts = jnp.stack([
            jnp.asarray(tokens.shape[0], jnp.float32) if row_mask is None
            else jnp.sum(row_mask.astype(jnp.float32)),
            jnp.sum(per_expert), jnp.max(per_expert),
            jnp.sum(per_expert > 0)]).astype(jnp.int32)
    gated = cfg.mlp_act != 'relu2'
    with jax.named_scope('moe_experts'):
        out = _expert_products(x, gates,
                               mp['gate_proj'] if gated else None,
                               mp['up_proj'], mp['down_proj'], cfg)
    if cfg.n_shared_experts:
        if cfg.shared_expert_combine not in ('sum', 'average'):
            raise ValueError(
                'Unknown shared_expert_combine '
                f"{cfg.shared_expert_combine!r}; have 'sum', 'average'.")
        weight = (1.0 / cfg.n_shared_experts
                  if cfg.shared_expert_combine == 'average' else 1.0)
        with jax.named_scope('moe_shared'):
            out = out + _expert_products(
                x, jnp.full((tokens.shape[0], cfg.n_shared_experts),
                            weight, jnp.float32),
                mp['shared_gate_proj'] if gated else None,
                mp['shared_up_proj'],
                mp['shared_down_proj'], cfg)

    # Load-balancing auxiliary loss (Switch Transformer eq. 4) over the
    # published width: the share of tokens whose first choice an expert
    # is, times its mean normalised score.
    density = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], cfg.n_experts,
                                      dtype=jnp.float32), axis=0)
    proxy = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True),
                     axis=0)
    aux = jnp.sum(density * proxy) * cfg.n_experts * \
        cfg.router_aux_loss_coef
    return out, aux, counts


class _Router(nn.Module):
    """Holds the router's kernel under `router/kernel`, the path a
    dense layer of that name gave it (checkpoints, import_weights and
    quantize's skip rule name it so)."""
    n_experts: int
    param_dtype: Any

    @nn.compact
    def __call__(self, d: int):
        return self.param(
            'kernel',
            nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                         ('embed', 'expert')),
            (d, self.n_experts), self.param_dtype)


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense MLP block."""
    config: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, d = x.shape

        def param(name, shape, logical):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), logical),
                shape, cfg.param_dtype)

        mp = {'router': {'kernel': _Router(
            cfg.n_experts, cfg.param_dtype, name='router')(d)}}
        for prefix, n in (('', cfg.held_experts[1]),
                          ('shared_', cfg.n_shared_experts)):
            if not n:
                continue
            if cfg.mlp_act != 'relu2':
                mp[f'{prefix}gate_proj'] = param(
                    f'{prefix}gate_proj', (n, d, cfg.d_ff),
                    ('expert', 'embed', 'mlp'))
            mp[f'{prefix}up_proj'] = param(
                f'{prefix}up_proj', (n, d, cfg.d_ff),
                ('expert', 'embed', 'mlp'))
            mp[f'{prefix}down_proj'] = param(
                f'{prefix}down_proj', (n, cfg.d_ff, d),
                ('expert', 'mlp', 'embed'))
        out, aux, _ = moe_apply(x.reshape(b * s, d), mp, cfg)
        self.sow('losses', 'moe_aux_loss', aux)
        return out.astype(x.dtype).reshape(b, s, d)
