"""Mixture-of-Experts layer with expert parallelism.

TPU-first design (SURVEY.md §2.3: EP is a build mandate — the reference
only carries MoE as a user example): dense GShard-style top-k dispatch —
one-hot dispatch/combine einsums, static capacity — so XLA lowers the
whole layer onto the MXU with a single all-to-all pair when the experts
are sharded over the 'expert' mesh axis (params annotated
('expert', 'embed', 'mlp'); GSPMD inserts the collectives).

The dispatch math lives in the pure `moe_apply` so the training module
and the KV-cache decode path (models/decode.py) share one
implementation.
"""
from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models.configs import ModelConfig


def moe_apply(tokens, router_logits, w_gate, w_up, w_down,
              cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Capacity-dispatched top-k MoE on [N, d] tokens given [N, E]
    router logits.

    Returns (out [N, d] float32, aux_loss scalar).  Pure function —
    shared by the flax training module below and the inference prefill
    path (decode.py), so the routing math exists exactly once.
    """
    n_exp = cfg.n_experts
    top_k = cfg.expert_top_k
    n_tokens, _ = tokens.shape

    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)       # [N, k]
    # Renormalize the selected gates (Mixtral convention).
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Static per-expert capacity; overflow tokens are dropped
    # (their residual path still carries them).
    capacity = max(1, int(cfg.expert_capacity_factor * n_tokens *
                          top_k / n_exp))

    # One-hot expert choice per (token, slot): [N, k, E].
    choice = jax.nn.one_hot(gate_idx, n_exp, dtype=jnp.float32)
    # Position of each token within its expert's buffer, computed
    # over the flattened (slot-major) order.
    flat_choice = choice.reshape(n_tokens * top_k, n_exp)
    position = jnp.cumsum(flat_choice, axis=0) * flat_choice - 1.0
    in_capacity = (position >= 0) & (position < capacity)
    position = position.reshape(n_tokens, top_k, n_exp)
    in_capacity = in_capacity.reshape(n_tokens, top_k, n_exp)

    # dispatch [N, E, C]: token -> (expert, buffer slot).
    pos_onehot = jax.nn.one_hot(position.astype(jnp.int32), capacity,
                                dtype=jnp.float32)
    dispatch = jnp.einsum('nke,nkec->nec', choice * in_capacity,
                          pos_onehot * in_capacity[..., None])
    combine = jnp.einsum('nk,nke,nkec->nec', gate_vals,
                         choice * in_capacity,
                         pos_onehot * in_capacity[..., None])

    expert_in = jnp.einsum('nec,nd->ecd', dispatch,
                           tokens.astype(jnp.float32))
    expert_in = nn.with_logical_constraint(
        expert_in.astype(cfg.dtype), ('expert', None, 'embed'))

    act = {'silu': jax.nn.silu, 'gelu': jax.nn.gelu}[cfg.mlp_act]
    h = act(jnp.einsum('ecd,edf->ecf', expert_in,
                       w_gate.astype(cfg.dtype)))
    h = h * jnp.einsum('ecd,edf->ecf', expert_in,
                       w_up.astype(cfg.dtype))
    expert_out = jnp.einsum('ecf,efd->ecd', h,
                            w_down.astype(cfg.dtype))
    expert_out = nn.with_logical_constraint(
        expert_out, ('expert', None, 'embed'))

    out = jnp.einsum('nec,ecd->nd', combine,
                     expert_out.astype(jnp.float32))

    # Load-balancing auxiliary loss (Switch Transformer eq. 4).
    density = jnp.mean(choice[:, 0, :], axis=0)          # router picks
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * n_exp * \
        cfg.router_aux_loss_coef
    return out, aux


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense MLP block."""
    config: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)

        router = nn.Dense(
            cfg.n_experts, use_bias=False, dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ('embed', 'expert')),
            name='router')

        def expert_param(name, shape, logical):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), logical),
                shape, cfg.param_dtype)

        w_gate = expert_param('gate_proj', (cfg.n_experts, d, cfg.d_ff),
                              ('expert', 'embed', 'mlp'))
        w_up = expert_param('up_proj', (cfg.n_experts, d, cfg.d_ff),
                            ('expert', 'embed', 'mlp'))
        w_down = expert_param('down_proj', (cfg.n_experts, cfg.d_ff, d),
                              ('expert', 'mlp', 'embed'))

        logits = router(tokens.astype(jnp.float32))
        out, aux = moe_apply(tokens, logits, w_gate, w_up, w_down, cfg)
        self.sow('losses', 'moe_aux_loss', aux)
        return out.astype(x.dtype).reshape(b, s, d)
