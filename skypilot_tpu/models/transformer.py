"""Llama-style decoder-only transformer (flax.linen), TPU-first.

- GQA attention through ops.flash_attention (Pallas on TPU) or
  ops.ring_attention when the mesh has a non-trivial 'sequence' axis
  (long-context; SURVEY.md §5).
- All parameters carry logical axis names via nn.with_logical_partitioning
  so parallel/sharding.py rules place them on the [dcn, ici] mesh; GSPMD
  inserts the collectives.
- Layers run under nn.scan + nn.remat: one compiled layer body,
  rematerialised activations (HBM-friendly).
"""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.ops import flash_attention
from skypilot_tpu.ops import ring_attention
from skypilot_tpu.ops import ulysses_attention


def _rope_freqs(d: int, cfg: ModelConfig):
    """Per-pair rotary frequencies [d/2], with the config's long-context
    scaling applied (HF rope_scaling parity; see ModelConfig)."""
    freqs = 1.0 / (cfg.rope_theta **
                   (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    st = cfg.rope_scaling_type
    if st is None:
        return freqs
    factor = cfg.rope_scaling_factor
    if st == 'linear':
        return freqs / factor
    if st == 'llama3':
        orig = float(cfg.rope_original_max_len)
        low_wl = orig / cfg.rope_low_freq_factor    # longest kept-ish
        high_wl = orig / cfg.rope_high_freq_factor  # shortest scaled-ish
        wavelen = 2.0 * jnp.pi / freqs
        smooth = ((orig / wavelen - cfg.rope_low_freq_factor) /
                  (cfg.rope_high_freq_factor - cfg.rope_low_freq_factor))
        mid = (1.0 - smooth) * freqs / factor + smooth * freqs
        return jnp.where(wavelen > low_wl, freqs / factor,
                         jnp.where(wavelen < high_wl, freqs, mid))
    raise ValueError(f'Unknown rope_scaling_type {st!r}; '
                     "have None, 'linear', 'llama3'.")


def _rope(x, positions, cfg: ModelConfig):
    """Rotary embeddings on [b, h, s, d]; positions [s] (shared) or
    [b, s] (per-sequence — continuous batching decodes slots at
    different depths in one step)."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, cfg)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    if angles.ndim == 2:
        cos = jnp.cos(angles)[None, None]   # [1,1,s,d/2]
        sin = jnp.sin(angles)[None, None]
    else:
        cos = jnp.cos(angles)[:, None]      # [b,1,s,d/2]
        sin = jnp.sin(angles)[:, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def _remat_policy(cfg: ModelConfig):
    """ModelConfig.remat_policy → jax.checkpoint policy (None = save
    nothing, i.e. full recompute)."""
    if cfg.remat_policy == 'full':
        return None
    if cfg.remat_policy == 'dots':
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(f'Unknown remat_policy {cfg.remat_policy!r}; '
                     "have 'full', 'dots'.")


class RMSNorm(nn.Module):
    eps: float = 1e-5
    # Gemma-style: scale = (1 + w) with w initialized to zero, so the
    # norm starts as identity-scale.
    scale_plus_one: bool = False

    @nn.compact
    def __call__(self, x):
        init = (nn.initializers.zeros if self.scale_plus_one
                else nn.initializers.ones)
        scale = self.param(
            'scale', nn.with_logical_partitioning(init, ('embed',)),
            (x.shape[-1],), jnp.float32)
        if self.scale_plus_one:
            scale = 1.0 + scale
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


class Attention(nn.Module):
    config: ModelConfig
    mesh: Optional[Any] = None
    # Set when the module already runs INSIDE a manual (shard_map)
    # region whose named axis shards the sequence dim (PP x SP
    # composition, parallel/pipeline.py): attention then rings over
    # that axis directly instead of wrapping its own shard_map.
    sequence_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        b, s, _ = x.shape
        hd = cfg.head_dim

        def proj(name, heads, logical):
            return nn.DenseGeneral(
                features=(heads, hd), axis=-1, use_bias=cfg.qkv_bias,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), logical),
                name=name)

        q = proj('q_proj', cfg.n_heads, ('embed', 'heads', 'head_dim'))(x)
        k = proj('k_proj', cfg.n_kv_heads, ('embed', 'kv_heads', 'head_dim'))(x)
        v = proj('v_proj', cfg.n_kv_heads, ('embed', 'kv_heads', 'head_dim'))(x)

        # [b, s, h, d] -> [b, h, s, d]
        q = q.transpose(0, 2, 1, 3)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        q = _rope(q, positions, cfg)
        k = _rope(k, positions, cfg)

        # GQA is native to the attention ops: the Pallas kernels map
        # q-head -> kv-head via their BlockSpec index maps, so repeated
        # K/V is never materialised in HBM (XLA fallbacks broadcast
        # internally).
        if cfg.sequence_parallel not in ('ring', 'ulysses'):
            raise ValueError(
                f'Unknown sequence_parallel {cfg.sequence_parallel!r}; '
                "have 'ring', 'ulysses'.")
        seq_parallel = (self.mesh is not None and
                        'sequence' in self.mesh.axis_names and
                        self.mesh.shape['sequence'] > 1)
        if self.sequence_axis is not None:
            # Already inside a manual region sharded over sequence_axis
            # (a nested shard_map would be illegal here): call the
            # chosen strategy's sharded body directly.
            from skypilot_tpu.ops.ring_attention import _ring_attention_sharded  # pylint: disable=import-outside-toplevel
            from skypilot_tpu.ops.ulysses_attention import _ulysses_attention_sharded  # pylint: disable=import-outside-toplevel
            sharded = (_ulysses_attention_sharded
                       if cfg.sequence_parallel == 'ulysses'
                       else _ring_attention_sharded)
            out = sharded(
                q, k, v, axis_name=self.sequence_axis,
                sm_scale=float(hd) ** -0.5, causal=True,
                block_q=128, block_k=128)
        elif seq_parallel:
            attn = (ulysses_attention
                    if cfg.sequence_parallel == 'ulysses'
                    else ring_attention)
            out = attn(q, k, v, mesh=self.mesh, causal=True)
        else:
            out = flash_attention(q, k, v, causal=True, mesh=self.mesh)

        out = out.transpose(0, 2, 1, 3)  # [b, s, h, d]
        return nn.DenseGeneral(
            features=cfg.d_model, axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(),
                ('heads', 'head_dim', 'embed')),
            name='o_proj')(out)


class MLP(nn.Module):
    config: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def dense(name, feats, logical):
            return nn.DenseGeneral(
                features=feats, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), logical),
                name=name)

        act = {'silu': nn.silu, 'gelu': nn.gelu}[cfg.mlp_act]
        gate = dense('gate_proj', cfg.d_ff, ('embed', 'mlp'))(x)
        up = dense('up_proj', cfg.d_ff, ('embed', 'mlp'))(x)
        return dense('down_proj', cfg.d_model, ('mlp', 'embed'))(
            act(gate) * up)


class DecoderLayer(nn.Module):
    config: ModelConfig
    mesh: Optional[Any] = None
    sequence_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        x = x + Attention(cfg, self.mesh, self.sequence_axis,
                          name='attn')(
            RMSNorm(cfg.norm_eps, cfg.norm_scale_plus_one,
                    name='attn_norm')(x), positions)
        if cfg.n_experts > 0:
            from skypilot_tpu.models.moe import MoEMLP  # pylint: disable=import-outside-toplevel
            mlp = MoEMLP(cfg, name='moe_mlp')
        else:
            mlp = MLP(cfg, name='mlp')
        x = x + mlp(RMSNorm(cfg.norm_eps, cfg.norm_scale_plus_one,
                            name='mlp_norm')(x))
        return x


class LMHead(nn.Module):
    """Untied vocab projection as an explicit module so the fused-CE
    path (models/losses.py) can fetch the kernel WITHOUT running the
    [b,s,V] matmul.  Param tree ('lm_head'/'kernel', [d_model, vocab],
    lecun_normal) is identical to the nn.DenseGeneral it replaces —
    same init stream, so checkpoints and import_weights are unaffected.
    """
    config: ModelConfig

    @nn.compact
    def __call__(self, x=None, *, return_kernel: bool = False):
        cfg = self.config
        kernel = self.param(
            'kernel',
            nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                         ('embed', 'vocab')),
            (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
        mm_dtype = jnp.float32 if cfg.logits_in_f32 else cfg.dtype
        if return_kernel:
            return kernel.astype(mm_dtype)
        return jnp.einsum('bsd,dv->bsv', x.astype(mm_dtype),
                          kernel.astype(mm_dtype))


class _ScannedLayer(nn.Module):
    """DecoderLayer with the (carry, out) signature nn.scan expects."""
    config: ModelConfig
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, positions):
        return DecoderLayer(self.config, self.mesh, name='layer')(
            x, positions), None


class Transformer(nn.Module):
    config: ModelConfig
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        """tokens [b,s] -> logits [b,s,V] f32; with return_hidden=True,
        -> (final hidden [b,s,d], lm-head kernel [d,V] pre-cast to the
        cfg.logits_in_f32 matmul dtype) for the fused linear+CE loss
        (models/losses.py) — the [b,s,V] tensor is never built."""
        cfg = self.config
        if (cfg.layer_pattern or cfg.parallel_block or
                cfg.norm_type != 'rms' or cfg.logit_scale != 1.0 or
                cfg.post_norms or cfg.loop_passes != 1):
            raise ValueError(
                'the training module builds one kind of layer (RMSNorm, '
                'sequential attention then FFN), run once; '
                'layer_pattern, parallel_block, norm_type, logit_scale, '
                'post_norms and loop_passes are served by '
                'models/decode.py only')
        _, s = tokens.shape
        positions = jnp.arange(s)

        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('vocab', 'embed')),
            name='embed')
        x = embed(tokens)
        if cfg.scale_embeddings:  # Gemma: embeddings carry sqrt(d).
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'embed'))

        if cfg.scan_layers:
            scan_target = _ScannedLayer
            if cfg.remat:
                scan_target = nn.remat(scan_target, prevent_cse=False,
                                       policy=_remat_policy(cfg))
            x, _ = nn.scan(
                scan_target,
                variable_axes={'params': 0},
                split_rngs={'params': True},
                in_axes=nn.broadcast,
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: 'layers'},
            )(cfg, self.mesh, name='layers')(x, positions)
        else:
            layer_cls = (nn.remat(DecoderLayer, policy=_remat_policy(cfg))
                         if cfg.remat else DecoderLayer)
            for i in range(cfg.n_layers):
                x = layer_cls(cfg, self.mesh, name=f'layer_{i}')(
                    x, positions)

        x = RMSNorm(cfg.norm_eps, cfg.norm_scale_plus_one,
                    name='final_norm')(x)
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'embed'))
        mm_dtype = jnp.float32 if cfg.logits_in_f32 else cfg.dtype
        if cfg.tie_embeddings:
            # lm_head = embed^T (Gemma/GPT-style weight tying).  NOT
            # embed.attend(): that promotes to the module dtype (bf16),
            # silently undoing the logits_in_f32 upcast.
            kernel = embed.embedding.astype(mm_dtype).T  # [d, V]
            if return_hidden:
                return x, kernel
            logits = jnp.einsum('bsd,dv->bsv', x.astype(mm_dtype),
                                kernel)
        else:
            head = LMHead(cfg, name='lm_head')
            if return_hidden:
                return x, head(return_kernel=True)
            logits = head(x)
        # Logits leave in f32 regardless of matmul precision: the CE
        # loss' log_softmax is always computed in f32.
        return logits.astype(jnp.float32)
