"""Import real released checkpoints (HF safetensors) into the framework.

The reference owns no model code, so it serves real Llama/Gemma/Mixtral
through user recipes (/root/reference/llm/llama-3_1-finetuning/readme.md,
/root/reference/llm/mixtral/README.md) — tokenization and weights are
someone else's problem.  This framework OWNS its compute layer, so weight
import is a framework obligation: this module maps HuggingFace-format
safetensors (Llama / Gemma / Qwen2 / Mixtral families) onto the flax
param tree of models/transformer.py and writes an orbax checkpoint that
`data.checkpoints.restore_params` / `restore_or_init` consume directly
(i.e. the serving AND finetune entry points).

TPU-first choices:
- Pure-numpy safetensors parsing over mmap: tensors stream zero-copy
  from disk per layer; bf16 maps through ml_dtypes (no torch on the
  import path, nothing materializes twice).
- RoPE convention conversion happens ONCE at import: HF stores q/k
  projections for the rotate-half layout; our kernels use the
  interleaved (even/odd) layout, which keeps the Pallas rope fusion a
  pure stride trick.  The q/k output rows are permuted here so runtime
  logits match transformers exactly (pinned by tests against HF).
- Layer stacking for nn.scan: per-layer HF tensors land in ONE
  [n_layers, ...] array per parameter (the scan-over-layers layout that
  keeps XLA compile time flat), filled layer-by-layer.

CLI:
    python -m skypilot_tpu.models.import_weights \
        --src /path/to/hf_checkpoint --out /path/to/skytpu_ckpt \
        [--dtype bfloat16]
"""
from __future__ import annotations

import argparse
import json
import mmap
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from skypilot_tpu import sky_logging
from skypilot_tpu.models import configs

logger = sky_logging.init_logger(__name__)

MODEL_CONFIG_FILENAME = 'model_config.json'

# Tokenizer artifacts copied alongside the converted checkpoint so a
# serve/finetune YAML points at ONE directory.
_TOKENIZER_FILES = ('tokenizer.json', 'tokenizer_config.json',
                    'tokenizer.model', 'special_tokens_map.json')


# --------------------------------------------------------------------------
# Safetensors reading (pure numpy + mmap; bf16 via ml_dtypes)
# --------------------------------------------------------------------------

_SAFETENSORS_DTYPES: Dict[str, Any] = {
    'F64': np.float64,
    'F32': np.float32,
    'F16': np.float16,
    'I64': np.int64,
    'I32': np.int32,
    'I16': np.int16,
    'I8': np.int8,
    'U8': np.uint8,
    'BOOL': np.bool_,
}


def _st_dtype(name: str):
    if name == 'BF16':
        import ml_dtypes  # pylint: disable=import-outside-toplevel
        return ml_dtypes.bfloat16
    try:
        return _SAFETENSORS_DTYPES[name]
    except KeyError:
        raise ValueError(f'Unsupported safetensors dtype {name!r}') from None


class SafetensorsFile:
    """One .safetensors file: 8-byte LE header length + JSON header
    {name: {dtype, shape, data_offsets}} + raw little-endian data.
    Tensors are views over an mmap — nothing is copied until a
    transform needs to."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._f = open(path, 'rb')  # pylint: disable=consider-using-with
        header_len = int.from_bytes(self._f.read(8), 'little')
        if header_len > 100 * 1024 * 1024:
            raise ValueError(f'{path}: implausible header ({header_len}B)')
        header = json.loads(self._f.read(header_len))
        header.pop('__metadata__', None)
        self._entries: Dict[str, Tuple[Any, Tuple[int, ...], int, int]] = {}
        data_start = 8 + header_len
        for name, meta in header.items():
            begin, end = meta['data_offsets']
            self._entries[name] = (_st_dtype(meta['dtype']),
                                   tuple(meta['shape']),
                                   data_start + begin, data_start + end)
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)

    def keys(self) -> List[str]:
        return list(self._entries)

    def get(self, name: str) -> np.ndarray:
        dtype, shape, begin, end = self._entries[name]
        # frombuffer over the mmap with an offset is a TRUE zero-copy
        # view (slicing the mmap first would copy the tensor bytes).
        count = (end - begin) // np.dtype(dtype).itemsize
        arr = np.frombuffer(self._mm, dtype=dtype, count=count,
                            offset=begin)
        return arr.reshape(shape)

    def close(self) -> None:
        try:
            self._mm.close()
        except BufferError:
            # A zero-copy view escaped (caller bug): leave the map to
            # the GC rather than crash the conversion at the finish.
            pass
        self._f.close()


class CheckpointReader:
    """Uniform reader over a single model.safetensors or a sharded
    model.safetensors.index.json checkpoint directory."""

    def __init__(self, src_dir: str) -> None:
        self.src_dir = src_dir
        self._files: Dict[str, SafetensorsFile] = {}
        self._where: Dict[str, str] = {}
        index = os.path.join(src_dir, 'model.safetensors.index.json')
        if os.path.exists(index):
            with open(index, encoding='utf-8') as f:
                self._where = json.load(f)['weight_map']
        else:
            single = [f for f in sorted(os.listdir(src_dir))
                      if f.endswith('.safetensors')]
            if not single:
                raise FileNotFoundError(
                    f'No .safetensors files under {src_dir}')
            for fname in single:
                for key in self._file(fname).keys():
                    self._where[key] = fname

    def _file(self, fname: str) -> SafetensorsFile:
        if fname not in self._files:
            self._files[fname] = SafetensorsFile(
                os.path.join(self.src_dir, fname))
        return self._files[fname]

    def keys(self) -> List[str]:
        return list(self._where)

    def __contains__(self, name: str) -> bool:
        return name in self._where

    def get(self, name: str) -> np.ndarray:
        if name not in self._where:
            raise KeyError(
                f'{name} not in checkpoint (have e.g. '
                f'{sorted(self._where)[:5]}...)')
        return self._file(self._where[name]).get(name)

    def close(self) -> None:
        for f in self._files.values():
            f.close()


# --------------------------------------------------------------------------
# HF config.json -> ModelConfig
# --------------------------------------------------------------------------

_FAMILIES = ('llama', 'qwen2', 'gemma', 'mixtral', 'ouro', 'lfm2_moe',
             'nemotron_h')


def config_from_hf(hf: Dict[str, Any]) -> Tuple[configs.ModelConfig, str]:
    """(ModelConfig, family) from an HF config.json dict."""
    family = hf.get('model_type', 'llama')
    if family not in _FAMILIES:
        raise ValueError(
            f'Unsupported model_type {family!r}; have {_FAMILIES}')
    import jax.numpy as jnp  # pylint: disable=import-outside-toplevel
    n_heads = hf['num_attention_heads']
    d_model = hf['hidden_size']
    head_dim = hf.get('head_dim') or d_model // n_heads
    if family == 'lfm2_moe':
        return _lfm2_moe_config(hf), family
    if family == 'nemotron_h':
        return _nemotron_h_config(hf), family
    common = dict(
        vocab_size=hf['vocab_size'],
        d_model=d_model,
        n_layers=hf['num_hidden_layers'],
        n_heads=n_heads,
        n_kv_heads=hf.get('num_key_value_heads', n_heads),
        d_ff=hf['intermediate_size'],
        max_seq_len=hf.get('max_position_embeddings', 8192),
        rope_theta=float(hf.get('rope_theta', 10000.0)),
        norm_eps=float(hf.get('rms_norm_eps', 1e-5)),
        head_dim_override=(head_dim
                           if head_dim != d_model // n_heads else None),
        dtype=jnp.bfloat16,
        param_dtype=jnp.float32,
        tie_embeddings=bool(hf.get('tie_word_embeddings', False)),
    )
    # rope_scaling (Llama-3.1+, long-context Qwen2): silently importing
    # with plain RoPE would contradict the module's exact-fidelity
    # contract — map the supported schemes, reject the rest loudly.
    rs = hf.get('rope_scaling') or None
    if rs:
        rtype = rs.get('rope_type') or rs.get('type')
        if rtype in (None, 'default'):
            pass
        elif rtype == 'llama3':
            common.update(
                rope_scaling_type='llama3',
                rope_scaling_factor=float(rs['factor']),
                rope_low_freq_factor=float(rs.get('low_freq_factor', 1.0)),
                rope_high_freq_factor=float(
                    rs.get('high_freq_factor', 4.0)),
                rope_original_max_len=int(
                    rs.get('original_max_position_embeddings', 8192)),
            )
        elif rtype == 'linear':
            common.update(rope_scaling_type='linear',
                          rope_scaling_factor=float(rs['factor']))
        else:
            raise ValueError(
                f'Unsupported rope_scaling type {rtype!r} (have '
                "'llama3', 'linear'); importing with plain RoPE would "
                'silently diverge from the source model.')
    # A sliding window counts only where it would cut attention inside
    # the usable context (configs often carry an inert window >=
    # max_position_embeddings).  One on EVERY layer is a layer pattern
    # this program serves (models/decode.py: the masked path and the
    # paged kernel take a layer's window; the training module does not
    # build it yet and says so).  One on some layers only, where those
    # are not a repeating period, it does not.
    window = hf.get('sliding_window')
    window_active = (window is not None and
                     int(window) < int(common['max_seq_len']))
    if family == 'qwen2':
        window_active = window_active and bool(
            hf.get('use_sliding_window', False))
        # Qwen2 windows the layers from `max_window_layers` on.
        first_windowed = hf.get('max_window_layers')
        if window_active and first_windowed != 0:
            if (first_windowed is not None and
                    int(first_windowed) >= common['n_layers']):
                window_active = False     # no layer reaches it
            else:
                raise ValueError(
                    f'{family} checkpoint uses sliding-window attention '
                    f'(window={window} < context='
                    f'{common["max_seq_len"]}) on the layers from '
                    f'max_window_layers={first_windowed} on only: not '
                    'a repeating layer pattern, which is what this '
                    'program serves; importing would silently change '
                    'attention semantics.')
    if window_active:
        common.update(layer_pattern=('window',),
                      sliding_window=int(window))
    if family == 'qwen2':
        common['qkv_bias'] = True
    elif family == 'gemma':
        # HF GemmaRMSNorm computes x * (1 + w) — same as our
        # scale_plus_one — and hidden_activation is tanh-approx gelu,
        # matching flax nn.gelu(approximate=True).
        common.update(tie_embeddings=True, mlp_act='gelu',
                      norm_scale_plus_one=True, scale_embeddings=True)
    elif family == 'mixtral':
        common.update(
            n_experts=hf['num_local_experts'],
            expert_top_k=hf['num_experts_per_tok'],
            router_aux_loss_coef=float(
                hf.get('router_aux_loss_coef', 0.02)),
        )
    elif family == 'ouro':
        # A looped stack: the layers run `total_ut_steps` times a token
        # over the same weights, with sandwich norms and an exit gate
        # (models/decode.py serves it; the training module does not
        # build it and says so).
        common.update(
            loop_passes=int(hf['total_ut_steps']),
            exit_threshold=float(hf.get('early_exit_threshold', 1.0)),
            post_norms=True)
    return configs.ModelConfig(**common), family


def _lfm2_moe_config(hf: Dict[str, Any]) -> configs.ModelConfig:
    """LFM2's expert model: gated short-conv layers beside attention
    layers with per-head q/k norms, `num_dense_layers` dense layers,
    then experts chosen by sigmoid score + bias (the program's layers
    stacked by kind, models/decode.py; the training module does not
    build them and says so).  The kinds after the dense layers are the
    shortest run of them that repeats whole (the 40-layer published
    list's 38 are one run)."""
    import jax.numpy as jnp  # pylint: disable=import-outside-toplevel
    for key, want in (('conv_bias', False), ('norm_topk_prob', True),
                      ('routed_scaling_factor', 1)):
        if hf.get(key, want) != want:
            raise ValueError(f'lfm2_moe with {key}={hf[key]!r} is not '
                             f'served (needs {want!r})')
    kinds = ['conv' if t == 'conv' else 'full'
             for t in hf['layer_types'][:hf['num_hidden_layers']]]
    lead = int(hf.get('num_dense_layers', 0))
    rest = kinds[lead:]
    period = next(rest[:n] for n in range(1, len(rest) + 1)
                  if len(rest) % n == 0 and
                  rest == rest[:n] * (len(rest) // n))
    rope = hf.get('rope_parameters') or {}
    return configs.ModelConfig(
        vocab_size=hf['vocab_size'], d_model=hf['hidden_size'],
        n_layers=hf['num_hidden_layers'],
        n_heads=hf['num_attention_heads'],
        n_kv_heads=hf['num_key_value_heads'],
        d_ff=hf['moe_intermediate_size'],
        dense_layers=tuple(kinds[:lead]),
        dense_d_ff=hf['intermediate_size'], layer_pattern=tuple(period),
        conv_kernel=hf['conv_L_cache'], qk_norm=True,
        max_seq_len=hf.get('max_position_embeddings', 8192),
        rope_theta=float(rope.get('rope_theta',
                                  hf.get('rope_theta', 1e6))),
        norm_eps=float(hf.get('norm_eps', 1e-5)),
        tie_embeddings=bool(hf.get('tie_word_embeddings', True)),
        n_experts=hf['num_experts'],
        expert_top_k=hf['num_experts_per_tok'],
        expert_score_fn='sigmoid',
        expert_bias=bool(hf.get('use_expert_bias', False)),
        expert_gate_eps=1e-6, dtype=jnp.bfloat16,
        param_dtype=jnp.float32)


# Nemotron-H's letters of `hybrid_override_pattern` -> layer kinds.
_NEMOTRON_H_KINDS = {'M': 'mamba', 'E': 'experts', '*': 'attn_nope'}


def _nemotron_h_config(hf: Dict[str, Any]) -> configs.ModelConfig:
    """Nemotron-H: Mamba-2 layers, expert layers (non-gated relu^2
    experts chosen by sigmoid score + bias, a shared expert) and
    position-free attention layers, one sub-block a layer, named by
    `hybrid_override_pattern` (the program's layers stacked by kind, in
    runs of periods; the training module does not build them)."""
    import jax.numpy as jnp  # pylint: disable=import-outside-toplevel
    for key, want in (('n_group', 1), ('topk_group', 1),
                      ('norm_topk_prob', True), ('mlp_hidden_act', 'relu2'),
                      ('mamba_hidden_act', 'silu'), ('use_conv_bias', True),
                      ('n_shared_experts', 1)):
        if hf.get(key, want) != want:
            raise ValueError(f'nemotron_h with {key}={hf[key]!r} is not '
                             f'served (needs {want!r})')
    names = [_NEMOTRON_H_KINDS[c] for c in hf['hybrid_override_pattern']]
    return configs.ModelConfig(
        vocab_size=hf['vocab_size'], d_model=hf['hidden_size'],
        n_layers=hf['num_hidden_layers'],
        n_heads=hf['num_attention_heads'],
        n_kv_heads=hf['num_key_value_heads'],
        head_dim_override=hf['head_dim'],
        d_ff=hf['moe_intermediate_size'],
        shared_d_ff=hf['moe_shared_expert_intermediate_size'],
        layer_pattern=tuple(names), conv_kernel=hf['conv_kernel'],
        ssm_heads=hf['mamba_num_heads'], ssm_head_dim=hf['mamba_head_dim'],
        ssm_groups=hf['n_groups'], ssm_state=hf['ssm_state_size'],
        ssm_chunk=hf['chunk_size'],
        max_seq_len=hf.get('max_position_embeddings', 8192),
        norm_eps=float(hf.get('norm_eps', 1e-5)), mlp_act='relu2',
        tie_embeddings=bool(hf.get('tie_word_embeddings', False)),
        n_experts=hf['n_routed_experts'],
        expert_top_k=hf['num_experts_per_tok'],
        expert_score_fn='sigmoid', expert_bias=True,
        expert_gate_eps=1e-20,
        expert_scale=float(hf.get('routed_scaling_factor', 1.0)),
        n_shared_experts=hf['n_shared_experts'],
        dtype=jnp.bfloat16, param_dtype=jnp.float32)


# --------------------------------------------------------------------------
# Name mapping + tensor transforms
# --------------------------------------------------------------------------


def _unpermute_rope(w: np.ndarray, heads: int, head_dim: int) -> np.ndarray:
    """HF rotate-half q/k rows -> interleaved even/odd rows.

    HF pairs output row j with j + head_dim/2 (rotate_half); our _rope
    pairs 2j with 2j+1.  Both use freq_j = theta^(-2j/head_dim), so the
    conversion is a pure per-head row permutation of the projection:
        ours[2j] = hf[j];  ours[2j+1] = hf[j + head_dim/2].
    `w` arrives as [..., heads*head_dim] (last axis = output rows).
    """
    shape = w.shape
    w = w.reshape(shape[:-1] + (heads, head_dim))
    out = np.empty_like(w)
    half = head_dim // 2
    out[..., 0::2] = w[..., :half]
    out[..., 1::2] = w[..., half:]
    return out.reshape(shape)


def _t(w: np.ndarray) -> np.ndarray:
    """torch Linear stores [out, in]; flax Dense wants [in, out]."""
    return np.ascontiguousarray(w.T)


def _plan_for(cfg: configs.ModelConfig, family: str):
    """Mapping plan: our param path -> (HF name template, transform).

    Paths are tuples under the UNSTACKED per-layer tree; '{i}' in the
    HF name is the layer index.  Transforms receive the raw HF tensor
    and return the per-layer flax array.  A model stacked by kind maps
    full paths of its tree (`_plan_by_kind`).
    """
    if cfg.stacked_by_kind:
        if family == 'nemotron_h':
            return _plan_nemotron_h(cfg)
        return _plan_by_kind(cfg)
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    d, dff = cfg.d_model, cfg.d_ff

    def qk_kernel(heads: int) -> Callable[[np.ndarray], np.ndarray]:
        def f(w):  # [heads*hd, d] -> [d, heads, hd], rope-converted
            return _unpermute_rope(_t(w), heads, hd).reshape(d, heads, hd)
        return f

    def qk_bias(heads: int) -> Callable[[np.ndarray], np.ndarray]:
        def f(b):  # [heads*hd] -> [heads, hd], rope-converted
            return _unpermute_rope(b, heads, hd).reshape(heads, hd)
        return f

    plan: Dict[Tuple[str, ...], Tuple[str, Callable]] = {
        ('embed', 'embedding'):
            ('model.embed_tokens.weight', lambda w: w),
        ('final_norm', 'scale'): ('model.norm.weight', lambda w: w),
        ('attn', 'q_proj', 'kernel'):
            ('model.layers.{i}.self_attn.q_proj.weight', qk_kernel(nh)),
        ('attn', 'k_proj', 'kernel'):
            ('model.layers.{i}.self_attn.k_proj.weight', qk_kernel(nkv)),
        ('attn', 'v_proj', 'kernel'):
            ('model.layers.{i}.self_attn.v_proj.weight',
             lambda w: _t(w).reshape(d, nkv, hd)),
        ('attn', 'o_proj', 'kernel'):
            ('model.layers.{i}.self_attn.o_proj.weight',
             lambda w: _t(w).reshape(nh, hd, d)),
        ('attn_norm', 'scale'):
            ('model.layers.{i}.input_layernorm.weight', lambda w: w),
        ('mlp_norm', 'scale'):
            ('model.layers.{i}.post_attention_layernorm.weight',
             lambda w: w),
    }
    if not cfg.tie_embeddings:
        plan[('lm_head', 'kernel')] = ('lm_head.weight', _t)
    if cfg.post_norms:
        # The sandwich norms on each sub-layer's output.
        plan[('attn_post_norm', 'scale')] = (
            'model.layers.{i}.input_layernorm_2.weight', lambda w: w)
        plan[('mlp_post_norm', 'scale')] = (
            'model.layers.{i}.post_attention_layernorm_2.weight',
            lambda w: w)
    if cfg.loop_passes > 1:
        # torch Linear(d_model, 1): weight [1, d] -> kernel [d, 1].
        plan[('exit_gate', 'kernel')] = ('model.early_exit_gate.weight', _t)
        plan[('exit_gate', 'bias')] = ('model.early_exit_gate.bias',
                                       lambda b: b)
    if cfg.qkv_bias:
        plan[('attn', 'q_proj', 'bias')] = (
            'model.layers.{i}.self_attn.q_proj.bias', qk_bias(nh))
        plan[('attn', 'k_proj', 'bias')] = (
            'model.layers.{i}.self_attn.k_proj.bias', qk_bias(nkv))
        plan[('attn', 'v_proj', 'bias')] = (
            'model.layers.{i}.self_attn.v_proj.bias',
            lambda b: b.reshape(nkv, hd))
    if cfg.n_experts > 0:
        # Mixtral experts: w1 = gate, w3 = up, w2 = down; ours are
        # stacked [n_experts, in, out].
        plan[('moe_mlp', 'router', 'kernel')] = (
            'model.layers.{i}.block_sparse_moe.gate.weight', _t)
        for ours, theirs, in_dim in (('gate_proj', 'w1', d),
                                     ('up_proj', 'w3', d),
                                     ('down_proj', 'w2', dff)):
            del in_dim
            plan[('moe_mlp', ours)] = (
                'model.layers.{i}.block_sparse_moe.experts.{e}.'
                f'{theirs}.weight', _t)
    else:
        for ours, theirs in (('gate_proj', 'gate_proj'),
                             ('up_proj', 'up_proj'),
                             ('down_proj', 'down_proj')):
            plan[('mlp', ours, 'kernel')] = (
                f'model.layers.{{i}}.mlp.{theirs}.weight', _t)
    del family
    return plan


def _plan_by_kind(cfg: configs.ModelConfig):
    """The plan of a model stacked by kind (LFM2's names: the conv,
    attention, norm and dense-FFN names of the local dense
    `modeling_lfm2.py`; the expert block's `feed_forward.gate`,
    `feed_forward.expert_bias` and `feed_forward.experts.{e}.w1/w2/w3`
    those of its expert model): full path in the tree -> (HF name
    template, transform, the checkpoint's layers stacked there, in
    order).  q, k and their per-head norm scales are rotate-half
    converted as every model's q and k are."""
    hd, nh, nkv, d = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    names = cfg.layer_names()
    lead = len(cfg.dense_layers)
    layer = 'model.layers.{i}.'

    def qk_kernel(heads):
        return lambda w: _unpermute_rope(_t(w), heads, hd).reshape(
            d, heads, hd)

    plan: Dict[Tuple[str, ...], Tuple[Any, ...]] = {
        ('embed', 'embedding'): ('model.embed_tokens.weight', lambda w: w),
        ('final_norm', 'scale'): ('model.embedding_norm.weight',
                                  lambda w: w)}
    if not cfg.tie_embeddings:
        plan[('lm_head', 'kernel')] = ('lm_head.weight', _t)
    for group, layers in (('lead', range(lead)),
                          ('layers', range(lead, cfg.n_layers))):
        conv = [i for i in layers if names[i] == 'conv']
        attn = [i for i in layers if names[i] != 'conv']
        layers = list(layers)
        if not layers:
            continue
        at = lambda *path, group=group: (group,) + path
        if conv:
            for path, (name, fn) in {
                    ('conv_norm', 'scale'): ('operator_norm.weight',
                                             lambda w: w),
                    ('conv', 'in_proj', 'kernel'): ('conv.in_proj.weight',
                                                    _t),
                    # torch Conv1d(d, d, K, groups=d): [d, 1, K] -> [K, d].
                    ('conv', 'kernel'): ('conv.conv.weight',
                                         lambda w: _t(w[:, 0, :])),
                    ('conv', 'out_proj', 'kernel'): ('conv.out_proj.weight',
                                                     _t)}.items():
                plan[at('conv', *path)] = (layer + name, fn, conv)
        if attn:
            for path, (name, fn) in {
                    ('attn_norm', 'scale'): ('operator_norm.weight',
                                             lambda w: w),
                    ('attn', 'q_proj', 'kernel'): ('self_attn.q_proj.weight',
                                                   qk_kernel(nh)),
                    ('attn', 'k_proj', 'kernel'): ('self_attn.k_proj.weight',
                                                   qk_kernel(nkv)),
                    ('attn', 'v_proj', 'kernel'): (
                        'self_attn.v_proj.weight',
                        lambda w: _t(w).reshape(d, nkv, hd)),
                    ('attn', 'o_proj', 'kernel'): (
                        'self_attn.out_proj.weight',
                        lambda w: _t(w).reshape(nh, hd, d)),
                    ('attn', 'q_norm', 'scale'): (
                        'self_attn.q_layernorm.weight',
                        lambda w: _unpermute_rope(w, 1, hd)),
                    ('attn', 'k_norm', 'scale'): (
                        'self_attn.k_layernorm.weight',
                        lambda w: _unpermute_rope(w, 1, hd))}.items():
                plan[at('attn', *path)] = (layer + name, fn, attn)
        plan[at('ffn', 'mlp_norm', 'scale')] = (
            layer + 'ffn_norm.weight', lambda w: w, layers)
        if group == 'layers' and cfg.n_experts:
            moe = layer + 'feed_forward.'
            plan[at('ffn', 'moe_mlp', 'router', 'kernel')] = (
                moe + 'gate.weight', _t, layers)
            if cfg.expert_bias:
                plan[at('ffn', 'moe_mlp', 'expert_bias')] = (
                    moe + 'expert_bias', lambda b: b, layers)
            for ours, theirs in (('gate_proj', 'w1'), ('up_proj', 'w3'),
                                 ('down_proj', 'w2')):
                plan[at('ffn', 'moe_mlp', ours)] = (
                    moe + 'experts.{e}.' + theirs + '.weight', _t, layers)
        else:
            for ours, theirs in (('gate_proj', 'w1'), ('up_proj', 'w3'),
                                 ('down_proj', 'w2')):
                plan[at('ffn', 'mlp', ours, 'kernel')] = (
                    layer + 'feed_forward.' + theirs + '.weight', _t,
                    layers)
    return plan


def _plan_nemotron_h(cfg: configs.ModelConfig):
    """The plan of Nemotron-H (the `nemotron_h` modeling code's names:
    `backbone.layers.{i}.norm` and `.mixer.*` for every layer's one
    sub-block, `backbone.embeddings`, `backbone.norm_f`, `lm_head`):
    full path in the tree -> (HF name template, transform, the
    checkpoint's layers stacked there, in order).  No rotation, so q
    and k are taken as they are."""
    hd, nh, nkv, d = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    names = cfg.layer_names()
    mixer = 'backbone.layers.{i}.mixer.'
    norm = 'backbone.layers.{i}.norm.weight'
    of = {kind: [i for i, n in enumerate(names) if n == kind]
          for kind in ('mamba', 'attn_nope', 'experts')}
    same = lambda w: w
    plan: Dict[Tuple[str, ...], Tuple[Any, ...]] = {
        ('embed', 'embedding'): ('backbone.embeddings.weight', same),
        ('final_norm', 'scale'): ('backbone.norm_f.weight', same),
        ('lm_head', 'kernel'): ('lm_head.weight', _t)}
    m, a, e = ('layers', 'mamba'), ('layers', 'attn'), ('layers', 'ffn')
    for path, (name, fn, layers) in {
            m + ('mamba_norm', 'scale'): (norm, same, of['mamba']),
            m + ('mamba', 'in_proj', 'kernel'): (mixer + 'in_proj.weight',
                                                 _t, of['mamba']),
            # torch Conv1d(C, C, K, groups=C): [C, 1, K] -> [K, C].
            m + ('mamba', 'conv_kernel'): (mixer + 'conv1d.weight',
                                           lambda w: _t(w[:, 0, :]),
                                           of['mamba']),
            m + ('mamba', 'conv_bias'): (mixer + 'conv1d.bias', same,
                                         of['mamba']),
            m + ('mamba', 'A_log'): (mixer + 'A_log', same, of['mamba']),
            m + ('mamba', 'dt_bias'): (mixer + 'dt_bias', same,
                                       of['mamba']),
            m + ('mamba', 'D'): (mixer + 'D', same, of['mamba']),
            m + ('mamba', 'norm', 'scale'): (mixer + 'norm.weight', same,
                                             of['mamba']),
            m + ('mamba', 'out_proj', 'kernel'): (mixer + 'out_proj.weight',
                                                  _t, of['mamba']),
            a + ('attn_norm', 'scale'): (norm, same, of['attn_nope']),
            a + ('attn', 'q_proj', 'kernel'): (
                mixer + 'q_proj.weight',
                lambda w: _t(w).reshape(d, nh, hd), of['attn_nope']),
            a + ('attn', 'k_proj', 'kernel'): (
                mixer + 'k_proj.weight',
                lambda w: _t(w).reshape(d, nkv, hd), of['attn_nope']),
            a + ('attn', 'v_proj', 'kernel'): (
                mixer + 'v_proj.weight',
                lambda w: _t(w).reshape(d, nkv, hd), of['attn_nope']),
            a + ('attn', 'o_proj', 'kernel'): (
                mixer + 'o_proj.weight',
                lambda w: _t(w).reshape(nh, hd, d), of['attn_nope']),
            e + ('mlp_norm', 'scale'): (norm, same, of['experts']),
            e + ('moe_mlp', 'router', 'kernel'): (mixer + 'gate.weight', _t,
                                                  of['experts']),
            e + ('moe_mlp', 'expert_bias'): (
                mixer + 'gate.e_score_correction_bias', same,
                of['experts']),
            e + ('moe_mlp', 'up_proj'): (
                mixer + 'experts.{e}.up_proj.weight', _t, of['experts']),
            e + ('moe_mlp', 'down_proj'): (
                mixer + 'experts.{e}.down_proj.weight', _t, of['experts']),
            # The one shared expert: a stack of one.
            e + ('moe_mlp', 'shared_up_proj'): (
                mixer + 'shared_experts.up_proj.weight',
                lambda w: _t(w)[None], of['experts']),
            e + ('moe_mlp', 'shared_down_proj'): (
                mixer + 'shared_experts.down_proj.weight',
                lambda w: _t(w)[None], of['experts'])}.items():
        plan[path] = (name, fn, layers)
    return plan


def expected_tree(cfg: configs.ModelConfig) -> Dict[str, Any]:
    """Shape/dtype skeleton of the model's param tree (eval_shape —
    nothing is materialized).  A model stacked by kind is served by
    models/decode.py alone, which says its tree."""
    if cfg.stacked_by_kind:
        from skypilot_tpu.models import decode  # pylint: disable=import-outside-toplevel
        return decode.stacked_param_shapes(cfg)
    import jax  # pylint: disable=import-outside-toplevel
    import jax.numpy as jnp  # pylint: disable=import-outside-toplevel
    import flax.linen as nn  # pylint: disable=import-outside-toplevel
    from skypilot_tpu.models.transformer import Transformer  # pylint: disable=import-outside-toplevel
    model = Transformer(cfg)
    tree = jax.eval_shape(
        lambda rng: model.init(rng, jnp.zeros((1, 8), jnp.int32))['params'],
        jax.random.PRNGKey(0))
    return nn.meta.unbox(tree)


_SCRATCH_MIN_BYTES = 64 * 1024 * 1024  # route tensors >= this to disk


def load_params(src_dir: str,
                cfg: Optional[configs.ModelConfig] = None,
                dtype: Optional[Any] = None,
                scratch_dir: Optional[str] = None,
                ) -> Tuple[Dict[str, Any], configs.ModelConfig]:
    """Read an HF checkpoint dir into our flax param tree (numpy).

    Returns (params, cfg).  Per-layer tensors are stacked into the
    nn.scan [n_layers, ...] layout; every array is shape-checked
    against eval_shape of the target model before returning.
    `dtype` overrides the stored parameter dtype (e.g. np 'bfloat16'
    for serving); default keeps cfg.param_dtype (f32).

    `scratch_dir` caps host RAM: large arrays are backed by disk
    memmaps under it instead of heap allocations, so peak RESIDENT
    memory is ~one layer's tensors (the page cache holds the rest and
    is evictable) — an 8B f32 import needs ~32 GB of scratch DISK but
    no longer ~32 GB of RAM.  The caller owns the directory's
    lifetime; the returned arrays are views into it.
    """
    with open(os.path.join(src_dir, 'config.json'),
              encoding='utf-8') as f:
        hf_cfg = json.load(f)
    derived, family = config_from_hf(hf_cfg)
    cfg = cfg or derived
    reader = CheckpointReader(src_dir)
    plan = _plan_for(cfg, family)
    expect = expected_tree(cfg)
    dtype = _resolve_np_dtype(cfg.param_dtype if dtype is None else dtype)

    def expect_at(path: Tuple[str, ...]):
        node: Any = expect
        for key in path:
            node = node[key]
        return node

    params: Dict[str, Any] = {}

    def set_at(path: Tuple[str, ...], value: np.ndarray) -> None:
        node = params
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = value

    def alloc(shape, path: Tuple[str, ...]) -> np.ndarray:
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if scratch_dir is None or nbytes < _SCRATCH_MIN_BYTES:
            return np.empty(shape, dtype)
        return np.memmap(
            os.path.join(scratch_dir, '.'.join(path) + '.bin'),
            dtype=dtype, mode='w+', shape=tuple(shape))

    try:
        for path, (template, transform, *stack) in sorted(plan.items()):
            per_layer = '{i}' in template
            # A plan by kind names full paths and the layers it stacks.
            layers = stack[0] if stack else range(cfg.n_layers)
            tgt_path = (('layers', 'layer') + path
                        if per_layer and not stack else path)
            want = expect_at(tgt_path)
            if not per_layer:
                name = template
                if (cfg.tie_embeddings is False and
                        template == 'lm_head.weight' and
                        template not in reader):
                    # Some checkpoints tie in storage even when config
                    # says untied: fall back to embeddings transposed.
                    arr = np.ascontiguousarray(
                        reader.get('model.embed_tokens.weight').T)
                else:
                    arr = transform(reader.get(name))
                if tuple(arr.shape) != tuple(want.shape):
                    raise ValueError(
                        f'{name}: shape {tuple(arr.shape)} != '
                        f'expected {tuple(want.shape)}')
                # Copy straight into the destination (heap or scratch
                # memmap): one copy total, and pass-through tensors
                # stop being views into the source mmap, which must
                # not outlive the reader.
                out = alloc(want.shape, tgt_path)
                np.copyto(out, arr, casting='unsafe')
                del arr
                set_at(tgt_path, out)
                continue
            # Stacked layout: allocate [n_layers, ...] once, fill
            # layer-by-layer straight from the mmap (peak extra heap
            # = one layer's tensor; scratch-backed when configured).
            stacked = alloc(want.shape, tgt_path)
            for j, i in enumerate(layers):
                if '{e}' in template:
                    lo, held = cfg.held_experts
                    layer = np.stack([
                        transform(reader.get(
                            template.format(i=i, e=e)))
                        for e in range(lo, lo + held)
                    ])
                else:
                    layer = transform(reader.get(template.format(i=i)))
                if tuple(layer.shape) != tuple(want.shape[1:]):
                    raise ValueError(
                        f'{template.format(i=i)}: shape {layer.shape} '
                        f'!= expected {tuple(want.shape[1:])}')
                stacked[j] = layer.astype(dtype)
            set_at(tgt_path, stacked)
    finally:
        reader.close()

    _assert_complete(params, expect)
    return params, cfg


def _resolve_np_dtype(dtype: Any):
    if isinstance(dtype, str) and dtype == 'bfloat16':
        import ml_dtypes  # pylint: disable=import-outside-toplevel
        return ml_dtypes.bfloat16
    try:
        if np.dtype(dtype).name == 'bfloat16':
            import ml_dtypes  # pylint: disable=import-outside-toplevel
            return ml_dtypes.bfloat16
    except TypeError:
        pass
    return np.dtype(dtype)


def _assert_complete(params: Dict[str, Any], expect: Any,
                     path: str = '') -> None:
    if isinstance(expect, dict):
        missing = set(expect) - set(params if isinstance(params, dict)
                                    else {})
        if missing:
            raise ValueError(
                f'Converted tree is missing {sorted(missing)} at '
                f'{path or "<root>"}')
        for key, sub in expect.items():
            _assert_complete(params[key], sub, f'{path}/{key}')


# --------------------------------------------------------------------------
# Conversion entry point: HF dir -> orbax checkpoint dir
# --------------------------------------------------------------------------


def save_converted(out_dir: str, params: Dict[str, Any],
                   cfg: configs.ModelConfig) -> None:
    """Write the converted-checkpoint layout every consumer reads
    (`--model auto` servers, `--init-from` trainers):

      <out>/0/...              orbax step-0 checkpoint of
                               {'params': tree} (what
                               checkpoints.restore_params reads and
                               what finetune resume starts from)
      <out>/model_config.json  ModelConfig for the tree's shapes

    `params` is the unboxed param tree (numpy or jax arrays); its
    stored dtype is what a server restores — `cfg.param_dtype` only
    says what a trainer casts to.
    """
    import orbax.checkpoint as ocp  # pylint: disable=import-outside-toplevel
    os.makedirs(out_dir, exist_ok=True)
    mgr = ocp.CheckpointManager(
        os.path.abspath(out_dir),
        options=ocp.CheckpointManagerOptions(max_to_keep=1, create=True))
    mgr.save(0, args=ocp.args.PyTreeSave({'params': params}))
    mgr.wait_until_finished()
    mgr.close()
    with open(os.path.join(out_dir, MODEL_CONFIG_FILENAME), 'w',
              encoding='utf-8') as f:
        json.dump(cfg.to_json_dict(), f, indent=1)


def convert(src_dir: str, out_dir: str,
            dtype: Optional[str] = None) -> configs.ModelConfig:
    """Convert an HF safetensors checkpoint to our orbax layout:
    `save_converted`'s files plus <out>/tokenizer.* copied from src
    when present."""
    import shutil  # pylint: disable=import-outside-toplevel
    import tempfile  # pylint: disable=import-outside-toplevel

    os.makedirs(out_dir, exist_ok=True)
    # Disk-backed staging caps resident memory at ~one layer (an 8B f32
    # import used ~32 GB of heap); orbax then streams from the memmaps
    # and the scratch dir is removed.
    # Sweep scratch left by a killed prior run first — without this a
    # crashed convert leaks tens of GB inside the checkpoint dir that
    # every later rsync/upload of it would drag along.
    import glob as glob_lib  # pylint: disable=import-outside-toplevel
    for stale in glob_lib.glob(
            os.path.join(out_dir, '.convert_scratch_*')):
        shutil.rmtree(stale, ignore_errors=True)
    scratch = tempfile.mkdtemp(prefix='.convert_scratch_', dir=out_dir)
    try:
        params, cfg = load_params(src_dir, dtype=dtype,
                                  scratch_dir=scratch)
        save_converted(out_dir, params, cfg)
        n_params = sum(
            int(np.prod(a.shape)) for a in _iter_leaves(params))
        del params
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    copied = []
    for fname in _TOKENIZER_FILES:
        src = os.path.join(src_dir, fname)
        if os.path.exists(src):
            shutil.copy2(src, os.path.join(out_dir, fname))
            copied.append(fname)
    logger.info(f'Converted {n_params / 1e6:.1f}M params from {src_dir} '
                f'-> {out_dir} (tokenizer files: {copied or "none"})')
    return cfg


def load_model_config(directory: str) -> Optional[configs.ModelConfig]:
    """The ModelConfig written next to a converted checkpoint, if any."""
    path = os.path.join(directory, MODEL_CONFIG_FILENAME)
    if not os.path.exists(path):
        return None
    with open(path, encoding='utf-8') as f:
        return configs.config_from_json_dict(json.load(f))


def _iter_leaves(tree: Any):
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _iter_leaves(v)
    else:
        yield tree


def main() -> None:
    parser = argparse.ArgumentParser(
        description='Convert an HF safetensors checkpoint '
                    '(Llama/Gemma/Qwen2/Mixtral) to the skypilot_tpu '
                    'orbax layout.')
    parser.add_argument('--src', required=True,
                        help='HF checkpoint dir (config.json + '
                             '*.safetensors [+ index]).')
    parser.add_argument('--out', required=True,
                        help='Output checkpoint dir.')
    parser.add_argument('--dtype', default=None,
                        help="Parameter dtype override, e.g. 'bfloat16' "
                             '(serving) — default keeps f32.')
    args = parser.parse_args()
    cfg = convert(args.src, args.out, dtype=args.dtype)
    print(json.dumps({'out': args.out, 'd_model': cfg.d_model,
                      'n_layers': cfg.n_layers,
                      'vocab_size': cfg.vocab_size}))


if __name__ == '__main__':
    main()
