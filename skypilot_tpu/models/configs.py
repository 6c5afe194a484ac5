"""Model configurations (flagship: Llama-3-8B, per BASELINE.json)."""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp

# Layer kinds a `layer_pattern` may name, with what each kind sets: does
# the layer rotate q and k (rotary embedding), does a query see only
# the last `sliding_window` keys, which mixer it has ('attn', 'conv': a
# gated short convolution, 'mamba': a Mamba-2 state-space mixer, or
# None) and whether an FFN follows the mixer.  A new kind is a row
# here, read by `ModelConfig.layer_kinds`; the layer body takes the
# settings as per-layer data and never switches on a kind's name.  A
# 'conv' layer (LFM2's) keeps no keys and no pages: a sequence carries
# the last `conv_kernel - 1` rows of the layer's gated input from step
# to step.  A 'mamba' layer (Nemotron-H's) is its mixer alone and
# carries a matrix of state a head and the last `conv_kernel - 1` rows
# of its convolved input; an 'experts' layer is an FFN alone and an
# 'attn_nope' layer position-free attention alone.
LAYER_KINDS = {
    'full': {'rope': True, 'window': False, 'mixer': 'attn', 'ffn': True},
    'window': {'rope': True, 'window': True, 'mixer': 'attn', 'ffn': True},
    'full_nope': {'rope': False, 'window': False, 'mixer': 'attn',
                  'ffn': True},
    'conv': {'rope': False, 'window': False, 'mixer': 'conv', 'ffn': True},
    'mamba': {'rope': False, 'window': False, 'mixer': 'mamba',
              'ffn': False},
    'experts': {'rope': False, 'window': False, 'mixer': None, 'ffn': True},
    'attn_nope': {'rope': False, 'window': False, 'mixer': 'attn',
                  'ffn': False},
}


def count_mixer(kinds, mixer) -> int:
    """How many of the layer kinds `kinds` have the mixer `mixer`."""
    return sum(LAYER_KINDS[k]['mixer'] == mixer for k in kinds)


def runs_of(names) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    """Layer kind names as runs of repeated periods: cut after each
    layer with attention and no FFN, equal neighbours merged
    (Nemotron-H's `MEMEM*`, `EMEMEM*` four times, ...)."""
    pieces, piece = [], []
    for name in names:
        piece.append(name)
        if LAYER_KINDS[name]['mixer'] == 'attn' and not LAYER_KINDS[name][
                'ffn']:
            pieces.append(tuple(piece))
            piece = []
    if piece:
        pieces.append(tuple(piece))
    runs: List[Tuple[Tuple[str, ...], int]] = []
    for piece in pieces:
        if runs and runs[-1][0] == piece:
            runs[-1] = (piece, runs[-1][1] + 1)
        else:
            runs.append((piece, 1))
    return tuple(runs)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    # RoPE frequency scaling for long-context checkpoints.  None = plain
    # RoPE; 'linear' divides every frequency by rope_scaling_factor
    # (position interpolation); 'llama3' is the Llama-3.1 scheme —
    # low-frequency (long-wavelength) bands divide by the factor,
    # high-frequency bands pass through, with a smooth ramp between the
    # low/high cutoffs derived from the original pretrain context.
    rope_scaling_type: Optional[str] = None
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_len: int = 8192
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16   # activations/compute
    param_dtype: jnp.dtype = jnp.float32
    remat: bool = True                # jax.checkpoint each layer
    # What the layer checkpoint saves: 'full' recomputes everything in
    # the backward (min HBM, ~4/3 flops); 'dots' saves non-batch matmul
    # outputs (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    # — most of the recompute gone for a modest activation footprint).
    # Ignored when remat=False (everything saved; fastest if it fits).
    remat_policy: str = 'full'
    scan_layers: bool = True          # lax.scan over layers (fast compile)
    # lm_head matmul precision.  False runs the vocab projection on the
    # MXU in the activation dtype (bf16) and upcasts the logits to f32
    # immediately after — softmax/CE numerics stay f32 either way.  True
    # forces the matmul itself into f32 (slower; the MXU is bf16-native).
    logits_in_f32: bool = True
    # Long-context sequence parallelism over the 'sequence' mesh axis:
    # 'ring' (k/v rotate the ICI ring; any head count) or 'ulysses'
    # (two all-to-alls re-shard seq<->heads, one plain flash per
    # device; needs heads % sequence_axis == 0).  See ops/.
    sequence_parallel: str = 'ring'
    # Mixture-of-Experts (0 experts = dense MLP); d_ff is one expert's
    # width.  n_experts is the ROUTER's width (the published count);
    # `experts_held` = (lo, n) says which of them this program holds
    # (None = all): the layer routes over all n_experts and computes its
    # own experts' part of the result (models/moe.py).
    n_experts: int = 0
    expert_top_k: int = 2
    experts_held: Optional[Tuple[int, int]] = None
    expert_score_fn: str = 'softmax'  # 'softmax' | 'sigmoid'
    # Shared experts every token passes through, added to the routed
    # sum as their 'sum' or their 'average'.
    n_shared_experts: int = 0
    shared_expert_combine: str = 'sum'
    router_aux_loss_coef: float = 0.02
    # One period of layer kinds (names of LAYER_KINDS), repeated over
    # n_layers; () = every layer 'full'.  'window' layers attend the
    # last `sliding_window` keys.
    layer_pattern: Tuple[str, ...] = ()
    sliding_window: int = 0
    # 'rms' (RMSNorm) | 'layernorm' (mean-subtracting, scale, no bias).
    norm_type: str = 'rms'
    # Attention and the FFN read ONE normed input and are added to the
    # residual together (no mlp_norm).
    parallel_block: bool = False
    logit_scale: float = 1.0          # logits x this, after the head
    # Family switches beyond Llama (Gemma/Qwen-style decoders):
    tie_embeddings: bool = False      # lm_head = embed^T (Gemma)
    qkv_bias: bool = False            # bias on q/k/v projections (Qwen2)
    mlp_act: str = 'silu'             # 'silu' (Llama) | 'gelu' (Gemma)
    norm_scale_plus_one: bool = False  # RMSNorm x (1 + w), w init 0 (Gemma)
    scale_embeddings: bool = False    # embed x sqrt(d_model) (Gemma)
    # Per-head width when decoupled from d_model // n_heads (Gemma-7B:
    # d_model 3072, 16 heads x head_dim 256).  None = derived.
    head_dim_override: Optional[int] = None
    # A looped stack: the n_layers layers run `loop_passes` times a
    # token over the same weights, the final norm applied at the end of
    # every pass (its output is the next pass's input).  Pass t, layer
    # l keeps its keys and values in cache layer t * n_layers + l
    # (`cache_layers`).  A gate (params['exit_gate']) reads each pass's
    # normed output; the head reads the first pass at which the gate's
    # cumulative exit mass reaches `exit_threshold`, else the last
    # (models/decode.py).  1 pass: no gate, no selection.
    loop_passes: int = 1
    exit_threshold: float = 1.0
    # Sandwich norms: each sub-layer's OUTPUT is normed before it joins
    # the residual (`attn_post_norm`, `mlp_post_norm` beside the two
    # input norms).
    post_norms: bool = False
    # 'conv' layers: the width of the depthwise kernel over positions
    # (a sequence carries `conv_kernel - 1` rows a conv layer).
    conv_kernel: int = 0
    # Leading dense layers: one kind name a layer, run before the
    # periods of `layer_pattern` (which repeat over the n_layers after
    # them), each with a dense FFN of width `dense_d_ff` where the
    # layers after them have experts.
    dense_layers: Tuple[str, ...] = ()
    dense_d_ff: int = 0
    # RMSNorm of each head's q and k (a scale of head_dim each), before
    # the rotation.
    qk_norm: bool = False
    # Experts chosen by score + a learned bias (`expert_bias` beside
    # the router's kernel): the bias picks the top-k and never weights
    # them; the gates are the chosen scores over their sum + this eps.
    expert_bias: bool = False
    expert_gate_eps: float = 0.0
    # The routed gates times this (`routed_scaling_factor`).
    expert_scale: float = 1.0
    # A shared expert's width where it is not `d_ff`.
    shared_d_ff: int = 0
    # 'mamba' layers (Mamba-2): `ssm_heads` heads of `ssm_head_dim`
    # channels, B and C in `ssm_groups` groups of `ssm_state` (a head
    # reads group head // (ssm_heads / ssm_groups)); a causal depthwise
    # convolution of `conv_kernel` over x, B and C; prefill scans in
    # chunks of `ssm_chunk` positions.  The gated output norm is an
    # RMSNorm over each of `ssm_groups` groups of channels.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_chunk: int = 128

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.d_model // self.n_heads

    def __post_init__(self):
        if self.loop_passes < 1:
            raise ValueError(
                f'loop_passes must be >= 1, got {self.loop_passes}')
        if self.stacked_by_kind:
            names = self.layer_names()   # whole periods of known kinds
            if (any(LAYER_KINDS[n]['window'] for n in names) and
                    self.sliding_window <= 0):
                raise ValueError('window layers need sliding_window > 0')
            if self.conv_layers and self.conv_kernel < 2:
                raise ValueError('conv layers need conv_kernel >= 2, '
                                 f'got {self.conv_kernel}')
            if self.mamba_layers and (
                    self.conv_kernel < 2 or self.ssm_state <= 0 or
                    self.ssm_head_dim <= 0 or self.ssm_groups <= 0 or
                    self.ssm_heads % self.ssm_groups):
                raise ValueError(
                    'mamba layers need conv_kernel >= 2, ssm_state and '
                    'ssm_head_dim > 0, and ssm_heads a multiple of '
                    'ssm_groups')
            if self.conv_layers and self.mamba_layers:
                raise ValueError('a model carries one kind of recurrent '
                                 'state: conv or mamba layers, not both')
            if self.dense_layers and self.dense_d_ff <= 0:
                raise ValueError('dense_layers need dense_d_ff > 0')
            if (self.loop_passes != 1 or self.parallel_block or
                    self.post_norms or not self.scan_layers):
                raise ValueError(
                    'layers stacked by kind (conv or mamba layers, '
                    'leading dense layers or runs of periods) are served '
                    'once a token, in sequence (no loop_passes, '
                    'parallel_block or post_norms), from the scanned '
                    'layout')

    @property
    def stacked_by_kind(self) -> bool:
        """Whether the weights are stacked by layer kind and the layers
        run as periods (models/decode.py): a model with leading dense
        layers, runs of periods, or a kind whose mixer is not attention
        or that has no FFN.  Every other model is one stack of alike
        layers, and takes the code it took before kinds were stacked."""
        return (bool(self.dense_layers) or any(
            LAYER_KINDS.get(k, LAYER_KINDS['full'])['mixer'] != 'attn' or
            not LAYER_KINDS.get(k, LAYER_KINDS['full'])['ffn']
            for k in self.layer_pattern))

    def runs(self) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
        """The layers after the leading dense ones as runs of repeated
        periods: `layer_pattern` repeated whole, or, where it names
        each of those layers once, its own runs (`runs_of`: a pattern
        that is no whole number of periods, Nemotron-H's)."""
        pattern = self.layer_pattern or ('full',)
        rest = self.n_layers - len(self.dense_layers)
        if rest < 0 or rest % len(pattern):
            raise ValueError(
                f'n_layers {self.n_layers} less {len(self.dense_layers)} '
                f'dense layers is not whole periods of layer_pattern '
                f'{pattern}')
        if rest == len(pattern) and set(pattern) <= set(LAYER_KINDS):
            return runs_of(pattern)
        return ((pattern, rest // len(pattern)),)

    def layer_names(self) -> Tuple[str, ...]:
        """Every layer's kind name, in order: the leading dense layers',
        then each run's period repeated."""
        runs = self.runs()
        unknown = set(self.dense_layers).union(
            *(period for period, _ in runs)) - set(LAYER_KINDS)
        if unknown:
            raise ValueError(f'Unknown layer kinds {sorted(unknown)}; '
                             f'have {sorted(LAYER_KINDS)}')
        names = self.dense_layers + sum(
            (period * n for period, n in runs), ())
        if len(names) != self.n_layers:
            raise ValueError(
                f'the layer kinds give {len(names)} layers, n_layers is '
                f'{self.n_layers}')
        return names

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is the short convolution: no keys, no
        pages, a tail of rows a sequence."""
        if not self.stacked_by_kind:
            return 0
        return count_mixer(self.layer_names(), 'conv')

    @property
    def mamba_layers(self) -> int:
        """Layers whose mixer is Mamba-2: no keys, no pages, a matrix of
        state a head and a tail of rows a sequence."""
        if not self.stacked_by_kind:
            return 0
        return count_mixer(self.layer_names(), 'mamba')

    @property
    def ssm_inner(self) -> int:
        """A mamba layer's channels of x (and of z, and of y)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """A mamba layer's convolved channels: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def cache_layers(self) -> int:
        """Layers of the KV caches: one for every (pass, attention
        layer)."""
        if not self.stacked_by_kind:
            return self.n_layers * self.loop_passes
        return count_mixer(self.layer_names(), 'attn') * self.loop_passes

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(lo, n): the routed experts held, all where none is named."""
        return self.experts_held or (0, self.n_experts)

    def layer_kinds(self) -> Optional[Tuple[Tuple[bool, int], ...]]:
        """Per attention layer (rope, window): whether q and k are
        rotated, and how many keys a query sees (0 = all).  None where
        every layer is 'full', so a model of one kind of layer takes the
        code it took before kinds existed.  A layer without attention
        has neither and is left out (`stacked_by_kind`)."""
        if self.stacked_by_kind:
            return tuple(
                (LAYER_KINDS[name]['rope'],
                 self.sliding_window if LAYER_KINDS[name]['window'] else 0)
                for name in self.layer_names()
                if LAYER_KINDS[name]['mixer'] == 'attn')
        if not self.layer_pattern:
            return None
        unknown = set(self.layer_pattern) - set(LAYER_KINDS)
        if unknown:
            raise ValueError(f'Unknown layer kinds {sorted(unknown)}; '
                             f'have {sorted(LAYER_KINDS)}')
        if self.n_layers % len(self.layer_pattern):
            raise ValueError(
                f'n_layers {self.n_layers} is not whole periods of '
                f'layer_pattern {self.layer_pattern}')
        period = []
        for name in self.layer_pattern:
            kind = LAYER_KINDS[name]
            if kind['window'] and self.sliding_window <= 0:
                raise ValueError(
                    f'layer kind {name!r} needs sliding_window > 0')
            period.append((kind['rope'],
                           self.sliding_window if kind['window'] else 0))
        return tuple(period) * (self.n_layers // len(period))

    def replace(self, **kw) -> 'ModelConfig':
        return dataclasses.replace(self, **kw)

    def to_json_dict(self) -> dict:
        """JSON-serializable form (dtypes as strings); inverse of
        config_from_json_dict.  Written next to converted checkpoints
        so servers/trainers can reconstruct non-preset shapes."""
        import numpy as np  # pylint: disable=import-outside-toplevel
        d = dataclasses.asdict(self)
        d['dtype'] = np.dtype(self.dtype).name
        d['param_dtype'] = np.dtype(self.param_dtype).name
        return d


def config_from_json_dict(d: dict) -> ModelConfig:
    import numpy as np  # pylint: disable=import-outside-toplevel
    d = dict(d)
    for key in ('dtype', 'param_dtype'):
        if isinstance(d.get(key), str):
            # np.dtype resolves 'bfloat16' via ml_dtypes registration.
            d[key] = (jnp.bfloat16 if d[key] == 'bfloat16'
                      else np.dtype(d[key]).type)
    for key in ('experts_held', 'layer_pattern', 'dense_layers'):
        if isinstance(d.get(key), list):   # JSON has no tuple
            d[key] = tuple(d[key])
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = set(d) - known
    if unknown:
        raise ValueError(f'Unknown ModelConfig fields {sorted(unknown)}')
    return ModelConfig(**d)


LLAMA3_8B = ModelConfig()
LLAMA3_70B = ModelConfig(d_model=8192, n_layers=80, n_heads=64,
                         n_kv_heads=8, d_ff=28672)
# Small config for single-chip benches; tiny for CPU tests.
SMALL = ModelConfig(vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
                    n_kv_heads=8, d_ff=4096, max_seq_len=2048)
TINY = ModelConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, d_ff=128, max_seq_len=128,
                   dtype=jnp.float32, remat=False)
# Mixtral-style MoE (8 experts, top-2).
MIXTRAL_8X7B = ModelConfig(vocab_size=32000, d_model=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, d_ff=14336,
                           rope_theta=1e6, n_experts=8, expert_top_k=2)
TINY_MOE = TINY.replace(n_experts=4, expert_top_k=2)
# Gemma family: tied embeddings, GeGLU, (1+w) norms, scaled embeddings,
# head_dim decoupled via extra heads convention (7B: 16 heads x 256 =
# d_model 3072 x ... here heads x head_dim must equal d_model, so the
# 2B shape is used for the preset).
GEMMA_2B = ModelConfig(vocab_size=256000, d_model=2048, n_layers=18,
                       n_heads=8, n_kv_heads=1, d_ff=16384,
                       rope_theta=10000.0, tie_embeddings=True,
                       mlp_act='gelu', norm_scale_plus_one=True,
                       scale_embeddings=True)
# Qwen2 family: biases on q/k/v, high-theta rope.
QWEN2_7B = ModelConfig(vocab_size=152064, d_model=3584, n_layers=28,
                       n_heads=28, n_kv_heads=4, d_ff=18944,
                       rope_theta=1e6, qkv_bias=True)
TINY_GEMMA = TINY.replace(tie_embeddings=True, mlp_act='gelu',
                          norm_scale_plus_one=True, scale_embeddings=True,
                          n_kv_heads=1)
TINY_QWEN = TINY.replace(qkv_bias=True)

PRESETS = {
    'llama3-8b': LLAMA3_8B,
    'llama3-70b': LLAMA3_70B,
    'mixtral-8x7b': MIXTRAL_8X7B,
    'gemma-2b': GEMMA_2B,
    'qwen2-7b': QWEN2_7B,
    'small': SMALL,
    'tiny': TINY,
    'tiny-moe': TINY_MOE,
    'tiny-gemma': TINY_GEMMA,
    'tiny-qwen': TINY_QWEN,
}


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in PRESETS:
        raise ValueError(f'Unknown model preset {name!r}; '
                         f'have {sorted(PRESETS)}')
    cfg = PRESETS[name]
    return cfg.replace(**overrides) if overrides else cfg
