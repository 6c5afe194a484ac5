"""KV-cache autoregressive decoding for the model family.

TPU-first inference path (no reference equivalent — SkyPilot ships no
model code): static-shape KV caches (max_len fixed at jit time,
position advanced with `lax.dynamic_update_slice`), a flash-kernel
prefill (the Pallas kernel natively handles q_len < k_len decode
shapes), and a jit-able single-token step for the generation loop.
Serving replicas (serve/) wrap this in their model servers.

Design notes:
- The cache is a plain pytree {k: [L, b, h_kv, max_len, d], v: ...,
  'index': []} — the per-layer caches are stacked on a leading axis
  exactly like the scan-layout params, so cache shardings follow the
  same logical rules (kv_heads on 'tensor').  The layer loop CARRIES
  the stacked caches and writes each layer's new rows into them in
  place; it never slices a layer's share out and stacks it back
  (`_scan_layers_and_unembed`).  L is the cache's layers: the model's
  layers, times the passes of a looped stack (`cfg.cache_layers`).
- Decode attends with an explicit length mask (positions > index are
  masked), so one compiled step serves every sequence length.
- Sampling: greedy or temperature/top-k, RNG threaded explicitly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import heads
from skypilot_tpu.models import mamba
from skypilot_tpu.models import moe
from skypilot_tpu.models.configs import LAYER_KINDS
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.configs import count_mixer
from skypilot_tpu.models.quantize import is_quantized_leaf
from skypilot_tpu.models.quantize import maybe_dequant
from skypilot_tpu.models.transformer import _rope
from skypilot_tpu.ops import paged_attention as paged_attention_ops
from skypilot_tpu.ops.attention import NEG_INF
from skypilot_tpu.ops.attention import flash_attention
from skypilot_tpu.ops.ssm_decode import ssm_decode_step


class _PagedView(NamedTuple):
    """The paged-KERNEL path's cache 'view': instead of gathering the
    pool into a dense [b, h_kv, len, d] array, attention receives the
    WHOLE pool leaf ([L, n_pages, h_kv, ps, d], never sliced by layer)
    + the layer's index + block tables + lengths, and the Pallas kernel
    does the (layer, table-indexed page) reads inside its grid (neither
    the layer's share nor the gathered view materialises in HBM).
    Produced by `_paged_forward`'s view_fn when kernel='pallas';
    `_layer_forward` dispatches on it.  `new` [B, h_kv, 1, d]: the
    step's new rows, not yet in `leaf`, which the kernel writes itself
    (`paged_attention_ops.writes_in_kernel`); None where a scatter
    wrote them before the view was taken."""
    leaf: Any
    layer: jax.Array
    tables: jax.Array
    lengths: jax.Array
    new: Any = None


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0   # 0 = greedy
    top_k: int = 0             # 0 = no top-k filtering
    # Per-request RNG seed for temperature sampling (serving: a client
    # pins its own stream; greedy ignores it).
    seed: int = 0


def bind(fn, *args, **kwargs):
    """`functools.partial(fn, *args, **kwargs)` under `fn`'s own name.
    `jax.jit` names the compiled program after `__name__`, and that is
    the name a device trace shows it by (`jit_prefill_chunk`); a
    partial or a lambda comes out as `jit__unknown` / `jit__lambda_`,
    which no reader of the trace can tell apart.  Every jitted entry
    of the serving engines is bound through here."""

    def call(*a, **k):
        return fn(*args, *a, **kwargs, **k)

    call.__name__ = fn.__name__
    call.__qualname__ = fn.__qualname__
    return call


def init_cache(cfg: ModelConfig, batch: int, max_len: int
               ) -> Dict[str, Any]:
    """Zeroed KV cache pytree (stacked over the cache's layers, one a
    (pass, attention layer) of a looped stack: `cfg.cache_layers`).  A
    model with conv layers adds their tails, 'conv' [conv layers,
    batch, conv_kernel - 1, d]: the rows each sequence carries; a model
    with mamba layers their states (`_ssm_states`)."""
    shape = (cfg.cache_layers, batch, cfg.n_kv_heads, max_len,
             cfg.head_dim)
    cache = {
        'k': jnp.zeros(shape, cfg.dtype),
        'v': jnp.zeros(shape, cfg.dtype),
        'index': jnp.zeros((), jnp.int32),
    }
    if cfg.conv_layers:
        cache['conv'] = _conv_tails(cfg, batch)
    if cfg.mamba_layers:
        cache.update(_ssm_states(cfg, batch))
    return cache


def _conv_tails(cfg: ModelConfig, n: int):
    """Zeroed conv tails [conv layers, n, conv_kernel - 1, d]: n
    sequences' (or pages') rows before position 0."""
    return jnp.zeros((cfg.conv_layers, n, cfg.conv_kernel - 1, cfg.d_model),
                     cfg.dtype)


def _ssm_states(cfg: ModelConfig, n: int, store: bool = False):
    """Zeroed states of the mamba layers for n sequences: 'ssm' [mamba
    layers, n, H, P, N] float32 (a matrix of state a head) and 'xbc'
    [mamba layers, n, K - 1, conv channels] (the rows before the
    convolution that the next row's taps read); `store`: n entries of
    every layer's state, [n, mamba layers, ...]."""
    lead = (n, cfg.mamba_layers) if store else (cfg.mamba_layers, n)
    return {'ssm': jnp.zeros(lead + (cfg.ssm_heads, cfg.ssm_head_dim,
                                     cfg.ssm_state), jnp.float32),
            'xbc': jnp.zeros(lead + (cfg.conv_kernel - 1,
                                     cfg.ssm_conv_dim), cfg.dtype)}


def _recurrent(cache):
    """The per-sequence state of a cache or pool beside its keys: the
    conv tails (an array), the mamba states ({'ssm', 'xbc'}) or None."""
    if 'conv' in cache:
        return cache['conv']
    if 'ssm' in cache:
        return {'ssm': cache['ssm'], 'xbc': cache['xbc']}
    return None


def _recurrent_items(state) -> Dict[str, Any]:
    """`_recurrent` back: the cache keys that hold `state`."""
    if state is None:
        return {}
    return dict(state) if isinstance(state, dict) else {'conv': state}


def _layer_params(params: Dict[str, Any], cfg: ModelConfig):
    """-> per-layer param pytree with leading [L] axis (scan layout)."""
    if cfg.scan_layers:
        return params['layers']['layer']
    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f'layer_{i}'] for i in range(cfg.n_layers)])
    return stacked


def _attn_proj(x, proj, heads: int, head_dim: int):
    """[b, s, d_model] x the q/k/v kernel -> [b, heads, s, hd].
    `proj` is the q/k/v param dict; bias present iff cfg.qkv_bias.

    The kernel comes in one of two forms, told by its rank: the serving
    form [d_model, heads * hd] (`serving_params`), which is what the
    product reads, or the training form [d_model, heads, hd].  Under
    the layer scan the TPU compiler fuses the slice of layer l of a
    stacked [L, d_model, heads * hd] into the product; out of a stacked
    [L, d_model, heads, hd], tiled over (heads, hd), it copies the
    layer's kernel first and the product reads the copy
    (tests/unit/test_tpu_compile.py holds both)."""
    kernel = maybe_dequant(proj['kernel'], x.dtype)
    bias = proj.get('bias')
    if kernel.ndim == 3:
        out = jnp.einsum('bsd,dhk->bhsk', x, kernel)
        if bias is not None:  # [heads, hd] over [b, ., s, .]
            out = out + bias.astype(x.dtype)[None, :, None, :]
        return out
    out = jnp.einsum('bsd,df->bsf', x, kernel)
    if bias is not None:      # [heads * hd]
        out = out + bias.astype(x.dtype)
    # The product ends here.  Left to itself the compiler moves the
    # split of the flat axis into the product's kernel operand, which
    # is the training form again: the layer's kernel sliced out of the
    # stack and transposed, two copies a projection where there was one.
    out = jax.lax.optimization_barrier(out)
    b, s, _ = out.shape
    return out.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)


def _qkv_projs(cfg: ModelConfig, params) -> Dict[Tuple[str, ...], Any]:
    """{path: the q/k/v param dict at it} over every group of layers:
    the one stacked group of the scanned layout, or each `layer_{i}`;
    a model stacked by kind, the attention stacks of its groups."""
    if cfg.stacked_by_kind:
        groups = tuple((key, 'attn') for key in ('lead', 'layers')
                       if 'attn' in params.get(key, {}))
    else:
        groups = ((('layers', 'layer'),) if cfg.scan_layers else
                  tuple((f'layer_{i}',) for i in range(cfg.n_layers)))
    out = {}
    for group in groups:
        attn = params
        for key in group + ('attn',):
            attn = attn[key]
        for name in ('q_proj', 'k_proj', 'v_proj'):
            out[group + ('attn', name)] = attn[name]
    return out


def _in_serving_form(cfg: ModelConfig, proj) -> bool:
    """Whether a q/k/v param dict holds its kernel as [.., d_model,
    heads * hd]: one axis short of the training layout's."""
    kernel = proj['kernel']
    if is_quantized_leaf(kernel):
        kernel = kernel['qvalue']
    return kernel.ndim == 2 + bool(cfg.scan_layers)


def _merged_sharding(leaf):
    """The placement of `leaf` once its two last axes are one: heads
    over a mesh axis become the flat axis over it, the same bytes on
    the same device.  None (the compiler's choice) for a leaf that is
    not placed over a mesh, or whose last axis is split."""
    sharding = getattr(leaf, 'sharding', None)
    if not isinstance(sharding, jax.sharding.NamedSharding):
        return None
    spec = tuple(sharding.spec) + (None,) * (leaf.ndim -
                                             len(sharding.spec))
    if spec[-1] is not None:
        return None
    return jax.sharding.NamedSharding(
        sharding.mesh, jax.sharding.PartitionSpec(*spec[:-1]))


def serving_params(cfg: ModelConfig, params):
    """`params` with every layer's q/k/v projection in the form the
    serving programs' product reads: kernel [.., d_model, heads, hd] ->
    [.., d_model, heads * hd], its bias [.., heads, hd] ->
    [.., heads * hd], an int8 kernel's `qvalue` and `scale` each by the
    same merge of the two last axes; the scanned layout and the
    unscanned one alike.  Every other leaf is `params`' own, and
    `params` stays as it was: nothing is donated, a caller goes on
    running `generate` or a reference on its tree, and while it keeps
    the tree both forms of these kernels are held.  A sharded leaf
    keeps its placement.  A tree already in this form comes back as it
    is."""
    picked = {path: proj for path, proj in _qkv_projs(cfg, params).items()
              if not _in_serving_form(cfg, proj)}
    if not picked:
        return params
    merged = jax.jit(
        lambda tree: jax.tree.map(
            lambda leaf: leaf.reshape(leaf.shape[:-2] + (-1,)), tree),
        out_shardings=jax.tree.map(_merged_sharding, picked))(picked)
    for path, proj in merged.items():
        params = _with_node(params, path, proj)
    return params


def _with_node(tree, path: Tuple[str, ...], node):
    """`tree` with `node` at `path`: the dicts along the path are new,
    everything beside it is `tree`'s own."""
    if not path:
        return node
    return {**tree, path[0]: _with_node(tree[path[0]], path[1:], node)}


def serving_form_bytes(cfg: ModelConfig, params) -> int:
    """Bytes of `params`' q/k/v projections held in the serving form
    (`serving_params`); 0 for a training-layout tree."""
    return sum(
        leaf.nbytes for proj in _qkv_projs(cfg, params).values()
        if _in_serving_form(cfg, proj) for leaf in jax.tree.leaves(proj))


def _mlp(x, lp, cfg, row_mask=None):
    """The layer's FFN on [b, s, d] -> (out, counts): the dense SwiGLU
    (counts None), or the expert layer without drops (float32 out,
    which the caller casts to the stream's dtype), with its int32 [3]
    counts over the rows `row_mask` [b * s] marks (`moe.moe_apply`: the
    one layer for a prefill chunk and a decode tick alike; a token's
    result depends on no other token, so chunks may be padded and split
    and slots batched).  Which of the two `lp` holds says which it is:
    a model's leading dense layers have the dense FFN beside experts."""
    if 'moe_mlp' in lp:
        b, s, d = x.shape
        out, _, counts = moe.moe_apply(x.reshape(b * s, d), lp['moe_mlp'],
                                       cfg, row_mask)
        return out.reshape(b, s, d), counts
    act = {'silu': jax.nn.silu, 'gelu': jax.nn.gelu}[cfg.mlp_act]
    gate = jnp.einsum('bsd,df->bsf', x,
                      maybe_dequant(lp['mlp']['gate_proj']['kernel'],
                                    x.dtype))
    up = jnp.einsum('bsd,df->bsf', x,
                    maybe_dequant(lp['mlp']['up_proj']['kernel'],
                                  x.dtype))
    return jnp.einsum('bsf,fd->bsd', act(gate) * up,
                      maybe_dequant(lp['mlp']['down_proj']['kernel'],
                                    x.dtype)), None


def _norm(x, scale, cfg):
    """The model's norm: RMSNorm (Gemma: weights parameterize 1 + w)
    or, `norm_type` 'layernorm', the mean-subtracting norm with a
    scale and no bias."""
    if cfg.norm_scale_plus_one:
        scale = 1.0 + scale
    x32 = x.astype(jnp.float32)
    if cfg.norm_type == 'layernorm':
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    elif cfg.norm_type != 'rms':
        raise ValueError(f'Unknown norm_type {cfg.norm_type!r}; '
                         "have 'rms', 'layernorm'.")
    normed = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + cfg.norm_eps)
    return (normed * scale).astype(x.dtype)


# A window that cuts nothing: what a layer without one passes where the
# window is per-layer data.
_NO_WINDOW = 1 << 30


class _ChunkRows(NamedTuple):
    """A second group of rows in the layer loop: a prefill chunk that
    rides a decode tick (`paged_engine_step_with_chunk`).  Its `n` rows
    follow the slots' in the one joined [1, B + n, d] stream, so every
    row-wise product reads its weights once for both groups; attention
    runs per group, the chunk's against its private cache k/v
    [L, 1, h_kv, max_len, d] (in the scan: the layer's view of it),
    where its keys are written at `positions` [n].  `x` [1, n, d] is
    the chunk's embedded tokens, what it enters the loop with; `rows`
    (int32 scalar, or None: all n) how many of them are the prompt's,
    the rest padding up to the program's width, whose attention is
    not worth computing (`_attend`) and which no conv tail takes.
    A model with mamba layers: `conv` the chunk's own states
    ({'ssm', 'xbc'}, `_ssm_states` of one), `snap` the states after
    the first `snap_at` (int32 scalar) of its rows, a snapshot
    (`_mamba_layer`)."""
    x: Any
    positions: jax.Array
    k: Any
    v: Any
    use_flash: bool
    rows: Any = None
    # A model with conv layers: the chunk's own tails [conv layers, 1,
    # K - 1, d], and the tail at the end of each page of its prompt,
    # 'snap' [conv layers, pages, K - 1, d], of `page_size` tokens.
    conv: Any = None
    snap: Any = None
    page_size: int = 0
    snap_at: Any = None

    @property
    def n(self) -> int:
        return self.positions.shape[0]


def _split_rows(t, n: int):
    """[1, h, B + n, d] of the joined stream -> (the slots' rows as the
    tick has them, [B, h, 1, d], the chunk's [1, h, n, d])."""
    b = t.shape[2] - n
    return t[0, :, :b].transpose(1, 0, 2)[:, :, None], t[:, :, b:]


def _join_rows(slots, chunk):
    """`_split_rows` back: [B, h, 1, d] and [1, h, n, d] ->
    [1, h, B + n, d]."""
    return jnp.concatenate(
        [slots[:, :, 0].transpose(1, 0, 2)[None], chunk], axis=2)


# Query rows to a block of a padded chunk's masked attention (`_attend`).
_ATTEND_BLOCK = 64


def _attend(q, cfg, positions, k_cache, v_cache, *, use_flash: bool,
            mesh, window, dtype, rows=None):
    """Attention of one group of rows: q [b, h_q, s, hd] (rotated)
    against its view of the KV cache, which already holds this call's
    k/v at `positions` (or, a `_PagedView` with `new` rows, is given
    them to write) -> ([b, h_q, s, hd] in `dtype`, the pool's (k, v)
    leaves the kernel wrote the new rows into, or None).  `rows` (a
    padded chunk's, `_ChunkRows`): only the first `rows` of the s are
    anyone's; the masked path skips the blocks past them."""
    if isinstance(k_cache, _PagedView):
        # Paged-kernel decode: the Pallas kernel copies each slot's
        # live K/V pages of this layer from the whole pool by (layer,
        # block-table) index (fused int8 dequant on the loaded
        # operand); `positions` is implied by the view's lengths —
        # query token j of slot b sits at lengths[b] + j.
        with jax.named_scope('paged_attention'):
            out = paged_attention_ops.paged_attention(
                q, k_cache.leaf, v_cache.leaf, k_cache.tables,
                k_cache.lengths, sm_scale=cfg.head_dim ** -0.5,
                mesh=mesh, window=window, layer=k_cache.layer,
                new_k=k_cache.new, new_v=v_cache.new,
                heads_per_row=heads_per_row(cfg, k_cache.leaf))
        if k_cache.new is None:
            return out.astype(dtype), None
        return out[0].astype(dtype), out[1:]
    if use_flash:
        # Prefill from index 0: the valid cache region is exactly the
        # prompt window [0, s) — a STATIC slice (q.shape[2]), as jit
        # requires.  (Chunks at index>0 take the masked path instead,
        # and so does a chunk longer than a layer's window: the caller
        # sees to it, `_flash_ok`.)
        s = q.shape[2]
        with jax.named_scope('flash_attention'):
            return flash_attention(q, k_cache[:, :, :s],
                                   v_cache[:, :, :s], causal=True,
                                   mesh=mesh), None
    if rows is None or q.shape[2] <= _ATTEND_BLOCK:
        return _masked_attention(q, cfg, positions, k_cache, v_cache,
                                 window, dtype), None
    # A chunk padded to its program's width: the masked path reads the
    # whole cache in float32 for every row, so blocks that hold pad
    # rows only are left at zero (their results are garbage nobody
    # reads either way: pad positions lie past every real query's
    # horizon and are overwritten before anything attends them).
    blocks = []
    for start in range(0, q.shape[2], _ATTEND_BLOCK):
        block = slice(start, start + _ATTEND_BLOCK)
        attend = functools.partial(
            _masked_attention, q[:, :, block], cfg, positions[block],
            k_cache, v_cache, window, dtype)
        blocks.append(attend() if start == 0 else jax.lax.cond(
            start < rows, attend,
            lambda block=block: jnp.zeros_like(q[:, :, block], dtype)))
    return jnp.concatenate(blocks, axis=2), None


def _masked_attention(q, cfg, positions, k_cache, v_cache, window, dtype):
    """q [b, h_q, s, hd] at `positions` against a dense cache view
    [b, h_kv, len, hd], in float32, every key at or before the query's
    position (and inside its layer's window) -> [b, h_q, s, hd]."""
    # Masked decode: grouped einsums against the cache — GQA
    # q-heads fold into a `rep` axis per kv-head, so the repeated
    # K/V never materialises (8x cache-read savings on llama3-70b).
    b, h_q, qs, d = q.shape
    rep = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, cfg.n_kv_heads, rep, qs, d).astype(jnp.float32)
    k32 = k_cache.astype(jnp.float32)
    s = jnp.einsum('bgrqd,bgkd->bgrqk', qg, k32) * (
        cfg.head_dim ** -0.5)
    kpos = jnp.arange(k_cache.shape[2])
    # Per-query-position causal mask: query at absolute position p
    # attends keys at kpos <= p.  positions is [s] (single-sequence
    # prefill continuation), [B, 1] (slot-batched decode — every
    # slot at its own depth), or [B, s] — so one masked path serves
    # single-token decode AND multi-token chunked prefill at
    # index > 0 (where the flash window-from-0 trick is invalid).
    pos = jnp.asarray(positions)
    if pos.ndim == 1:
        pos = pos[None]                               # [1, s]
    kpos = kpos[None, None, None, None, :]
    pos = pos[:, None, None, :, None]
    mask = kpos <= pos
    if window is not None:
        mask = mask & (kpos > pos - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bgrqk,bgkd->bgrqd', p,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, h_q, qs, d).astype(dtype)


def _layer_forward(x, lp, cfg, positions, k_cache, v_cache,
                   *, use_flash: bool, mesh=None, rope_on=None,
                   window=None, row_mask=None, chunk=None, ffn=True):
    """One decoder layer against its view of the KV cache.

    x [b, s, d]; k_cache/v_cache [b, h_kv, max_len, hd] (or the paged
    kernel's `_PagedView` of the whole pool and the layer's index)
    already contain this call's k/v written at [positions], or (a view
    with `new` rows) the kernel writes them.  Returns (the layer
    output, the expert layer's counts or None, the pool's (k, v) leaves
    the kernel wrote or None).  `mesh` (the mesh the params
    and cache are sharded over, if any) goes to the attention kernels,
    which run per shard under it.

    `rope_on` (bool scalar) and `window` (int32 scalar, `_NO_WINDOW`
    where the layer has none) are this layer's kind where the model has
    layers of more than one (`cfg.layer_kinds`); both None for a model
    of one kind of layer, which takes the code it always took.  A
    query at position p of a window layer sees keys p - window + 1 .. p.
    `cfg.parallel_block` and `cfg.post_norms` are settings of this one
    body.

    With a `chunk` (`_ChunkRows`, its k/v the layer's views) x is the
    joined stream [1, B + n, d]: the norms, the projections and the FFN
    run on it whole, and only attention takes the two groups apart,
    the slots' rows against `k_cache`/`v_cache` at `positions` [B, 1]
    and the chunk's against its own.

    `ffn` False: attention alone, no FFN after it ('attn_nope').
    """
    h = _norm(x, lp['attn_norm']['scale'], cfg)
    q = _qk_norm(_attn_proj(h, lp['attn']['q_proj'], cfg.n_heads,
                            cfg.head_dim), lp, 'q_norm', cfg)
    if chunk is not None:
        q, chunk_q = _split_rows(q, chunk.n)
    q = _rope_if(rope_on, q, positions, cfg)
    out, written = _attend(q, cfg, positions, k_cache, v_cache,
                           use_flash=use_flash, mesh=mesh, window=window,
                           dtype=x.dtype)
    if chunk is not None:
        chunk_q = _rope_if(rope_on, chunk_q, chunk.positions, cfg)
        out = _join_rows(out, _attend(
            chunk_q, cfg, chunk.positions, chunk.k, chunk.v,
            use_flash=chunk.use_flash, mesh=mesh, window=window,
            dtype=x.dtype, rows=chunk.rows)[0])

    out = jnp.einsum('bhsk,hkd->bsd', out,
                     maybe_dequant(lp['attn']['o_proj']['kernel'],
                                   x.dtype))
    out = _post_norm(out, lp, 'attn_post_norm', cfg)
    if cfg.parallel_block:
        # Attention and the FFN read the one normed input and join the
        # residual together.
        with jax.named_scope('mlp'):
            m, counts = _mlp(h, lp, cfg, row_mask)
        return x + out + _post_norm(m.astype(x.dtype), lp,
                                    'mlp_post_norm', cfg), counts, written
    if not ffn:
        return x + out, None, written
    return (*_ffn_residual(x + out, lp, cfg, row_mask), written)


def _ffn_residual(x, lp, cfg, row_mask):
    """The FFN half of a sequential layer: x + FFN(norm(x)) -> (that,
    the expert layer's counts or None)."""
    h = _norm(x, lp['mlp_norm']['scale'], cfg)
    with jax.named_scope('mlp'):
        m, counts = _mlp(h, lp, cfg, row_mask)
    return x + _post_norm(m.astype(x.dtype), lp, 'mlp_post_norm',
                          cfg), counts


def _qk_norm(t, lp, name: str, cfg):
    """`cfg.qk_norm`: each head's q or k [b, h, s, hd] RMS-normed over
    hd by the layer's scale `lp['attn'][name]` [hd], before rotation."""
    if not cfg.qk_norm:
        return t
    return _norm(t, lp['attn'][name]['scale'], cfg)


def _post_norm(out, lp, name: str, cfg):
    """Sandwich norms (`cfg.post_norms`): a sub-layer's output is
    normed, by a scale of its own, before it joins the residual."""
    return _norm(out, lp[name]['scale'], cfg) if cfg.post_norms else out


def _rope_if(rope_on, x, positions, cfg):
    """Rotary embedding where the layer's kind has one (`rope_on` None:
    every layer has; False: this one has none, known when traced)."""
    if rope_on is False:
        return x
    rotated = _rope(x, positions, cfg)
    return rotated if rope_on is None else jnp.where(rope_on, rotated, x)


def _flash_ok(cfg, use_flash: bool, s: int) -> bool:
    """The flash path attends the whole causal triangle of its [0, s)
    chunk: right for every layer only where no window is shorter than
    the chunk."""
    return use_flash and not (cfg.layer_pattern and
                              0 < cfg.sliding_window < s)


def _embed(cfg, params, tokens):
    x = jnp.take(params['embed']['embedding'], tokens,
                 axis=0).astype(cfg.dtype)
    if cfg.scale_embeddings:  # Gemma
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _scan_layers_and_unembed(cfg, params, x, positions, cache_k, cache_v,
                             write_fn, *, use_flash: bool,
                             view_fn=None, all_positions: bool = False,
                             mesh=None, row_mask=None, chunk=None,
                             conv=None):
    """The shared per-layer loop: project+rope k/v, write them into
    layer l of the cache via `write_fn(cache, l, new) -> cache`, run
    the layer against `view_fn(cache, l)`, then final-norm + unembed
    the last position (`write_fn` None: the paged kernel writes them,
    handed them by `view_fn(cache, l, new)`; `_attention_layer`).
    Single-sequence decode, slot-batched decode and the page pool
    differ ONLY in write_fn / view_fn / positions shapes.
    Returns (logits, new_k, new_v, counts, exit_p, chunk_kv, new_conv):
    counts is None for a model without experts, else the expert layers'
    int32 [4] counts summed over the layers (`moe.moe_apply`, over the
    rows `row_mask` marks); exit_p is None for a model of one pass, else
    the float32 [passes, b, s] exit mass of each unembedded position
    (below); chunk_kv is None without a `chunk`; new_conv is the conv
    tails `conv` [conv layers, b, K - 1, d] of the b sequences whose
    rows x carries, advanced past them (None for a model without conv
    layers, `_scan_by_kind`).

    `chunk` (`_ChunkRows`) is a second group of rows that rides a
    slot-batched step (x [B, 1, d]): a prefill chunk's, with a private
    cache of its own.  Its rows join the slots' in one stream
    [1, B + n, d], so each layer's weights are read once for both;
    each group's keys go to its own cache and its queries attend it
    (`_layer_forward`); `row_mask` [B + n] then marks rows of the
    stream.  Only the slots' rows reach the head (and the exit gate):
    the chunk's leave as chunk_kv, its private cache's (k, v) with the
    chunk's keys of every cache layer written at its positions.

    The stacked caches (`[L, ...]` leaves, or int8 {'q','scale'} dicts
    of them) ride the loop as its CARRY beside `x`, whole: the scanned
    inputs are the layers' weights, their kinds and the layer's index
    `l` (a traced int32 scalar).  So a layer's share of a cache is
    never sliced out of the stack and written back: `write_fn` puts the
    new rows where they belong in the carried buffer (in place, when
    the caller donated it) and the returned caches are the carried
    ones.

    `view_fn(cache, l)` maps the stored cache to what layer l's
    attention reads — by default the layer's slice `cache[l]`
    ([b, h_kv, len, d], the dense caches); the paged cache gathers (and
    dequantizes) the layer's pages through it, or hands the Pallas
    kernel a `_PagedView` of the whole pool and `l`, so one layer body
    serves every cache layout.

    Layers of more than one kind (`cfg.layer_kinds`: rotary or not, a
    window or none) run under the one scan: each layer's kind rides the
    scan as data beside its weights and reaches the rotation, the mask
    and the kernel's first page.

    A looped stack (`cfg.loop_passes` > 1) runs the one layer scan
    once a pass, under a scan over the passes: pass t, layer l writes
    and reads cache layer t * n_layers + l (a position's keys differ
    from pass to pass, and later positions attend them in every pass),
    the caches still the carry.  The final norm ends every pass and
    its output is the next pass's input; the exit gate reads it, and
    the head reads the pass the gate selects (`_exit_select`).  A
    model of one pass takes none of this: its program is the one it
    was before passes existed.

    `all_positions=True` unembeds EVERY position ([b, s, V] logits
    instead of last-position [b, V]) — the speculative verify step
    needs the model's output after each drafted token.  The norm and
    unembed are per-position, so position j's logits are the same
    either way.
    """
    if cfg.stacked_by_kind:
        return _scan_by_kind(
            cfg, params, x, positions, cache_k, cache_v, conv, write_fn,
            use_flash=use_flash, view_fn=view_fn or _layer_of,
            all_positions=all_positions, mesh=mesh, row_mask=row_mask,
            chunk=chunk)
    layers = _layer_params(params, cfg)
    if view_fn is None:
        view_fn = _layer_of
    kinds = cfg.layer_kinds()
    use_flash = _flash_ok(cfg, use_flash, x.shape[1])
    slots = x.shape[0]
    private = ()
    if chunk is not None:
        x = jnp.concatenate([x.reshape(1, slots, -1), chunk.x], axis=1)
        private = (chunk.k, chunk.v)
        chunk = chunk._replace(
            x=None, use_flash=_flash_ok(cfg, chunk.use_flash, chunk.n))
    xs = (layers, jnp.arange(cfg.n_layers, dtype=jnp.int32))
    if kinds is not None:
        xs += (jnp.asarray([rope for rope, _ in kinds]),
               jnp.asarray([w or _NO_WINDOW for _, w in kinds],
                           jnp.int32))

    def stack(carry, first):
        """One pass over the layers; `first` is the pass's first cache
        layer (None where there is one pass: the layer's own index)."""

        def body(carry, layer_state):
            x, k_cache, v_cache, private = carry
            lp, l = layer_state[:2]
            if first is not None:
                l = first + l
            rope_on, window = layer_state[2:] or (None, None)
            x, k_cache, v_cache, private, counts = _attention_layer(
                x, lp, l, k_cache, v_cache, private, cfg=cfg,
                positions=positions, write_fn=write_fn, view_fn=view_fn,
                use_flash=use_flash, mesh=mesh, rope_on=rope_on,
                window=window, row_mask=row_mask, chunk=chunk)
            return (x, k_cache, v_cache, private), counts

        # The caches are in the carry, so nothing cache-sized is sliced
        # or stacked around the body: in a device trace, an op under
        # `layer_scan` that is under none of the scopes inside the body
        # and moves a layer's share of a cache is a copy the compiler
        # put back.
        with jax.named_scope('layer_scan'):
            return jax.lax.scan(body, carry, xs)

    def head_rows(x):
        """The rows of the stream that the head reads: the slots' own
        (the chunk's stay behind), each sequence's last unless all are
        asked for."""
        if chunk is not None:
            x = x[0, :slots, None]
        return x if all_positions else x[:, -1:]

    final = params['final_norm']['scale']
    if cfg.loop_passes == 1:
        (x, new_k, new_v, private), counts = stack(
            (x, cache_k, cache_v, private), None)
        if counts is not None:
            counts = jnp.sum(counts, axis=0)
        with jax.named_scope('lm_head'):
            x = _norm(head_rows(x), final, cfg)
            logits = heads.unembed(x, params, cfg)
        return (logits if all_positions else logits[:, 0], new_k, new_v,
                counts, None, private or None, None)

    def one_pass(carry, t):
        with jax.named_scope('loop_pass'):
            (x, *caches), counts = stack(carry, t * cfg.n_layers)
        with jax.named_scope('pass_norm'):
            x = _norm(x, final, cfg)
        return (x, *caches), (head_rows(x), counts)

    (_, new_k, new_v, private), (hs, counts) = jax.lax.scan(
        one_pass, (x, cache_k, cache_v, private),
        jnp.arange(cfg.loop_passes, dtype=jnp.int32))
    if counts is not None:
        counts = jnp.sum(counts, axis=(0, 1))
    with jax.named_scope('exit_gate'):
        x, exit_p = _exit_select(cfg, params['exit_gate'], hs)
    with jax.named_scope('lm_head'):
        logits = heads.unembed(x, params, cfg)
    return (logits if all_positions else logits[:, 0], new_k, new_v,
            counts, exit_p, private or None, None)


def _attention_layer(x, lp, l, k_cache, v_cache, private, *, cfg,
                     positions, write_fn, view_fn, use_flash, mesh,
                     rope_on, window, row_mask, chunk, ffn=True):
    """One attention layer of the layer loop: project (and norm) k and
    v, rotate k, write both into cache layer `l` (`write_fn`), a riding
    chunk's into its private cache `private` (k, v), and run the layer
    against `view_fn(cache, l)` (`_layer_forward`).  `write_fn` None:
    the paged kernel writes the slots' rows itself, handed them by
    `view_fn(cache, l, new)`, and the caches are the pools it returns
    (`_paged_forward`).  Returns (x, k_cache, v_cache, private,
    counts)."""
    h = _norm(x, lp['attn_norm']['scale'], cfg)
    k = _qk_norm(_attn_proj(h, lp['attn']['k_proj'], cfg.n_kv_heads,
                            cfg.head_dim), lp, 'k_norm', cfg)
    v = _attn_proj(h, lp['attn']['v_proj'], cfg.n_kv_heads,
                   cfg.head_dim)
    rows = None
    if chunk is not None:
        k, chunk_k = _split_rows(k, chunk.n)
        v, chunk_v = _split_rows(v, chunk.n)
    k = _rope_if(rope_on, k, positions, cfg)
    with jax.named_scope('kv_write'):
        if write_fn is not None:
            k_cache = write_fn(k_cache, l, k)
            v_cache = write_fn(v_cache, l, v)
        if chunk is not None:
            chunk_k = _rope_if(rope_on, chunk_k, chunk.positions, cfg)
            private = tuple(
                _write_private(c, l, new, chunk.positions[0])
                for c, new in zip(private, (chunk_k, chunk_v)))
            rows = chunk._replace(k=_layer_of(private[0], l),
                                  v=_layer_of(private[1], l))
    if write_fn is None:
        views = view_fn(k_cache, l, k), view_fn(v_cache, l, v)
    else:
        views = view_fn(k_cache, l), view_fn(v_cache, l)
    x, counts, written = _layer_forward(
        x, lp, cfg, positions, *views, use_flash=use_flash, mesh=mesh,
        rope_on=rope_on, window=window, row_mask=row_mask, chunk=rows,
        ffn=ffn)
    if written is not None:
        k_cache, v_cache = written
    return x, k_cache, v_cache, private, counts


# ---------------------------------------- layers stacked by their kind
# A model stacked by kind (`cfg.stacked_by_kind`) holds its weights by
# kind: params[group]['attn'] (the attention layers' norm and q/k/v/o,
# [n, ...]), params[group]['conv'] (the conv layers' norm, in/out
# projections and kernel, [n, ...]), params[group]['mamba'] (the mamba
# layers' norm and mixer, [n, ...]) and params[group]['ffn'] (the FFN
# norm and dense FFN or experts of every layer that has one, [n, ...]),
# in the group 'lead' (the leading dense layers) and 'layers' (the
# runs of periods).  The page pool holds the attention layers only: an
# attention layer's cache layer is its index among them.  A conv
# layer's state is a tail of `conv_kernel - 1` rows a sequence, carried
# beside the pool ('conv' [conv layers, slots, K - 1, d]); a mamba
# layer's a matrix a head and a tail ('ssm', 'xbc': `_ssm_states`).


def stacked_param_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    """The tree a model stacked by kind is read from, as shapes in
    `cfg.param_dtype`: per group ('lead': the leading dense layers,
    'layers': the runs of periods) the conv layers' stack under 'conv'
    (the mixer's norm 'conv_norm', in/out projections, the depthwise
    kernel [K, d]), the mamba layers' under 'mamba' (the norm
    'mamba_norm'; in_proj, the depthwise 'conv_kernel' [K, C] and its
    'conv_bias', 'A_log', 'dt_bias', 'D' of a head each, the gated
    norm's scale and out_proj), the attention layers' under 'attn' (as
    every model's, with 'q_norm'/'k_norm' scales of hd under
    `cfg.qk_norm`) and the FFN of every layer that has one under 'ffn'
    ('mlp_norm' and a dense 'mlp', or the experts 'moe_mlp' with the
    router, the selection bias under `cfg.expert_bias`, the held
    experts' stacks and the shared experts'; no 'gate_proj' where
    `cfg.mlp_act` is 'relu2')."""
    d, hd, dtype = cfg.d_model, cfg.head_dim, cfg.param_dtype
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    tree: Dict[str, Any] = {'embed': {'embedding': sds(cfg.vocab_size, d)},
                            'final_norm': {'scale': sds(d)}}
    if not cfg.tie_embeddings:
        tree['lm_head'] = {'kernel': sds(d, cfg.vocab_size)}
    names = cfg.layer_names()
    lead = len(cfg.dense_layers)
    gated = cfg.mlp_act != 'relu2'
    for key, kinds in (('lead', names[:lead]), ('layers', names[lead:])):
        if not kinds:
            continue
        n_conv, n_attn = count_mixer(kinds, 'conv'), count_mixer(kinds,
                                                                 'attn')
        n_mamba = count_mixer(kinds, 'mamba')
        n = sum(LAYER_KINDS[k]['ffn'] for k in kinds)
        group = tree[key] = {}
        if n_conv:
            group['conv'] = {
                'conv_norm': {'scale': sds(n_conv, d)},
                'conv': {'in_proj': {'kernel': sds(n_conv, d, 3 * d)},
                         'kernel': sds(n_conv, cfg.conv_kernel, d),
                         'out_proj': {'kernel': sds(n_conv, d, d)}}}
        if n_mamba:
            heads, inner = cfg.ssm_heads, cfg.ssm_inner
            conv_dim = cfg.ssm_conv_dim
            group['mamba'] = {
                'mamba_norm': {'scale': sds(n_mamba, d)},
                'mamba': {
                    'in_proj': {'kernel': sds(n_mamba, d,
                                              inner + conv_dim + heads)},
                    'conv_kernel': sds(n_mamba, cfg.conv_kernel, conv_dim),
                    'conv_bias': sds(n_mamba, conv_dim),
                    'A_log': sds(n_mamba, heads),
                    'dt_bias': sds(n_mamba, heads),
                    'D': sds(n_mamba, heads),
                    'norm': {'scale': sds(n_mamba, inner)},
                    'out_proj': {'kernel': sds(n_mamba, inner, d)}}}
        if n_attn:
            attn = {name: {'kernel': sds(n_attn, d, heads, hd)}
                    for name, heads in (('q_proj', cfg.n_heads),
                                        ('k_proj', cfg.n_kv_heads),
                                        ('v_proj', cfg.n_kv_heads))}
            attn['o_proj'] = {'kernel': sds(n_attn, cfg.n_heads, hd, d)}
            if cfg.qk_norm:
                attn.update(q_norm={'scale': sds(n_attn, hd)},
                            k_norm={'scale': sds(n_attn, hd)})
            group['attn'] = {'attn_norm': {'scale': sds(n_attn, d)},
                             'attn': attn}
        if not n:
            continue
        ffn = group['ffn'] = {'mlp_norm': {'scale': sds(n, d)}}
        if key == 'layers' and cfg.n_experts:
            e, f = cfg.held_experts[1], cfg.d_ff
            moe_mlp = ffn['moe_mlp'] = {
                'router': {'kernel': sds(n, d, cfg.n_experts)},
                'up_proj': sds(n, e, d, f), 'down_proj': sds(n, e, f, d)}
            if gated:
                moe_mlp['gate_proj'] = sds(n, e, d, f)
            if cfg.expert_bias:
                moe_mlp['expert_bias'] = sds(n, cfg.n_experts)
            if cfg.n_shared_experts:
                e, f = cfg.n_shared_experts, cfg.shared_d_ff or cfg.d_ff
                moe_mlp.update(shared_up_proj=sds(n, e, d, f),
                               shared_down_proj=sds(n, e, f, d))
                if gated:
                    moe_mlp['shared_gate_proj'] = sds(n, e, d, f)
        else:
            f = cfg.dense_d_ff if key == 'lead' else cfg.d_ff
            ffn['mlp'] = {'gate_proj': {'kernel': sds(n, d, f)},
                          'up_proj': {'kernel': sds(n, d, f)},
                          'down_proj': {'kernel': sds(n, f, d)}}
    return tree


def _scan_by_kind(cfg, params, x, positions, cache_k, cache_v, conv,
                  write_fn, *, use_flash, view_fn, all_positions, mesh,
                  row_mask, chunk):
    """`_scan_layers_and_unembed` for a model stacked by kind: the
    leading dense layers, then each run of periods, each one scan over
    its whole periods whose body runs the period's layers in order
    (every weight read in place from its kind's stack, `_periods`);
    then the head.  The carry is the stream, the pool's k/v, the
    sequences' recurrent state (conv tails, or mamba states) and, with
    a riding chunk, its private k/v, its own state and its snapshots."""
    use_flash = _flash_ok(cfg, use_flash, x.shape[1])
    slots = x.shape[0]
    private, own = (), ()
    if chunk is not None:
        x = jnp.concatenate([x.reshape(1, slots, -1), chunk.x], axis=1)
        private, own = (chunk.k, chunk.v), (chunk.conv, chunk.snap)
        chunk = chunk._replace(
            x=None, k=None, v=None, conv=None, snap=None,
            use_flash=_flash_ok(cfg, chunk.use_flash, chunk.n))
    carry = (x, cache_k, cache_v, conv, private, own)
    lead = len(cfg.dense_layers)
    groups = [('lead', cfg.dense_layers, 1)] if lead else []
    groups += [('layers', period, n) for period, n in cfg.runs()]
    # The first layer of each mixer's state (attention: its cache
    # layer; conv, mamba: its tail and state), over the whole model;
    # and, within each group, where the run's weights start in each
    # kind's stack (attention, conv, mamba, FFN).
    first, starts, counts = [0, 0, 0], {}, None
    for key, kinds, n_periods in groups:
        at = starts.setdefault(key, [0, 0, 0, 0])
        carry, c = _periods(
            cfg, params[key], kinds, n_periods, tuple(first), tuple(at),
            carry, positions=positions, write_fn=write_fn,
            view_fn=view_fn, use_flash=use_flash, mesh=mesh,
            row_mask=row_mask, chunk=chunk)
        if c is not None:
            counts = c if counts is None else counts + c
        mixers = [count_mixer(kinds, mixer)
                  for mixer in ('attn', 'conv', 'mamba')]
        for i, n in enumerate(mixers):
            first[i] += n_periods * n
        for i, n in enumerate(mixers +
                              [sum(LAYER_KINDS[k]['ffn'] for k in kinds)]):
            at[i] += n_periods * n
    x, new_k, new_v, conv, private, own = carry
    if chunk is not None:
        x = x[0, :slots, None]
    with jax.named_scope('lm_head'):
        x = _norm(x if all_positions else x[:, -1:],
                  params['final_norm']['scale'], cfg)
        logits = heads.unembed(x, params, cfg)
    chunk_kv = None
    if chunk is not None:
        chunk_kv = dict(zip(('k', 'v'), private), snap=own[1],
                        **_recurrent_items(own[0]))
    return (logits if all_positions else logits[:, 0], new_k, new_v,
            counts, None, chunk_kv, conv)


def _periods(cfg, group, kinds, n_periods, first, at, carry, *,
             positions, write_fn, view_fn, use_flash, mesh, row_mask,
             chunk):
    """One run's layers: `n_periods` periods of `kinds`, a scan over
    the periods whose body runs a period's layers in order.  The
    group's stacks ride the loop whole, and layer j of period p reads
    its weights at index (the run's start in its kind's stack) + p *
    (its kind's layers a period) + (its place among them) of its kind's
    stack, one dynamic index a leaf that the product reading it takes
    in place: a slice of a period's weights, read by more than one
    layer, would be copied out of the stack first (as compiled for a
    v5e).  `first` = (attention, conv, mamba) index of the run's
    first layer of each mixer over the model: a layer's cache layer, or
    its state's; `at` = (attention, conv, mamba, FFN) the run's start
    in each stack of the group.  Returns (carry, the expert layers'
    counts summed or None)."""
    n_attn = count_mixer(kinds, 'attn')
    n_conv = count_mixer(kinds, 'conv')
    n_mamba = count_mixer(kinds, 'mamba')
    n_ffn = sum(LAYER_KINDS[k]['ffn'] for k in kinds)

    def layer(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            tree)

    def index(p, n, i, start):
        idx = p * n + i
        return idx + start if start else idx

    def body(carry, p):
        x, k_cache, v_cache, conv, private, own = carry
        a, c, m, f, counts = 0, 0, 0, 0, None
        for name in kinds:
            kind = LAYER_KINDS[name]
            lp, cnt = {}, None
            if kind['ffn']:
                lp = layer(group['ffn'], index(p, n_ffn, f, at[3]))
                f += 1
            if kind['mixer'] == 'conv':
                lp.update(layer(group['conv'], index(p, n_conv, c, at[1])))
                x, conv, own, cnt = _conv_layer(
                    x, lp, first[1] + p * n_conv + c, conv, own, cfg=cfg,
                    row_mask=row_mask, chunk=chunk)
                c += 1
            elif kind['mixer'] == 'mamba':
                lp.update(layer(group['mamba'],
                                index(p, n_mamba, m, at[2])))
                x, conv, own = _mamba_layer(
                    x, lp, first[2] + p * n_mamba + m, conv, own, cfg=cfg,
                    row_mask=row_mask, chunk=chunk)
                m += 1
            elif kind['mixer'] == 'attn':
                lp.update(layer(group['attn'], index(p, n_attn, a, at[0])))
                x, k_cache, v_cache, private, cnt = _attention_layer(
                    x, lp, first[0] + p * n_attn + a, k_cache, v_cache,
                    private, cfg=cfg, positions=positions,
                    write_fn=write_fn, view_fn=view_fn,
                    use_flash=use_flash, mesh=mesh,
                    rope_on=None if kind['rope'] else False,
                    window=cfg.sliding_window if kind['window'] else None,
                    row_mask=row_mask, chunk=chunk, ffn=kind['ffn'])
                a += 1
            else:
                x, cnt = _ffn_residual(x, lp, cfg, row_mask)
            if cnt is not None:
                counts = cnt if counts is None else counts + cnt
        return (x, k_cache, v_cache, conv, private, own), counts

    with jax.named_scope('layer_scan'):
        carry, counts = jax.lax.scan(
            body, carry, jnp.arange(n_periods, dtype=jnp.int32))
    return carry, None if counts is None else jnp.sum(counts, axis=0)


def _mamba_layer(x, lp, m, state, own, *, cfg, row_mask, chunk):
    """One Mamba-2 layer, its mixer alone (`models/mamba.py` has the
    equations): x + mixer(norm(x)).  `state` ({'ssm', 'xbc'}, each
    [mamba layers, b, ...]) holds the states of the b sequences whose
    rows x [b, s, d] carries, `m` the layer's index among the mamba
    layers.  One row a sequence (the decode tick) advances each state
    in place through the kernel; more rows scan in the chunked form.
    With a riding `chunk` x is the joined stream [1, B + n, d]: the
    slots' rows take the kernel against their states and the chunk's
    the chunked scan from its own, `own` = (its states, its snapshot
    {'ssm', 'xbc'} after `chunk.snap_at` rows), so no row reads another
    sequence's state.  Returns (x, state, own)."""
    mp = lp['mamba']
    inner = cfg.ssm_inner
    with jax.named_scope('mamba_mixer'):
        h = _norm(x, lp['mamba_norm']['scale'], cfg)
        proj = jnp.einsum('bsd,de->bse', h, maybe_dequant(
            mp['in_proj']['kernel'], h.dtype))
        z, xbc, dt = jnp.split(proj, [inner, inner + cfg.ssm_conv_dim],
                               axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             mp['dt_bias'].astype(jnp.float32))
        if chunk is None and x.shape[1] == 1:
            y, state = _ssm_step(xbc[:, 0], dt[:, 0], mp, m, state, cfg,
                                 row_mask)
            y = y[:, None]
        elif chunk is None:
            y, state, _ = _ssm_rows(xbc, dt, mp, m, state, cfg)
        else:
            n = chunk.n
            y, state = _ssm_step(
                xbc[0, :-n], dt[0, :-n], mp, m, state, cfg,
                None if row_mask is None else row_mask[:-n])
            y_chunk, mine, snap = _ssm_rows(
                xbc[:, -n:], dt[:, -n:], mp, m, own[0], cfg, chunk.rows,
                chunk.snap_at)
            own = (mine, jax.tree.map(
                lambda leaf, new: _set_layer(leaf, m, new), own[1], snap))
            y = jnp.concatenate([y[None], y_chunk], axis=1)
        out = mamba.gated_norm(y.reshape(y.shape[:2] + (inner,)), z,
                               mp['norm']['scale'], cfg.ssm_groups,
                               cfg.norm_eps, x.dtype)
        out = jnp.einsum('bse,ed->bsd', out, maybe_dequant(
            mp['out_proj']['kernel'], x.dtype))
    return x + out, state, own


def _ssm_step(xbc, dt, mp, m, state, cfg, active):
    """One row a sequence: xbc [b, C] and dt [b, H] of the b sequences
    whose states `state` holds -> (y [b, H, P] float32 with the D x
    term, the states advanced).  A sequence that `active` [b] (None:
    all) leaves out keeps its state."""
    with jax.named_scope('ssm_state'):
        tail = _layer_of(state['xbc'], m)                  # [b, K - 1, C]
        cat = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)],
                              axis=1)
        u = mamba.conv_rows(cat, mp['conv_kernel'], mp['conv_bias'], 1)
        xs, bs, cs = mamba.split_xbc(u[:, 0], cfg.ssm_heads,
                                     cfg.ssm_groups, cfg.ssm_state)
        decay = jnp.exp(dt * -jnp.exp(mp['A_log'].astype(jnp.float32)))
        live = (jnp.ones(dt.shape[:1], jnp.int32) if active is None
                else active.astype(jnp.int32))
        ssm, y = ssm_decode_step(
            state['ssm'], m, jnp.swapaxes(dt[..., None] * xs, 1, 2),
            decay[:, None], bs, cs, live)
        y = (jnp.swapaxes(y, 1, 2) +
             mp['D'].astype(jnp.float32)[:, None] * xs)
        new_tail = cat[:, 1:]
        if active is not None:
            new_tail = jnp.where(active[:, None, None], new_tail, tail)
        return y, {'ssm': ssm, 'xbc': _set_layer(state['xbc'], m,
                                                 new_tail)}


def _ssm_rows(xbc, dt, mp, m, state, cfg, rows=None, snap_at=None):
    """Rows of b sequences in the chunked form: xbc [b, s, C], dt
    [b, s, H] -> (y [b, s, H, P] float32 with the D x term, the states
    after the first `rows` rows (int32 scalar; None: all s), the states
    after the first `snap_at` rows or None)."""
    s = xbc.shape[1]
    tail = _layer_of(state['xbc'], m)
    cat = jnp.concatenate([tail, xbc.astype(tail.dtype)], axis=1)
    u = mamba.conv_rows(cat, mp['conv_kernel'], mp['conv_bias'], s)
    xs, bs, cs = mamba.split_xbc(u, cfg.ssm_heads, cfg.ssm_groups,
                                 cfg.ssm_state)
    if rows is not None:
        dt = jnp.where(jnp.arange(s)[None, :, None] < rows, dt, 0.0)
    a_rate = -jnp.exp(mp['A_log'].astype(jnp.float32))
    s0 = _layer_of(state['ssm'], m)
    with jax.named_scope('ssm_chunk_scan'):
        y, s_end = mamba.chunk_scan(xs, dt, a_rate, bs, cs, s0,
                                    cfg.ssm_chunk)
    y = y + mp['D'].astype(jnp.float32)[:, None] * xs
    width = tail.shape[1]
    new_tail = (cat[:, s:] if rows is None else
                jax.lax.dynamic_slice_in_dim(cat, rows, width, axis=1))
    state = {'ssm': _set_layer(state['ssm'], m, s_end),
             'xbc': _set_layer(state['xbc'], m, new_tail)}
    snap = None
    if snap_at is not None:
        with jax.named_scope('ssm_chunk_scan'):
            snap = {'ssm': mamba.state_at(xs, dt, a_rate, bs, s0, snap_at),
                    'xbc': jax.lax.dynamic_slice_in_dim(cat, snap_at, width,
                                                        axis=1)}
    return y, state, snap


def _conv_layer(x, lp, c, conv, own, *, cfg, row_mask, chunk):
    """One gated short-convolution layer (LFM2's): u = norm(x); [B | C |
    X] = u W_in; z = B * X; y_t = sum_j k_j z_{t-K+1+j} over each
    sequence's own rows, its carried tail before them; x + (C * y) W_out,
    then the FFN.  `conv` [conv layers, b, K - 1, d] holds the tails of
    the b sequences whose rows x [b, s, d] carries, `c` the layer's
    index among the conv layers.  With a riding `chunk` x is the joined
    stream [1, B + n, d]: the slots' rows take their tails and the
    chunk's rows its own, `own` = (tails [conv layers, 1, K - 1, d], the
    page snapshots [conv layers, pages, K - 1, d]), so no row reads
    another sequence's.  Returns (x, conv, own, counts)."""
    with jax.named_scope('short_conv'):
        h = _norm(x, lp['conv_norm']['scale'], cfg)
        gated = jnp.einsum('bsd,de->bse', h, maybe_dequant(
            lp['conv']['in_proj']['kernel'], h.dtype))
        b_gate, c_gate, xin = jnp.split(gated, 3, axis=-1)
        z = b_gate * xin
        kernel = lp['conv']['kernel']
        if chunk is None:
            y, tail, _ = _short_conv(z, _layer_of(conv, c), kernel)
        else:
            n = chunk.n
            y, tail, _ = _short_conv(z[0, :-n, None], _layer_of(conv, c),
                                     kernel)
            y_chunk, tail_own, cat = _short_conv(
                z[:, -n:], _layer_of(own[0], c), kernel, chunk.rows)
            y = jnp.concatenate([y[:, 0][None], y_chunk], axis=1)
            with jax.named_scope('conv_tail'):
                own = (_set_layer(own[0], c, tail_own),
                       _snapshots(own[1], c, cat, chunk.positions[0],
                                  chunk.rows, chunk.page_size))
        with jax.named_scope('conv_tail'):
            conv = _set_layer(conv, c, tail)
        out = jnp.einsum('bsd,de->bse', c_gate * y, maybe_dequant(
            lp['conv']['out_proj']['kernel'], h.dtype))
    x, counts = _ffn_residual(x + out, lp, cfg, row_mask)
    return x, conv, own, counts


def _short_conv(z, tail, kernel, rows=None):
    """The causal depthwise convolution of each sequence's new rows z
    [b, s, d] after its tail [b, K - 1, d] (its K - 1 rows before them,
    oldest first; zeros before position 0), kernel [K, d]: y_t =
    sum_j kernel[j] * z_{t - K + 1 + j}, in float32.  Returns (y [b, s,
    d] in z's dtype, the new tail: the K - 1 rows before row `rows` of
    z (s where None: a padded chunk's real rows end there), and the
    rows [tail; z])."""
    width, s = kernel.shape[0], z.shape[1]
    cat = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
    rows32 = cat.astype(jnp.float32)
    taps = kernel.astype(jnp.float32)
    y = sum(taps[j] * rows32[:, j:j + s] for j in range(width))
    if rows is None:
        new = cat[:, s:]
    else:
        new = jax.lax.dynamic_slice_in_dim(cat, rows, width - 1, axis=1)
    return y.astype(z.dtype), new, cat


def _set_layer(cache, l, new):
    """`cache` [L, ...] with layer `l` replaced by `new`."""
    return jax.lax.dynamic_update_index_in_dim(
        cache, new.astype(cache.dtype), l, axis=0)


def _snapshots(snap, c, cat, start, rows, page_size: int):
    """Conv layer `c`'s tail at the end of every page that ends inside
    a prefill chunk's real rows, into the prompt's snapshots snap [conv
    layers, pages, K - 1, d] at the page's index: what a prefix hit on
    that page restores (`paged_seed_private`).  cat [1, K - 1 + n, d]
    holds the chunk's rows after its tail, the first at position
    start - K + 1, so the tail at page end e is cat[e - start :
    e - start + K - 1].  A page that ends past the real rows (`rows`,
    all n where None) is left alone: a later chunk writes it, or it is
    not full and no hit reaches it."""
    width = snap.shape[2]
    n = cat.shape[1] - width
    page = start // page_size + jnp.arange(n // page_size + 1)
    local = (page + 1) * page_size - start          # 1 .. n + page_size
    real = n if rows is None else rows
    idx = jnp.minimum(local, n)[:, None] + jnp.arange(width)[None, :]
    return snap.at[c, jnp.where(local <= real, page, snap.shape[1])].set(
        cat[0, idx].astype(snap.dtype), mode='drop')


def _layer_of(cache, l):
    """Layer `l`'s slice of a dense stacked cache [L, b, h_kv, len, d]."""
    return jax.lax.dynamic_index_in_dim(cache, l, axis=0, keepdims=False)


def _write_private(cache, l, new, start):
    """new [1, h_kv, s, d] into layer `l` of a single sequence's dense
    cache [L, 1, h_kv, len, d], at positions start .. start + s - 1."""
    return jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype)[None], (l, 0, 0, start, 0))


def _exit_select(cfg, gate, hs):
    """Which pass's hidden state the head reads, per position.

    hs [T, b, s, d]: every pass's normed output H_t.  The gate gives
    lam_t = sigmoid(H_t w + bias); a position leaves after pass t with
    probability p_t = lam_t * prod_{j<t} (1 - lam_j) (the last pass
    takes what is left), and the head reads the first pass at which
    sum_{j<=t} p_j reaches `cfg.exit_threshold`, else the last.  Every
    pass has run for every position whatever is selected: later
    positions attend this one's keys in every cache layer.  Returns
    (the selected H [b, s, d], p [T, b, s]).

    The gate's product, the sigmoid and the running products are
    float32 whatever the stream's dtype: a bfloat16 sigmoid reads
    exactly 1 from a logit near 6, and would end a position's passes
    where the model's do not.  T is small and static, so the running
    products are written out pass by pass, in one fixed order."""
    last = hs.shape[0] - 1          # whose own gate nothing reads
    w = gate['kernel'].astype(jnp.float32)[:, 0]
    lam = jax.nn.sigmoid(
        jnp.sum(hs[:last].astype(jnp.float32) * w, axis=-1) +
        gate['bias'].astype(jnp.float32)[0])
    stay = jnp.ones_like(lam[0])
    mass, reached = [], []
    total = jnp.zeros_like(stay)
    for t in range(last):
        mass.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
        total = total + mass[-1]
        reached.append(total >= cfg.exit_threshold)
    mass.append(stay)
    x = hs[last]
    for t in reversed(range(last)):
        x = jnp.where(reached[t][..., None], hs[t], x)
    return x, jnp.stack(mass)


def _forward_with_cache(cfg, params, tokens, cache, *, use_flash: bool,
                        mesh=None):
    """Shared prefill/step body: embeds tokens at cache['index'],
    updates every layer's cache, returns (logits_last, new_cache)."""
    _, s = tokens.shape
    start = cache['index']
    positions = start + jnp.arange(s)
    cache_len = start + s

    logits, new_k, new_v, _, _, _, conv = _scan_layers_and_unembed(
        cfg, params, _embed(cfg, params, tokens), positions,
        cache['k'], cache['v'],
        lambda c, l, new: _write_private(c, l, new, start),
        use_flash=use_flash, mesh=mesh, conv=_recurrent(cache))
    new = {'k': new_k, 'v': new_v, 'index': cache_len}
    if conv is not None:
        # The tails (or mamba states) after every row given (the
        # standalone programs are given no padding that a state could
        # take).
        new = dict(cache, **new, **_recurrent_items(conv))
    return logits, new


def prefill(cfg: ModelConfig, params, tokens, *, max_len: int,
            mesh=None):
    """Process the prompt [b, s] into a FRESH cache; returns
    (last-token logits [b, V], cache).  Flash-kernel attention (per
    shard under `mesh`, when the params are sharded over one).

    Builds the cache itself: the flash path is only correct from
    index 0 (it attends over the static [0, s) window), so accepting a
    caller-supplied cache would invite silent corruption on index>0.
    """
    cache = init_cache(cfg, tokens.shape[0], max_len)
    return _forward_with_cache(cfg, params, tokens, cache,
                               use_flash=True, mesh=mesh)


def decode_step(cfg: ModelConfig, params, token, cache):
    """One token [b, 1] -> (logits [b, V], cache).  jit this."""
    return _forward_with_cache(cfg, params, token, cache,
                               use_flash=False)


def prefill_sp(cfg: ModelConfig, params, tokens, *, mesh, max_len: int,
               axis_name: str = 'sequence'):
    """Sequence-parallel full-prompt prefill for multi-host slices.

    tokens [1, S] (S divisible by the mesh's sequence-axis size) ->
    a private prefill cache {'k', 'v', 'index'} with k/v
    [L, 1, h_kv, max_len, d] — the SAME layout the chunked admission
    path produces, so `insert_prefill_pages` adopts it unchanged.  Attention runs through ops/ring_attention over the
    'sequence' axis: each host holds S/P positions and k/v chunks
    rotate the ring, so a 100k-token context splits its quadratic
    attention (and its activation memory) across the slice instead of
    OOMing one host.  Projections and MLP stay GSPMD-partitioned (the
    params keep their fsdp/tensor sharding; activations are constrained
    onto the sequence axis), matching models/transformer.py's own SP
    composition.

    Exactness: k/v are cached post-RoPE exactly like
    `_scan_layers_and_unembed` writes them, and the ring merge is the
    same logaddexp-weighted flash combine the training path uses — so
    a slice replica's prefill is token-compatible with the
    single-process chunked path (pinned by tests/unit/
    test_slice_replica.py).

    Configs with a layer pattern are rejected: ring attention has no
    window, and the body below is the one block of a model whose layers
    are all alike, run once.
    """
    if cfg.conv_layers or cfg.mamba_layers:
        raise ValueError('sequence-parallel prefill cannot serve layers '
                         'with recurrent state (conv or mamba): it runs '
                         'through the sequence, and the ring splits it')
    if (cfg.layer_pattern or cfg.parallel_block or cfg.post_norms or
            cfg.loop_passes != 1 or cfg.stacked_by_kind):
        raise ValueError('sequence-parallel prefill serves one kind of '
                         'layer, once (no layer_pattern, no parallel '
                         'block, no post_norms, one pass)')
    from skypilot_tpu.ops.ring_attention import ring_attention  # pylint: disable=import-outside-toplevel

    b, s = tokens.shape
    if b != 1:
        raise ValueError(f'prefill_sp serves one sequence, got '
                         f'batch {b}')
    positions = jnp.arange(s)
    x = _embed(cfg, params, tokens)
    if axis_name in mesh.axis_names:
        # Pin activations onto the sequence axis so the projections
        # below compute sequence-parallel instead of gathering the
        # whole prompt onto every host.
        seq_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, axis_name, None))
        x = jax.lax.with_sharding_constraint(x, seq_sharding)
    layers = _layer_params(params, cfg)

    def body(x, lp):
        h = _norm(x, lp['attn_norm']['scale'], cfg)
        q = _rope(_attn_proj(h, lp['attn']['q_proj'], cfg.n_heads,
                             cfg.head_dim), positions, cfg)
        k = _rope(_attn_proj(h, lp['attn']['k_proj'], cfg.n_kv_heads,
                             cfg.head_dim), positions, cfg)
        v = _attn_proj(h, lp['attn']['v_proj'], cfg.n_kv_heads,
                       cfg.head_dim)
        out = ring_attention(q, k, v, mesh=mesh, axis_name=axis_name,
                             causal=True,
                             sm_scale=cfg.head_dim ** -0.5)
        out = jnp.einsum('bhsk,hkd->bsd', out,
                         maybe_dequant(lp['attn']['o_proj']['kernel'],
                                       x.dtype))
        x = x + out
        h = _norm(x, lp['mlp_norm']['scale'], cfg)
        # k/v cached post-RoPE, exactly like the chunked write path.
        return x + _mlp(h, lp, cfg)[0].astype(x.dtype), (
            k.astype(cfg.dtype), v.astype(cfg.dtype))

    _, (ks, vs) = jax.lax.scan(body, x, layers)

    # ks/vs: [L, 1, h_kv, S, d] -> pad the position axis to max_len so
    # the cache drops into the engine's private-prefill slots verbatim.
    def pad(leaf):
        full = jnp.zeros(
            (cfg.n_layers, 1, cfg.n_kv_heads, max_len, cfg.head_dim),
            cfg.dtype)
        return jax.lax.dynamic_update_slice(
            full, leaf.astype(cfg.dtype), (0, 0, 0, 0, 0))

    return {'k': pad(ks), 'v': pad(vs),
            'index': jnp.asarray(s, jnp.int32)}


def prefill_chunk(cfg: ModelConfig, params, tokens, cache):
    """Continue a prefill at cache['index'] with a multi-token chunk.

    tokens [b, c] -> (last-position logits [b, V], cache with index
    advanced by c).  Uses the masked path with a per-query-position
    causal mask, so it is exact at ANY starting index — this is what
    lets a serving engine split a long prompt's prefill into bounded
    chunks interleaved with decode ticks instead of stalling every
    in-flight request for the whole prompt.  Chunk 0 can still use
    `prefill` (flash path); later chunks must come through here.
    """
    return _forward_with_cache(cfg, params, tokens, cache,
                               use_flash=False)


def sample(logits, rng, sampling: SamplingConfig):
    """logits [b, V] -> token ids [b]."""
    return _sample(logits, rng, sampling.temperature,
                   greedy=sampling.temperature <= 0.0,
                   top_k=sampling.top_k)


def _sample(logits, rng, temperature, *, greedy: bool, top_k: int):
    """Jit-friendly split: `greedy`/`top_k` are static (they change the
    graph shape); `temperature` is traced (a serving replica must not
    recompile per client-supplied float)."""
    if greedy:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k > 0:
        top = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < top, NEG_INF, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def _generate_impl(cfg, params, prompt, rng, temperature,
                   max_new_tokens, max_len, greedy, top_k, mesh):
    logits, cache = prefill(cfg, params, prompt, max_len=max_len,
                            mesh=mesh)
    rng, first_rng = jax.random.split(rng)
    first = _sample(logits, first_rng, temperature, greedy=greedy,
                    top_k=top_k)

    def step(carry, step_rng):
        token, cache = carry
        logits, cache = decode_step(cfg, params, token[:, None], cache)
        nxt = _sample(logits, step_rng, temperature, greedy=greedy,
                      top_k=top_k)
        return (nxt, cache), nxt

    (_, _), rest = jax.lax.scan(
        step, (first, cache), jax.random.split(rng, max_new_tokens - 1))
    new_tokens = jnp.concatenate(
        [first[:, None], rest.transpose(1, 0)], axis=1)
    return jnp.concatenate([prompt, new_tokens], axis=1), new_tokens


# One compile per (cfg, prompt shape, generation length, greedy flag,
# top_k) — cached at module level so every caller (model server, the
# serving bench, tests) reuses it.  Temperature is TRACED: client-
# supplied floats must not trigger recompiles (compile-storm DoS on a
# replica); top_k stays static because lax.top_k's k shapes the graph.
_generate_jit = jax.jit(
    _generate_impl,
    static_argnames=('cfg', 'max_new_tokens', 'max_len', 'greedy',
                     'top_k', 'mesh'))


def generate(cfg: ModelConfig, params, prompt, *, max_new_tokens: int,
             max_len: Optional[int] = None,
             sampling: Optional[SamplingConfig] = None,
             rng: Optional[jax.Array] = None, mesh=None
             ) -> Tuple[jax.Array, jax.Array]:
    """Greedy/temperature generation.  prompt [b, s] -> (tokens
    [b, s+max_new_tokens], new token slice [b, max_new_tokens]).

    The whole prefill + step loop runs as ONE cached jit: static
    shapes, one compile per configuration, the full decode device-side.
    """
    sampling = sampling or SamplingConfig()
    rng = (rng if rng is not None
           else jax.random.PRNGKey(sampling.seed))
    prompt_len = prompt.shape[1]
    max_len = max_len or (prompt_len + max_new_tokens)
    if max_len < prompt_len + max_new_tokens:
        raise ValueError(f'max_len {max_len} < prompt {prompt_len} + '
                         f'new {max_new_tokens}')
    return _generate_jit(
        cfg, params, prompt, rng,
        jnp.asarray(max(sampling.temperature, 1e-6), jnp.float32),
        max_new_tokens, max_len, sampling.temperature <= 0.0,
        sampling.top_k, mesh)


# -------------------------------------------------- slot-batched decoding
# Building blocks for continuous batching (serve/batching_engine.py):
# a fixed number of B slots, each at its OWN depth in the page pool
# (below), decoded together in one jit'd step.  Static shapes
# throughout — slots, not requests, are the batch dimension.


def batched_sample(logits, keys, temperature, top_k, *,
                   max_top_k: int = 64):
    """Per-slot token selection, fully on device: logits [B, V],
    keys [B, 2] (one PRNG key per slot), temperature [B] (<= 0 means
    greedy for that slot), top_k [B] (0 = no filtering).

    temperature and top_k are TRACED — per-request sampling params must
    not recompile a serving replica.  lax.top_k needs a static k, so
    the graph computes the top `max_top_k` once and each slot reads its
    own (traced) k-th threshold out of that table; submit-side
    validation keeps requested top_k <= max_top_k.  Row-for-row parity
    with `sample`: the same key and logits produce the same token
    (pinned by tests/unit/test_decode.py).
    """
    greedy_tok = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    kk = min(max(int(max_top_k), 1), logits.shape[-1])
    topvals = jax.lax.top_k(scaled, kk)[0]               # [B, kk]
    idx = jnp.clip(top_k - 1, 0, kk - 1)[:, None]
    kth = jnp.take_along_axis(topvals, idx, axis=1)      # [B, 1]
    scaled = jnp.where((top_k[:, None] > 0) & (scaled < kth),
                       NEG_INF, scaled)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled)


def init_engine_state(slots: int, max_stop_ids: int = 16
                      ) -> Dict[str, Any]:
    """Device-resident per-slot decode state for the serving engine:
    everything the hot loop needs so a tick never waits on Python.

    tokens      [B]    next input token (tick t+1 input IS tick t output)
    active      [B]    slot is decoding (flips off ON DEVICE at stop)
    remaining   [B]    max_new_tokens countdown
    stop_ids    [B,S]  per-slot stop set, -1 padded (multi-EOS)
    keys        [B,2]  per-slot PRNG key chain (split once per tick)
    temperature [B]    <= 0 -> greedy
    top_k       [B]    0 -> no filtering
    """
    return {
        'tokens': jnp.zeros((slots,), jnp.int32),
        'active': jnp.zeros((slots,), jnp.bool_),
        'remaining': jnp.zeros((slots,), jnp.int32),
        'stop_ids': jnp.full((slots, max_stop_ids), -1, jnp.int32),
        'keys': jnp.zeros((slots, 2), jnp.uint32),
        'temperature': jnp.zeros((slots,), jnp.float32),
        'top_k': jnp.zeros((slots,), jnp.int32),
    }


def _select_and_bookkeep(state, logits, new_cache, counts, exit_mass,
                         *, max_top_k: int):
    """The tick's tail: on-device token selection + stop/countdown
    bookkeeping (see `paged_engine_step`)."""
    active = state['active']
    with jax.named_scope('sampling'):
        split = jax.vmap(lambda k: jax.random.split(k, 2))(state['keys'])
        nxt = batched_sample(logits, split[:, 1], state['temperature'],
                             state['top_k'], max_top_k=max_top_k)
    nxt = jnp.where(active, nxt.astype(jnp.int32), state['tokens'])
    stopped = jnp.any(nxt[:, None] == state['stop_ids'], axis=1)
    remaining = state['remaining'] - active.astype(jnp.int32)
    finished = active & (stopped | (remaining <= 0))
    new_state = dict(
        state,
        tokens=nxt,
        active=active & ~finished,
        remaining=remaining,
        keys=split[:, 0],
    )
    return new_state, new_cache, finished, counts, exit_mass


# ------------------------------------------------------ paged KV cache
# Block-pool decoding (serve/cache_manager.py owns the host-side
# allocator): the KV cache is a fixed pool of PAGES
# [L, n_pages, h_kv, page_size, d] plus per-slot block tables — a
# slot's cache is the concatenation of the pages its table names, so
# memory is bounded by the tokens a request actually touches, not by
# slots * max_len.  Attention gathers pages by table index inside the
# jitted step; writes scatter one token into (page, offset) derived
# from the slot's length.  Optional int8 KV storage (per-page-per-head
# scales at token granularity, absmax symmetric like models/quantize)
# halves page bytes; dequant happens on the gathered operand where XLA
# fuses it into the attention einsum.


def _page_size_of(paged: Dict[str, Any]) -> int:
    return _pool_array(paged['k']).shape[3]


def _pool_array(leaf):
    return leaf['q'] if isinstance(leaf, dict) else leaf


def heads_per_row(cfg: ModelConfig, leaf) -> int:
    """The kv heads one row of the pool `leaf` holds side by side
    (`init_paged_cache`): 1 but where a narrow head shares a row."""
    return cfg.n_kv_heads // _pool_array(leaf).shape[-3]


def to_rows(x, leaf, axis: int):
    """x [..., d] with its h_kv heads on `axis`, laid out as rows of the
    pool `leaf` (`init_paged_cache`): where the pool's rows hold g heads,
    each g adjacent heads side by side in one row (the head axis
    becomes h_kv / g); the row then padded with zeros to the pool's
    width.  Only the piece written moves, never the pool."""
    pool = _pool_array(leaf)
    g = x.shape[axis] // pool.shape[-3]
    if g > 1:
        x = jnp.moveaxis(x, axis, -2)
        x = jnp.moveaxis(x.reshape(x.shape[:-2] + (
            x.shape[-2] // g, g * x.shape[-1])), -2, axis)
    pad = pool.shape[-1] - x.shape[-1]
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) +
                                     [(0, pad)])


def from_rows(x, cfg: ModelConfig, axis: int):
    """`to_rows` undone: x, pool rows with their heads on `axis` ->
    [..., d] with the model's h_kv heads there."""
    d = cfg.head_dim
    g = cfg.n_kv_heads // x.shape[axis]
    if g == 1:
        return x[..., :d]
    x = jnp.moveaxis(x, axis, -2)[..., :g * d]
    return jnp.moveaxis(x.reshape(x.shape[:-2] + (x.shape[-2] * g, d)),
                        -2, axis)


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     slots: int, max_pages_per_slot: int,
                     quantize_kv: bool = False,
                     head_lanes: int = 0,
                     sharded_heads: bool = False,
                     snapshots: int = 0) -> Dict[str, Any]:
    """Zeroed page-pool cache.  k/v are [L, n_pages, h_kv, ps, d], L
    the cache's layers (`cfg.cache_layers`: a page holds its tokens'
    keys of every layer and pass; int8 {'q','scale'} leaves when
    quantize_kv); block_tables [B, P]
    name each slot's pages in order (0 = the reserved null page) and
    lengths [B] are the per-slot decode depths.

    The layout is row-major as written, a page of a layer one
    contiguous [h_kv, ps, d] slab: it is what the paged kernel's page
    copies read and write back (`hbm.at[layer, page]`), and the decode
    tick keeps the donated leaves in it from argument to result: a
    tick's new rows go in through the kernel (one token a slot, a bf16
    pool, one device) or as scatters of [d] rows at (layer, page, head,
    offset) (the verify tick, int8 pools, a mesh, the gather path), and
    nothing slices the leaves by layer (`_paged_forward`).

    A model with conv layers holds beside the pool, which has its
    attention layers only, each slot's conv tails 'conv' [conv layers,
    slots, K - 1, d] (the tick carries and advances them) and each
    page's 'conv_snap' [n_pages, K - 1, conv layers, d]: the tails at
    the end of a prompt page that prefill filled (`insert_prefill_pages`),
    which a prefix hit on the page restores.  So a cached page carries
    its tails wherever the prefix cache's hashing and eviction take it.
    The null page's stay zero: the tails before position 0.

    A model with mamba layers holds beside the pool each slot's states
    ('ssm', 'xbc': `_ssm_states` of `slots`; the tick advances them in
    place) and a store of `snapshots` entries of every mamba layer's
    states ('ssm_store', 'xbc_store'), which the engine fills at chosen
    page boundaries of prompts (`save_snapshot`) and a prefix hit
    restores (`paged_seed_private`, `paged_admit_slot`): a page's
    snapshot would be a sequence's whole state, so pages do not carry
    one.

    `head_lanes` (the engine's, where the paged kernel runs on a TPU: 128)
    makes a row a whole number of that many lanes.  The kernel copies a
    page of a layer by one DMA, and Mosaic slices a DMA's last axis by
    whole 128-lane tiles only.  A head of d < 128 that divides the row
    shares it: g = 128 / d adjacent kv heads side by side
    (`heads_per_row`), a page [h_kv / g, ps, 128], so the kernel copies
    the keys' own bytes and no padding (LFM2's 8 heads of 64: 4 KiB a
    token of keys where padded rows read 8).  Where the heads cannot
    share rows they are padded with zeros: an int8 pool (its scales are
    a head's), `sharded_heads` (the engine's under a mesh of more than
    one device, which may split the kv heads by a tensor degree that
    divides h_kv but not h_kv / g; a slice's follower mirrors its rank
    0), h_kv not a multiple of g, d not dividing 128.  The layout is read from these arguments
    alone, so every rank given them builds the same pool.  Whatever
    writes rows lays them out so (`to_rows`), whatever reads them takes
    the heads back (`from_rows`), and the kernel spreads the query over
    the row's lanes (`ops/paged_attention`).  A head of 128 is its own
    row."""
    width, h_kv = cfg.head_dim, cfg.n_kv_heads
    if head_lanes:
        width = -(-width // head_lanes) * head_lanes
        g = width // cfg.head_dim
        if (width % cfg.head_dim == 0 and h_kv % g == 0 and
                not quantize_kv and not sharded_heads):
            h_kv //= g
    kv_shape = (cfg.cache_layers, n_pages, h_kv, page_size, width)

    def kv_leaf():
        if quantize_kv:
            return {'q': jnp.zeros(kv_shape, jnp.int8),
                    'scale': jnp.ones(kv_shape[:-1], jnp.float32)}
        return jnp.zeros(kv_shape, cfg.dtype)

    paged = {
        'k': kv_leaf(),
        'v': kv_leaf(),
        'block_tables': jnp.zeros((slots, max_pages_per_slot),
                                  jnp.int32),
        'lengths': jnp.zeros((slots,), jnp.int32),
    }
    if cfg.conv_layers:
        paged['conv'] = _conv_tails(cfg, slots)
        # Pages lead, and a page's [K - 1, conv layers, d] slab has the
        # layers as its rows: what a prefill's scatter writes whole, in
        # the layout the compiler gives the leaf.  Laid out [conv
        # layers, n_pages, K - 1, d] (or pages first with K - 1 rows),
        # the leaf was copied whole to another layout around each
        # scatter: 0.4 GB and 2.5 ms an admission (compiled for a v5e;
        # chip trace, PR 37).
        paged['conv_snap'] = _conv_tails(cfg, n_pages).transpose(
            1, 2, 0, 3)
    if cfg.mamba_layers:
        paged.update(_ssm_states(cfg, slots))
        paged.update({f'{key}_store': value for key, value in _ssm_states(
            cfg, snapshots, store=True).items()})
    return paged


def _quant_kv(x):
    """Symmetric absmax int8 over the last (head_dim) axis: returns
    (int8 values, f32 scales without the last axis).  Round-trip
    stable: requantizing dequantized values reproduces the same bytes
    (the absmax element quantizes to exactly +-127)."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127,
                 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequant_kv(leaf_slice, dtype):
    """Dequantize a gathered int8 kv slice {'q','scale'} (or pass an
    array through).  The multiply fuses into the consuming einsum's
    operand read — int8 stays the HBM-resident form."""
    if isinstance(leaf_slice, dict):
        return (leaf_slice['q'].astype(dtype) *
                leaf_slice['scale'].astype(dtype)[..., None])
    return leaf_slice.astype(dtype)


def _paged_forward(cfg: ModelConfig, params, tokens, paged, *,
                   kernel=None, all_positions: bool = False, mesh=None,
                   active=None, chunk=None):
    """Shared write-then-attend body for paged decode: tokens [B, S]
    land at positions lengths..lengths+S-1, then every query attends
    through the pool.  Returns (logits, new_k, new_v, counts, exit_p,
    chunk_kv, new_conv: the slots' conv tails or None) WITHOUT advancing
    lengths — callers own the bookkeeping
    (the speculative step only advances by the accepted count).  counts:
    the expert layers' over the rows of the `active` [B] slots (None
    without experts); exit_p: a looped stack's exit mass [passes, B, S
    or 1] of each unembedded position (None for one pass); chunk_kv:
    the private cache's (k, v) of a prefill `chunk` (`_ChunkRows`) that
    rode the step (S = 1), whose rows no count covers; None without.

    The pool leaves are handed to the layer loop whole and come back
    whole (its carry).  Layer l's new row of each (slot, token, kv
    head) goes to (l, block_tables[b, pos//ps], head, pos % ps) of the
    stacked leaf by one of two routes, chosen by what the call is
    (`kernel_writes_rows`).  Where the Pallas kernel runs, one token a
    slot, into a bf16 (or f32) pool on one device, the kernel writes
    the rows itself: the page it attends last is the one the row goes
    to, patched in VMEM and copied back, and the layer's pools are the
    kernel's results, aliased to its operands (`ops/paged_attention`).
    Everywhere else (the verify tick, int8 pools, a mesh of more than
    one device, the gather path) each row is one [d] row of a scatter
    before attention, which XLA does in place on a donated pool.  Every
    page but the null page holds the same bytes after the step either
    way (the null page takes rows nobody reads: a parked slot's, whose
    attention output, and so its next layer's rows, the two routes
    compute from different copies of that page).  The head is an index
    and not a slice of the scatter on
    purpose: with `[h_kv, d]` windows the TPU compiler gives the
    carried pool a layout with the heads beside the lanes, and then
    converts the WHOLE pool to the kernel's row-major layout and back
    around every layer (compiled for a v5e, PR 31); with [d] rows the
    pool keeps the one layout the kernel reads.  Positions past the
    slot's table ([n_rows * ps, ...))
    route to the reserved null page instead of clipping — clipping
    would corrupt the LAST VALID page of a near-full slot when a
    speculative tick writes drafts beyond the allocation.  Inactive
    slots still write (at their frozen length) — the engine parks
    freed slots' tables on the null page so a stale write can never
    corrupt recycled pages.

    kernel='pallas' hands attention a `_PagedView` of the whole pool
    and the layer's index (the Pallas kernel reads layer l's pages by
    table index in-grid), with the layer's new rows where the kernel
    writes them; None/'gather' gathers layer l's pages that the tables
    name into the dense view.
    """
    lengths = paged['lengths']                     # [B]
    tables = paged['block_tables']                 # [B, P]
    ps = _page_size_of(paged)
    n_rows = tables.shape[1]
    b, s_q = tokens.shape
    in_kernel = kernel_writes_rows(kernel, isinstance(paged['k'], dict),
                                   s_q, mesh)
    positions = lengths[:, None] + jnp.arange(s_q)[None, :]   # [B, S]
    rows_raw = positions // ps                     # [B, S]
    in_range = rows_raw < n_rows
    rows = jnp.clip(rows_raw, 0, n_rows - 1)
    pages = jnp.where(in_range,
                      jnp.take_along_axis(tables, rows, axis=1), 0)
    offsets = positions % ps                       # [B, S]
    flat_pages = pages.reshape(-1)                 # [B*S]
    flat_off = offsets.reshape(-1)

    def write(c, l, new):
        # new [B, h_kv, S, d] -> one pool row per (slot, token, pool
        # head) at (layer, page, head, offset) of the whole pool (why
        # the head is indexed too: the docstring).
        tok = to_rows(new.transpose(0, 2, 1, 3).reshape(
            b * s_q, new.shape[1], new.shape[3]), c, 1)  # [B*S, h, W]
        at = (l, flat_pages[:, None], jnp.arange(tok.shape[1])[None, :],
              flat_off[:, None])
        if isinstance(c, dict):
            q, scale = _quant_kv(tok)
            return {'q': c['q'].at[at].set(q),
                    'scale': c['scale'].at[at].set(scale)}
        return c.at[at].set(tok.astype(c.dtype))

    if kernel == 'pallas':
        def view(c, l, new=None):
            return _PagedView(c, l, tables, lengths, new)
    else:
        def view(c, l):
            # Gather layer l's pool rows that each slot's table names
            # -> [B, P, h_kv, ps, d], dequantized, then fold pages into
            # the position axis (table order IS position order).
            if isinstance(c, dict):
                arr = _dequant_kv({'q': c['q'][l, tables],
                                   'scale': c['scale'][l, tables]},
                                  cfg.dtype)
            else:
                arr = c[l, tables]
            bb, p, h, s, w = arr.shape
            return from_rows(arr.transpose(0, 2, 1, 3, 4).reshape(
                bb, h, p * s, w), cfg, 1)

    x = _embed(cfg, params, tokens)
    row_mask = None if active is None else jnp.repeat(active, s_q)
    if chunk is not None and row_mask is not None:
        row_mask = jnp.pad(row_mask, (0, chunk.n))   # none of its rows
    return _scan_layers_and_unembed(
        cfg, params, x, positions,
        paged['k'], paged['v'], None if in_kernel else write,
        use_flash=False, view_fn=view,
        all_positions=all_positions, mesh=mesh, row_mask=row_mask,
        chunk=chunk, conv=_recurrent(paged))


def kernel_writes_rows(kernel, quantized: bool, s_q: int,
                       mesh=None) -> bool:
    """Whether a paged step of `s_q` tokens a slot writes its new K/V
    rows through the decode kernel (`kernel` 'pallas', where the kernel
    can: `paged_attention_ops.writes_in_kernel`) rather than by the
    scatter of `_paged_forward`.  The engine counts its ticks' writes
    by it (`stats()['paged_kernel']`)."""
    return kernel == 'pallas' and paged_attention_ops.writes_in_kernel(
        quantized, s_q, mesh)


def paged_batched_step(cfg: ModelConfig, params, tokens, paged,
                       active=None, *, kernel=None, mesh=None):
    """One decode step across ALL slots against the page pool; each
    slot attends its own depth (the gathered pages in table order ARE
    the slot's cache with positions page_index * page_size + offset;
    the Pallas kernel path computes the same online-softmax sums
    without materialising the gather).  tokens [B, 1]; returns (logits
    [B, V], new paged cache, the expert layers' counts over the active
    slots or None, a looped stack's exit mass [passes] summed over the
    active slots or None).  Without `active`, every length advances by 1
    (callers ignore/reset inactive slots).  With `active` [B] bool,
    only active slots advance — inactive slots' writes land at their
    frozen length (garbage that is overwritten by the next admission)
    and their logits are garbage the caller masks out."""
    return _paged_tick(cfg, params, tokens, paged, active, kernel=kernel,
                       mesh=mesh)[:4]


def _paged_tick(cfg, params, tokens, paged, active, *, kernel, mesh,
                chunk=None):
    """`paged_batched_step` and, fifth, the private cache's (k, v) of a
    prefill `chunk` that rode it (None without one)."""
    logits, new_k, new_v, counts, exit_p, chunk_kv, conv = _paged_forward(
        cfg, params, tokens, paged, kernel=kernel, mesh=mesh,
        active=active, chunk=chunk)
    lengths = paged['lengths']
    advance = (jnp.ones_like(lengths) if active is None
               else active.astype(lengths.dtype))
    new = dict(paged, k=new_k, v=new_v, lengths=lengths + advance)
    new.update(_recurrent_items(conv))
    return (logits, new, counts,
            _exit_mass(exit_p, None if active is None
                       else active[:, None]), chunk_kv)


def _exit_mass(exit_p, decoded):
    """exit_p [passes, B, S] summed over the (slot, position) pairs
    `decoded` [B, S] marks (None: all) -> float32 [passes]: how much of
    the tokens decoded left after each pass.  None where the model has
    one pass."""
    if exit_p is None:
        return None
    if decoded is not None:
        exit_p = jnp.where(decoded[None], exit_p, 0.0)
    return jnp.sum(exit_p, axis=(1, 2))


def paged_engine_step(cfg: ModelConfig, params, state, paged, *,
                      max_top_k: int = 64, kernel=None, mesh=None):
    """One fully-on-device serving tick: decode every active slot
    against the page pool, select its next token (greedy or
    temperature/top-k), and update the stop bookkeeping — no host
    round-trip anywhere in the loop.

    Returns (new_state, new_paged, finished [B], counts, exit_mass):
    counts is None for a model without experts, else the expert layers'
    int32 [3] counts of the tick over the active slots
    (`moe.moe_apply`); exit_mass is None for a model of one pass, else
    float32 [passes], the share of the tick's decoded tokens that left
    after each pass (`_exit_select`).  The engine reads both one tick
    behind with `finished`.
    new_state['tokens'] is the next tick's input, so the engine can
    dispatch tick t+1 before fetching tick t's tokens and read results
    one tick behind; slots that stop at tick t are already inactive ON
    DEVICE when tick t+1 runs, so the pipelined tick never decodes past
    a stop.  Inactive slots freeze: their token/remaining are unchanged
    and their cache length does not advance."""
    return _select_and_bookkeep(state, *paged_batched_step(
        cfg, params, state['tokens'][:, None], paged,
        state['active'], kernel=kernel, mesh=mesh),
        max_top_k=max_top_k)


def paged_engine_step_with_chunk(cfg: ModelConfig, params, state, paged,
                                 tokens, cache=None, rows=None,
                                 snap_at=None, *, max_len: int,
                                 max_top_k: int = 64, kernel=None,
                                 mesh=None):
    """`paged_engine_step` with one prefill chunk riding it: one
    program in which every layer's weights are read once, for the live
    slots' tokens and the chunk's rows together.  Between two ticks a
    standalone chunk streams the very weights the tick has just
    streamed, for rows that fit under the same read.

    tokens [1, c] is the chunk; `cache` its prompt's private cache
    ({'k', 'v', 'index'}, k/v [L, 1, h_kv, max_len, d]; jit with it and
    `paged` donated), or None for a prompt's first chunk, which starts
    a fresh one of `max_len` and attends by the flash kernel, as
    `prefill` does; a later chunk attends by the masked path at
    cache['index'], as `prefill_chunk` does, and where `rows` (int32
    scalar) says how many of the c tokens are the prompt's and not
    padding, only over the blocks of rows that hold any.  Returns
    `paged_engine_step`'s five results and the private cache with index
    advanced by c (a model with conv layers: its tails after the
    chunk's `rows`, and the tails at the end of each page the chunk
    completed, `_snapshots`; a model with mamba layers: its states after
    the chunk's `rows` and, under 'snap', after its first `snap_at`
    rows, int32 scalar).  The tick's results are what the plain
    tick's would be: the head, sampling and the bookkeeping see the slots' rows
    only, and the counts (`moe`, `exit_mass`) cover the live slots and
    nothing of the chunk, whose rows are not unembedded (the prompt's
    last token rides a later tick, as after a standalone chunk)."""
    fresh = cache is None
    ps = _page_size_of(paged)
    if fresh:
        cache = _private_cache(cfg, max_len, ps)
    start = cache['index']
    chunk = _ChunkRows(_embed(cfg, params, tokens),
                       start + jnp.arange(tokens.shape[1]), cache['k'],
                       cache['v'], use_flash=fresh, rows=rows,
                       conv=_recurrent(cache), snap=cache.get('snap'),
                       page_size=ps, snap_at=snap_at)
    *tick, private = _paged_tick(
        cfg, params, state['tokens'][:, None], paged, state['active'],
        kernel=kernel, mesh=mesh, chunk=chunk)
    if isinstance(private, tuple):
        private = dict(zip(('k', 'v'), private))
    return _select_and_bookkeep(state, *tick, max_top_k=max_top_k) + (
        dict(private, index=start + tokens.shape[1]),)


def _private_cache(cfg: ModelConfig, max_len: int, page_size: int):
    """A prompt's fresh private prefill cache: `init_cache` of one
    sequence and, for a model with conv layers, room for the tails at
    the end of each of its pages ('snap', `_snapshots`); with mamba
    layers, for one snapshot of its states ('snap', `_mamba_layer`)."""
    cache = init_cache(cfg, 1, max_len)
    if cfg.conv_layers:
        cache['snap'] = _conv_tails(cfg, max_len // page_size)
    if cfg.mamba_layers:
        cache['snap'] = _ssm_states(cfg, 1)
    return cache


def paged_spec_engine_step(cfg: ModelConfig, params, state, paged,
                           drafts, *, max_top_k: int = 64, kernel=None,
                           mesh=None):
    """Self-speculative verify tick: ONE batched forward checks k
    drafted tokens per slot against the paged cache and the longest
    exact prefix (plus the bonus correction token) is emitted.

    drafts [B, k] are host-proposed continuations of state['tokens']
    (any valid vocab ids — wrong guesses cost nothing but the write).
    The forward feeds [t0, d1..dk] at positions len..len+k, writes all
    k+1 KV entries, and unembeds every position; token selection then
    replays the per-slot PRNG chain ONE SPLIT PER EMITTED TOKEN — so
    greedy output is byte-identical to plain ticking by construction,
    and sampled output is seed-deterministic parity (each emitted
    token sees the same (logits, key) pair a plain tick would have).
    Rejected drafts' KV writes land beyond the advanced length and are
    overwritten by the next tick before anything attends them;
    overflow past the slot's table routes to the reserved null page
    (see `_paged_forward`).

    Returns (new_state, new_paged, finished [B], toks [B, k+1],
    counts [B], the expert layers' counts or None, a looped stack's
    exit mass [passes] over the emitted tokens or None); the host
    pushes toks[b, :counts[b]] per live slot.  Inactive slots emit
    nothing (counts 0).
    """
    if cfg.conv_layers or cfg.mamba_layers:
        raise ValueError(
            'the speculative tick cannot serve layers with recurrent '
            'state (conv or mamba): a rejected draft would leave its rows '
            'in the state, which keeps no position to roll back to')
    active = state['active']
    b, _ = drafts.shape
    s_q = drafts.shape[1] + 1
    tokens = jnp.concatenate(
        [state['tokens'][:, None], jnp.asarray(drafts, jnp.int32)],
        axis=1)                                    # [B, S]
    logits, new_k, new_v, moe_counts, exit_p, _, _ = _paged_forward(
        cfg, params, tokens, paged, kernel=kernel, all_positions=True,
        mesh=mesh, active=active)

    # Per-slot key chain: position j samples with exactly the key a
    # plain tick would use at that step; carries[j] is the post-split
    # carry after j+1 splits (matches _select_and_bookkeep's
    # split-sample-carry convention).
    def chain(key):
        def body(c, _):
            s = jax.random.split(c, 2)
            return s[0], (s[0], s[1])
        _, (carries, skeys) = jax.lax.scan(body, key, None, length=s_q)
        return carries, skeys

    with jax.named_scope('sampling'):
        carries, skeys = jax.vmap(chain)(state['keys'])  # [B, S, 2] each
        vocab = logits.shape[-1]
        toks = batched_sample(
            logits.reshape(b * s_q, vocab), skeys.reshape(b * s_q, 2),
            jnp.repeat(state['temperature'], s_q),
            jnp.repeat(state['top_k'], s_q),
            max_top_k=max_top_k).reshape(b, s_q).astype(jnp.int32)

    # Longest exact prefix: draft j is accepted iff it equals the
    # model's own output at the previous position AND everything
    # before it was accepted.
    match = (jnp.asarray(drafts, jnp.int32) == toks[:, :-1])
    accepted = jnp.cumprod(match.astype(jnp.int32), axis=1)
    num_accepted = jnp.sum(accepted, axis=1)       # [B] in 0..k

    # Emission replays the plain-tick stop/countdown bookkeeping
    # sequentially: position j emits iff it is inside the accepted
    # prefix (+1 bonus), no EARLIER emitted token was a stop (the stop
    # itself emits, like a plain tick), and the max_new_tokens
    # countdown still covers it.
    is_stop = jnp.any(
        toks[:, :, None] == state['stop_ids'][:, None, :], axis=2)
    stops_before = (jnp.cumsum(is_stop.astype(jnp.int32), axis=1) -
                    is_stop.astype(jnp.int32))
    idx = jnp.arange(s_q)[None, :]
    emit = ((idx <= num_accepted[:, None]) & (stops_before == 0) &
            (idx < state['remaining'][:, None]) & active[:, None])
    counts = jnp.sum(emit.astype(jnp.int32), axis=1)   # [B]

    last = jnp.clip(counts - 1, 0, s_q - 1)[:, None]
    nxt = jnp.take_along_axis(toks, last, axis=1)[:, 0]
    nxt = jnp.where(active, nxt, state['tokens'])
    new_keys = jnp.where(
        active[:, None],
        jnp.take_along_axis(carries, last[:, :, None], axis=1)[:, 0],
        carries[:, 0])
    remaining = state['remaining'] - counts
    emitted_stop = jnp.any(is_stop & emit, axis=1)
    finished = active & (emitted_stop | (remaining <= 0))
    new_state = dict(
        state,
        tokens=nxt,
        active=active & ~finished,
        remaining=remaining,
        keys=new_keys,
    )
    new_paged = dict(paged, k=new_k, v=new_v,
                     lengths=paged['lengths'] + counts)
    return (new_state, new_paged, finished, toks, counts, moe_counts,
            _exit_mass(exit_p, emit))


def paged_admit_slot(paged, slot, pages_row, length, tail=None,
                     snap_page=None, states=None, snapshot=None):
    """Point `slot` at its pages and depth (jit with paged donated).  A
    model with conv layers also sets the slot's tails: `tail` [conv
    layers, K - 1, d] (a prefill's), else the snapshot of pool page
    `snap_page` (the last page of a prefix hit; the null page's is the
    tails before position 0).  A model with mamba layers sets the
    slot's states: `states` (a prefill's private {'ssm', 'xbc'}, each
    [mamba layers, 1, ...]), else entry `snapshot` of the snapshot store
    (a prefix hit's), else zeros: a slot never keeps another request's
    state."""
    new = dict(
        paged,
        block_tables=paged['block_tables'].at[slot].set(
            jnp.asarray(pages_row, jnp.int32)),
        lengths=paged['lengths'].at[slot].set(
            jnp.asarray(length, jnp.int32)))
    # skytpu: lint-ok[tracer-safety] reason=a key of the pool's dict, known when traced
    if 'conv' in paged:
        if tail is None:
            tail = paged['conv_snap'][
                0 if snap_page is None else snap_page].transpose(1, 0, 2)
        new['conv'] = paged['conv'].at[:, slot].set(
            tail.astype(paged['conv'].dtype))
    # skytpu: lint-ok[tracer-safety] reason=a key of the pool's dict, known when traced
    if 'ssm' in paged:
        with jax.named_scope('ssm_state'):
            for key in ('ssm', 'xbc'):
                if states is not None:
                    value = states[key][:, 0]
                elif snapshot is not None:
                    value = jax.lax.dynamic_index_in_dim(
                        paged[f'{key}_store'], snapshot, keepdims=False)
                else:
                    value = jnp.zeros_like(paged[key][:, 0])
                new[key] = jax.lax.dynamic_update_index_in_dim(
                    paged[key], value.astype(paged[key].dtype), slot,
                    axis=1)
    return new


def save_snapshot(paged, states, entry):
    """Entry `entry` of the pool's snapshot store ('ssm_store',
    'xbc_store' [entries, mamba layers, ...]) set to `states` ({'ssm',
    'xbc'}, each [mamba layers, 1, ...]: a prefill's snapshot).  Jit
    with paged donated: the store is written in place."""
    with jax.named_scope('ssm_state'):
        return dict(paged, **{
            f'{key}_store': jax.lax.dynamic_update_index_in_dim(
                paged[f'{key}_store'],
                states[key][:, 0].astype(paged[f'{key}_store'].dtype),
                entry, axis=0)
            for key in ('ssm', 'xbc')})


def paged_release_slot(paged, slot):
    """Park a freed slot's table on the null page BEFORE its pages are
    recycled: the slot may still be written by an in-flight tick (at
    its frozen length), and that write must land in garbage nobody
    reads, not in a page the allocator just handed to someone else."""
    row = jnp.zeros((paged['block_tables'].shape[1],), jnp.int32)
    return dict(
        paged,
        block_tables=paged['block_tables'].at[slot].set(row),
        lengths=paged['lengths'].at[slot].set(jnp.zeros((), jnp.int32)))


def _private_as_pages(private_leaf, ps: int):
    """[L, 1, h_kv, T, d] private prefill cache -> [L, T/ps, h_kv,
    ps, d] page-major layout (T must be a multiple of ps)."""
    l, _, h, t, d = private_leaf.shape
    return private_leaf.reshape(l, h, t // ps, ps, d).transpose(
        0, 2, 1, 3, 4)


def insert_prefill_pages(paged, private_cache, pages_row, *,
                         first_page: int):
    """Scatter a completed private prefill cache into pool pages.

    private_cache k/v are [L, 1, h_kv, T, d] with T % page_size == 0;
    its pages [first_page, first_page + len(pages_row)) land in pool
    pages `pages_row` (skipping the first_page prefix-cache hits whose
    pool pages already hold identical content — rewriting a SHARED
    page, even with equal values, is what this avoids).  Jit with
    first_page static and paged donated.
    """
    ps = _page_size_of(paged)
    n = pages_row.shape[0]
    ids = jnp.asarray(pages_row, jnp.int32)

    def leaf(pool_leaf, private_leaf):
        piece = to_rows(_private_as_pages(private_leaf, ps)[
            :, first_page:first_page + n], pool_leaf, 2)  # [L, n, h, ps, W]
        if isinstance(pool_leaf, dict):
            q, scale = _quant_kv(piece)
            return {'q': pool_leaf['q'].at[:, ids].set(q),
                    'scale': pool_leaf['scale'].at[:, ids].set(scale)}
        return pool_leaf.at[:, ids].set(piece.astype(pool_leaf.dtype))

    with jax.named_scope('page_scatter'):
        new = dict(paged, k=leaf(paged['k'], private_cache['k']),
                   v=leaf(paged['v'], private_cache['v']))
    # skytpu: lint-ok[tracer-safety] reason=a key of the pool's dict, known when traced
    if 'conv_snap' in paged:
        # Each page's tails go with it (`init_paged_cache`).
        with jax.named_scope('conv_tail'):
            new['conv_snap'] = paged['conv_snap'].at[ids].set(
                private_cache['snap'][:, first_page:first_page + n]
                .transpose(1, 2, 0, 3))
    return new


# A pool leaf of this many bytes or more is copied out page by page
# where a smaller one is gathered (`paged_seed_private`).
_GATHER_LIMIT_BYTES = 1 << 31


def paged_seed_private(cfg: ModelConfig, paged, pages_row, snapshot=None,
                       *, priv_len: int):
    """Build a private prefill cache whose leading positions are the
    dequantized contents of cached pages `pages_row` — the prefix-hit
    admission path: the remaining prompt tokens then chunk-prefill
    against this cache from index len(pages_row) * page_size, exactly
    as if the prefix had been prefilled here.  Jit with priv_len
    static; paged is read-only (NOT donated).

    The pages are one gather out of the pool leaf, unless the leaf
    holds 2 GiB or more (a looped stack's 192 cache layers): the TPU
    compiler splits a gather over such an operand and copies two
    thirds of the leaf first (2.2 GB of temporaries beside a 3.3 GB
    leaf, compiled for a v5e, PR 35), so there the pages are sliced
    out one at a time under a scan, which copies nothing else.

    A model with mamba layers takes its states from entry `snapshot` of
    the snapshot store: the states after the hit's last page (no page
    of keys could give them again)."""
    ps = _page_size_of(paged)
    r = pages_row.shape[0]
    ids = jnp.asarray(pages_row, jnp.int32)

    def leaf(pool_leaf):
        whole = (pool_leaf['q'] if isinstance(pool_leaf, dict)
                 else pool_leaf)
        if whole.nbytes >= _GATHER_LIMIT_BYTES:
            return _seed_by_pages(pool_leaf)
        if isinstance(pool_leaf, dict):
            arr = _dequant_kv({'q': pool_leaf['q'][:, ids],
                               'scale': pool_leaf['scale'][:, ids]},
                              cfg.dtype)
        else:
            arr = pool_leaf[:, ids]            # [L, r, h, ps, W]
        arr = from_rows(arr, cfg, 2)          # [L, r, h_kv, ps, d]
        l, _, h, _, d = arr.shape
        dense = arr.transpose(0, 2, 1, 3, 4).reshape(
            l, 1, h, r * ps, d)               # [L, 1, h_kv, r*ps, d]
        out = jnp.zeros((l, 1, h, priv_len, d), cfg.dtype)
        return out.at[:, :, :, :r * ps, :].set(dense.astype(cfg.dtype))

    def _seed_by_pages(pool_leaf):
        def put(out, i):
            page = _dequant_kv(jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, ids[i], axis=1, keepdims=False), pool_leaf),
                cfg.dtype)
            page = from_rows(page, cfg, 1)    # [L, h_kv, ps, d]
            return jax.lax.dynamic_update_slice(
                out, page[:, None], (0, 0, 0, i * ps, 0)), None

        l = jax.tree.leaves(pool_leaf)[0].shape[0]
        return jax.lax.scan(
            put, jnp.zeros((l, 1, cfg.n_kv_heads, priv_len, cfg.head_dim),
                           cfg.dtype),
            jnp.arange(r))[0]

    cache = {'k': leaf(paged['k']), 'v': leaf(paged['v']),
             'index': jnp.asarray(r * ps, jnp.int32)}
    if 'conv_snap' in paged:
        # The tails where the hit ends: the last cached page's snapshot
        # (z at a layer depends on the whole prefix: no page of keys
        # could give it again).
        with jax.named_scope('conv_tail'):
            cache['conv'] = paged['conv_snap'][ids[-1]].transpose(
                1, 0, 2)[:, None]
            cache['snap'] = _conv_tails(cfg, priv_len // ps)
    if 'ssm_store' in paged:
        with jax.named_scope('ssm_state'):
            cache.update({key: jax.lax.dynamic_index_in_dim(
                paged[f'{key}_store'], snapshot, keepdims=False)[:, None]
                for key in ('ssm', 'xbc')})
            cache['snap'] = _ssm_states(cfg, 1)
    return cache


def write_pages(paged, k_pages, v_pages, pages_row):
    """Adopt IMPORTED page contents into pool pages (KV handoff).

    k_pages/v_pages are float `[L, n, h_kv, ps, d]` (the wire format
    dequantizes int8 payloads to f32 before this); they land in pool
    pages `pages_row`, quantized on the way in when the pool is int8 —
    `_quant_kv` is round-trip stable, so a quantize -> dequantize ->
    requantize chain across replicas reproduces the same bytes as a
    local prefill would have written.  Jit with paged donated.
    """
    ids = jnp.asarray(pages_row, jnp.int32)

    def leaf(pool_leaf, piece):
        piece = to_rows(piece, pool_leaf, 2)
        if isinstance(pool_leaf, dict):
            q, scale = _quant_kv(piece)
            return {'q': pool_leaf['q'].at[:, ids].set(q),
                    'scale': pool_leaf['scale'].at[:, ids].set(scale)}
        return pool_leaf.at[:, ids].set(piece.astype(pool_leaf.dtype))

    return dict(paged, k=leaf(paged['k'], k_pages),
                v=leaf(paged['v'], v_pages))


def write_pages_quantized(paged, k_q, v_q, k_scale, v_scale,
                          pages_row):
    """Adopt ALREADY-QUANTIZED page contents into an int8 pool (the
    int8->int8 handoff fast path): the wire's q/scale bytes land
    verbatim — no dequantize/requantize round trip on the decode
    replica's critical path, and byte-identity with the exporter is
    trivial.  Jit with paged donated."""
    ids = jnp.asarray(pages_row, jnp.int32)

    def leaf(pool_leaf, q, scale):
        q = to_rows(q, pool_leaf, 2)
        return {'q': pool_leaf['q'].at[:, ids].set(q),
                'scale': pool_leaf['scale'].at[:, ids].set(scale)}

    return dict(paged, k=leaf(paged['k'], k_q, k_scale),
                v=leaf(paged['v'], v_q, v_scale))


def export_private_pages(private_cache, n_pages: int, page_size: int,
                         quantize: bool = False):
    """Slice a private prefill cache's first `n_pages` FULL pages into
    page-major layout for the handoff wire.

    Returns `(k, v)` as `[L, n_pages, h_kv, ps, d]` float32 arrays, or
    `(k, v, k_scale, v_scale)` with int8 values + f32 scales when
    `quantize` (the same `_quant_kv` the int8 pool uses, so receiver-
    side requantization is byte-identical)."""
    span = n_pages * page_size

    def leaf(private_leaf):
        return _private_as_pages(private_leaf[:, :, :, :span, :],
                                 page_size)

    k = leaf(private_cache['k'])
    v = leaf(private_cache['v'])
    if quantize:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        return kq, vq, ks, vs
    return k.astype(jnp.float32), v.astype(jnp.float32)


def admit_slot_state(state, slot, token, max_new_tokens, stop_row, key,
                     temperature, top_k):
    """Write one slot's admission into the engine state (jit this with
    the state donated): ONE dispatch per admission instead of seven
    eager `.at[slot].set` updates on the hot path."""
    return {
        'tokens': state['tokens'].at[slot].set(
            jnp.asarray(token, jnp.int32)),
        'active': state['active'].at[slot].set(True),
        'remaining': state['remaining'].at[slot].set(
            jnp.asarray(max_new_tokens, jnp.int32)),
        'stop_ids': state['stop_ids'].at[slot].set(
            jnp.asarray(stop_row, jnp.int32)),
        'keys': state['keys'].at[slot].set(key),
        'temperature': state['temperature'].at[slot].set(
            jnp.asarray(temperature, jnp.float32)),
        'top_k': state['top_k'].at[slot].set(
            jnp.asarray(top_k, jnp.int32)),
    }
