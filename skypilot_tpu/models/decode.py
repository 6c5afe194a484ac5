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
from skypilot_tpu.models import moe
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.quantize import is_quantized_leaf
from skypilot_tpu.models.quantize import maybe_dequant
from skypilot_tpu.models.transformer import _rope
from skypilot_tpu.ops import paged_attention as paged_attention_ops
from skypilot_tpu.ops.attention import NEG_INF
from skypilot_tpu.ops.attention import flash_attention


class _PagedView(NamedTuple):
    """The paged-KERNEL path's cache 'view': instead of gathering the
    pool into a dense [b, h_kv, len, d] array, attention receives the
    WHOLE pool leaf ([L, n_pages, h_kv, ps, d], never sliced by layer)
    + the layer's index + block tables + lengths, and the Pallas kernel
    does the (layer, table-indexed page) reads inside its grid (neither
    the layer's share nor the gathered view materialises in HBM).
    Produced by `_paged_forward`'s view_fn when kernel='pallas';
    `_layer_forward` dispatches on it."""
    leaf: Any
    layer: jax.Array
    tables: jax.Array
    lengths: jax.Array


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
    (pass, layer) of a looped stack: `cfg.cache_layers`)."""
    shape = (cfg.cache_layers, batch, cfg.n_kv_heads, max_len,
             cfg.head_dim)
    return {
        'k': jnp.zeros(shape, cfg.dtype),
        'v': jnp.zeros(shape, cfg.dtype),
        'index': jnp.zeros((), jnp.int32),
    }


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
    the one stacked group of the scanned layout, or each `layer_{i}`."""
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
    and slots batched)."""
    if cfg.n_experts > 0:
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
    not worth computing (`_attend`)."""
    x: Any
    positions: jax.Array
    k: Any
    v: Any
    use_flash: bool
    rows: Any = None

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
    k/v at `positions` -> [b, h_q, s, hd] in `dtype`.  `rows` (a
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
                mesh=mesh, window=window, layer=k_cache.layer)
        return out.astype(dtype)
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
                                   mesh=mesh)
    if rows is None or q.shape[2] <= _ATTEND_BLOCK:
        return _masked_attention(q, cfg, positions, k_cache, v_cache,
                                 window, dtype)
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
    return jnp.concatenate(blocks, axis=2)


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
                   window=None, row_mask=None, chunk=None):
    """One decoder layer against its view of the KV cache.

    x [b, s, d]; k_cache/v_cache [b, h_kv, max_len, hd] (or the paged
    kernel's `_PagedView` of the whole pool and the layer's index)
    already contain this call's k/v written at [positions].  Returns (the layer output,
    the expert layer's counts or None).  `mesh` (the mesh the params
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
    """
    h = _norm(x, lp['attn_norm']['scale'], cfg)
    q = _attn_proj(h, lp['attn']['q_proj'], cfg.n_heads, cfg.head_dim)
    if chunk is not None:
        q, chunk_q = _split_rows(q, chunk.n)
    q = _rope_if(rope_on, q, positions, cfg)
    out = _attend(q, cfg, positions, k_cache, v_cache, use_flash=use_flash,
                  mesh=mesh, window=window, dtype=x.dtype)
    if chunk is not None:
        chunk_q = _rope_if(rope_on, chunk_q, chunk.positions, cfg)
        out = _join_rows(out, _attend(
            chunk_q, cfg, chunk.positions, chunk.k, chunk.v,
            use_flash=chunk.use_flash, mesh=mesh, window=window,
            dtype=x.dtype, rows=chunk.rows))

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
                                    'mlp_post_norm', cfg), counts
    x = x + out
    h = _norm(x, lp['mlp_norm']['scale'], cfg)
    with jax.named_scope('mlp'):
        m, counts = _mlp(h, lp, cfg, row_mask)
    return x + _post_norm(m.astype(x.dtype), lp, 'mlp_post_norm',
                          cfg), counts


def _post_norm(out, lp, name: str, cfg):
    """Sandwich norms (`cfg.post_norms`): a sub-layer's output is
    normed, by a scale of its own, before it joins the residual."""
    return _norm(out, lp[name]['scale'], cfg) if cfg.post_norms else out


def _rope_if(rope_on, x, positions, cfg):
    """Rotary embedding where the layer's kind has one (`rope_on` None:
    every layer has)."""
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
                             mesh=None, row_mask=None, chunk=None):
    """The shared per-layer loop: project+rope k/v, write them into
    layer l of the cache via `write_fn(cache, l, new) -> cache`, run
    the layer against `view_fn(cache, l)`, then final-norm + unembed
    the last position.  Single-sequence decode, slot-batched decode and
    the page pool differ ONLY in write_fn / view_fn / positions shapes.
    Returns (logits, new_k, new_v, counts, exit_p, chunk_kv): counts is
    None for a model without experts, else the expert layers' int32 [3]
    counts summed over the layers (`moe.moe_apply`, over the rows
    `row_mask` marks); exit_p is None for a model of one pass, else the
    float32 [passes, b, s] exit mass of each unembedded position
    (below); chunk_kv is None without a `chunk`.

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
            h = _norm(x, lp['attn_norm']['scale'], cfg)
            k = _attn_proj(h, lp['attn']['k_proj'], cfg.n_kv_heads,
                           cfg.head_dim)
            v = _attn_proj(h, lp['attn']['v_proj'], cfg.n_kv_heads,
                           cfg.head_dim)
            rows = None
            if chunk is not None:
                k, chunk_k = _split_rows(k, chunk.n)
                v, chunk_v = _split_rows(v, chunk.n)
            k = _rope_if(rope_on, k, positions, cfg)
            with jax.named_scope('kv_write'):
                k_cache = write_fn(k_cache, l, k)
                v_cache = write_fn(v_cache, l, v)
                if chunk is not None:
                    chunk_k = _rope_if(rope_on, chunk_k, chunk.positions,
                                       cfg)
                    private = tuple(
                        _write_private(c, l, new, chunk.positions[0])
                        for c, new in zip(private, (chunk_k, chunk_v)))
                    rows = chunk._replace(k=_layer_of(private[0], l),
                                          v=_layer_of(private[1], l))
            x, counts = _layer_forward(
                x, lp, cfg, positions, view_fn(k_cache, l),
                view_fn(v_cache, l), use_flash=use_flash, mesh=mesh,
                rope_on=rope_on, window=window, row_mask=row_mask,
                chunk=rows)
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
                counts, None, private or None)

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
            counts, exit_p, private or None)


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

    logits, new_k, new_v, _, _, _ = _scan_layers_and_unembed(
        cfg, params, _embed(cfg, params, tokens), positions,
        cache['k'], cache['v'],
        lambda c, l, new: _write_private(c, l, new, start),
        use_flash=use_flash, mesh=mesh)
    return logits, {'k': new_k, 'v': new_v, 'index': cache_len}


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
    if (cfg.layer_pattern or cfg.parallel_block or cfg.post_norms or
            cfg.loop_passes != 1):
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
    leaf = paged['k']['q'] if isinstance(paged['k'], dict) else paged['k']
    return leaf.shape[3]


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     slots: int, max_pages_per_slot: int,
                     quantize_kv: bool = False) -> Dict[str, Any]:
    """Zeroed page-pool cache.  k/v are [L, n_pages, h_kv, ps, d], L
    the cache's layers (`cfg.cache_layers`: a page holds its tokens'
    keys of every layer and pass; int8 {'q','scale'} leaves when
    quantize_kv); block_tables [B, P]
    name each slot's pages in order (0 = the reserved null page) and
    lengths [B] are the per-slot decode depths.

    The layout is row-major as written, a page of a layer one
    contiguous [h_kv, ps, d] slab: it is what the paged kernel's page
    copies read (`hbm.at[layer, page]`), and the decode tick keeps the
    donated leaves in it from argument to result: writes are scatters
    of [d] rows at (layer, page, head, offset) and nothing slices the
    leaves by layer (`_paged_forward`)."""
    kv_shape = (cfg.cache_layers, n_pages, cfg.n_kv_heads, page_size,
                cfg.head_dim)

    def kv_leaf():
        if quantize_kv:
            return {'q': jnp.zeros(kv_shape, jnp.int8),
                    'scale': jnp.ones(kv_shape[:-1], jnp.float32)}
        return jnp.zeros(kv_shape, cfg.dtype)

    return {
        'k': kv_leaf(),
        'v': kv_leaf(),
        'block_tables': jnp.zeros((slots, max_pages_per_slot),
                                  jnp.int32),
        'lengths': jnp.zeros((slots,), jnp.int32),
    }


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
    chunk_kv) WITHOUT advancing lengths — callers own the bookkeeping
    (the speculative step only advances by the accepted count).  counts:
    the expert layers' over the rows of the `active` [B] slots (None
    without experts); exit_p: a looped stack's exit mass [passes, B, S
    or 1] of each unembedded position (None for one pass); chunk_kv:
    the private cache's (k, v) of a prefill `chunk` (`_ChunkRows`) that
    rode the step (S = 1), whose rows no count covers; None without.

    The pool leaves are handed to the layer loop whole and come back
    whole (its carry): layer l's writes scatter each (slot, token, kv
    head) as one [d] row at (l, block_tables[b, pos//ps], head,
    pos % ps) of the stacked leaf, which XLA does in place on a donated
    pool.  The head is an index and not a slice of the scatter on
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
    table index in-grid); None/'gather' gathers layer l's pages that
    the tables name into the dense view.
    """
    lengths = paged['lengths']                     # [B]
    tables = paged['block_tables']                 # [B, P]
    ps = _page_size_of(paged)
    n_rows = tables.shape[1]
    b, s_q = tokens.shape
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
        # new [B, h_kv, S, d] -> one [d] row per (slot, token, head)
        # at (layer, page, head, offset) of the whole pool (why the
        # head is indexed too: the docstring).
        tok = new.transpose(0, 2, 1, 3).reshape(
            b * s_q, new.shape[1], new.shape[3])   # [B*S, h_kv, d]
        at = (l, flat_pages[:, None], jnp.arange(new.shape[1])[None, :],
              flat_off[:, None])
        if isinstance(c, dict):
            q, scale = _quant_kv(tok)
            return {'q': c['q'].at[at].set(q),
                    'scale': c['scale'].at[at].set(scale)}
        return c.at[at].set(tok.astype(c.dtype))

    if kernel == 'pallas':
        def view(c, l):
            return _PagedView(c, l, tables, lengths)
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
            bb, p, h, s, d = arr.shape
            return arr.transpose(0, 2, 1, 3, 4).reshape(bb, h, p * s, d)

    x = _embed(cfg, params, tokens)
    row_mask = None if active is None else jnp.repeat(active, s_q)
    if chunk is not None and row_mask is not None:
        row_mask = jnp.pad(row_mask, (0, chunk.n))   # none of its rows
    return _scan_layers_and_unembed(
        cfg, params, x, positions,
        paged['k'], paged['v'], write, use_flash=False, view_fn=view,
        all_positions=all_positions, mesh=mesh, row_mask=row_mask,
        chunk=chunk)


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
    logits, new_k, new_v, counts, exit_p, chunk_kv = _paged_forward(
        cfg, params, tokens, paged, kernel=kernel, mesh=mesh,
        active=active, chunk=chunk)
    lengths = paged['lengths']
    advance = (jnp.ones_like(lengths) if active is None
               else active.astype(lengths.dtype))
    return (logits, dict(paged, k=new_k, v=new_v,
                         lengths=lengths + advance), counts,
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
                                 tokens, cache=None, rows=None, *,
                                 max_len: int, max_top_k: int = 64,
                                 kernel=None, mesh=None):
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
    advanced by c.  The tick's results are what the plain tick's would
    be: the head, sampling and the bookkeeping see the slots' rows
    only, and the counts (`moe`, `exit_mass`) cover the live slots and
    nothing of the chunk, whose rows are not unembedded (the prompt's
    last token rides a later tick, as after a standalone chunk)."""
    fresh = cache is None
    if fresh:
        cache = init_cache(cfg, 1, max_len)
    start = cache['index']
    chunk = _ChunkRows(_embed(cfg, params, tokens),
                       start + jnp.arange(tokens.shape[1]), cache['k'],
                       cache['v'], use_flash=fresh, rows=rows)
    *tick, (new_k, new_v) = _paged_tick(
        cfg, params, state['tokens'][:, None], paged, state['active'],
        kernel=kernel, mesh=mesh, chunk=chunk)
    return _select_and_bookkeep(state, *tick, max_top_k=max_top_k) + (
        {'k': new_k, 'v': new_v, 'index': start + tokens.shape[1]},)


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
    active = state['active']
    b, _ = drafts.shape
    s_q = drafts.shape[1] + 1
    tokens = jnp.concatenate(
        [state['tokens'][:, None], jnp.asarray(drafts, jnp.int32)],
        axis=1)                                    # [B, S]
    logits, new_k, new_v, moe_counts, exit_p, _ = _paged_forward(
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


def paged_admit_slot(paged, slot, pages_row, length):
    """Point `slot` at its pages and depth (jit with paged donated)."""
    return dict(
        paged,
        block_tables=paged['block_tables'].at[slot].set(
            jnp.asarray(pages_row, jnp.int32)),
        lengths=paged['lengths'].at[slot].set(
            jnp.asarray(length, jnp.int32)))


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
        piece = _private_as_pages(private_leaf, ps)[
            :, first_page:first_page + n]      # [L, n, h_kv, ps, d]
        if isinstance(pool_leaf, dict):
            q, scale = _quant_kv(piece)
            return {'q': pool_leaf['q'].at[:, ids].set(q),
                    'scale': pool_leaf['scale'].at[:, ids].set(scale)}
        return pool_leaf.at[:, ids].set(piece.astype(pool_leaf.dtype))

    with jax.named_scope('page_scatter'):
        return dict(paged, k=leaf(paged['k'], private_cache['k']),
                    v=leaf(paged['v'], private_cache['v']))


# A pool leaf of this many bytes or more is copied out page by page
# where a smaller one is gathered (`paged_seed_private`).
_GATHER_LIMIT_BYTES = 1 << 31


def paged_seed_private(cfg: ModelConfig, paged, pages_row, *,
                       priv_len: int):
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
    out one at a time under a scan, which copies nothing else."""
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
            arr = pool_leaf[:, ids]            # [L, r, h_kv, ps, d]
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
                cfg.dtype)                     # [L, h_kv, ps, d]
            return jax.lax.dynamic_update_slice(
                out, page[:, None], (0, 0, 0, i * ps, 0)), None

        l, _, h, _, d = jax.tree.leaves(pool_leaf)[0].shape
        return jax.lax.scan(
            put, jnp.zeros((l, 1, h, priv_len, d), cfg.dtype),
            jnp.arange(r))[0]

    return {'k': leaf(paged['k']), 'v': leaf(paged['v']),
            'index': jnp.asarray(r * ps, jnp.int32)}


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
