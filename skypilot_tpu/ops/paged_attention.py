"""Pallas paged-attention decode kernel: block-table reads in-kernel.

The paged engine's fallback decode path gathers every slot's pages
into a dense `[b, h_kv, len, d]` view before attending
(`paged_batched_step`'s view closure) — fine on CPU emulation, a
bandwidth disaster on TPU: the gather materialises the whole cache
window in HBM every tick.  This kernel reads K/V pages directly from
the page pool by block-table index inside the kernel grid — the
gathered view never exists.  Grid is (slot, kv_head, table_row); the
block tables and per-slot lengths ride in scalar-prefetch memory so
each program's K/V BlockSpec index map picks its pool page
dynamically, and an online softmax accumulates across the table-row
grid axis in VMEM scratch (TPU grids iterate the minor axis
sequentially, so scratch carries between pages of the same slot).

Queries generalise to S tokens per slot (query row r sits at absolute
position `lengths[b] + r % S`), so one kernel serves single-token
decode (S=1) AND the self-speculative verify step (S=k+1) — drafts
are verified through the same paged kernel.

int8 pools (PR 7's per-page absmax scales) run the same kernel body
with the dequant fused into the score and probability tiles: the int8
bytes are what moves from HBM, the scales multiply VMEM-resident tiles.

Same interpret-mode pattern as ops/attention.py
(`SKYTPU_PALLAS_INTERPRET=1`, CPU backend only); off-TPU without
interpret mode a pure `jnp` gather reference with identical masking
math is used, and `SKYTPU_DECODE_KERNEL=pallas|gather` pins the
engine's path choice (default: pallas wherever Pallas can run, else
gather).

Shapes: q [B, h_q, S, d]; pool leaves [n_pages, h_kv, ps, d] (int8
pools: {'q': int8, 'scale': f32 [n_pages, h_kv, ps]}); tables [B, P];
lengths [B] (pre-write depths — the S new tokens are assumed already
written at positions lengths..lengths+S-1, exactly how
`paged_batched_step` orders write-then-attend).
"""
from __future__ import annotations

import functools
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from skypilot_tpu.ops.attention import NEG_INF
from skypilot_tpu.ops.attention import _LANES
from skypilot_tpu.ops.attention import _use_pallas
from skypilot_tpu.ops.attention import interpret_mode

KERNEL_CHOICES = ('pallas', 'gather')


def decode_kernel_choice() -> str:
    """Resolve the decode attention path: 'pallas' (this kernel) or
    'gather' (the dense page-gather view).  SKYTPU_DECODE_KERNEL pins
    it; default is pallas wherever Pallas can run (TPU, or CPU with
    SKYTPU_PALLAS_INTERPRET=1) and gather otherwise."""
    interpret_mode()  # refuses a non-CPU backend whatever the pin says
    choice = os.environ.get('SKYTPU_DECODE_KERNEL', '').strip().lower()
    if choice:
        if choice not in KERNEL_CHOICES:
            raise ValueError(
                f'SKYTPU_DECODE_KERNEL={choice!r}: expected one of '
                f'{KERNEL_CHOICES}')
        return choice
    return 'pallas' if _use_pallas() else 'gather'


def _paged_decode_kernel(tables_ref, lengths_ref, q_ref, *refs,
                         page_size: int, s_q: int, num_rows: int,
                         quantized: bool):
    """One (slot, kv_head, table_row) program streams its pool page
    through VMEM and folds it into the slot's online softmax.

    Refs: q [1, 1, R, d] pre-scaled f32 (R = rep * s_q); k/v
    [1, 1, ps, d] in the pool dtype; int8 pools add ks/vs
    [1, h_kv, ps] f32 per-token scales (the block carries every kv
    head of the page — a `(1, 1, ps)` block has a second-to-last dim
    Mosaic cannot tile — and the program reads its own head's row);
    o [1, 1, R, d].  Scratch acc [R, d], m/l [R, _LANES] (per-row
    scalars broadcast across lanes for Mosaic tiling, like the flash
    kernels' LSE layout).

    int8 dequant is fused without ever building the f32 page: with
    k[t] = kq[t] * ks[t], q.k[t] = (q.kq[t]) * ks[t] scales a COLUMN of
    the score tile, and sum_t p[t] v[t] = sum_t (p[t] vs[t]) vq[t]
    scales a column of the probabilities — both are row-vector
    broadcasts of the [1, ps] scale row as loaded.
    """
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel

    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    hh = pl.program_id(1)
    i = pl.program_id(2)
    length = lengths_ref[b]

    @pl.when(i == 0)
    def _init():  # pylint: disable=unused-variable
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Pages past the written window contribute nothing; row 0 always
    # computes (kpos 0 <= length), so m is finite from the first page.
    @pl.when(i * page_size <= length + s_q - 1)
    def _compute():  # pylint: disable=unused-variable
        q = q_ref[0, 0]
        r = q.shape[0]
        s = jax.lax.dot_general(
            q, k_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quantized:
            s = s * ks_ref[0, pl.ds(hh, 1), :]
        kpos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (r, page_size), 1)
        # Query row r sits at absolute position length + (r % s_q): the
        # GQA fold keeps the S query tokens of each q-head contiguous.
        qpos = length + jax.lax.broadcasted_iota(
            jnp.int32, (r, page_size), 0) % s_q
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = jnp.max(m_ref[...], axis=-1, keepdims=True)
        l_prev = jnp.max(l_ref[...], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, pl.ds(hh, 1), :]
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, (r, _LANES))
        l_ref[...] = jnp.broadcast_to(l_new, (r, _LANES))

    @pl.when(i == num_rows - 1)
    def _finish():  # pylint: disable=unused-variable
        l = jnp.max(l_ref[...], axis=-1, keepdims=True)
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_leaf, v_leaf, tables, lengths, *,
                            sm_scale: float):
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel
    from jax.experimental.pallas import tpu as pltpu  # pylint: disable=import-outside-toplevel

    b, h_q, s_q, d = q.shape
    quantized = isinstance(k_leaf, dict)
    pool = k_leaf['q'] if quantized else k_leaf
    h_kv, ps = pool.shape[1], pool.shape[2]
    rep = h_q // h_kv
    r = rep * s_q
    num_rows = tables.shape[1]
    # Fold GQA + the S query tokens into one row axis: row
    # qh_local * s_q + j is q-head (qh_local within the kv group) at
    # query token j.  sm_scale is folded into q once, outside.
    qr = (q.reshape(b, h_kv, rep, s_q, d).reshape(b, h_kv, r, d)
          .astype(jnp.float32) * sm_scale)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    grid = (b, h_kv, num_rows)
    q_spec = pl.BlockSpec(
        (1, 1, r, d), lambda bb, hh, ii, tt, ll: (bb, hh, 0, 0),
        memory_space=pltpu.VMEM)
    # The block-table read happens HERE: each program's K/V page is
    # pool row tables[b, i] — the gathered view never materialises.
    kv_spec = pl.BlockSpec(
        (1, 1, ps, d),
        lambda bb, hh, ii, tt, ll: (tt[bb, ii], hh, 0, 0),
        memory_space=pltpu.VMEM)
    # Last two block dims equal the array's (h_kv, ps): see the kernel
    # docstring.
    scale_spec = pl.BlockSpec(
        (1, h_kv, ps), lambda bb, hh, ii, tt, ll: (tt[bb, ii], 0, 0),
        memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec(
        (1, 1, r, d), lambda bb, hh, ii, tt, ll: (bb, hh, 0, 0),
        memory_space=pltpu.VMEM)
    scratch = [pltpu.VMEM((r, d), jnp.float32),
               pltpu.VMEM((r, _LANES), jnp.float32),
               pltpu.VMEM((r, _LANES), jnp.float32)]
    if quantized:
        in_specs = [q_spec, kv_spec, scale_spec, kv_spec, scale_spec]
        operands = (qr, k_leaf['q'], k_leaf['scale'], v_leaf['q'],
                    v_leaf['scale'])
    else:
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (qr, k_leaf, v_leaf)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=ps, s_q=s_q,
                          num_rows=num_rows, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, r, d), q.dtype),
        interpret=interpret_mode(),
        name='paged_decode_attention',
    )(tables, lengths, *operands)
    return out.reshape(b, h_kv, rep, s_q, d).reshape(b, h_q, s_q, d)


def _paged_attention_reference(q, k_leaf, v_leaf, tables, lengths, *,
                               sm_scale: float):
    """Pure-jnp reference with the kernel's exact masking math: gather
    the pool rows each table names, dequant, attend.  Used off-TPU
    without interpret mode (and by parity tests as the pinned
    semantics of the kernel)."""
    b, h_q, s_q, d = q.shape
    quantized = isinstance(k_leaf, dict)

    def gather(leaf):
        if quantized:
            vals = leaf['q'][tables].astype(jnp.float32)
            scale = leaf['scale'][tables].astype(jnp.float32)
            arr = vals * scale[..., None]
        else:
            arr = leaf[tables].astype(jnp.float32)
        bb, p, h, s, dd = arr.shape
        return arr.transpose(0, 2, 1, 3, 4).reshape(bb, h, p * s, dd)

    k = gather(k_leaf)                              # [B, h_kv, P*ps, d]
    v = gather(v_leaf)
    h_kv = k.shape[1]
    rep = h_q // h_kv
    qg = q.reshape(b, h_kv, rep, s_q, d).astype(jnp.float32)
    s = jnp.einsum('bgrqd,bgkd->bgrqk', qg, k) * sm_scale
    kpos = jnp.arange(k.shape[2])
    qpos = lengths[:, None] + jnp.arange(s_q)[None, :]      # [B, S]
    mask = (kpos[None, None, None, None, :] <=
            qpos[:, None, None, :, None])
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bgrqk,bgkd->bgrqd', p, v)
    return out.reshape(b, h_q, s_q, d).astype(q.dtype)


def paged_attention(q, k_leaf: Any, v_leaf: Any, tables, lengths, *,
                    sm_scale: Optional[float] = None, mesh=None):
    """Paged decode attention over one layer's page pool.

    q [B, h_q, S, d] (query token j of slot b at absolute position
    lengths[b] + j, already written into the pool); pool leaves
    [n_pages, h_kv, ps, d] (or int8 {'q','scale'}); tables [B, P];
    lengths [B].  Returns [B, h_q, S, d] in q's dtype.

    Under a `mesh` of more than one device each device runs the kernel
    on its own heads (ops/sp_common.py says why): q heads and the
    pool's kv heads are sharded over 'tensor' as
    parallel/sharding.page_pool_sharding places them; slots, tables
    and lengths are replicated.
    """
    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    if mesh is not None and mesh.size > 1:
        from skypilot_tpu.ops import sp_common  # pylint: disable=import-outside-toplevel
        P = jax.sharding.PartitionSpec
        _, head_axes, _ = sp_common.batch_head_axes(mesh)
        heads = P(None, head_axes)
        leaf_spec = jax.tree.map(lambda _: heads, k_leaf)
        fn = functools.partial(paged_attention, sm_scale=sm_scale)
        return sp_common.sp_shard_map(
            fn, mesh, (heads, leaf_spec, leaf_spec, P(), P()),
            heads)(q, k_leaf, v_leaf, tables, lengths)
    if _use_pallas():
        return _paged_attention_pallas(q, k_leaf, v_leaf, tables,
                                       lengths, sm_scale=sm_scale)
    return _paged_attention_reference(q, k_leaf, v_leaf, tables,
                                      lengths, sm_scale=sm_scale)
