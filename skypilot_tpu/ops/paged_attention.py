"""Pallas paged-attention decode kernel: block-table reads in-kernel,
bounded by what each slot's cache holds.

The paged engine's fallback decode path gathers every slot's pages
into a dense `[b, h_kv, len, d]` view before attending
(`paged_batched_step`'s view closure) — fine on CPU emulation, a
bandwidth disaster on TPU: the gather materialises the whole cache
window in HBM every tick.  This kernel reads K/V pages directly from
the page pool by block-table index — the gathered view never exists.

The kernel takes the WHOLE pool, every layer's pages stacked
(`[L, n_pages, h_kv, ps, d]`, as `models/decode.init_paged_cache` lays
it out and the decode tick's layer loop carries it), and the layer to
read as a traced scalar: a layer's share is never sliced out of the
pool for the kernel's operand, which is what lets the tick keep the
pool in place.

The grid has one program a slot and nothing else: a table row is not
a grid step.  The pools stay in HBM; inside a program a loop of
DYNAMIC length `ceil((lengths[b] + S) / (pages_per_step * ps))` walks
the slot's live pages, `pages_per_step` of them a step.  Each page of
a layer is one contiguous `[h_kv, ps, d]` slab of the pool, so one
async copy by (layer, table) index (tables, lengths and the layer ride
in scalar-prefetch memory) brings every kv head into a double-buffered
VMEM scratch; the next step's
copies — or the next slot's first — start before this step's are
waited for.  Table rows past a slot's length are never read, their
pages never fetched, and they cost no step, so the kernel's time
follows the caches and not `max_len`; a freed slot (length 0) costs
one step of one page.  The online softmax of all kv heads accumulates
across a slot's steps in VMEM scratch.  `pages_per_step` comes from
shapes alone (`_pages_per_step`).

A window (`window`, a traced scalar: the layer's, where a model has
layers with and without one) moves the walk's START: a query at
position p sees keys p - window + 1 .. p, so the loop begins at the
page that holds the first query's first key and masks inside it; the
pages behind the window are neither fetched nor stepped over.  Without
a window (None) the kernel is the one it was.

Queries generalise to S tokens per slot (query row r sits at absolute
position `lengths[b] + r % S`), so one kernel serves single-token
decode (S=1) AND the self-speculative verify step (S=k+1) — drafts
are verified through the same paged kernel.

int8 pools (PR 7's per-page absmax scales) run the same kernel body
with the dequant fused into the score and probability tiles: the int8
bytes are what moves from HBM, their scales ride the same copies and
multiply VMEM-resident tiles.  The scales (3% of an int8 pool) reach
the kernel as the layer's slice, a page a row.

Same interpret-mode pattern as ops/attention.py
(`SKYTPU_PALLAS_INTERPRET=1`, CPU backend only); off-TPU without
interpret mode a pure `jnp` gather reference with identical masking
math is used, and `SKYTPU_DECODE_KERNEL=pallas|gather` pins the
engine's path choice (default: pallas wherever Pallas can run, else
gather).

Shapes: q [B, h_q, S, d]; pool leaves [L, n_pages, h_kv, ps, d] (int8
pools: {'q': int8, 'scale': f32 [L, n_pages, h_kv, ps]}) with `layer`
an int32 scalar, or one layer's [n_pages, h_kv, ps, d] without it;
tables [B, P]; lengths [B] (pre-write depths — the S new tokens are
assumed already written at positions lengths..lengths+S-1, exactly
how `paged_batched_step` orders write-then-attend).
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


# VMEM the kernel's double-buffered K and V page buffers may take, and
# the most tokens one step attends.  Both only cap `pages_per_step`:
# the budget where pages are fat (many kv heads a page), the token cap
# where they are thin (a tensor shard's two heads), so that a short
# slot's last step is not mostly masked columns.
_KV_VMEM_BUDGET = 4 * 1024 * 1024
_STEP_TOKENS = 512


def _pages_per_step(num_rows: int, h_kv: int, page_size: int, d: int,
                    itemsize: int) -> int:
    """Pages one loop step fetches and attends: as many as the VMEM
    budget holds twice over (K and V, two buffers each), no more than
    `_STEP_TOKENS` tokens' worth, no more than the table has rows."""
    page_bytes = h_kv * page_size * d * itemsize
    by_vmem = _KV_VMEM_BUDGET // (4 * page_bytes)
    return max(1, min(by_vmem, _STEP_TOKENS // page_size, num_rows))


def _paged_decode_kernel(tables_ref, lengths_ref, layer_ref, *refs,
                         page_size: int, s_q: int, pages_per_step: int,
                         sm_scale: float, quantized: bool,
                         windowed: bool = False):
    """One program per slot: walk the slot's live pages,
    `pages_per_step` a step, through a double-buffered VMEM scratch and
    fold each step into the online softmax of every kv head.

    Refs: tables [B, P], lengths [B] and the layer [1] in SMEM (and,
    `windowed`, the window [1] after them); q [1, h_kv, R, d]
    (R = rep * s_q padded to whole sublane tiles, unscaled, q's dtype);
    the pools in HBM (k/v [L, n_pages, h_kv, ps, d], of which only
    layer `layer`'s pages are read; int8 pools add ks/vs [n_pages, W]
    f32, the layer's, a page's [h_kv, ps] scales as one row);
    o [1, h_kv, R, d].  Scratch: k/v buffers
    [2, pages_per_step, h_kv, ps, d] (and scale buffers
    [2, pages_per_step, W]), DMA semaphores [2, 2] (buffer; K or V),
    base [1] in SMEM (see below), acc [h_kv * R, d] and m/l
    [h_kv * R, _LANES] (rows head-major; per-row scalars broadcast
    across lanes for Mosaic tiling, like the flash kernels' LSE
    layout).

    Step i covers table rows [i * pps, (i + 1) * pps) cut off at the
    slot's live page count ceil((length + s_q) / ps): a row past it is
    not read from the table, its page is not fetched, and a step made
    only of such rows does not run.  `windowed`, the rows count from
    the slot's first page inside the window, (length - window + 1) //
    ps, and keys at or before position - window are masked.

    int8 dequant is fused without ever building the f32 page: with
    k[t] = kq[t] * ks[t], q.k[t] = (q.kq[t]) * ks[t] scales a COLUMN of
    the score tile, and sum_t p[t] v[t] = sum_t (p[t] vs[t]) vq[t]
    scales a column of the probabilities.
    """
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel
    from jax.experimental.pallas import tpu as pltpu  # pylint: disable=import-outside-toplevel

    if windowed:
        window_ref, q_ref, *refs = refs
    else:
        q_ref, *refs = refs
    if quantized:
        (k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
         vs_buf, sems, base_ref, acc_ref, m_ref, l_ref) = refs
        streams = ((k_hbm, k_buf, 0), (ks_hbm, ks_buf, 0),
                   (v_hbm, v_buf, 1), (vs_hbm, vs_buf, 1))
    else:
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, base_ref, acc_ref, m_ref,
         l_ref) = refs
        streams = ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1))
    pps = pages_per_step
    _, h_kv, r, d = q_ref.shape
    hr = h_kv * r
    t = pps * page_size
    n_slots = pl.num_programs(0)
    b = pl.program_id(0)
    length = lengths_ref[b]
    layer = layer_ref[0]

    def live_pages(bb):
        return jnp.minimum(
            (lengths_ref[bb] + s_q + page_size - 1) // page_size,
            tables_ref.shape[1])

    def first_page(bb):
        """The page that holds the first key slot bb's first query sees
        in the window (only asked where there is one)."""
        return jnp.maximum(
            lengths_ref[bb] - window_ref[0] + 1, 0) // page_size

    def walk_pages(bb):
        return live_pages(bb) - first_page(bb) if windowed \
            else live_pages(bb)

    n_steps = (walk_pages(b) + pps - 1) // pps

    def copies(bb, i, slot, which, fn):
        """Apply fn to the copy descriptor of every live page of slot
        bb's step i (into buffer `slot`) that signals a semaphore in
        `which` (0: K, 1: V)."""
        rel = i * pps
        first = first_page(bb) + rel if windowed else rel

        def one(j, _):
            page = tables_ref[bb, first + j]
            for hbm, buf, w in streams:
                if w in which:
                    # A pool page is hbm[layer, page]; a scale page is
                    # one row of a 2-D array, sliced (Mosaic slices a
                    # DMA's last two dims only by whole tiles).
                    src, dst = ((hbm.at[layer, page], buf.at[slot, j])
                                if len(hbm.shape) == 5 else
                                (hbm.at[pl.ds(page, 1)],
                                 buf.at[slot, pl.ds(j, 1)]))
                    fn(pltpu.make_async_copy(src, dst, sems.at[slot, w]))
            return _

        jax.lax.fori_loop(
            0, jnp.minimum(pps, walk_pages(bb) - rel), one, None)

    def start(bb, i, slot):
        copies(bb, i, slot, (0, 1), lambda c: c.start())

    # The two buffers alternate over the steps of ALL programs (which
    # run in order on one core): a program's last step starts the next
    # program's first copies, so only the call's very first step waits
    # for a copy nothing overlaps.  base_ref holds the buffer of this
    # program's step 0.
    @pl.when(b == 0)
    def _first():  # pylint: disable=unused-variable
        # A step's unfetched tail keeps what an earlier step left in
        # the buffer; its columns are masked to p = 0, and 0 * v must
        # be 0, so the buffers start finite.
        for _, buf, _ in streams:
            buf[...] = jnp.zeros_like(buf)
        base_ref[0] = 0
        start(0, 0, 0)

    # What bf16 holds exactly (bf16 and int8 pages) goes to the MXU as
    # bf16; an f32 pool (tests) stays f32.
    exact = k_buf.dtype in (jnp.bfloat16, jnp.int8)
    k_dtype = (jnp.bfloat16 if exact and q_ref.dtype == jnp.bfloat16
               else jnp.float32)

    def per_head(fn):
        return jnp.concatenate([fn(hh) for hh in range(h_kv)], axis=0)

    base = base_ref[0]
    base_ref[0] = (base + n_steps) % 2
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def step(i, _):
        slot = (base + i) % 2
        more = i + 1 < n_steps
        next_b = jnp.where(more, b, b + 1)

        @pl.when(next_b < n_slots)
        def _prefetch():  # pylint: disable=unused-variable
            start(next_b, jnp.where(more, i + 1, 0), 1 - slot)

        kpos = (first_page(b) * page_size + i * t if windowed
                else i * t) + jax.lax.broadcasted_iota(
                    jnp.int32, (hr, t), 1)
        # Rows are head-major, r a head.  Row j of a head sits at
        # absolute position length + (j % s_q): the GQA fold keeps the
        # S query tokens of each q-head contiguous.
        qpos = length + jax.lax.broadcasted_iota(
            jnp.int32, (hr, t), 0) % r % s_q

        def flat(buf, hh, dtype):
            """Head hh of the step's pages as one [t, d] MXU operand
            (int8 pages by way of f32, whose [pps, ps, d] Mosaic can
            reshape without moving data)."""
            x = buf[slot, :, hh]
            if x.dtype == jnp.int8 or dtype == jnp.float32:
                x = x.astype(jnp.float32)
            return x.reshape(t, d).astype(dtype)

        def scale_rows(buf):
            """The step's scales over the score or probability tile,
            [hr, t]: head hh's rows all hold its [1, t] scale row.  A
            page's scales sit in one buffer row, head-major; Mosaic has
            no reshape from [pps, ps] to [1, t], so a row is a lane
            concatenation."""
            x = buf[slot]
            return per_head(lambda hh: jnp.broadcast_to(
                jnp.concatenate(
                    [x[j:j + 1, hh * page_size:(hh + 1) * page_size]
                     for j in range(pps)], axis=1), (r, t)))

        # The dots are a head's; everything between them runs once on
        # the [hr, t] tile of all heads.
        copies(b, i, slot, (0,), lambda c: c.wait())
        # bf16 q and K go to the MXU as they are (products exact, f32
        # accumulation) and the SCORES are scaled; any other pair is
        # widened to f32 first.
        s = per_head(lambda hh: jax.lax.dot_general(
            q_ref[0, hh].astype(k_dtype), flat(k_buf, hh, k_dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) * sm_scale
        if quantized:
            s = s * scale_rows(ks_buf)
        live = kpos <= qpos
        if windowed:
            live = live & (kpos > qpos - window_ref[0])
        s = jnp.where(live, s, NEG_INF)
        # The walk's first column is always live (kpos 0 <= length; in a
        # window, the first query's first key), so m is finite from the
        # first step on.
        m_prev = jnp.max(m_ref[...], axis=-1, keepdims=True)
        l_prev = jnp.max(l_ref[...], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        copies(b, i, slot, (1,), lambda c: c.wait())
        if quantized:
            p = p * scale_rows(vs_buf)
        if exact:
            # p as three bf16 pieces whose sum is p exactly (3 x 8
            # mantissa bits), a head's three stacked on the row axis so
            # that its V is loaded into the MXU once: every product is
            # exact in f32, as with p and V both widened, in one pass.
            hi = p.astype(jnp.bfloat16).astype(jnp.float32)
            mid = (p - hi).astype(jnp.bfloat16).astype(jnp.float32)
            pieces = [x.astype(jnp.bfloat16) for x in (hi, mid, p - hi - mid)]

            def pv_head(hh):
                o3 = jax.lax.dot_general(
                    jnp.concatenate([x[hh * r:(hh + 1) * r] for x in pieces],
                                    axis=0),
                    flat(v_buf, hh, jnp.bfloat16), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return o3[:r] + o3[r:2 * r] + o3[2 * r:]
        else:
            def pv_head(hh):
                return jax.lax.dot_general(
                    p[hh * r:(hh + 1) * r], flat(v_buf, hh, jnp.float32),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + per_head(pv_head)
        m_ref[...] = jnp.broadcast_to(m_new, (hr, _LANES))
        l_ref[...] = jnp.broadcast_to(l_new, (hr, _LANES))
        return _

    jax.lax.fori_loop(0, n_steps, step, None)
    l = jnp.max(l_ref[...], axis=-1, keepdims=True)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).reshape(
        h_kv, r, d).astype(o_ref.dtype)


def _stacked(k_leaf, v_leaf, layer):
    """-> (k_leaf, v_leaf, layer) with the leaves stacked by layer: one
    layer's leaves (`layer` None) are layer 0 of a stack of one, a
    reshape that moves nothing."""
    if layer is not None:
        return k_leaf, v_leaf, jnp.asarray(layer, jnp.int32)
    lift = lambda leaf: jax.tree.map(lambda a: a[None], leaf)
    return lift(k_leaf), lift(v_leaf), jnp.zeros((), jnp.int32)


@functools.lru_cache(maxsize=None)
def _decode_call(b, h_kv, r_pad, d, ps, pps, s_q, sm_scale, quantized,
                 windowed, pool_dtype, q_dtype, interpret):
    """The decode kernel's `pallas_call` for one set of shapes, built
    once a process.  The call is a jitted function that traces the
    kernel's body on its first use; every program of an engine that
    holds the kernel (the tick, the verify tick, each width of the tick
    with a chunk riding it) calls it with the same shapes, and one
    object lets them share that trace, a third of a second a program
    on a serving host, where a fresh call would trace the body anew
    each time.  What the call lowers to is the same either way."""
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel
    from jax.experimental.pallas import tpu as pltpu  # pylint: disable=import-outside-toplevel

    row_spec = pl.BlockSpec(
        (1, h_kv, r_pad, d), lambda bb, *_: (bb, 0, 0, 0),
        memory_space=pltpu.VMEM)
    # The pools never enter VMEM whole, nor is a layer's share sliced
    # out of them: the kernel copies the pages of `layer` that a slot's
    # table names, and only the live ones.
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, pps, h_kv, ps, d), pool_dtype)
    buffers = [kv_buf, kv_buf]
    if quantized:
        width = -(-h_kv * ps // _LANES) * _LANES
        scale_buf = pltpu.VMEM((2, pps, width), jnp.float32)
        buffers += [scale_buf, scale_buf]
    scratch = buffers + [pltpu.SemaphoreType.DMA((2, 2)),
                         pltpu.SMEM((1,), jnp.int32),
                         pltpu.VMEM((h_kv * r_pad, d), jnp.float32),
                         pltpu.VMEM((h_kv * r_pad, _LANES), jnp.float32),
                         pltpu.VMEM((h_kv * r_pad, _LANES), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=ps, s_q=s_q,
                          pages_per_step=pps, sm_scale=sm_scale,
                          quantized=quantized, windowed=windowed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + windowed,
            grid=(b,),
            in_specs=[row_spec] + [pool_spec] * (4 if quantized else 2),
            out_specs=row_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, r_pad, d), q_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
        name='paged_decode_attention',
    )


def _paged_attention_pallas(q, k_leaf, v_leaf, tables, lengths, *,
                            sm_scale: float, window=None, layer=None):
    b, h_q, s_q, d = q.shape
    k_leaf, v_leaf, layer = _stacked(k_leaf, v_leaf, layer)
    quantized = isinstance(k_leaf, dict)
    pool = k_leaf['q'] if quantized else k_leaf
    h_kv, ps = pool.shape[2], pool.shape[3]
    rep = h_q // h_kv
    r = rep * s_q
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    pps = _pages_per_step(tables.shape[1], h_kv, ps, d,
                          pool.dtype.itemsize)
    # Fold GQA + the S query tokens into one row axis: row
    # qh_local * s_q + j is q-head (qh_local within the kv group) at
    # query token j.  Rows are padded to whole sublane tiles (the MXU
    # and the vector unit work on 8 rows whatever r is); a padding row
    # is a zero query, its output dropped.
    r_pad = -(-r // 8) * 8
    qr = jnp.pad(q.reshape(b, h_kv, rep, s_q, d).reshape(b, h_kv, r, d),
                 ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))

    if quantized:
        # The layer's scales, a page's [h_kv, ps] as one row, padded to
        # whole lane tiles: what a DMA can slice by page index.
        width = -(-h_kv * ps // _LANES) * _LANES

        def rows(scale):
            flat = jax.lax.dynamic_index_in_dim(
                scale, layer, axis=0, keepdims=False).reshape(
                    scale.shape[1], h_kv * ps)
            return jnp.pad(flat, ((0, 0), (0, width - h_kv * ps)))

        operands = (qr, k_leaf['q'], rows(k_leaf['scale']), v_leaf['q'],
                    rows(v_leaf['scale']))
    else:
        operands = (qr, k_leaf, v_leaf)
    # Tables, lengths and the layer's index ride in scalar-prefetch
    # memory, and the layer's window after them where it has one.
    scalars = (tables, lengths, layer.reshape(1)) + (
        () if window is None else (
            jnp.asarray(window, jnp.int32).reshape(1),))
    out = _decode_call(
        b, h_kv, r_pad, d, ps, pps, s_q, float(sm_scale), quantized,
        window is not None, jnp.dtype(pool.dtype), jnp.dtype(q.dtype),
        interpret_mode())(*scalars, *operands)
    return out[:, :, :r].reshape(b, h_kv, rep, s_q, d).reshape(
        b, h_q, s_q, d)


def _paged_attention_reference(q, k_leaf, v_leaf, tables, lengths, *,
                               sm_scale: float, window=None, layer=None):
    """Pure-jnp reference with the kernel's exact masking math: gather
    the rows of the pool's layer that each table names, dequant,
    attend.  Used off-TPU without interpret mode (and by parity tests
    as the pinned semantics of the kernel)."""
    b, h_q, s_q, d = q.shape
    k_leaf, v_leaf, layer = _stacked(k_leaf, v_leaf, layer)
    quantized = isinstance(k_leaf, dict)

    def gather(leaf):
        if quantized:
            vals = leaf['q'][layer, tables].astype(jnp.float32)
            scale = leaf['scale'][layer, tables].astype(jnp.float32)
            arr = vals * scale[..., None]
        else:
            arr = leaf[layer, tables].astype(jnp.float32)
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
    kpos = kpos[None, None, None, None, :]
    qpos = qpos[:, None, None, :, None]
    mask = kpos <= qpos
    if window is not None:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bgrqk,bgkd->bgrqd', p, v)
    return out.reshape(b, h_q, s_q, d).astype(q.dtype)


def paged_attention(q, k_leaf: Any, v_leaf: Any, tables, lengths, *,
                    sm_scale: Optional[float] = None, mesh=None,
                    window=None, layer=None):
    """Paged decode attention over one layer of the page pool.

    q [B, h_q, S, d] (query token j of slot b at absolute position
    lengths[b] + j, already written into the pool); pool leaves
    [L, n_pages, h_kv, ps, d] (or int8 {'q','scale'}), every layer's
    pages, of which `layer` (an int32 scalar, traced or not) is the one
    attended; tables [B, P]; lengths [B].  Without `layer` the leaves
    are one layer's, [n_pages, h_kv, ps, d].  Returns [B, h_q, S, d] in
    q's dtype.  `window` (an int32 scalar, traced or not; None = no
    window): a query at position p sees keys p - window + 1 .. p only.

    Under a `mesh` of more than one device each device runs the kernel
    on its own heads (ops/sp_common.py says why): q heads and the
    pool's kv heads are sharded over 'tensor' as
    parallel/sharding.page_pool_sharding places them; layers, slots,
    tables and lengths are replicated.
    """
    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    if mesh is not None and mesh.size > 1:
        from skypilot_tpu.ops import sp_common  # pylint: disable=import-outside-toplevel
        k_leaf, v_leaf, layer = _stacked(k_leaf, v_leaf, layer)
        P = jax.sharding.PartitionSpec
        _, head_axes, _ = sp_common.batch_head_axes(mesh)
        heads = P(None, head_axes)
        pool_heads = P(None, None, head_axes)
        leaf_spec = jax.tree.map(lambda _: pool_heads, k_leaf)
        # Traced scalars (the layer, a window) are operands too.
        args = (q, k_leaf, v_leaf, tables, lengths, layer)
        specs = (heads, leaf_spec, leaf_spec, P(), P(), P())
        if window is not None:
            args += (jnp.asarray(window, jnp.int32),)
            specs += (P(),)

        def fn(q, k_leaf, v_leaf, tables, lengths, layer, window=None):
            return paged_attention(q, k_leaf, v_leaf, tables, lengths,
                                   sm_scale=sm_scale, window=window,
                                   layer=layer)

        return sp_common.sp_shard_map(fn, mesh, specs, heads)(*args)
    impl = (_paged_attention_pallas if _use_pallas()
            else _paged_attention_reference)
    return impl(q, k_leaf, v_leaf, tables, lengths, sm_scale=sm_scale,
                window=window, layer=layer)
