"""Causal multi-head attention: Pallas flash kernels + blockwise fallback.

Design (TPU-first):
- Forward on TPU uses a Pallas flash-attention kernel: online softmax,
  q-blocks on the grid, k-blocks streamed through VMEM, matmuls in
  bfloat16 onto the MXU with float32 accumulation.  The kernel also
  emits the per-row logsumexp (LSE).
- Backward on TPU is two Pallas kernels (recompute-style flash
  backward): a dq kernel gridded over q-blocks and a fused dk/dv kernel
  gridded over k-blocks, both recomputing p = exp(s - lse) instead of
  materialising the O(seq^2) probability matrix, with causal
  block-skipping.  `delta = rowsum(dO * O)` is a cheap XLA-fused
  pre-pass.
- On the CPU backend (tests) the same kernels run under Pallas
  interpret mode when SKYTPU_PALLAS_INTERPRET=1 (an error on any other
  backend); otherwise a blockwise `lax.scan` implementation with
  identical online-softmax math is used, and its autodiff is the
  backward.  The choice follows `jax.default_backend()` alone: a TPU
  backend always takes the compiled kernels.

No reference equivalent: SkyPilot ships no kernels (SURVEY.md §2.1).
Shapes follow [batch, num_heads, seq, head_dim].
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
# Padded q rows get LSE=+BIG so recomputed p = exp(s - lse) underflows
# to exactly 0 in the backward kernels (no separate validity mask).
LSE_PAD = 1e30
# Mosaic requires the last two dims of every block to be divisible by
# (8, 128) (f32 tile) or equal to the array dims.  Per-row scalars (LSE,
# delta) therefore ride in a broadcast 128-lane trailing dim — the same
# layout the official JAX TPU flash kernel uses for its l/m residuals.
_LANES = 128
# Mosaic's default scoped-VMEM budget for one kernel, and what a v5e
# core physically has.  The flash kernels hold a whole K/V row (forward,
# dq) or a whole Q/dO/LSE/delta row (dk/dv) per program, so at long
# sequences they must ask for more than the default.
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024
_MAX_SCOPED_VMEM = 100 * 1024 * 1024


def _vmem_params(block_bytes: int):
    """CompilerParams that raise the scoped-VMEM limit when the
    kernel's double-buffered blocks (`block_bytes` = one copy of every
    in/out block) plus working room would not fit the default."""
    from jax.experimental.pallas import tpu as pltpu  # pylint: disable=import-outside-toplevel
    need = 2 * block_bytes + 8 * 1024 * 1024
    if need <= _DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(need, _MAX_SCOPED_VMEM))


def _on_tpu() -> bool:
    # A backend that fails to initialise raises here and fails the
    # process: never a quiet blockwise run on a host that has a chip.
    return jax.default_backend() == 'tpu'


def interpret_mode() -> bool:
    """SKYTPU_PALLAS_INTERPRET=1: run the Pallas kernels in the
    interpreter.  A test mode for the CPU backend only; on any other
    backend it would put interpreted kernels on the accelerator, so it
    is an error there, not a mode."""
    if os.environ.get('SKYTPU_PALLAS_INTERPRET', '') != '1':
        return False
    # skytpu: lint-ok[tracer-safety] reason=the backend name is a host string, not a traced value
    if jax.default_backend() != 'cpu':
        raise RuntimeError(
            f'SKYTPU_PALLAS_INTERPRET=1 is a CPU-backend test mode; the '
            f'JAX backend is {jax.default_backend()!r}.  Unset it to run '
            f'the compiled kernels.')
    return True


def _use_pallas() -> bool:
    # interpret_mode() first: it must get the chance to refuse a
    # non-CPU backend before _on_tpu() short-circuits.
    return interpret_mode() or _on_tpu()


def _repeat_kv(q, k, v):
    """GQA: broadcast kv heads up to q heads (XLA paths only — the
    Pallas kernels instead fold the repeat into their index maps so the
    repeated K/V never materialises in HBM)."""
    rep = q.shape[1] // k.shape[1]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


def mha_reference(q, k, v, *, causal: bool = True,
                  sm_scale: Optional[float] = None):
    """O(seq^2)-memory reference attention (tests / tiny shapes)."""
    k, v = _repeat_kv(q, k, v)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        qpos = jnp.arange(q_len)[:, None] + (k_len - q_len)
        kpos = jnp.arange(k_len)[None, :]
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', probs, v).astype(q.dtype)


def _blockwise_attention(q, k, v, *, causal: bool, sm_scale: float,
                         block_k: int, return_lse: bool = False):
    """Online-softmax attention scanning over k/v blocks."""
    k, v = _repeat_kv(q, k, v)
    orig_dtype = q.dtype
    b, h, q_len, d = q.shape
    k_len = k.shape[2]
    num_blocks = max(1, (k_len + block_k - 1) // block_k)
    pad = num_blocks * block_k - k_len
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, num_blocks, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, num_blocks, block_k, d).transpose(2, 0, 1, 3, 4)

    q32 = q.astype(jnp.float32)
    qpos = jnp.arange(q_len) + (k_len - q_len)

    @jax.checkpoint
    def step(carry, blk):
        o, m, l = carry
        k_blk, v_blk, blk_idx = blk
        s = jnp.einsum('bhqd,bhkd->bhqk', q32, k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * sm_scale
        kpos = blk_idx * block_k + jnp.arange(block_k)
        mask = kpos[None, :] < k_len  # padding mask
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            'bhqk,bhkd->bhqd', p, v_blk.astype(jnp.float32))
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((b, h, q_len, d), jnp.float32)
    m0 = jnp.full((b, h, q_len), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, q_len), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        step, (o0, m0, l0),
        (kb, vb, jnp.arange(num_blocks)))
    out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(orig_dtype)
    if return_lse:
        return out, m + jnp.log(jnp.maximum(l, 1e-30))
    return out


# ---------------------------------------------------------------- Pallas


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      sm_scale: float, causal: bool, block_k: int,
                      k_len: int, pos_offset: int):
    """One (batch*head, q_block) program: stream k/v blocks through VMEM.

    Refs: q [1, block_q, d]; k/v [1, k_len_padded, d]; o [1, block_q, d];
    lse [1, block_q, _LANES] (per-row LSE broadcast across the lane dim
    so the block satisfies Mosaic tiling).  Leading dim is the
    batch*head grid axis, blocked to 1.  Row-wise softmax stats are kept
    as 2D (block_q, 1) values for layout-safe Mosaic lowering.
    """
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel

    _, block_q, d = q_ref.shape
    q_blk_idx = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale
    # pos_offset = k_len - q_len aligns the causal diagonal when q is a
    # suffix of the kv sequence (decode-style q_len < k_len), matching
    # mha_reference/_blockwise_attention.
    qpos = pos_offset + q_blk_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    num_k_blocks = pl.cdiv(k_len, block_k)
    if causal:
        # Skip k-blocks strictly above the diagonal for this q-block.
        num_k_blocks = jnp.minimum(
            num_k_blocks,
            pl.cdiv(pos_offset + (q_blk_idx + 1) * block_q, block_k))

    def body(kb, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < k_len
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, num_k_blocks, body, (o0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l_safe).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l_safe), (block_q, _LANES))


def _flash_fwd_pallas(q, k, v, *, causal: bool, sm_scale: float,
                      block_q: int, block_k: int):
    """Returns (out [b,h,q,d], lse [b,h,q] float32)."""
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel
    from jax.experimental.pallas import tpu as pltpu  # pylint: disable=import-outside-toplevel

    b, h, q_len, d = q.shape
    h_kv, k_len = k.shape[1], k.shape[2]
    # GQA: the kernel maps q-head bh to kv-head bh // rep via the k/v
    # index maps — the repeated K/V never exists in HBM.
    rep = h // h_kv
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    # Pad seq lens to block multiples; kernel masks the padding.
    q_pad = (-q_len) % block_q
    k_pad = (-k_len) % block_k
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    qp = q.reshape(b * h, q_len + q_pad, d)
    kp = k.reshape(b * h_kv, k_len + k_pad, d)
    vp = v.reshape(b * h_kv, k_len + k_pad, d)

    grid = (b * h, (q_len + q_pad) // block_q)
    kernel = functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, block_k=block_k, k_len=k_len,
                               pos_offset=k_len - q_len)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k_len + k_pad, d),
                         lambda bh, qi: (bh // rep, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k_len + k_pad, d),
                         lambda bh, qi: (bh // rep, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, q_len + q_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, q_len + q_pad, _LANES),
                                 jnp.float32),
        ],
        compiler_params=_vmem_params(
            2 * (k_len + k_pad) * d * k.dtype.itemsize +
            2 * block_q * d * q.dtype.itemsize + block_q * _LANES * 4),
        interpret=interpret_mode(),
        name='flash_fwd',
    )(qp, kp, vp)
    return (out.reshape(b, h, q_len + q_pad, d)[:, :, :q_len],
            lse[:, :, 0].reshape(b, h, q_len + q_pad)[:, :, :q_len])


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, sm_scale: float, causal: bool,
                         block_k: int, k_len: int, pos_offset: int):
    """dQ for one (batch*head, q_block): stream k/v blocks, recompute
    p = exp(s - lse).  dS = P * (dP - delta); dQ = scale * dS @ K."""
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel

    _, block_q, d = q_ref.shape
    q_blk_idx = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    # lse/delta blocks are [1, block_q, _LANES] with all lanes equal; a
    # lane-max recovers the per-row scalar as a 2D (block_q, 1) value.
    lse = jnp.max(lse_ref[0], axis=-1, keepdims=True)
    delta = jnp.max(delta_ref[0], axis=-1, keepdims=True)
    qpos = pos_offset + q_blk_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    num_k_blocks = pl.cdiv(k_len, block_k)
    if causal:
        num_k_blocks = jnp.minimum(
            num_k_blocks,
            pl.cdiv(pos_offset + (q_blk_idx + 1) * block_q, block_k))

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < k_len
        if causal:
            mask &= kpos <= qpos
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_k_blocks, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, sm_scale: float, causal: bool,
                          block_q: int, q_len: int, pos_offset: int):
    """Fused dK/dV for one (batch*head, k_block): stream q/do blocks.
    dV = P^T @ dO; dK = scale * dS^T @ Q.  Padded q rows carry
    lse=LSE_PAD so their recomputed p underflows to 0."""
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel

    _, block_k, d = k_ref.shape
    k_blk_idx = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    kpos = k_blk_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    num_q_blocks = pl.cdiv(q_len, block_q)
    if causal:
        # First q block whose last row can see this k block:
        # qpos >= kpos  <=>  qi >= kpos - pos_offset.
        first = jnp.maximum(
            0, (k_blk_idx * block_k - pos_offset) // block_q)
    else:
        first = 0

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(
            jnp.float32)
        lse_blk = jnp.max(lse_ref[0, pl.ds(qb * block_q, block_q), :],
                          axis=-1, keepdims=True)
        delta_blk = jnp.max(delta_ref[0, pl.ds(qb * block_q, block_q), :],
                            axis=-1, keepdims=True)
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        qpos = pos_offset + qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = kpos >= 0  # k padding handled by caller slicing
        if causal:
            mask &= kpos <= qpos
        p = jnp.where(mask, jnp.exp(s - lse_blk), 0.0)
        dv = dv + jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk)
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first, num_q_blocks, body, (dk0, dv0))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, g, g_lse, *, causal: bool,
                      sm_scale: float, block_q: int, block_k: int):
    from jax.experimental import pallas as pl  # pylint: disable=import-outside-toplevel
    from jax.experimental.pallas import tpu as pltpu  # pylint: disable=import-outside-toplevel

    b, h, q_len, d = q.shape
    h_kv, k_len = k.shape[1], k.shape[2]
    rep = h // h_kv
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    q_pad = (-q_len) % block_q
    k_pad = (-k_len) % block_k
    pos_offset = k_len - q_len

    # delta = rowsum(dO * O) — cheap XLA-fused pre-pass.  An incoming
    # LSE cotangent folds in exactly here: dS = P*(dP - delta + g_lse)
    # since dlse/dS = P, so delta_eff = delta - g_lse.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    if q_pad:
        pad4 = ((0, 0), (0, 0), (0, q_pad), (0, 0))
        q = jnp.pad(q, pad4)
        g = jnp.pad(g, pad4)
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, q_pad)),
                      constant_values=LSE_PAD)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, q_pad)))
    if k_pad:
        pad4 = ((0, 0), (0, 0), (0, k_pad), (0, 0))
        k = jnp.pad(k, pad4)
        v = jnp.pad(v, pad4)
    qlp, klp = q_len + q_pad, k_len + k_pad
    qp = q.reshape(b * h, qlp, d)
    kp = k.reshape(b * h_kv, klp, d)
    vp = v.reshape(b * h_kv, klp, d)
    dop = g.reshape(b * h, qlp, d)
    # Per-row scalars ride in a broadcast 128-lane trailing dim so their
    # BlockSpecs satisfy Mosaic tiling (see _LANES).
    lsep = jnp.broadcast_to(lse.reshape(b * h, qlp)[:, :, None],
                            (b * h, qlp, _LANES))
    deltap = jnp.broadcast_to(delta.reshape(b * h, qlp)[:, :, None],
                              (b * h, qlp, _LANES))

    qd_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0),
                           memory_space=pltpu.VMEM)
    q1_spec = pl.BlockSpec((1, block_q, _LANES),
                           lambda bh, qi: (bh, qi, 0),
                           memory_space=pltpu.VMEM)
    kfull_spec = pl.BlockSpec((1, klp, d), lambda bh, qi: (bh // rep, 0, 0),
                              memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_k=block_k, k_len=k_len,
                          pos_offset=pos_offset),
        grid=(b * h, qlp // block_q),
        in_specs=[qd_spec, kfull_spec, kfull_spec, qd_spec, q1_spec,
                  q1_spec],
        out_specs=qd_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, qlp, d), q.dtype),
        compiler_params=_vmem_params(
            2 * klp * d * k.dtype.itemsize +
            3 * block_q * d * q.dtype.itemsize +
            2 * block_q * _LANES * 4),
        interpret=interpret_mode(),
        name='flash_bwd_dq',
    )(qp, kp, vp, dop, lsep, deltap)

    kd_in_spec = pl.BlockSpec((1, block_k, d),
                              lambda bh, ki: (bh // rep, ki, 0),
                              memory_space=pltpu.VMEM)
    kd_out_spec = pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0),
                               memory_space=pltpu.VMEM)
    qfull_spec = pl.BlockSpec((1, qlp, d), lambda bh, ki: (bh, 0, 0),
                              memory_space=pltpu.VMEM)
    qfull1_spec = pl.BlockSpec((1, qlp, _LANES), lambda bh, ki: (bh, 0, 0),
                               memory_space=pltpu.VMEM)
    # GQA: each program computes q-head bh's contribution to kv-head
    # bh // rep; the per-q-head partials are group-summed below (one
    # cheap XLA reduction — dq/dk/dv stay a single kernel pass each).
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, q_len=q_len,
                          pos_offset=pos_offset),
        grid=(b * h, klp // block_k),
        in_specs=[qfull_spec, kd_in_spec, kd_in_spec, qfull_spec,
                  qfull1_spec, qfull1_spec],
        out_specs=[kd_out_spec, kd_out_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, klp, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, klp, d), v.dtype)],
        compiler_params=_vmem_params(
            2 * qlp * d * q.dtype.itemsize + 2 * qlp * _LANES * 4 +
            4 * block_k * d * k.dtype.itemsize),
        interpret=interpret_mode(),
        name='flash_bwd_dkv',
    )(qp, kp, vp, dop, lsep, deltap)

    dq = dq.reshape(b, h, qlp, d)[:, :, :q_len]
    dk = dk.reshape(b, h_kv, rep, klp, d)[:, :, :, :k_len]
    dv = dv.reshape(b, h_kv, rep, klp, d)[:, :, :, :k_len]
    if rep > 1:
        # Sum in f32: rep-way bf16 accumulation would lose mantissa bits.
        dk = dk.astype(jnp.float32).sum(axis=2).astype(k.dtype)
        dv = dv.astype(jnp.float32).sum(axis=2).astype(v.dtype)
    else:
        dk = dk[:, :, 0]
        dv = dv[:, :, 0]
    return dq, dk, dv


# ------------------------------------------------------------- public op


def _flash_impl(q, k, v, causal, sm_scale, block_q, block_k):
    """Returns (out, lse)."""
    if _use_pallas():
        return _flash_fwd_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                                 block_q=block_q, block_k=block_k)
    return _blockwise_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                block_k=block_k, return_lse=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, sm_scale, block_q, block_k):
    return _flash_impl(q, k, v, causal, sm_scale, block_q, block_k)


def _flash_lse_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    out, lse = _flash_impl(q, k, v, causal, sm_scale, block_q, block_k)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    if _use_pallas():
        # Kernel-grade backward: recompute-style Pallas dq + dk/dv.
        return _flash_bwd_pallas(q, k, v, out, lse, g_out, g_lse,
                                 causal=causal, sm_scale=sm_scale,
                                 block_q=block_q, block_k=block_k)
    # CPU fallback: autodiff of the blockwise forward (same math).
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _blockwise_attention(
            q_, k_, v_, causal=causal, sm_scale=sm_scale, block_k=block_k,
            return_lse=True),
        q, k, v)
    return vjp((g_out, g_lse))


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128, mesh=None):
    """Flash attention over [batch, heads, seq, head_dim] arrays.

    Under a `mesh` of more than one device each device runs the kernel
    on its own batch/head shard (ops/sp_common.py says why); the
    sequence dim stays whole, so a mesh whose 'sequence' axis shards
    activations wants ring/ulysses attention, not this."""
    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    if mesh is not None and mesh.size > 1:
        from skypilot_tpu.ops import sp_common  # pylint: disable=import-outside-toplevel
        batch_axes, head_axes, tp = sp_common.batch_head_axes(
            mesh, q.shape[0])
        k, v = sp_common.broadcast_gqa_if_indivisible(q, k, v, tp)
        spec = jax.sharding.PartitionSpec(batch_axes, head_axes, None,
                                          None)
        fn = functools.partial(flash_attention, causal=causal,
                               sm_scale=sm_scale, block_q=block_q,
                               block_k=block_k)
        return sp_common.sp_shard_map(fn, mesh, (spec, spec, spec),
                                      spec)(q, k, v)
    out, _ = _flash_lse(q, k, v, causal, float(sm_scale), block_q, block_k)
    return out


def flash_attention_with_lse(q, k, v, *, causal: bool = True,
                             sm_scale: Optional[float] = None,
                             block_q: int = 128, block_k: int = 128):
    """Flash attention returning (out, lse) — the building block for
    ring attention's per-hop online-softmax combine.  Gradients flow
    through BOTH outputs (the LSE cotangent folds into the Pallas
    backward's delta term)."""
    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    return _flash_lse(q, k, v, causal, float(sm_scale), block_q, block_k)
