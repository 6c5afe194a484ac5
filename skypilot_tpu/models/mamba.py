"""The arithmetic of a Mamba-2 mixer (Nemotron-H's), for
`models/decode.py`'s layer loop.

For a sequence's rows u (the layer's normed input) the mixer is

    [z | xBC | dt] = u W_in
    xBC <- silu(causal depthwise conv(xBC) + bias)     kernel K over rows
    [x | B | C] = xBC      x: H heads of P; B, C: G groups of N
    dt <- softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T           per head, [P, N]
    y_t = S_t C_t + D x_t
    out = RMSNorm_grouped(y * silu(z)) W_out

head h reading group h // (H / G).  A sequence carries, from one step
to the next, S of every head and the last K - 1 rows of xBC before the
convolution (its tail).  S and every product that makes or reads it are
float32: the state decays by exp(dt A) at each token and adds to
itself over thousands of them, and rounded to bfloat16 at every step
it drifts (`benchmarks/families/nemotron_h.py` measures by how much).

Two forms of the same recurrence:

- `chunk_scan`, the chunked ("state space duality") form that prefill
  takes, in `jax.numpy` on the MXU: within a chunk of `chunk` rows the
  outputs are decay-masked products C B^T over the chunk's rows, and
  the state is handed from chunk to chunk.
- the decode step, one row a sequence, through the kernel
  `ops/ssm_decode.ssm_decode_step`, which updates the state in place.

A padded chunk's rows past its real ones are given dt = 0: they neither
decay the state nor add to it, so the state after the chunk is the
state after its real rows.  `state_at` is the state after the first
`upto` rows of a chunk, what a snapshot of a page boundary inside the
chunk keeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def conv_rows(cat, kernel, bias, n: int):
    """silu of the causal depthwise convolution of the last n rows of
    cat [b, K - 1 + n, C] (a tail of K - 1 rows, then the new rows),
    kernel [K, C], bias [C] -> [b, n, C] float32."""
    width = kernel.shape[0]
    rows = cat.astype(jnp.float32)
    taps = kernel.astype(jnp.float32)
    y = sum(taps[j] * rows[:, j:j + n] for j in range(width))
    return jax.nn.silu(y + bias.astype(jnp.float32))


def split_xbc(xbc, heads: int, groups: int, state: int):
    """[..., C] -> x [..., H, P], B and C [..., G, N]."""
    inner = xbc.shape[-1] - 2 * groups * state
    x, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(lead + (heads, inner // heads)),
            b.reshape(lead + (groups, state)),
            c.reshape(lead + (groups, state)))


def gated_norm(y, z, scale, groups: int, eps: float, dtype):
    """RMSNorm of y * silu(z) over each of `groups` groups of channels,
    times `scale`: y, z [..., D] -> [..., D] in `dtype`."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(shape) * scale.astype(jnp.float32)).astype(dtype)


def chunk_scan(x, dt, a_rate, b, c, s0, chunk: int):
    """The recurrence over n rows of b sequences in the chunked form:
    x [b, n, H, P], dt [b, n, H], a_rate [H] (A), b and c [b, n, G, N],
    s0 [b, H, P, N] the state before the rows, all float32 ->
    (y [b, n, H, P] without the D x term, the state after the rows)."""
    bsz, n, heads, p = x.shape
    groups, size = b.shape[2:]
    r = heads // groups
    q = min(chunk, n)
    nc = -(-n // q)
    pad = nc * q - n
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    x = x.reshape(bsz, nc, q, groups, r, p)
    dt = dt.reshape(bsz, nc, q, groups, r)
    b = b.reshape(bsz, nc, q, groups, size)
    c = c.reshape(bsz, nc, q, groups, size)
    ln = jnp.cumsum(dt * a_rate.reshape(groups, r), axis=2)
    # Within a chunk: y_i = sum_{j <= i} (C_i . B_j) exp(L_i - L_j)
    # dt_j x_j.
    lower = jnp.tril(jnp.ones((q, q), bool))[:, :, None, None]
    diff = ln[:, :, :, None] - ln[:, :, None, :]          # [., i, j, G, r]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    cb = jnp.einsum('bcign,bcjgn->bcijg', c, b, precision=_HIGHEST)
    w = cb[..., None] * decay * dt[:, :, None]
    y = jnp.einsum('bcijgr,bcjgrp->bcigrp', w, x, precision=_HIGHEST)
    # What each chunk adds to the state at its end, then the state
    # handed from chunk to chunk.
    last = ln[:, :, -1]                                    # [b, nc, G, r]
    add = jnp.einsum('bcjgr,bcjgrp,bcjgn->bcgrpn',
                     jnp.exp(last[:, :, None] - ln) * dt, x, b,
                     precision=_HIGHEST)

    def carry(s, step):
        add_c, last_c = step
        return jnp.exp(last_c)[..., None, None] * s + add_c, s

    s_end, s_in = jax.lax.scan(
        carry, s0.reshape(bsz, groups, r, p, size).astype(jnp.float32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(last, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)                        # [b, nc, G, r, P, N]
    y = y + jnp.einsum('bcign,bcgrpn->bcigrp', c, s_in,
                       precision=_HIGHEST) * jnp.exp(ln)[..., None]
    y = y.reshape(bsz, nc * q, heads, p)[:, :n]
    return y, s_end.reshape(bsz, heads, p, size)


def state_at(x, dt, a_rate, b, s0, upto):
    """The state after the first `upto` (int32 scalar) of the n rows:
    x [b, n, H, P], dt [b, n, H], a_rate [H], b [b, n, G, N], s0
    [b, H, P, N] -> [b, H, P, N] float32."""
    bsz, n, heads, p = x.shape
    groups, size = b.shape[2:]
    r = heads // groups
    dt = jnp.where(jnp.arange(n)[None, :, None] < upto, dt, 0.0)
    ln = jnp.cumsum(dt * a_rate, axis=1)                   # [b, n, H]
    total = ln[:, -1]
    w = (jnp.exp(total[:, None] - ln) * dt).reshape(bsz, n, groups, r)
    add = jnp.einsum('btgr,btgrp,btgn->bgrpn', w,
                     x.reshape(bsz, n, groups, r, p), b,
                     precision=_HIGHEST).reshape(bsz, heads, p, size)
    return jnp.exp(total)[..., None, None] * s0 + add
