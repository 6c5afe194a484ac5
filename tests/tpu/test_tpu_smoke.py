"""Hardware-gated TPU tests: real Mosaic lowering + execution.

Interpret mode skips BlockSpec tiling legality checks, so a kernel can
be interpret-green yet fail to lower on hardware.  This suite runs ONLY
on a real TPU:

    SKYTPU_TPU_TESTS=1 python -m pytest tests/tpu -q

With SKYTPU_TPU_TESTS=1 and no TPU backend the run FAILS before
collection (tests/conftest.py) — a chip suite that skips is a chip
suite that passed nothing.  Without the variable (the hermetic
`JAX_PLATFORMS=cpu` env of `pytest tests/`) every test here skips.
"""
from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get('SKYTPU_TPU_TESTS') != '1',
    reason='chip suite: SKYTPU_TPU_TESTS=1 on a TPU host (interpret '
    'mode cannot validate Mosaic lowering)')


def _qkv(b=2, h=4, h_kv=None, s=512, d=128, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, h_kv or h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h_kv or h, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize('h,h_kv', [(4, 4), (8, 2)])
def test_flash_forward_lowers_and_matches(h, h_kv):
    """The Pallas forward lowers through Mosaic and matches reference."""
    from skypilot_tpu.ops.attention import flash_attention, mha_reference
    q, k, v = _qkv(h=h, h_kv=h_kv)
    out = jax.jit(flash_attention)(q, k, v)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2)


@pytest.mark.parametrize('h,h_kv', [(4, 4), (8, 2)])
def test_flash_backward_lowers_and_matches(h, h_kv):
    from skypilot_tpu.ops.attention import flash_attention, mha_reference
    q, k, v = _qkv(h=h, h_kv=h_kv)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(
        q, k, v)
    gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        scale = max(1.0, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale,
            np.asarray(b, np.float32) / scale, atol=2e-2)


def test_flash_ragged_and_decode_shapes_lower():
    """Non-block-multiple and decode-style (q suffix) shapes lower."""
    from skypilot_tpu.ops.attention import flash_attention, mha_reference
    for (ql, kl) in [(384, 384), (200, 200), (8, 512)]:
        q, k, v = _qkv(s=kl)
        q = q[:, :, kl - ql:]
        out = flash_attention(q, k, v)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2)


def test_ring_attention_lowers_on_tpu():
    """The TPU-native SP path (ring attention -> per-hop flash kernel)
    lowers and runs on hardware.  A 1-device mesh degenerates to a
    single causal hop — the kernel call is identical to any ring
    position's, which is exactly what round 2 found broken (VERDICT
    §2.3: flash failed to lower, so SP never ran on TPUs)."""
    from skypilot_tpu.ops.attention import mha_reference
    from skypilot_tpu.ops.ring_attention import ring_attention
    from skypilot_tpu.parallel import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(sequence=1), devices=jax.devices()[:1])
    q, k, v = _qkv(h=4, s=256)
    out = ring_attention(q, k, v, mesh=mesh)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2)


def test_kv_cache_generation_on_tpu():
    """Prefill (flash kernel, q_len<k_len path) + jit'd decode loop
    produce greedy-parity tokens on the real chip."""
    import flax.linen as nn

    from skypilot_tpu.models import configs, decode
    from skypilot_tpu.models.transformer import Transformer
    cfg = configs.get_config('tiny')
    model = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0),
                                      prompt)['params'])
    tokens, new = decode.generate(cfg, params, prompt,
                                  max_new_tokens=4, max_len=16)
    assert new.shape == (2, 4)
    full = model.apply({'params': params}, tokens[:, :-1])
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(full[:, -1], axis=-1)),
        np.asarray(new[:, -1]))


def test_train_step_runs_on_tpu():
    """The flagship model's full train step (flash attention included)
    compiles and descends loss on the real chip."""
    from skypilot_tpu.models import configs
    from skypilot_tpu.models.train import (TrainConfig, create_train_state,
                                           train_step)
    cfg = configs.get_config('tiny')
    state, _ = create_train_state(cfg, TrainConfig(), batch_size=2,
                                  seq_len=256)
    step = jax.jit(train_step, donate_argnums=(0,))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 257), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    batch = {'tokens': tokens}
    state, m0 = step(state, batch)
    first = float(jax.device_get(m0['loss']))
    for _ in range(5):
        state, m = step(state, batch)
    last = float(jax.device_get(m['loss']))
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first


def test_int8_decode_on_tpu():
    """Weight-only int8 decode (dequant fused into the matmul operand
    read) runs on hardware with close logits."""
    import flax.linen as nn

    from skypilot_tpu.models import configs, decode, quantize
    from skypilot_tpu.models.transformer import Transformer
    cfg = configs.get_config('tiny')
    model = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0),
                                      prompt)['params'])
    qparams = quantize.quantize_params(params)
    fp, _ = decode.prefill(cfg, params, prompt, max_len=16)
    q8, _ = decode.prefill(cfg, qparams, prompt, max_len=16)
    err = np.max(np.abs(np.asarray(q8) - np.asarray(fp)))
    spread = np.max(np.abs(np.asarray(fp))) + 1e-6
    assert err / spread < 0.15, (err, spread)


def test_ulysses_single_device_on_tpu():
    """Ulysses degenerates to one flash call on a 1-device sequence
    axis — validates the all-to-all + flash composition lowers."""
    from skypilot_tpu.ops.attention import mha_reference
    from skypilot_tpu.ops.ulysses_attention import ulysses_attention
    from skypilot_tpu.parallel import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(sequence=1), devices=jax.devices()[:1])
    q, k, v = _qkv(h=4, s=256)
    out = ulysses_attention(q, k, v, mesh=mesh)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2)


def test_family_variants_forward_on_tpu():
    """Gemma-style (tied/scaled/gelu/+1-norm) and Qwen-style (qkv bias)
    forwards lower and run on hardware."""
    import flax.linen as nn

    from skypilot_tpu.models import configs
    from skypilot_tpu.models.transformer import Transformer
    for preset in ('tiny-gemma', 'tiny-qwen'):
        cfg = configs.get_config(preset, dtype=jnp.bfloat16)
        model = Transformer(cfg)
        tokens = jnp.ones((1, 64), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)
        logits = jax.jit(lambda p, t, m=model: m.apply(p, t))(params,
                                                              tokens)
        assert logits.shape == (1, 64, cfg.vocab_size)
        assert logits.dtype == jnp.float32


# ------------------------------------------------- Llama-3-8B widths
# What chip_smoke.py serves: 32 q heads / 8 kv heads, head_dim 128,
# 16-token pages.  References run in float32 at 'highest' matmul
# precision (a TPU f32 matmul otherwise rounds its operands to bf16).


def _paged_case(quantized: bool, s_q: int, h_q: int = 32, seed: int = 0):
    from skypilot_tpu.models.decode import _quant_kv
    h_kv, d, ps = 8, 128, 16
    n_pages, slots, rows = 192, 4, 40
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (slots, h_q, s_q, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (n_pages, h_kv, ps, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (n_pages, h_kv, ps, d), jnp.bfloat16)
    if quantized:
        kq, kscale = _quant_kv(k)
        vq, vscale = _quant_kv(v)
        k = {'q': kq, 'scale': kscale}
        v = {'q': vq, 'scale': vscale}
    # Every slot reads its own scattered pages (0 is the null page);
    # depths cover an empty slot, a mid-page one, one that ends on the
    # kernel's step boundary (32 pages) and a full table (two steps,
    # the second of 8 pages).
    tables = jax.random.permutation(ks[3], jnp.arange(1, n_pages))[
        :slots * rows].reshape(slots, rows).astype(jnp.int32)
    lengths = jnp.asarray([0, 37, 32 * ps - s_q, rows * ps - s_q],
                          jnp.int32)
    return q, k, v, tables, lengths


@pytest.mark.parametrize('h_q', [32, 16], ids=['gqa4', 'gqa2'])
@pytest.mark.parametrize('s_q', [1, 4])
@pytest.mark.parametrize('quantized', [False, True])
def test_paged_attention_matches_reference_at_llama_widths(quantized,
                                                           s_q, h_q):
    """The paged decode kernel (bf16 and int8 pools; S = 1 decode and
    S = k+1 speculative verify; Llama-3-8B's and Mistral-7B's 32 query
    heads on 8, InternLM2-1.8B's 16 on 8) lowers and agrees with the
    gather reference.

    Tolerance: the kernel returns bf16 (q's dtype), 8 mantissa bits,
    so an output of magnitude up to ~2 (a softmax-weighted mean of
    N(0,1) values; the deepest slot averages 640 of them, the
    shallowest attends a single key) carries up to 2 * 2^-8 = 8e-3 of
    rounding; the reference is rounded the same way once more.  2e-2
    leaves 2x room and is far under what a wrong page, a wrong scale
    row or a mask off by one would produce (errors of order 1)."""
    from skypilot_tpu.ops import paged_attention as pa
    q, k, v, tables, lengths = _paged_case(quantized, s_q, h_q)
    sm_scale = 128 ** -0.5
    out = jax.jit(lambda *a: pa._paged_attention_pallas(
        *a, sm_scale=sm_scale))(q, k, v, tables, lengths)
    with jax.default_matmul_precision('highest'):
        ref = pa._paged_attention_reference(q, k, v, tables, lengths,
                                            sm_scale=sm_scale)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


def _packed_on_host(x, g, axis=-3):
    """x [..., h_kv (at `axis`), ..., d] with g adjacent heads side by
    side in one row, by numpy: what `decode.to_rows` must give."""
    x = np.moveaxis(np.asarray(x), axis, -2)
    x = x.reshape(x.shape[:-2] + (x.shape[-2] // g, g * x.shape[-1]))
    return np.moveaxis(x, -2, axis)


@pytest.mark.parametrize('d,g', [(128, 1), (64, 1), (64, 2)],
                         ids=['head128', 'head64-padded', 'head64-packed'])
def test_paged_kernel_writes_the_rows_the_scatter_wrote(d, g):
    """The kernel given each slot's new row (the tick's, PR 38) at
    Mistral's heads, 16-token pages, 3 layers stacked and the middle one
    a traced index: slots mid-page, on a fresh page's first row, on the
    table's last row, past the table (the null page) and two parked on
    the null page.  The pools after the call are, byte for byte, a
    scatter of the rows followed by the reading kernel's, and so is the
    output of every slot a caller reads (all but the parked).  A head
    of 64 sits in rows padded to 128 lanes, or (`head64-packed`, as the
    engine holds LFM2's) two heads side by side in one row of 128; the
    packed call's output is also the padded one's, bit for bit."""
    from skypilot_tpu.ops import paged_attention as pa
    h_kv, ps, rows, lanes = 8, 16, 40, 128
    lengths = jnp.asarray([37, 32 * ps, rows * ps - 1, rows * ps, 5, 9],
                          jnp.int32)
    parked = np.asarray([False] * 4 + [True] * 2)
    n_pages = 1 + 6 * rows + 3
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    tables = jnp.where(
        parked[:, None], 0, jax.random.permutation(
            ks[5], jnp.arange(1, n_pages))[:6 * rows].reshape(6, rows))
    pad = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - d)])

    def as_rows(x):
        """The pool's rows, made on the host: a head padded to 128
        lanes, or heads 2j and 2j + 1 side by side in row j."""
        return jnp.asarray(_packed_on_host(x, g) if g > 1 else pad(x))

    normal = lambda key, shape: jax.random.normal(key, shape, jnp.bfloat16)
    k = normal(ks[0], (3, n_pages, h_kv, ps, d))
    v = normal(ks[1], (3, n_pages, h_kv, ps, d))
    q = normal(ks[2], (6, 32, 1, d))
    new_k, new_v = (normal(key, (6, h_kv, 1, d)) for key in ks[3:5])
    layer = jnp.asarray(1, jnp.int32)

    row = lengths // ps
    page = jnp.where(row < rows, jnp.take_along_axis(
        tables, jnp.minimum(row, rows - 1)[:, None], 1)[:, 0], 0)
    at = (layer, page[:, None], jnp.arange(h_kv)[None], (lengths % ps)[:, None])
    k_heads = k.at[at].set(new_k[:, :, 0])
    v_heads = v.at[at].set(new_v[:, :, 0])
    k_ref, v_ref = as_rows(k_heads), as_rows(v_heads)
    ref = jax.jit(lambda *a: pa.paged_attention(
        *a, layer=layer, heads_per_row=g))(q, k_ref, v_ref, tables, lengths)
    out, k_out, v_out = jax.jit(lambda *a: pa.paged_attention(
        *a[:5], layer=layer, new_k=a[5], new_v=a[6], heads_per_row=g))(
            q, as_rows(k), as_rows(v), tables, lengths, new_k, new_v)
    padded = jax.jit(lambda *a: pa.paged_attention(*a, layer=layer))(
        q, pad(k_heads), pad(v_heads), tables, lengths)
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(padded, np.float32))
    np.testing.assert_array_equal(np.asarray(out, np.float32)[~parked],
                                  np.asarray(ref, np.float32)[~parked])
    for got, want in ((k_out, k_ref), (v_out, v_ref)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_packed_pages_go_in_and_come_out_as_they_were():
    """LFM2-24B-A2B's pool on the chip (8 kv heads of 64, two to a
    128-lane row), each program jitted as the engine jits it: a prompt's
    pages scattered in (`insert_prefill_pages`) are the host's packing
    of them; seeding a private cache from them (`paged_seed_private`,
    gathered and page by page), the handoff's export of them
    (`decode.from_rows`) and the tick's scatter rows (`decode.to_rows`)
    are what went in, byte for byte."""
    from skypilot_tpu.models import configs
    from skypilot_tpu.models import decode
    cfg = configs.ModelConfig(vocab_size=256, d_model=2048, n_layers=2,
                              n_heads=32, n_kv_heads=8, d_ff=64,
                              dtype=jnp.bfloat16)
    ps, rows = 16, 32
    paged = decode.init_paged_cache(cfg, 2 * rows + 1, ps, 2, rows,
                                    head_lanes=128)
    assert paged['k'].shape == (2, 2 * rows + 1, 4, ps, 128)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    private = {key: jax.random.normal(s, (2, 1, 8, rows * ps, 64),
                                      jnp.bfloat16)
               for key, s in zip('kv', ks)}
    ids = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 2 * rows + 1))[:rows], jnp.int32)
    paged = jax.jit(decode.insert_prefill_pages, static_argnames=(
        'first_page',), donate_argnums=(0,))(paged, private, ids,
                                             first_page=0)
    host = lambda a: np.asarray(a, np.float32)
    for key in 'kv':
        pages = host(private[key])[:, 0].reshape(2, 8, rows, ps, 64)
        np.testing.assert_array_equal(
            host(paged[key])[:, np.asarray(ids)],
            _packed_on_host(pages.transpose(0, 2, 1, 3, 4), 2))
        np.testing.assert_array_equal(
            host(jax.jit(lambda a: decode.from_rows(a[:, ids], cfg, 2))(
                paged[key])), pages.transpose(0, 2, 1, 3, 4))
    for limit in (1 << 31, 0):
        decode._GATHER_LIMIT_BYTES = limit  # pylint: disable=protected-access
        try:
            seeded = jax.jit(decode.bind(decode.paged_seed_private, cfg),
                             static_argnames=('priv_len',))(
                                 paged, ids, priv_len=rows * ps)
        finally:
            decode._GATHER_LIMIT_BYTES = 1 << 31  # pylint: disable=protected-access
        for key in 'kv':
            np.testing.assert_array_equal(host(seeded[key]),
                                          host(private[key]))
    tok = jax.random.normal(ks[2], (64, 8, 64), jnp.bfloat16)
    np.testing.assert_array_equal(
        host(jax.jit(lambda t: decode.to_rows(t, paged['k'], 1))(tok)),
        _packed_on_host(host(tok), 2, axis=1))


@pytest.mark.parametrize('writes', [False, True],
                         ids=['reads', 'writes-rows'])
def test_paged_kernel_time_follows_the_caches(writes):
    """The decode kernel's time follows what the caches hold, not the
    block tables' size: 16 slots on tables of 160 rows (the benchmark's
    Mistral engine), every cache 128 tokens against every cache 2,432.
    The long call moves 19 times the bytes; a grid over table rows took
    the same 3-6 ms for both (PERF.md, PR 25 and PR 27).  Held: the
    short call under a quarter of the long one; `writes-rows`, the call
    that also writes each slot's new row (the tick's since PR 38), each
    call's pools the next one's.

    Each timing is the best of 10 of a jitted chain of 16 calls (a
    tick's 16 layers), so dispatch is outside it."""
    from skypilot_tpu.ops import paged_attention as pa
    slots, rows, n_pages, calls = 16, 160, 2432, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (slots, 32, 1, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (n_pages, 8, 16, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (n_pages, 8, 16, 128), jnp.bfloat16)
    new = jax.random.normal(ks[4], (slots, 8, 1, 128), jnp.bfloat16)
    tables = jax.random.randint(ks[3], (slots, rows), 1, n_pages)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def chain(q, k, v, lengths):
        def call(_, carry):
            x, k, v = carry
            kw = dict(new_k=new, new_v=new) if writes else {}
            out = pa._paged_attention_pallas(
                x, k, v, tables, lengths, sm_scale=128 ** -0.5, **kw)
            return out if writes else (out, k, v)

        return jax.lax.fori_loop(0, calls, call, (q, k, v))

    def best_us(length):
        nonlocal k, v
        lengths = jnp.full((slots,), length, jnp.int32)
        best = float('inf')
        for i in range(11):
            t0 = time.perf_counter()
            _, k, v = chain(q, k, v, lengths)
            k.block_until_ready()
            if i:
                best = min(best, time.perf_counter() - t0)
        return best / calls * 1e6

    short, long_ = best_us(128), best_us(2432)
    print(f'paged kernel ({"writes" if writes else "reads"}): '
          f'{short:.1f} us at 128, {long_:.1f} us at 2432 a call')
    assert short < 0.25 * long_, (short, long_)


def test_flash_forward_backward_at_llama_head_dim_seq_4096():
    """Flash forward and both backward kernels at head_dim 128, GQA 4:1,
    seq 4096 — the length at which the dk/dv program's whole-row blocks
    approach the default scoped-VMEM limit.

    Tolerances: forward as the short-sequence tests above (bf16 output,
    3e-2).  Gradients are compared after dividing by the reference's
    largest magnitude; 2e-2 of that scale covers bf16 rounding of
    dq/dk/dv plus the f32-accumulation-order difference over 4096 keys,
    and a dropped k block or a wrong GQA group sum would be of order
    1."""
    from skypilot_tpu.ops.attention import flash_attention, mha_reference
    q, k, v = _qkv(b=1, h=8, h_kv=2, s=4096)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)

    out = jax.jit(flash_attention)(q, k, v)
    g = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(
        q, k, v)
    with jax.default_matmul_precision('highest'):
        ref = mha_reference(q, k, v)
        gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2)
    for a, b in zip(g, gr):
        scale = max(1.0, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale,
            np.asarray(b, np.float32) / scale, atol=2e-2)


def test_interpret_mode_is_refused_on_the_chip(monkeypatch):
    """SKYTPU_PALLAS_INTERPRET=1 on a TPU backend is an error, not a
    mode: nothing may put interpreted kernels on the chip."""
    from skypilot_tpu.ops import attention
    from skypilot_tpu.ops import paged_attention
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    with pytest.raises(RuntimeError, match='SKYTPU_PALLAS_INTERPRET'):
        attention.interpret_mode()
    with pytest.raises(RuntimeError, match='SKYTPU_PALLAS_INTERPRET'):
        paged_attention.decode_kernel_choice()


def test_block_until_ready_waits_for_the_device():
    """Timed work ends in block_until_ready (bench.py, the trainer):
    pin that on this backend it returns only when the device is done.
    256 chained 4096^3 bf16 matmuls are 3.5e13 FLOPs; no TPU generation
    in bench.py's peak table reaches 1e15 FLOP/s, so a return before
    35 ms would be one that did not wait, and a fetch of the result
    afterwards has nothing left to wait for."""
    n, depth = 4096, 256
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, depth, lambda _, y: y @ x, x)

    float(chain(x)[0, 0])          # compile + warm, the fetch included
    t0 = time.perf_counter()
    y = chain(x)
    dispatched = time.perf_counter() - t0
    y.block_until_ready()
    blocked = time.perf_counter() - t0
    t1 = time.perf_counter()
    float(y[0, 0])
    fetched = time.perf_counter() - t1
    assert blocked >= 2.0 * n ** 3 * depth / 1e15, (dispatched, blocked)
    assert fetched < 0.25 * blocked, (dispatched, blocked, fetched)
