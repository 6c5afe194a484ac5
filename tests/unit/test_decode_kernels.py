"""Decode-kernel and speculative-decoding tests (CPU interpreter
mode): the Pallas paged-attention kernel vs the gather view vs the
dense reference must be token-exact, greedy and sampled, bf16 and
int8 pages, aligned and misaligned prompts — and self-speculative
decoding must be byte-identical to plain decoding with acceptance
visible in stats/spans.

Kernel choice is resolved ONCE at engine construction
(`SKYTPU_DECODE_KERNEL`, default pallas wherever Pallas can run), so
fixtures pin the env only around construction.  Engines are
module-scoped: every instance re-jits the paged step."""
from __future__ import annotations

import os
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models.transformer import Transformer
from skypilot_tpu.ops import paged_attention
from skypilot_tpu.serve import batching_engine
from skypilot_tpu.serve import sampler as sampler_lib

# Misaligned on purpose: lengths 7 and 13 straddle neither the page
# (8) nor the chunk (8) boundary; 24 is multi-page aligned; 1 is the
# empty-prefill edge.
PROMPTS = (([3, 1, 4, 1, 5, 9, 2, 6], 6),
           ([7], 4),
           ([2, 7, 1, 8, 2, 8, 1], 7),
           (list(range(5, 18)), 5),
           (list(range(1, 25)), 5))


@pytest.fixture(scope='module')
def setup():
    cfg = configs.get_config('tiny')
    model = Transformer(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
    return cfg, params


def _reference(cfg, params, prompt_ids, n, max_len=64):
    prompt = jnp.asarray([prompt_ids], jnp.int32)
    _, new = decode.generate(cfg, params, prompt, max_new_tokens=n,
                             max_len=max_len)
    return [int(t) for t in np.asarray(new)[0]]


def _engine(cfg, params, *, kernel=None, **kw):
    """Build a paged engine with the decode kernel pinned via env for
    the duration of construction (where the choice is baked)."""
    kw.setdefault('max_len', 64)
    kw.setdefault('slots', 2)
    kw.setdefault('prefill_chunk', 8)
    kw.setdefault('kv_pages', 48)
    kw.setdefault('page_size', 8)
    saved = {k: os.environ.get(k) for k in
             ('SKYTPU_DECODE_KERNEL', 'SKYTPU_PALLAS_INTERPRET')}
    try:
        if kernel == 'pallas':
            os.environ['SKYTPU_DECODE_KERNEL'] = 'pallas'
            os.environ['SKYTPU_PALLAS_INTERPRET'] = '1'
        elif kernel == 'gather':
            os.environ['SKYTPU_DECODE_KERNEL'] = 'gather'
        return batching_engine.ContinuousBatchingEngine(cfg, params,
                                                        **kw)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.fixture(scope='module')
def gather_engine(setup):
    cfg, params = setup
    eng = _engine(cfg, params, kernel='gather')
    yield eng
    eng.stop()


@pytest.fixture(scope='module')
def pallas_engine(setup):
    cfg, params = setup
    eng = _engine(cfg, params, kernel='pallas')
    yield eng
    eng.stop()


@pytest.fixture(scope='module')
def spec_engine(setup):
    cfg, params = setup
    eng = _engine(cfg, params, kernel='gather', spec_tokens=3)
    yield eng
    eng.stop()


class TestKernelChoice:

    def test_default_off_tpu_is_gather(self, monkeypatch):
        monkeypatch.delenv('SKYTPU_DECODE_KERNEL', raising=False)
        monkeypatch.delenv('SKYTPU_PALLAS_INTERPRET', raising=False)
        assert paged_attention.decode_kernel_choice() == 'gather'

    def test_interpret_mode_defaults_to_pallas(self, monkeypatch):
        monkeypatch.delenv('SKYTPU_DECODE_KERNEL', raising=False)
        monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        assert paged_attention.decode_kernel_choice() == 'pallas'

    def test_explicit_pin_wins(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        monkeypatch.setenv('SKYTPU_DECODE_KERNEL', 'gather')
        assert paged_attention.decode_kernel_choice() == 'gather'
        monkeypatch.delenv('SKYTPU_PALLAS_INTERPRET', raising=False)
        monkeypatch.setenv('SKYTPU_DECODE_KERNEL', 'pallas')
        assert paged_attention.decode_kernel_choice() == 'pallas'

    def test_invalid_choice_rejected(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_DECODE_KERNEL', 'fused9000')
        with pytest.raises(ValueError, match='SKYTPU_DECODE_KERNEL'):
            paged_attention.decode_kernel_choice()

    def test_engine_reports_kernel(self, gather_engine, pallas_engine):
        assert gather_engine.decode_kernel == 'gather'
        assert pallas_engine.decode_kernel == 'pallas'
        assert gather_engine.stats()['decode_kernel'] == 'gather'
        assert pallas_engine.stats()['decode_kernel'] == 'pallas'


def _kernel_case(quantized, s_q, rep, poison, *, h_kv=2, d=32, ps=8,
                 slots=4, rows=11):
    """Pools, tables and ragged lengths for one kernel call: an empty
    slot, one a token short of a page boundary, one on it, one that
    fills its table.  Every slot's live rows name pages of its own;
    its dead rows name the null page and pages no slot uses.  With
    `poison` those pages hold NaN (an int8 pool: NaN scales), so one
    dead row fetched and used shows in the output."""
    from skypilot_tpu.models.decode import _quant_kv
    max_len = rows * ps
    lengths = np.asarray([0, 3 * ps - 1, 3 * ps, max_len - s_q], np.int32)
    live = -(-(lengths + s_q) // ps)
    n_pages = 1 + int(live.sum()) + 5
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (slots, h_kv * rep, s_q, d), jnp.float32)
    k = jax.random.normal(ks[1], (n_pages, h_kv, ps, d), jnp.float32)
    v = jax.random.normal(ks[2], (n_pages, h_kv, ps, d), jnp.float32)
    unused = np.arange(1 + int(live.sum()), n_pages)
    tables = np.zeros((slots, rows), np.int32)
    nxt = 1
    for b in range(slots):
        tables[b, :live[b]] = np.arange(nxt, nxt + live[b])
        nxt += live[b]
        dead = rows - live[b]
        tables[b, live[b]:] = np.resize(np.append(unused, 0), dead)
    bad = np.zeros((n_pages,), bool)
    bad[0] = True
    bad[unused] = True

    def pool(x):
        if quantized:
            xq, scale = _quant_kv(x)
            clean = {'q': xq, 'scale': scale}
            dirty = {'q': xq, 'scale': jnp.where(
                bad[:, None, None], jnp.nan, scale)}
        else:
            clean = x
            dirty = jnp.where(bad[:, None, None, None], jnp.nan, x)
        return clean, dirty if poison else clean

    (k_clean, k_run), (v_clean, v_run) = pool(k), pool(v)
    return (q, k_clean, v_clean, k_run, v_run, jnp.asarray(tables),
            jnp.asarray(lengths))


_ALONE = {}


class TestPagedKernel:
    """The kernel itself, in the interpreter, against the gather
    reference: no engine, so every shape of the walk is named here."""

    @pytest.mark.parametrize('poison', [False, True],
                             ids=['clean', 'nan-dead-pages'])
    @pytest.mark.parametrize('rep', [2, 4], ids=['gqa2', 'gqa4'])
    @pytest.mark.parametrize('s_q', [1, 4], ids=['decode', 'verify4'])
    @pytest.mark.parametrize('quantized', [False, True],
                             ids=['f32pool', 'int8pool'])
    def test_walk_matches_reference(self, monkeypatch, quantized, s_q,
                                    rep, poison):
        """Three pages a step over tables of eleven rows: a trip count
        that does not divide the table, slots of 1, 3, 4 and 11 live
        pages in one batch.  With dead pages poisoned the output is
        finite and equal to the reference on the clean pool, so a dead
        row is neither fetched nor used."""
        monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        monkeypatch.setattr(paged_attention, '_STEP_TOKENS', 3 * 8)
        q, k_clean, v_clean, k_run, v_run, tables, lengths = (
            _kernel_case(quantized, s_q, rep, poison))
        assert paged_attention._pages_per_step(
            tables.shape[1], 2, 8, 32, 1 if quantized else 4) == 3
        sm_scale = 32 ** -0.5
        out = paged_attention._paged_attention_pallas(
            q, k_run, v_run, tables, lengths, sm_scale=sm_scale)
        ref = paged_attention._paged_attention_reference(
            q, k_clean, v_clean, tables, lengths, sm_scale=sm_scale)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize('pool_dtype', [jnp.bfloat16, jnp.int8],
                             ids=['bf16pool', 'int8pool'])
    def test_bf16_operands_take_the_mxu_path(self, monkeypatch,
                                             pool_dtype):
        """bf16 q on bf16 or int8 pages (what a chip serves): K goes to
        the dot as bf16 and p as three bf16 pieces that sum to p, so
        the result is the f32 one to f32 rounding, not bf16's."""
        monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        monkeypatch.setattr(paged_attention, '_STEP_TOKENS', 3 * 8)
        quantized = pool_dtype == jnp.int8
        q, k, v, _, _, tables, lengths = _kernel_case(
            quantized, 1, 4, False)
        q = q.astype(jnp.bfloat16)
        if not quantized:
            k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        sm_scale = 32 ** -0.5
        out = paged_attention._paged_attention_pallas(
            q, k, v, tables, lengths, sm_scale=sm_scale)
        ref = paged_attention._paged_attention_reference(
            q.astype(jnp.float32), k, v, tables, lengths,
            sm_scale=sm_scale)
        assert out.dtype == jnp.bfloat16
        # One bf16 rounding of an output of magnitude <= 4.
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=2 ** -6)

    @pytest.mark.parametrize('layer', [0, 2, 4],
                             ids=['first', 'middle', 'last'])
    @pytest.mark.parametrize('window', [None, 12],
                             ids=['no-window', 'window12'])
    @pytest.mark.parametrize('s_q', [1, 4], ids=['decode', 'verify4'])
    @pytest.mark.parametrize('pool_dtype', [jnp.bfloat16, jnp.int8],
                             ids=['bf16pool', 'int8pool'])
    def test_stacked_pool_at_a_layer_is_that_layers_pool(
            self, monkeypatch, pool_dtype, s_q, window, layer):
        """The kernel is given every layer's pages and a layer's index
        (as the tick's layer loop carries them): it equals, bit for
        bit, the kernel on that layer's pool alone, and the reference
        on either.  The other layers hold NaN (an int8 pool: NaN
        scales), so a page of another layer fetched and used shows."""
        monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        monkeypatch.setattr(paged_attention, '_STEP_TOKENS', 3 * 8)
        quantized = pool_dtype == jnp.int8
        q, k, v, _, _, tables, lengths = _kernel_case(
            quantized, s_q, 2, False)
        q = q.astype(jnp.bfloat16)
        if not quantized:
            k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)

        def stack(leaf):
            def one(a):
                # int8 has no NaN: its other layers hold 127 under NaN
                # scales.
                other = jnp.full_like(
                    a, jnp.nan if a.dtype != jnp.int8 else 127)
                return jnp.stack([a if i == layer else other
                                  for i in range(5)])
            return jax.tree.map(one, leaf)

        kw = dict(sm_scale=32 ** -0.5, window=None if window is None
                  else jnp.asarray(window, jnp.int32))
        at = jnp.asarray(layer, jnp.int32)
        out = jax.jit(lambda *a: paged_attention._paged_attention_pallas(
            *a[:-1], layer=a[-1], **kw))(
                q, stack(k), stack(v), tables, lengths, at)
        # One call on the layer's pool alone serves the three layers.
        key = (quantized, s_q, window)
        if key not in _ALONE:
            _ALONE[key] = paged_attention._paged_attention_pallas(
                q, k, v, tables, lengths, **kw)
        alone = _ALONE[key]
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(alone, np.float32))
        ref = paged_attention._paged_attention_reference(
            q.astype(jnp.float32), stack(k), stack(v), tables, lengths,
            layer=at, **kw)
        ref_alone = paged_attention._paged_attention_reference(
            q.astype(jnp.float32), k, v, tables, lengths, **kw)
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(ref_alone))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=2 ** -6)

    @pytest.mark.parametrize('quantized', [False, True],
                             ids=['f32pool', 'int8pool'])
    def test_mesh_shards_the_stacked_pool_by_its_heads(self, monkeypatch,
                                                       quantized):
        """Under a tensor mesh each device attends its own kv heads of
        the stacked pool (layers and pages whole on every device, the
        layer and the window replicated scalars): the result is the
        unsharded one."""
        from skypilot_tpu.parallel import mesh as mesh_lib
        monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        q, k, v, _, _, tables, lengths = _kernel_case(quantized, 1, 2,
                                                      False)
        stack = lambda leaf: jax.tree.map(
            lambda a: jnp.stack([jnp.zeros_like(a), a]), leaf)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshConfig(tensor=2),
                                   devices=jax.devices()[:2])
        kw = dict(window=jnp.asarray(12, jnp.int32),
                  layer=jnp.asarray(1, jnp.int32))
        out = jax.jit(lambda *a: paged_attention.paged_attention(
            *a, mesh=mesh, **kw))(q, stack(k), stack(v), tables, lengths)
        ref = paged_attention.paged_attention(q, k, v, tables, lengths,
                                              window=kw['window'])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize('shape,expected', [
        # rows, kv heads, page size, head dim, itemsize -> pages a step
        ((160, 8, 16, 128, 2), 32),     # Mistral-7B, bf16: 512 tokens
        ((96, 8, 16, 128, 2), 32),      # InternLM2-1.8B
        ((160, 2, 16, 128, 2), 32),     # a tensor-4 shard: the token cap
        ((160, 32, 16, 128, 2), 8),     # fat pages: the VMEM budget
        ((20, 8, 16, 128, 1), 20),      # a short table: its rows
        ((8, 2, 8, 32, 4), 8),          # the tiny engine of these tests
    ])
    def test_pages_per_step_from_shapes(self, shape, expected):
        assert paged_attention._pages_per_step(*shape) == expected


def _scatter_rows(pool, layer, tables, lengths, new):
    """What the tick's scatter did before the kernel wrote the rows:
    slot b's new row [h_kv, d] of every head at row lengths[b] % ps of
    page tables[b, lengths[b] // ps] of `layer`, the null page where
    that row is past the table."""
    ps, n_rows = pool.shape[3], tables.shape[1]
    row = lengths // ps
    page = jnp.where(row < n_rows, jnp.take_along_axis(
        tables, jnp.minimum(row, n_rows - 1)[:, None], 1)[:, 0], 0)
    at = (layer, page[:, None], jnp.arange(pool.shape[2])[None],
          (lengths % ps)[:, None])
    return pool.at[at].set(new[:, :, 0].astype(pool.dtype))


# A case of the writing kernel: its head width (and the pool's row
# width, where it is padded), its window, and its slots, each one of:
# 'mid' (the row mid-page), 'fresh' (the first row of a fresh page),
# 'deep' (the table's last row), 'past' (a row past the table: the
# null page) and 'parked' (an inactive slot whose table is the null
# page, each at its own row of it).
_WRITE_CASES = {
    'gqa-head128': dict(d=128, slots=('mid', 'deep')),
    'head64-padded': dict(d=64, lanes=128, slots=('mid', 'fresh')),
    'window': dict(window=12, slots=('mid', 'deep', 'fresh')),
    'fresh-page': dict(slots=('fresh', 'mid')),
    'past-table': dict(slots=('past', 'mid')),
    'parked': dict(slots=('parked', 'mid', 'parked', 'parked')),
    'traced-layer': dict(traced=True,
                         slots=('mid', 'fresh', 'parked', 'past')),
}


def _tick_program(cfg, params, monkeypatch, kernel, s_q, quantized):
    """The paged step of `s_q` tokens a slot as a jaxpr's text, traced
    with the Pallas kernel in the interpreter."""
    paged = decode.init_paged_cache(cfg, 16, 8, 2, 8, quantize_kv=quantized)
    with monkeypatch.context() as m:
        m.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        return str(jax.make_jaxpr(lambda t, c: decode.paged_batched_step(
            cfg, params, t, c, kernel=kernel))(
                jnp.zeros((2, s_q), jnp.int32), paged))


class TestKernelWritesRows:
    """The paged kernel given the step's new rows: the same output and
    the same pool, byte for byte, as the scatter before the kernel that
    it replaces (`models/decode._paged_forward`)."""

    @pytest.mark.parametrize('case', list(_WRITE_CASES))
    def test_writes_what_the_scatter_wrote(self, monkeypatch, case):
        """A stacked pool of three layers, written and read at layer 1
        (`traced-layer`: the layer a traced scalar, as in the tick's
        scan), bf16 as the chip holds it.  Every slot's output but a
        parked one's is the scatter-then-kernel one exactly (nobody
        reads a parked slot's: the scatter put its row into every copy
        of the null page it attends, the kernel into none), and both
        pools are too, the null page included."""
        monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        opts = _WRITE_CASES[case]
        d, ps, rows, h_kv, rep = opts.get('d', 32), 8, 4, 2, 2
        width = opts.get('lanes', d)
        kinds = opts['slots']
        depth = {'mid': 13, 'fresh': 2 * ps, 'deep': rows * ps - 1,
                 'past': rows * ps}
        tables = np.zeros((len(kinds), rows), np.int32)
        lengths = []
        for b, kind in enumerate(kinds):
            if kind == 'parked':
                lengths.append(3 + 2 * b)
            else:
                tables[b] = 1 + b * rows + np.arange(rows)
                lengths.append(depth[kind])
        tables = jnp.asarray(tables)
        lengths = jnp.asarray(lengths, jnp.int32)
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        normal = lambda key, shape: jax.random.normal(
            key, shape, jnp.float32).astype(jnp.bfloat16)
        # Pad lanes hold zeros, as `decode._to_rows` leaves them.
        pad = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) +
                                [(0, width - d)])
        pool = (3, 1 + rows * len(kinds) + 2, h_kv, ps, d)
        k, v = pad(normal(ks[0], pool)), pad(normal(ks[1], pool))
        q = normal(ks[2], (len(kinds), h_kv * rep, 1, d))
        new_k = normal(ks[3], (len(kinds), h_kv, 1, d))
        new_v = normal(ks[4], (len(kinds), h_kv, 1, d))
        kw = dict(window=opts.get('window'))
        layer = 1

        def written(q, k, v, layer, new_k, new_v):
            return paged_attention.paged_attention(
                q, k, v, tables, lengths, layer=layer, new_k=new_k,
                new_v=new_v, **kw)

        if opts.get('traced'):
            written = jax.jit(written)
            layer = jnp.asarray(layer, jnp.int32)
        out, k_out, v_out = written(q, k, v, layer, new_k, new_v)
        k_ref = _scatter_rows(k, layer, tables, lengths, pad(new_k))
        v_ref = _scatter_rows(v, layer, tables, lengths, pad(new_v))
        ref = paged_attention.paged_attention(q, k_ref, v_ref, tables,
                                              lengths, layer=layer, **kw)
        assert out.shape == q.shape and k_out.shape == k.shape
        read = np.asarray([kind != 'parked' for kind in kinds])
        np.testing.assert_array_equal(np.asarray(out[read], np.float32),
                                      np.asarray(ref[read], np.float32))
        for got, want in ((k_out, k_ref), (v_out, v_ref)):
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))

    def test_writes_only_one_token_a_slot_into_a_float_pool(
            self, monkeypatch):
        """An int8 pool, or the verify tick's S tokens a slot, are the
        caller's scatter to write."""
        monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
        q, k, v, _, _, tables, lengths = _kernel_case(True, 1, 2, False)
        new = jnp.zeros((4, 2, 1, 32), jnp.float32)
        with pytest.raises(ValueError, match='new rows'):
            paged_attention.paged_attention(q, k, v, tables, lengths,
                                            new_k=new, new_v=new)
        assert not paged_attention.writes_in_kernel(True, 1)
        assert not paged_attention.writes_in_kernel(False, 4)
        assert paged_attention.writes_in_kernel(False, 1)


# LFM2-24B-A2B's attention, whose pool rows a TPU's engine packs two
# heads to 128 lanes: 32 query heads on 8 kv heads of 64, pages of 16;
# slots on tables of 4 rows, in a stacked pool of 3 layers read at 1.
_LFM2 = dict(vocab_size=256, d_model=2048, n_layers=2, n_heads=32,
             n_kv_heads=8, d_ff=64, dtype=jnp.bfloat16)
_LANES, _PS, _ROWS = 128, 16, 4


def _lfm2_case(lengths, parked=()):
    """(q, k, v, new_k, new_v, tables, lengths): bf16 keys and values of
    every head [3, n_pages, 8, 16, 64], each slot on pages of its own (a
    parked slot's table the null page), one new row a slot."""
    b = len(lengths)
    tables = np.zeros((b, _ROWS), np.int32)
    for i in range(b):
        if i not in parked:
            tables[i] = 1 + i * _ROWS + np.arange(_ROWS)
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    normal = lambda key, *shape: jax.random.normal(
        key, shape, jnp.float32).astype(jnp.bfloat16)
    pool = (3, 1 + b * _ROWS + 2, 8, _PS, 64)
    return (normal(ks[0], b, 32, 1, 64), normal(ks[1], *pool),
            normal(ks[2], *pool), normal(ks[3], b, 8, 1, 64),
            normal(ks[4], b, 8, 1, 64), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


def _padded(x):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, _LANES - x.shape[-1])])


def _packed(x, axis=2):
    """Two adjacent heads of x (on `axis`) a row of 128 lanes
    (`decode.to_rows`, given a pool of that many groups of rows)."""
    return decode.to_rows(x, jax.ShapeDtypeStruct(
        (1, 1, x.shape[axis] // 2, 1, _LANES), x.dtype), axis)


def _packed_read(window=None, traced=False, one_layer=False):
    """The packed pool read by the kernel is the padded pool read by
    it, bit for bit; the packed row of group j holds heads 2j and
    2j + 1 side by side."""
    q, k, v, _, _, tables, lengths = _lfm2_case([13, 37, 63, 5])
    packed = _packed(k)
    assert packed.shape == (3, k.shape[1], 4, _PS, _LANES)
    np.testing.assert_array_equal(
        np.asarray(packed[:, :, 1, :, 64:], np.float32),
        np.asarray(k[:, :, 3], np.float32))
    kw = dict(window=window)
    if one_layer:
        k, v = k[1], v[1]
    else:
        kw['layer'] = jnp.asarray(1, jnp.int32) if traced else 1

    def read(*a, **more):
        return paged_attention.paged_attention(*a, tables, lengths, **kw,
                                               **more)

    run = jax.jit(read, static_argnames=('heads_per_row',)) if traced \
        else read
    want = run(q, _padded(k), _padded(v))
    got = run(q, _packed(k, 1 if one_layer else 2),
              _packed(v, 1 if one_layer else 2), heads_per_row=2)
    assert got.shape == q.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _packed_write():
    """Given the new rows, the kernel leaves the packed pools a packed
    scatter of them makes (a row mid-page, a fresh page's first, the
    table's last, one past the table: the null page, two parked slots),
    and every read slot's output is the padded kernel's."""
    kinds = ('mid', 'fresh', 'deep', 'past', 'parked', 'parked')
    q, k, v, nk, nv, tables, lengths = _lfm2_case(
        [37, 2 * _PS, _ROWS * _PS - 1, _ROWS * _PS, 5, 9], parked=(4, 5))
    layer = jnp.asarray(1, jnp.int32)
    write = jax.jit(lambda *a, g=1: paged_attention.paged_attention(
        *a[:3], tables, lengths, layer=layer, new_k=a[3], new_v=a[4],
        heads_per_row=g), static_argnames=('g',))
    out, k_out, v_out = write(q, _packed(k), _packed(v), nk, nv, g=2)
    want, _, _ = write(q, _padded(k), _padded(v), nk, nv)
    for got, pool, new in ((k_out, k, nk), (v_out, v, nv)):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(_scatter_rows(_packed(pool), layer, tables, lengths,
                                     _packed(new, 1)), np.float32))
    read = np.asarray([kind != 'parked' for kind in kinds])
    np.testing.assert_array_equal(np.asarray(out[read], np.float32),
                                  np.asarray(want[read], np.float32))


def _packed_seed(monkeypatch):
    """A private cache put into packed pages and seeded back, gathered
    and page by page, is the cache it was."""
    cfg = configs.ModelConfig(**_LFM2)
    paged = decode.init_paged_cache(cfg, 9, _PS, 2, _ROWS,
                                    head_lanes=_LANES)
    assert paged['k'].shape == (2, 9, 4, _PS, _LANES)
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    private = {key: jax.random.normal(
        s, (2, 1, 8, _ROWS * _PS, 64), jnp.float32).astype(jnp.bfloat16)
        for key, s in zip('kv', ks)}
    pages = jnp.asarray([3, 5, 8, 2], jnp.int32)
    paged = decode.insert_prefill_pages(paged, private, pages, first_page=0)
    for limit in (decode._GATHER_LIMIT_BYTES, 0):  # pylint: disable=protected-access
        monkeypatch.setattr(decode, '_GATHER_LIMIT_BYTES', limit)
        seeded = decode.paged_seed_private(cfg, paged, pages,
                                           priv_len=_ROWS * _PS)
        for key in 'kv':
            np.testing.assert_array_equal(
                np.asarray(seeded[key], np.float32),
                np.asarray(private[key], np.float32))


def _packed_handoff():
    """The prefix export's pages of a packed pool are the wire's
    [L, n, h_kv, ps, d], byte for byte what an unpadded pool gives, and
    importing them into a packed pool restores it."""
    cfg = configs.ModelConfig(**_LFM2)
    wire = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 8, _PS, 64),
                             jnp.float32).astype(jnp.bfloat16)
    ids = jnp.asarray([4, 1, 6], jnp.int32)
    pools = {lanes: decode.write_pages(
        decode.init_paged_cache(cfg, 8, _PS, 2, _ROWS, head_lanes=lanes),
        wire.astype(jnp.float32), wire.astype(jnp.float32), ids)
        for lanes in (0, _LANES)}
    assert decode.heads_per_row(cfg, pools[_LANES]['k']) == 2
    exported = {lanes: np.asarray(decode.from_rows(
        paged['k'][:, ids], cfg, 2), np.float32)
        for lanes, paged in pools.items()}
    assert exported[_LANES].tobytes() == exported[0].tobytes()
    assert exported[_LANES].tobytes() == np.asarray(
        wire, np.float32).tobytes()
    np.testing.assert_array_equal(
        np.asarray(pools[_LANES]['v'], np.float32),
        np.asarray(decode.write_pages(
            pools[_LANES], exported[_LANES], exported[_LANES], ids)['v'],
            np.float32))


def _packed_layouts():
    """Heads share a row only where they fill it and the pool is float
    and not split over devices: an int8 pool, a head of 128, an odd
    h_kv, sharded heads and no `head_lanes` keep a head a row."""
    cases = [
        (dict(), {}, (4, _PS, _LANES)),
        (dict(), dict(quantize_kv=True), (8, _PS, _LANES)),
        (dict(d_model=4096), {}, (8, _PS, _LANES)),
        (dict(d_model=1920, n_heads=30, n_kv_heads=3), {}, (3, _PS, _LANES)),
        (dict(), dict(sharded_heads=True), (8, _PS, _LANES)),
        (dict(), dict(head_lanes=0), (8, _PS, 64)),
    ]
    for keys, kw, rows in cases:
        cfg = configs.ModelConfig(**dict(_LFM2, **keys))
        kw = dict(dict(head_lanes=_LANES), **kw)
        paged = jax.eval_shape(lambda: decode.init_paged_cache(  # pylint: disable=cell-var-from-loop
            cfg, 9, _PS, 2, _ROWS, **kw))
        leaf = decode._pool_array(paged['k'])  # pylint: disable=protected-access
        assert leaf.shape[2:] == rows, (keys, kw)
        assert decode.heads_per_row(cfg, paged['k']) == (
            cfg.n_kv_heads // rows[0])


_PACKED_CASES = {
    'read': lambda _: _packed_read(),
    'read-one-layer': lambda _: _packed_read(one_layer=True),
    'read-traced-layer': lambda _: _packed_read(traced=True),
    'read-window': lambda _: _packed_read(window=20, traced=True),
    'write': lambda _: _packed_write(),
    'seed-round-trip': _packed_seed,
    'handoff': lambda _: _packed_handoff(),
    'layouts': lambda _: _packed_layouts(),
}


@pytest.mark.parametrize('case', list(_PACKED_CASES))
def test_packed_rows_are_the_padded_rows(monkeypatch, case):
    """A pool whose rows hold two 64-wide heads side by side (what a
    TPU's engine holds for LFM2-24B-A2B) against the pool of one head a
    row padded to 128 lanes, in the interpreter: the kernel's reads and
    writes, the prefix hit's seeding, the handoff's pages and which
    pools pack at all."""
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    _PACKED_CASES[case](monkeypatch)


class TestPallasKernelParity:

    def test_greedy_parity_vs_dense_reference(self, setup,
                                              pallas_engine):
        """The in-kernel block-table read must reproduce the dense
        reference token-for-token, including prompts that straddle
        page and chunk boundaries."""
        cfg, params = setup
        for prompt, n in PROMPTS:
            got = pallas_engine.generate(prompt, n, timeout=180)
            assert got == _reference(cfg, params, prompt, n), prompt

    def test_greedy_parity_pallas_vs_gather(self, gather_engine,
                                            pallas_engine):
        """Both paged paths attend over the same pages with the same
        masking math — outputs must be identical, not just close."""
        for prompt, n in PROMPTS:
            a = gather_engine.generate(prompt, n, timeout=180)
            b = pallas_engine.generate(prompt, n, timeout=180)
            assert a == b, prompt

    def test_sampled_parity_pallas_vs_gather(self, gather_engine,
                                             pallas_engine):
        """Sampling depends only on (logits, key chain): at a fixed
        seed the kernel choice must not change a single token."""
        sampling = decode.SamplingConfig(temperature=0.8, top_k=10,
                                         seed=123)
        prompt = [3, 1, 4, 1, 5, 9, 2]
        a = gather_engine.generate(prompt, 6, sampling=sampling,
                                   timeout=180)
        b = pallas_engine.generate(prompt, 6, sampling=sampling,
                                   timeout=180)
        assert a == b

    def test_kernel_writes_every_tick_row_pallas_vs_gather(
            self, setup, monkeypatch, gather_engine, pallas_engine):
        """Where the kernel writes the ticks' rows itself (one token a
        slot, a float pool), the tick holds no scatter, the tokens are
        the gather path's, which scatters them, and
        `stats()['paged_kernel']` puts every cache layer's write of a
        tick on the kernel's side."""
        assert 'scatter' not in _tick_program(*setup, monkeypatch,
                                              'pallas', 1, False)
        for prompt, n in PROMPTS[:2]:
            assert (pallas_engine.generate(prompt, n, timeout=180) ==
                    gather_engine.generate(prompt, n, timeout=180)), prompt
        kernel = pallas_engine.stats()['paged_kernel']
        assert kernel['kernel_writes'] == kernel['calls'] > 0
        assert kernel['scatter_writes'] == 0

    @pytest.mark.parametrize('route', ['verify', 'int8', 'gather'])
    def test_scatter_routes_keep_the_scatter(self, setup, monkeypatch,
                                             gather_engine, route):
        """The verify tick (S = k + 1 tokens a slot), an int8 pool and
        the gather path write by the scatter before attention: their
        program holds it, and their engine counts every cache layer's
        write as `scatter_writes`."""
        cfg, params = setup
        kw = {'verify': dict(spec_tokens=3), 'int8': dict(quantize_kv=True),
              'gather': {}}[route]
        s_q = 4 if route == 'verify' else 1
        kernel = 'gather' if route == 'gather' else 'pallas'
        assert not decode.kernel_writes_rows(kernel, route == 'int8', s_q)
        assert 'scatter' in _tick_program(cfg, params, monkeypatch, kernel,
                                          s_q, route == 'int8')
        eng = (gather_engine if route == 'gather' else
               _engine(cfg, params, kernel=kernel, **kw))
        try:
            eng.generate([3, 1, 4, 1, 5], 3, timeout=180)
            counts = eng.stats()['paged_kernel']
        finally:
            if eng is not gather_engine:
                eng.stop()
        assert counts['scatter_writes'] == counts['calls'] > 0
        assert counts['kernel_writes'] == 0

    def test_int8_pages_greedy_parity(self, setup):
        """Fused in-kernel dequant must agree with the gather path's
        dequant-then-attend on int8 pools."""
        cfg, params = setup
        eng_p = _engine(cfg, params, kernel='pallas', quantize_kv=True)
        eng_g = _engine(cfg, params, kernel='gather', quantize_kv=True)
        try:
            for prompt, n in (([3, 1, 4, 1, 5, 9, 2, 6], 6),
                              ([2, 7, 1, 8, 2, 8, 1], 5)):
                assert (eng_p.generate(prompt, n, timeout=180) ==
                        eng_g.generate(prompt, n, timeout=180)), prompt
        finally:
            eng_p.stop()
            eng_g.stop()

    def test_paged_kernel_counter_follows_the_caches(self, setup):
        """stats()['paged_kernel']: per tick and live slot the pages
        that hold its cache and the new token, beside the rows of
        every table.  A request of prompt n and m new tokens rides
        m + 1 ticks (the loop dispatches one tick ahead of the read
        that shows it the finish) at cache lengths n - 1, n, ..."""
        from skypilot_tpu.observability import metrics as metrics_lib
        cfg, params = setup
        eng = _engine(cfg, params, kernel='pallas')
        try:
            ps, rows, slots = 8, 64 // 8, 2
            assert eng.stats()['paged_kernel'] == {
                'live_pages': 0, 'table_pages': 0, 'walked_pages': 0,
                'calls': 0, 'heads_per_row': 1, 'kernel_writes': 0,
                'scatter_writes': 0}
            script = (([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], 6),
                      ([7, 2, 9], 9))
            handles = [eng.submit(p, max_new_tokens=n)
                       for p, n in script]
            for h, (_, n) in zip(handles, script):
                assert len(h.result(timeout=180)) == n
            deadline = time.monotonic() + 30
            while (eng.stats()['busy_slots'] and
                   time.monotonic() < deadline):
                time.sleep(0.01)
            stats = eng.stats()
            walked = sum(-(-(len(p) - 1 + j + 1) // ps)
                         for p, n in script for j in range(n + 1))
            assert stats['paged_kernel']['live_pages'] == walked
            # No layer has a window: every layer walks every live page.
            assert stats['paged_kernel']['walked_pages'] == \
                walked * cfg.n_layers
            # Every dispatched tick adds the rows of every table; a
            # tick is counted in `ticks` only once it has been read.
            table_pages = stats['paged_kernel']['table_pages']
            assert table_pages % (slots * rows) == 0
            assert table_pages >= stats['ticks'] * slots * rows
            assert 0 < walked <= table_pages
            assert ('skytpu_engine_paged_kernel_live_page_share'
                    in metrics_lib.expose())
        finally:
            eng.stop()

    def test_kernel_gauge_tracks_choice(self, setup):
        from skypilot_tpu.observability import metrics as metrics_lib
        cfg, params = setup
        eng = _engine(cfg, params, kernel='pallas')
        try:
            text = metrics_lib.expose()
            assert 'skytpu_engine_decode_kernel_pallas 1' in text
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_greedy_sweep_misaligned_lengths(self, setup,
                                             gather_engine,
                                             pallas_engine):
        """Every prompt length across a page of offsets: the online-
        softmax accumulation over table rows must be exact wherever
        the write cursor lands within a page."""
        cfg, params = setup
        for plen in range(1, 18):
            prompt = [(7 * i + 3) % (cfg.vocab_size - 2) + 1
                      for i in range(plen)]
            ref = _reference(cfg, params, prompt, 4)
            assert gather_engine.generate(
                prompt, 4, timeout=180) == ref, plen
            assert pallas_engine.generate(
                prompt, 4, timeout=180) == ref, plen


class TestSpeculativeDecoding:

    def test_greedy_byte_identity_spec_on_vs_off(self, setup,
                                                 gather_engine,
                                                 spec_engine):
        """The acceptance rule (longest exact prefix + bonus token)
        makes speculation invisible in outputs — byte-identical to
        sequential greedy on every prompt shape."""
        del setup
        for prompt, n in PROMPTS:
            a = gather_engine.generate(prompt, n, timeout=180)
            b = spec_engine.generate(prompt, n, timeout=180)
            assert a == b, prompt

    def test_sampled_seed_identity_spec_on_vs_off(self, gather_engine,
                                                  spec_engine):
        """The key chain advances once per EMITTED token, so a fixed
        seed yields the same stream with speculation on or off."""
        sampling = decode.SamplingConfig(temperature=0.8, top_k=10,
                                         seed=123)
        prompt = [3, 1, 4, 1, 5, 9, 2]
        a = gather_engine.generate(prompt, 6, sampling=sampling,
                                   timeout=180)
        b = spec_engine.generate(prompt, 6, sampling=sampling,
                                 timeout=180)
        assert a == b

    def test_concurrent_spec_requests_exact(self, setup, spec_engine):
        cfg, params = setup
        prompts = [([3, 1, 4, 1, 5], 5), ([2, 7], 8),
                   ([9, 9, 8, 2, 1, 0, 3], 3)]
        requests = [spec_engine.submit(p, n) for p, n in prompts]
        for (p, n), r in zip(prompts, requests):
            assert r.result(timeout=180) == _reference(
                cfg, params, p, n), (p, n)

    def test_spec_stats_and_span_fields(self, spec_engine):
        spec_engine.generate(list(range(1, 20)), 8, timeout=180)
        st = spec_engine.stats()
        assert st['spec_tokens'] == 3
        assert st['spec_ticks'] > 0
        assert st['spec_proposed_tokens'] >= st['spec_accepted_tokens']
        assert st['spec_proposed_tokens'] > 0
        # 1.0 <= mean accept length <= k + 1 by construction.
        assert 1.0 <= st['spec_accept_len_mean'] <= 4.0
        span = st['recent_spans'][0]
        assert span['spec_steps'] > 0
        assert span['spec_accept_mean'] >= 1.0

    def test_spec_composes_with_pallas_and_int8(self, setup,
                                                gather_engine):
        cfg, params = setup
        eng = _engine(cfg, params, kernel='pallas', quantize_kv=True,
                      spec_tokens=3)
        try:
            assert eng.decode_kernel == 'pallas'
            for prompt, n in (([3, 1, 4, 1, 5, 9, 2, 6], 8),
                              ([2, 7, 1, 8, 2, 8, 1], 5)):
                ref = _engine(cfg, params, kernel='gather',
                              quantize_kv=True)
                try:
                    want = ref.generate(prompt, n, timeout=180)
                finally:
                    ref.stop()
                assert eng.generate(prompt, n, timeout=300) == want
        finally:
            eng.stop()

    def test_spec_on_the_derived_pool(self, setup):
        """Speculation needs no `kv_pages`: on the pool the engine
        derives, rejected drafts roll back through the null page and
        the stream is the reference's."""
        cfg, params = setup
        eng = _engine(cfg, params, kernel='gather', kv_pages=None,
                      spec_tokens=2)
        try:
            prompt = [3, 1, 4, 1, 5, 9, 2, 6] * 3
            assert eng.generate(prompt, 12, timeout=300) == _reference(
                cfg, params, prompt, 12)
            assert eng.stats()['spec_ticks'] > 0
        finally:
            eng.stop()

    def test_negative_spec_tokens_rejected(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError):
            batching_engine.ContinuousBatchingEngine(
                cfg, params, kv_pages=48, page_size=8, max_len=64,
                spec_tokens=-1)

    @pytest.mark.slow
    def test_spec_sweep_prompt_shapes(self, setup, gather_engine,
                                      spec_engine):
        """Wider identity sweep: every length across a couple of page
        offsets, greedy, and a second seed for the sampled path."""
        del setup
        for plen in (1, 2, 7, 8, 9, 15, 16, 17, 24, 30):
            prompt = [(5 * i + 2) % 200 + 1 for i in range(plen)]
            a = gather_engine.generate(prompt, 6, timeout=180)
            b = spec_engine.generate(prompt, 6, timeout=180)
            assert a == b, plen
        sampling = decode.SamplingConfig(temperature=1.1, top_k=5,
                                         seed=7)
        prompt = list(range(3, 17))
        assert (gather_engine.generate(prompt, 8, sampling=sampling,
                                       timeout=180) ==
                spec_engine.generate(prompt, 8, sampling=sampling,
                                     timeout=180))


class TestNgramDrafter:

    def test_prompt_lookup_replays_continuation(self):
        d = sampler_lib.NgramDrafter([1, 2, 3, 9, 1, 2])
        # Tail bigram [1, 2] last occurred at index 0; the following
        # tokens are [3, 9] — exactly what prompt-lookup replays.
        assert d.propose(2) == [3, 9]

    def test_pads_with_last_token(self):
        d = sampler_lib.NgramDrafter([5])
        # No earlier occurrence to extend: pad with the last history
        # token (a valid vocab id — pads are embedded before the
        # verify tick rejects them).
        assert d.propose(3) == [5, 5, 5]

    def test_observe_extends_history(self):
        d = sampler_lib.NgramDrafter([4, 6])
        d.observe([4, 6])
        # History [4, 6, 4, 6]: tail [4, 6] matches at index 0 and
        # replays [4, 6] — the greedy-cycle case speculation feeds on.
        assert d.propose(2) == [4, 6]

    def test_match_prefers_longest_ngram(self):
        d = sampler_lib.NgramDrafter([1, 2, 3, 7, 2, 3, 8, 1, 2, 3])
        # Trigram [1, 2, 3] matches at index 0 (-> 7); the bigram
        # [2, 3] alone would have matched index 4 (-> 8) — longest
        # n-gram wins.
        assert d.propose(1) == [7]
