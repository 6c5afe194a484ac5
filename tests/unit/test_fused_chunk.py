"""A prefill chunk riding the decode tick
(`decode.paged_engine_step_with_chunk`): the one program gives what the
standalone chunk followed by the plain tick gives, for the tick (tokens,
logits, pool, counts) and for the chunk (its private cache), on every
kind of stack the engine serves.  CPU, float32: the rows of the two
groups share each product, so results may differ by rounding order and
by nothing else."""
from __future__ import annotations

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from benchmarks.layouts import single
from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models.transformer import Transformer

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_TOL = 1e-5
_MAX_LEN, _PAGE, _SLOTS, _PAGES = 64, 16, 3, 13
_MODELS = ['tiny', 'tiny-qwen', 'tiny-moe', 'tiny-window-moe',
           'tiny-looped']


def build(name):
    """(cfg, params) of a tiny model in float32: a named config of the
    program, or a CPU twin of a benchmark configuration (layers of two
    kinds with a window of 8, a parallel block and experts; a stack of
    3 layers run 3 times with sandwich norms and the exit gate)."""
    if name in configs.PRESETS:
        cfg = configs.get_config(name)
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
        return cfg, params
    with open(os.path.join(_ROOT, 'benchmarks', 'tests', f'{name}.json'),
              encoding='utf-8') as f:
        model = json.load(f)
    model['torch_dtype'] = 'float32'
    if name == 'tiny-looped':
        model['early_exit_threshold'] = 0.5   # positions leave at any pass
    _, params = single.build(model, jax.devices()[:1], 1234)
    return families.of(model).program_config(model, _MAX_LEN), params


@pytest.fixture(scope='module', params=_MODELS)
def model(request):
    cfg, params = build(request.param)
    return cfg, decode.serving_params(cfg, params)


def _engine(cfg, params, live, quantize_kv=False):
    """(state, paged) with the slots of `live` {slot: prompt length}
    decoding, each at its own depth, and the other slots frozen on the
    null page, as the engine leaves them."""
    rng = np.random.default_rng(7)
    paged = decode.init_paged_cache(cfg, _PAGES, _PAGE, _SLOTS,
                                    _MAX_LEN // _PAGE,
                                    quantize_kv=quantize_kv)
    state = decode.init_engine_state(_SLOTS)
    for slot, n in live.items():
        prompt = rng.integers(1, cfg.vocab_size, size=n)
        _, cache = decode.prefill(cfg, params, jnp.asarray([prompt[:-1]]),
                                  max_len=_MAX_LEN)
        row = np.zeros(_MAX_LEN // _PAGE, np.int32)
        row[:] = 1 + 4 * slot + np.arange(4)
        paged = decode.insert_prefill_pages(
            paged, cache, jnp.asarray(row[:-(-(n - 1) // _PAGE)]),
            first_page=0)
        paged = decode.paged_admit_slot(paged, slot, row, n - 1)
        state = decode.admit_slot_state(
            state, slot, int(prompt[-1]), 9, np.full(16, -1),
            jax.random.PRNGKey(slot), 0.0, 0)
    return state, paged


def _close(got, want, what):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=_TOL, rtol=0, err_msg=f'{what}{path}')


# (live slots, the chunk's real tokens, its padded width, tokens of the
# prompt prefilled before it (0: a first chunk), int8 pages)
_CASES = {
    'first': ({0: 20, 2: 7}, 16, 16, 0, False),
    'padded-blocks': ({0: 20, 2: 7}, 6, 16, 16, False),
    'later': ({0: 20, 2: 7}, 16, 16, 16, False),
    'later-unaligned': ({0: 20, 2: 7}, 8, 8, 21, False),
    'padded-first': ({0: 20, 2: 7}, 11, 16, 0, False),
    'padded-later': ({0: 20, 2: 7}, 5, 8, 16, False),
    'int8-pages': ({0: 20, 2: 7}, 16, 16, 16, True),
    'frozen-only': ({}, 16, 16, 0, False),
    'frozen-only-later': ({}, 8, 8, 16, False),
}


@pytest.mark.parametrize('case', list(_CASES))
def test_fused_step_equals_chunk_then_tick(model, case, monkeypatch):
    cfg, params = model
    live, take, width, before, quantize_kv = _CASES[case]
    # Blocks of 4 query rows: a padded piece has blocks of pad rows
    # alone ('padded-blocks': two of four), whose attention is skipped.
    monkeypatch.setattr(decode, '_ATTEND_BLOCK', 4)
    state, paged = _engine(cfg, params, live, quantize_kv)
    prompt = np.random.default_rng(3).integers(
        1, cfg.vocab_size, size=before + take)
    piece = np.zeros((1, width), np.int32)
    piece[0, :take] = prompt[before:]
    cache = None
    if before:
        _, cache = decode.prefill(cfg, params, jnp.asarray([prompt[:before]]),
                                  max_len=_MAX_LEN)

    # The two programs the engine ran before, one after the other.
    if cache is None:
        _, want_cache = decode.prefill(cfg, params, jnp.asarray(piece),
                                       max_len=_MAX_LEN)
    else:
        _, want_cache = decode.prefill_chunk(cfg, params,
                                             jnp.asarray(piece), cache)
    want = decode.paged_engine_step(cfg, params, state, paged)
    want_logits = decode.paged_batched_step(
        cfg, params, state['tokens'][:, None], paged, state['active'])[0]

    *got, got_cache = decode.paged_engine_step_with_chunk(
        cfg, params, state, paged, jnp.asarray(piece), cache,
        jnp.asarray(take, jnp.int32), max_len=_MAX_LEN)
    got_logits = decode._paged_tick(  # pylint: disable=protected-access
        cfg, params, state['tokens'][:, None], paged, state['active'],
        kernel=None, mesh=None,
        chunk=decode._ChunkRows(  # pylint: disable=protected-access
            decode._embed(cfg, params, jnp.asarray(piece)),  # pylint: disable=protected-access
            before + jnp.arange(width),
            *((cache or decode.init_cache(cfg, 1, _MAX_LEN))[k]
              for k in 'kv'), use_flash=not before,
            rows=jnp.asarray(take, jnp.int32)))[0]

    assert int(got_cache['index']) == int(want_cache['index']) == (
        before + width)
    # The prompt's positions: what lies past them is padding, which
    # nothing reads (and whose attention the fused step leaves undone).
    real = lambda c: jax.tree.map(lambda a: a[:, :, :, :before + take],
                                  (c['k'], c['v']))
    _close(real(got_cache), real(want_cache), 'private cache')
    new_state, new_paged, finished, counts, exit_mass = got
    _close(got_logits[np.asarray(state['active'])],
           want_logits[np.asarray(state['active'])], 'logits')
    np.testing.assert_array_equal(new_state['tokens'], want[0]['tokens'])
    np.testing.assert_array_equal(new_state['active'], want[0]['active'])
    np.testing.assert_array_equal(new_state['keys'], want[0]['keys'])
    np.testing.assert_array_equal(finished, want[2])
    np.testing.assert_array_equal(new_paged['lengths'],
                                  want[1]['lengths'])
    # The pool but its null page, where frozen slots' rows land (the
    # fused step's and the tick's are both garbage nobody reads).
    _close(jax.tree.map(lambda a: a[:, 1:], (new_paged['k'],
                                             new_paged['v'])),
           jax.tree.map(lambda a: a[:, 1:], (want[1]['k'], want[1]['v'])),
           'pool')
    # The counts are the live slots' and hold nothing of the chunk.
    assert (counts is None) == (cfg.n_experts == 0)
    if counts is not None:
        np.testing.assert_array_equal(counts, want[3])
        assert int(counts[0]) == len(live) * cfg.n_layers
    assert (exit_mass is None) == (cfg.loop_passes == 1)
    if exit_mass is not None:
        np.testing.assert_allclose(exit_mass, want[4], atol=_TOL)
        np.testing.assert_allclose(float(jnp.sum(exit_mass)), len(live),
                                   atol=_TOL)


@pytest.mark.parametrize('name', _MODELS)
def test_plain_tick_holds_nothing_of_the_chunk(name):
    """A tick without a chunk takes none of the second group's code:
    no private cache is in its text, which lowers the same twice (the
    parent's text itself is pinned in test_tpu_compile.py)."""
    cfg, params = build(name)
    params = decode.serving_params(cfg, params)
    state, paged = _engine(cfg, params, {})
    private = f'x1x{cfg.n_kv_heads}x{_MAX_LEN}x{cfg.head_dim}x'
    lower = lambda: jax.jit(
        decode.bind(decode.paged_engine_step, cfg)).lower(
            params, state, paged).as_text()
    text = lower()
    assert text == lower()
    assert private not in text
    fused = jax.jit(decode.bind(
        decode.paged_engine_step_with_chunk, cfg, max_len=_MAX_LEN)).lower(
            params, state, paged, jnp.zeros((1, 16), jnp.int32)).as_text()
    assert private in fused


@pytest.mark.parametrize('stats0,stats1,want', [
    ({'prefill_chunks': 4, 'prefill_chunks_fused': 1},
     {'prefill_chunks': 12, 'prefill_chunks_fused': 7}, 75.0),
    ({'prefill_chunks': 4, 'prefill_chunks_fused': 1},
     {'prefill_chunks': 4, 'prefill_chunks_fused': 1}, None),
    ({'prefill_chunks': 4}, {'prefill_chunks': 12}, None),
], ids=['share', 'no-chunk-ran', 'no-counter'])
def test_benchmark_reader_of_the_counter(stats0, stats1, want):
    """`benchmarks/layers/chunk_fused_share.py`: the window's difference
    of the two counters in percent; nothing (and no error) where no
    chunk ran or the program has no such counter, as the parent
    commit's has not."""
    import types
    from benchmarks.layers import chunk_fused_share
    assert chunk_fused_share.compute(types.SimpleNamespace(
        stats0=stats0, stats1=stats1)) == want
