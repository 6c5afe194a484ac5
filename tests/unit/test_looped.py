"""A looped stack (the layers run several times a token, each pass on
cache layers of its own), sandwich norms and the exit gate, against the
plain reference (`benchmarks/families/ouro.py`: float32 `jax.numpy`,
nothing of the program in it).

At a tiny size with the served model's structure (`benchmarks/tests/
tiny-looped.json`: 3 layers run 3 times = 9 cache layers, hidden 64, 4
heads of 16 on 4 KV heads, FFN 160, four norm scales a layer, a gate),
in float32 on the CPU.  The tolerance, `_TOL` = 2e-5 on logits of
standard deviation 1.0: program and reference do the same float32
arithmetic in another order (fused products, online softmax, the scan),
which reads 4e-6 here; the reference with its matrix products rounded
to int8, the control, reads 0.3, and a dropped norm scale (seeded 1 +
0.1 z) or a pass reading another pass's keys reads 1e-2 or more.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmarks.families import ouro as family
from benchmarks.layouts import single
from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models import import_weights
from skypilot_tpu.models import transformer
from skypilot_tpu.serve import batching_engine
from skypilot_tpu.serve import handoff
from skypilot_tpu.serve import model_server

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_TOL = 2e-5
_LAYERS, _PASSES = 3, 3


def _twin(threshold):
    with open(os.path.join(_ROOT, 'benchmarks', 'tests',
                           'tiny-looped.json'), encoding='utf-8') as f:
        model = json.load(f)
    model['early_exit_threshold'] = threshold
    return model


def _setup(threshold):
    model = _twin(threshold)
    _, params = single.build(model, jax.devices()[:1], 1234)
    cfg = family.program_config(model, 64)
    tokens = np.random.default_rng(0).integers(1, 256, size=48).tolist()
    ref, exits, mass = (np.asarray(a) for a in
                        family.forward(model, params, tokens, 0, 48))
    return model, cfg, params, tokens, ref, exits, mass


@pytest.fixture(scope='module')
def setup():
    """The published threshold, 1: the head reads the last pass."""
    return _setup(1)


@pytest.fixture(scope='module')
def setup_half():
    """Threshold 0.5: positions leave after different passes."""
    return _setup(0.5)


def test_program_config_of_the_twin(setup):
    model, cfg, params, _, _, _, _ = setup
    assert (cfg.loop_passes, cfg.exit_threshold, cfg.post_norms,
            cfg.n_layers, cfg.cache_layers) == (3, 1.0, True, 3, 9)
    assert configs.config_from_json_dict(
        json.loads(json.dumps(cfg.to_json_dict()))) == cfg
    # Every model of before: one pass, as many cache layers as layers.
    assert (configs.TINY.loop_passes, configs.TINY.post_norms,
            configs.TINY.cache_layers) == (1, False, configs.TINY.n_layers)
    with pytest.raises(ValueError, match='loop_passes'):
        configs.TINY.replace(loop_passes=0)
    assert set(params['layers']['layer']) == {
        'attn', 'mlp', 'attn_norm', 'attn_post_norm', 'mlp_norm',
        'mlp_post_norm'}
    assert params['exit_gate']['kernel'].shape == (64, 1)
    assert family.cache_layers(model) == 9


@pytest.mark.parametrize('n', [1, 5, 20, 33])
def test_prefill_logits_match_reference(setup, n):
    _, cfg, params, tokens, ref, _, _ = setup
    logits, cache = decode.prefill(cfg, params,
                                   jnp.asarray([tokens[:n]]), max_len=64)
    assert int(cache['index']) == n
    assert cache['k'].shape[0] == _LAYERS * _PASSES
    np.testing.assert_allclose(np.asarray(logits[0]), ref[n - 1],
                               atol=_TOL, rtol=0)


def test_cached_decode_matches_reference(setup):
    _, cfg, params, tokens, ref, _, _ = setup
    _, cache = decode.prefill(cfg, params, jnp.asarray([tokens[:6]]),
                              max_len=64)
    step = jax.jit(lambda t, c: decode.decode_step(cfg, params, t, c))
    for p in range(6, 30):
        logits, cache = step(jnp.asarray([[tokens[p]]]), cache)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[p],
                                   atol=_TOL, rtol=0, err_msg=str(p))


# Two and three chunks, and a padded one (width 16 holding 11 tokens;
# the pad rows' keys lie behind every real query's horizon in every
# pass).
@pytest.mark.parametrize('cuts,pad', [((6, 9), 0), ((6, 19, 21), 0),
                                      ((4, 15), 5)])
def test_chunked_prefill_matches_reference(setup, cuts, pad):
    _, cfg, params, tokens, ref, _, _ = setup
    _, cache = decode.prefill(cfg, params,
                              jnp.asarray([tokens[:cuts[0]]]), max_len=64)
    for a, b in zip(cuts, cuts[1:]):
        piece = tokens[a:b] + [0] * pad
        logits, cache = decode.prefill_chunk(
            cfg, params, jnp.asarray([piece]), cache)
        cache = dict(cache, index=jnp.asarray(b, jnp.int32))
        if not pad:
            np.testing.assert_allclose(np.asarray(logits[0]), ref[b - 1],
                                       atol=_TOL, rtol=0)
    logits, _ = decode.decode_step(
        cfg, params, jnp.asarray([[tokens[cuts[-1]]]]), cache)
    np.testing.assert_allclose(np.asarray(logits[0]), ref[cuts[-1]],
                               atol=_TOL, rtol=0)


def test_control_and_altered_token_are_far(setup):
    """What the tolerance has to tell apart: the int8 control, and a
    sequence with one token changed."""
    model, _, params, tokens, ref, _, _ = setup
    low = np.asarray(family.logits(model, params, tokens, 0, 48,
                                   precision='int8'))
    assert np.max(np.abs(low - ref)) > 1000 * _TOL
    other = list(tokens)
    other[40] = (other[40] + 1) % 256 or 1
    alt = np.asarray(family.logits(model, params, other, 0, 48))
    np.testing.assert_allclose(alt[:40], ref[:40], atol=_TOL, rtol=0)
    assert np.max(np.abs(alt[40:] - ref[40:])) > 1000 * _TOL


# --------------------------------------------- each pass's own keys


def test_each_pass_keeps_its_own_keys(setup):
    """A position's keys differ from pass to pass, and a later token
    attends, in pass t, the keys pass t wrote: with cache layer t * L +
    l overwritten by layer l's of another pass the logits move.  A
    program that kept L cache layers (every pass on the last one's
    keys) cannot pass this and the reference's agreement both."""
    _, cfg, params, tokens, ref, _, _ = setup
    _, cache = decode.prefill(cfg, params, jnp.asarray([tokens[:12]]),
                              max_len=64)
    k = np.asarray(cache['k'])
    for t in range(1, _PASSES):
        for l in range(_LAYERS):
            assert np.abs(k[t * _LAYERS + l, :, :, :12] -
                          k[l, :, :, :12]).max() > 1e-2
    step = jax.jit(lambda c: decode.decode_step(
        cfg, params, jnp.asarray([[tokens[12]]]), c)[0])
    np.testing.assert_allclose(np.asarray(step(cache)[0]), ref[12],
                               atol=_TOL, rtol=0)
    for t, l, other in ((1, 0, 0), (2, 1, 0), (0, 2, 2)):
        swapped = {name: cache[name].at[t * _LAYERS + l].set(
            cache[name][other * _LAYERS + l]) for name in ('k', 'v')}
        moved = np.asarray(step(dict(cache, **swapped))[0])
        assert np.abs(moved - ref[12]).max() > 1000 * _TOL, (t, l)


def test_paged_tick_writes_every_cache_layer(monkeypatch, setup):
    """One write-then-attend forward over a pool of seeded noise: the
    kernel given the whole pool and cache layer t * L + l (which rides
    the two scans) against the gather view; both leave the same pool,
    in which each of the 9 cache layers got its own rows and nothing
    else moved."""
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    _, cfg, params, _, _, _, _ = setup
    rng = np.random.default_rng(9)
    slots, ps, rows, s_q = 3, 4, 10, 2
    paged = decode.init_paged_cache(cfg, 1 + slots * rows, ps, slots, rows)
    assert paged['k'].shape[0] == _LAYERS * _PASSES
    noise = lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype)
    paged = dict(
        paged, k=noise(paged['k']), v=noise(paged['v']),
        block_tables=jnp.asarray(
            1 + rng.permutation(slots * rows).reshape(slots, rows),
            jnp.int32),
        lengths=jnp.asarray([5, 8, 33], jnp.int32))
    tokens = jnp.asarray(rng.integers(1, 256, (slots, s_q)), jnp.int32)
    run = lambda kernel: jax.jit(lambda t, p: decode._paged_forward(
        cfg, params, t, p, kernel=kernel, all_positions=True))(
            tokens, paged)
    logits_g, k_g, _, _, exit_g, _ = run('gather')
    logits_p, k_p, _, _, exit_p, _ = run('pallas')
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_g),
                               atol=_TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(exit_p), np.asarray(exit_g),
                               atol=_TOL, rtol=0)
    assert exit_g.shape == (_PASSES, slots, s_q)
    np.testing.assert_allclose(np.asarray(k_p), np.asarray(k_g),
                               atol=_TOL, rtol=0)
    moved = np.argwhere((np.asarray(k_g) != np.asarray(paged['k'])).any(-1))
    # [cache layer, page, head, offset]
    assert len(moved) == _LAYERS * _PASSES * cfg.n_kv_heads * slots * s_q
    assert {int(c) for c, _, _, _ in moved} == set(range(9))


# -------------------------------------------------------- the norms

_SCALES = [('final_norm',), ('layers', 'layer', 'attn_norm'),
           ('layers', 'layer', 'attn_post_norm'),
           ('layers', 'layer', 'mlp_norm'),
           ('layers', 'layer', 'mlp_post_norm')]


def _with_scale(params, path, fn):
    node = params
    for key in path:
        node = node[key]
    return decode._with_node(params, path + ('scale',), fn(node['scale']))


@pytest.mark.parametrize('path', _SCALES, ids=[p[-1] for p in _SCALES])
def test_each_norm_scale_moves_program_and_reference_alike(setup, path):
    """The final norm (which also stands between two passes) and each
    of a layer's four norms: with one scale perturbed, program and
    reference move, and move alike; with it left out of the program
    (all ones, where the seeded scale is 1 + 0.1 z) they part."""
    model, cfg, params, tokens, ref, _, _ = setup
    n = 20
    bump = 1.0 + 0.3 * np.sin(np.arange(64, dtype=np.float32))
    changed = _with_scale(params, path, lambda s: s * bump)
    want = np.asarray(family.logits(model, changed, tokens, 0, n))[n - 1]
    assert np.abs(want - ref[n - 1]).max() > 1000 * _TOL
    prefill = lambda p: np.asarray(decode.prefill(
        cfg, p, jnp.asarray([tokens[:n]]), max_len=64)[0][0])
    np.testing.assert_allclose(prefill(changed), want, atol=_TOL, rtol=0)
    dropped = _with_scale(params, path, jnp.ones_like)
    assert np.abs(prefill(dropped) - ref[n - 1]).max() > 1000 * _TOL


# ---------------------------------------------------- the selection


def test_at_threshold_one_every_position_reads_the_last_pass(setup):
    _, _, _, _, _, exits, mass = setup
    assert (exits == _PASSES - 1).all()
    np.testing.assert_allclose(mass.sum(0), 1.0, atol=1e-6)


def test_selection_agrees_with_reference(setup_half):
    """At threshold 0.5 positions leave after different passes; the
    program reads the pass the reference reads (told from its exit
    mass by the rule itself) and gives that pass's logits, for the last
    position and for all positions alike."""
    model, cfg, params, tokens, ref, exits, mass = setup_half
    assert len(set(exits.tolist())) == _PASSES
    # The other passes' hidden states give other logits.
    last = _setup(1)[4]
    early = exits < _PASSES - 1
    assert np.abs(ref - last)[early].max(-1).min() > 1000 * _TOL
    np.testing.assert_array_equal(ref[~early], last[~early])

    n = 40
    cache = decode.init_cache(cfg, 1, 64)
    write = lambda c, l, new: jax.lax.dynamic_update_slice(
        c, new[None], (l, 0, 0, 0, 0))
    for all_positions in (True, False):
        logits, _, _, _, exit_p, _ = decode._scan_layers_and_unembed(
            cfg, params, decode._embed(cfg, params,
                                       jnp.asarray([tokens[:n]])),
            jnp.arange(n), cache['k'], cache['v'], write,
            use_flash=False, all_positions=all_positions)
        rows = slice(0, n) if all_positions else slice(n - 1, n)
        got = np.asarray(logits).reshape(-1, 256)
        np.testing.assert_allclose(got, ref[rows], atol=_TOL, rtol=0)
        exit_p = np.asarray(exit_p)[:, 0]               # [T, rows]
        np.testing.assert_allclose(exit_p, mass[:, rows], atol=1e-5)
        reached = np.cumsum(exit_p, 0) >= model['early_exit_threshold']
        picked = np.where(reached.any(0), reached.argmax(0), _PASSES - 1)
        np.testing.assert_array_equal(picked, exits[rows])
    for p in (3, 4, 6, 20):     # among them leavers after pass 0 and 1
        logits, _ = decode.prefill(cfg, params,
                                   jnp.asarray([tokens[:p + 1]]),
                                   max_len=64)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[p],
                                   atol=_TOL, rtol=0)


# ---------------------------------------------------------- the engine


def _engine(cfg, params, kernel, **kw):
    saved = {k: os.environ.get(k) for k in
             ('SKYTPU_DECODE_KERNEL', 'SKYTPU_PALLAS_INTERPRET')}
    os.environ['SKYTPU_DECODE_KERNEL'] = kernel
    if kernel == 'pallas':
        os.environ['SKYTPU_PALLAS_INTERPRET'] = '1'
    try:
        return batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, prefill_chunk=8, kv_pages=48,
            page_size=4, **kw)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _gap(model, params, prompt, served):
    """How far below the reference's best logit each served token's
    lies: 0 where the engine's greedy token is the reference's."""
    seq = prompt + served[:-1]
    ref = np.asarray(family.logits(model, params, seq, 0, len(seq)))
    rows = ref[len(prompt) - 1:]
    return float(np.max(rows.max(-1) - rows[np.arange(len(served)),
                                            served]))


@pytest.mark.parametrize('threshold', [1, 0.5])
@pytest.mark.parametrize('kernel', ['gather', 'pallas'])
def test_paged_engine_matches_reference(kernel, threshold):
    """The paged engine, kernel and gather paths: prefix miss and hit
    (cached pages seed all 9 cache layers of the private cache), padded
    tail chunks, requests batched beside each other and alone."""
    model, cfg, params, _, _, _, _ = _setup(threshold)
    rng = np.random.default_rng(6)
    doc = rng.integers(1, 256, size=21).tolist()
    prompts = [doc + rng.integers(1, 256, size=n).tolist()
               for n in (3, 7, 2, 11)]
    eng = _engine(cfg, params, kernel, slots=3)
    try:
        first = [eng.submit(p, 12) for p in prompts[:3]]   # batched
        outs = [r.result(timeout=300) for r in first]
        last = eng.submit(prompts[3], 12)                  # alone
        outs.append(last.result(timeout=300))
        for p, o in zip(prompts, outs):
            assert _gap(model, params, p, o) <= _TOL, len(p)
        # 21 shared tokens are 5 whole pages of 4.
        assert first[0].span.prefix_hit_pages == 0
        assert last.span.prefix_hit_pages == 5
        assert eng.generate(prompts[0], 12, timeout=300) == outs[0]
        assert eng.stats()['loop']['cache_layers'] == 9
    finally:
        eng.stop()


@pytest.mark.parametrize('quantize_kv', [False, True],
                         ids=['float', 'int8'])
def test_seed_by_pages_equals_the_gather(monkeypatch, setup, quantize_kv):
    """A pool leaf of 2 GiB or more is copied out page by page (the TPU
    compiler copies most of such a leaf around a gather,
    `tests/unit/test_tpu_compile.py`): the same private cache, bit for
    bit, as the gather gives, and the same served tokens on a prefix
    hit."""
    _, cfg, params, tokens, _, _, _ = setup
    rng = np.random.default_rng(11)
    paged = decode.init_paged_cache(cfg, 12, 4, 2, 8,
                                    quantize_kv=quantize_kv)
    fill = lambda a: jnp.asarray(
        rng.integers(-100, 100, a.shape) if a.dtype == jnp.int8
        else rng.normal(size=a.shape), a.dtype)
    paged = dict(paged, k=jax.tree.map(fill, paged['k']),
                 v=jax.tree.map(fill, paged['v']))
    ids = jnp.asarray([7, 2, 9], jnp.int32)
    seed = lambda: decode.paged_seed_private(cfg, paged, ids, priv_len=32)
    want = seed()
    monkeypatch.setattr(decode, '_GATHER_LIMIT_BYTES', 0)
    got = seed()
    assert int(got['index']) == 12
    for name in ('k', 'v'):
        assert got[name].shape == (9, 1, 4, 32, 16)
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))
    prompts = [tokens[:21] + tokens[30:33], tokens[:21] + tokens[40:47]]
    eng = _engine(cfg, params, 'gather', slots=1, quantize_kv=quantize_kv)
    try:
        outs = [eng.submit(p, 6) for p in prompts]
        outs = [(r.result(timeout=300), r.span.prefix_hit_pages)
                for r in outs]
        assert [hit for _, hit in outs] == [0, 5]
    finally:
        eng.stop()
    monkeypatch.undo()
    eng = _engine(cfg, params, 'gather', slots=1, quantize_kv=quantize_kv)
    try:
        assert [eng.generate(p, 6, timeout=300) for p in prompts] == [
            o for o, _ in outs]
    finally:
        eng.stop()


def test_handoff_pages_hold_every_cache_layer(setup):
    """`export_prefill` / `import_pages` (`export_private_pages`,
    `write_pages`): a prompt's pages, all 9 cache layers of them, go
    from one engine to another, which then serves the prompt from them
    with the tokens the first gives."""
    _, cfg, params, tokens, _, _, _ = setup
    prompt = tokens[:23]
    a = _engine(cfg, params, 'gather', slots=1)
    b = _engine(cfg, params, 'gather', slots=1)
    try:
        want = a.generate(prompt, 8, timeout=300)
        decoded = handoff.decode_payload(a.export_prefill(prompt))
        assert decoded['k'].shape[0] == _LAYERS * _PASSES
        assert b.import_pages(decoded['hashes'], decoded['page_size'],
                              decoded['k'], decoded['v']) == (5, 0)
        request = b.submit(prompt, 8)
        assert request.result(timeout=300) == want
        assert request.span.prefix_hit_pages == 5
    finally:
        a.stop()
        b.stop()


@pytest.mark.parametrize('threshold', [1, 0.5])
def test_speculative_tick_gives_the_plain_ticks_tokens(threshold):
    """The verify tick (`all_positions=True`: the gate selects a pass
    for every drafted position) emits what plain ticking emits; its
    exit mass counts the emitted tokens only."""
    _, cfg, params, tokens, _, _, _ = _setup(threshold)
    # A repetitive prompt, so that drafts are accepted.
    prompts = [(tokens[:7] * 4)[:25], tokens[10:21]]
    plain = _engine(cfg, params, 'gather', slots=2)
    spec = _engine(cfg, params, 'gather', slots=2, spec_tokens=3)
    try:
        want = [plain.generate(p, 16, timeout=300) for p in prompts]
        got = [r.result(timeout=300)
               for r in [spec.submit(p, 16) for p in prompts]]
        assert got == want
        loop = spec.stats()['loop']
        assert sum(loop['exit_mass']) == pytest.approx(32, abs=1e-3)
        assert loop['passes'] == _PASSES * spec.stats()['ticks']
    finally:
        plain.stop()
        spec.stop()


def test_engine_counters_by_hand(setup_half):
    """stats()['loop'] and ['paged_kernel'] on a three-request script,
    one slot, one request after another (page size 4, 9 cache
    layers)."""
    _, cfg, params, _, _, _, _ = setup_half
    eng = _engine(cfg, params, 'gather', slots=1)
    script = [([5, 6, 7], 4), (list(range(1, 12)), 3),
              (list(range(20, 42)), 2)]
    try:
        for prompt, n in script:
            assert len(eng.generate(prompt, n, timeout=300)) == n
        stats = eng.stats()
    finally:
        eng.stop()
    # Ticks run one ahead of the reads, so a request's slot rides one
    # tick more (frozen on the device) before the host learns it has
    # finished.  The kernel's counts are taken as a tick is dispatched,
    # `ticks` and `passes` as it is read: the last request's extra tick
    # may still be out when its answer is.
    ticks = sum(n + 1 for _, n in script)
    assert stats['ticks'] in (ticks - 1, ticks)
    loop = stats['loop']
    assert (loop['steps'], loop['cache_layers']) == (_PASSES, 9)
    assert loop['passes'] == _PASSES * stats['ticks']
    # A request of n answers is live ON THE DEVICE for n ticks; each
    # decoded token's exit mass adds up to 1 over the passes, and at
    # threshold 0.5 not all of it is the last pass's.
    assert len(loop['exit_mass']) == _PASSES
    assert sum(loop['exit_mass']) == pytest.approx(
        sum(n for _, n in script), abs=1e-4)
    assert all(m > 0 for m in loop['exit_mass'])
    live = sum(-(-(len(prompt) + j) // 4)
               for prompt, n in script for j in range(n + 1))
    kernel = stats['paged_kernel']
    assert kernel['live_pages'] == live
    assert kernel['walked_pages'] == 9 * live
    assert kernel['calls'] == 9 * ticks


def _tiny_params():
    return nn.meta.unbox(transformer.Transformer(configs.TINY).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])


def test_a_model_of_one_pass_reports_no_loop():
    cfg = configs.TINY
    params = _tiny_params()
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=64, slots=1, kv_pages=20, page_size=4)
    try:
        assert len(eng.generate([3, 4, 5], 3, timeout=300)) == 3
        stats = eng.stats()
    finally:
        eng.stop()
    assert 'loop' not in stats
    # One call a layer a tick dispatched: three answers and the tick
    # that rides ahead.
    assert stats['paged_kernel']['calls'] == cfg.n_layers * 4


# ------------------------------------- the bound on prompts mid-prefill


def _pool(cfg, pages):
    paged = decode.init_paged_cache(cfg, pages, 4, 4, 16)
    return paged['k'], paged['v']


def test_prefill_bound_counts_cache_layers(setup):
    """A private cache holds max_len positions of every cache layer: a
    pool of 48 pages of 4 holds three of 64 positions.  (Counting the 3
    layers, as before passes existed, would let all 4 slots prefill at
    once, beside a pool a third of their size.)"""
    _, cfg, params, _, _, _, _ = setup
    eng = _engine(cfg, params, 'gather', slots=4)
    try:
        assert eng._max_prefills == 3
    finally:
        eng.stop()


@pytest.mark.parametrize('left,want', [
    (None, 3),      # no statistics (the CPU): the pool's bound alone
    (10.0, 3),      # room for ten: the pool's bound holds
    (3.5, 2),       # three fit, one of them is the programs' room
    (2.0, 1), (0.4, 1), (-1.0, 1)])     # never under 1
def test_prefill_bound_follows_what_the_device_has_left(
        monkeypatch, setup, left, want):
    _, cfg, _, _, _, _, _ = setup
    private = 2 * 9 * 4 * 16 * 64 * 4
    monkeypatch.setattr(
        batching_engine, '_device_memory_left',
        lambda device: None if left is None else int(left * private))
    assert batching_engine._prefill_bound(4, private,
                                          _pool(cfg, 48)) == want


def test_device_memory_left_reads_the_backends_statistics():
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    assert batching_engine._device_memory_left(Device(None)) is None
    assert batching_engine._device_memory_left(Device({})) is None
    assert batching_engine._device_memory_left(Device(
        {'bytes_limit': 1000, 'bytes_in_use': 400,
         'peak_bytes_in_use': 900})) == 600
    # The CPU of these tests reports none: today's rule stands.
    assert batching_engine._device_memory_left(jax.devices()[0]) is None


# --------------------------------------- a model of one pass: as it was


def _tick_text(cfg, params):
    shapes = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    paged = jax.eval_shape(
        lambda: decode.init_paged_cache(cfg, 9, 4, 2, 4))
    state = jax.eval_shape(lambda: decode.init_engine_state(2))
    return jax.jit(decode.bind(decode.paged_engine_step, cfg,
                               kernel='gather')).lower(
                                   shapes(params), state, paged).as_text()


def test_one_pass_takes_the_code_it_took(setup):
    """`TINY`'s tick holds the loops it held (the layer scan and the
    sampler's two) and nothing of the passes or the gate: its lowered
    text was byte-identical to the parent commit's when passes were
    added (PERF.md, PR 35), and the settings that only a looped stack
    reads leave it as it is.  The looped twin's tick holds one loop
    more, the scan over passes around the layer scan."""
    cfg = configs.TINY
    params = jax.eval_shape(_tiny_params)
    text = _tick_text(cfg, params)
    assert text.count('stablehlo.while') == 3
    assert text == _tick_text(cfg.replace(exit_threshold=0.5), params)
    _, looped, looped_params, _, _, _, _ = setup
    assert _tick_text(looped, looped_params).count('stablehlo.while') == 4


def test_other_paths_reject_what_they_do_not_build(setup):
    _, cfg, params, tokens, _, _, _ = setup
    for bad in (configs.TINY.replace(loop_passes=2),
                configs.TINY.replace(post_norms=True)):
        with pytest.raises(ValueError, match='post_norms'):
            transformer.Transformer(bad).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        with pytest.raises(ValueError, match='no post_norms, one pass'):
            decode.prefill_sp(bad, params, jnp.asarray([tokens[:8]]),
                              mesh=None, max_len=64)


def test_model_flops_count_the_passes(setup):
    _, cfg, _, _, _, _, _ = setup
    n_params, max_len = 200_000, 64
    outside = 2 * 256 * 64
    attn = 2.0 * 9 * cfg.n_heads * cfg.head_dim * max_len
    assert model_server.model_flops_per_token(
        cfg, n_params, max_len) == pytest.approx(
            2.0 * (n_params + 2 * (n_params - outside)) + attn)


# ------------------------------------------------ importing a checkpoint


def test_config_from_hf_reads_the_looped_keys():
    hf = {k: v for k, v in _twin(1).items()
          if k not in ('family', 'layout', 'engine', 'published',
                       'reduced', 'name', 'source')}
    cfg, name = import_weights.config_from_hf(hf)
    assert name == 'ouro'
    assert (cfg.loop_passes, cfg.exit_threshold, cfg.post_norms,
            cfg.layer_pattern, cfg.cache_layers) == (3, 1.0, True, (), 9)
    assert (cfg.n_layers, cfg.d_model, cfg.n_kv_heads, cfg.d_ff,
            cfg.head_dim, cfg.rope_theta, cfg.norm_eps) == (
                3, 64, 4, 160, 16, 1e6, 1e-6)


def test_name_mapping_on_a_fake_state_dict():
    """The mapping plan over a fake state dict of the twin's shapes
    (torch layouts: Linear weights [out, in]) gives the tree
    `decode.py` reads: the family's `shapes`, leaf for leaf, the four
    norms and the gate from the names the published checkpoint uses."""
    model = _twin(1)
    cfg = family.program_config(model, 64)
    plan = import_weights._plan_for(cfg, 'ouro')
    names = {template for template, _ in plan.values()}
    assert {'model.layers.{i}.input_layernorm.weight',
            'model.layers.{i}.input_layernorm_2.weight',
            'model.layers.{i}.post_attention_layernorm.weight',
            'model.layers.{i}.post_attention_layernorm_2.weight',
            'model.early_exit_gate.weight',
            'model.early_exit_gate.bias'} <= names
    d, f, v, hd = 64, 160, 256, 16
    torch_shapes = {
        'embed_tokens': (v, d), 'norm': (d,), 'lm_head': (v, d),
        'early_exit_gate.weight': (1, d), 'early_exit_gate.bias': (1,),
        'q_proj': (4 * hd, d), 'k_proj': (4 * hd, d),
        'v_proj': (4 * hd, d), 'o_proj': (d, 4 * hd),
        'gate_proj': (f, d), 'up_proj': (f, d), 'down_proj': (d, f),
        'layernorm': (d,)}
    rng = np.random.default_rng(0)

    def fake(name):
        key = next(k for k in torch_shapes if k in name)
        return rng.normal(size=torch_shapes[key]).astype(np.float32)

    tree = {}
    for path, (template, transform) in plan.items():
        if '{i}' in template:
            leaf = np.stack([transform(fake(template.format(i=i)))
                             for i in range(cfg.n_layers)])
            path = ('layers', 'layer') + path
        else:
            leaf = transform(fake(template))
        tree[path] = leaf.shape
    assert tree == {path: shape for path, (shape, _) in
                    family.shapes(model).items()}
    # The gate's torch weight [1, d] lands as the kernel [d, 1].
    gate = rng.normal(size=(1, d)).astype(np.float32)
    np.testing.assert_array_equal(
        plan[('exit_gate', 'kernel')][1](gate), gate.T)
