"""Mamba-2 layers with per-slot matrix state beside a page pool of the
attention layers alone, expert layers of non-gated relu^2 experts beside
a shared one, the state-update kernel and the snapshots a prefix hit
restores, against the plain reference (`benchmarks/families/
nemotron_h.py`: float32 `jax.numpy`, the recurrence one position a
step, nothing of the program in it).

At a tiny size with the served model's structure (`benchmarks/tests/
tiny-ssm-moe.json`: the pattern `MEM*EM`; hidden 64; 4 Mamba heads of
16 in 2 groups of state 16, conv kernel 4, chunks of 8; 4 query heads
on 2 KV heads of 16, no rotation; 8 experts top-2 by score + bias and
one shared), in float32 on the CPU.  The tolerance, `_TOL` = 2e-5 on
logits of standard deviation 1.0: program and reference do the same
float32 arithmetic in another order (the chunked scan against the
sequential one, fused products), which reads 1e-6 to 3e-6 here; the
reference with its matrix products rounded to int8, the control, moves
them by more than 5e-2 (`benchmarks/tests/test_nemotron_h.py`).
"""
from __future__ import annotations

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import nemotron_h as family
from benchmarks.layouts import single_seeded
from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models import import_weights
from skypilot_tpu.models import mamba
from skypilot_tpu.models import moe
from skypilot_tpu.ops import ssm_decode
from skypilot_tpu.serve import batching_engine
from skypilot_tpu.serve import cache_manager
from skypilot_tpu.serve import handoff

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_TOL = 2e-5
_MAX_LEN, _PAGE, _SLOTS, _PAGES = 64, 16, 3, 13


def _load(*parts):
    with open(os.path.join(_ROOT, 'benchmarks', *parts),
              encoding='utf-8') as f:
        return json.load(f)


@pytest.fixture(scope='module')
def setup():
    model = _load('tests', 'tiny-ssm-moe.json')
    _, params = single_seeded.build(model, jax.devices()[:1], 1234)
    cfg = family.program_config(model, _MAX_LEN)
    tokens = np.random.default_rng(0).integers(1, 256, size=48).tolist()
    return model, cfg, params, tokens, _reference(model, params, tokens)


def test_config_of_the_twin(setup):
    """The layers from the pattern, as runs of periods; only attention
    has a cache layer; the config survives its JSON form; what does not
    add up is refused."""
    _, cfg, params, _, _ = setup
    assert cfg.runs() == ((('mamba', 'experts', 'mamba', 'attn_nope'), 1),
                          (('experts', 'mamba'), 1))
    assert cfg.layer_names() == ('mamba', 'experts', 'mamba', 'attn_nope',
                                 'experts', 'mamba')
    assert (cfg.mamba_layers, cfg.conv_layers, cfg.cache_layers,
            cfg.layer_kinds()) == (3, 0, 1, ((False, 0),))
    assert (cfg.ssm_inner, cfg.ssm_conv_dim) == (64, 128)
    assert configs.config_from_json_dict(
        json.loads(json.dumps(cfg.to_json_dict()))) == cfg
    assert set(params['layers']) == {'mamba', 'attn', 'ffn'}
    for bad, match in ((dict(n_layers=7), 'whole periods'),
                       (dict(ssm_state=0), 'mamba'),
                       (dict(ssm_groups=3), 'mamba'),
                       (dict(layer_pattern=('mamba', 'conv', 'experts',
                                            'attn_nope', 'experts',
                                            'mamba')), 'not both'),
                       (dict(post_norms=True), 'stacked by kind')):
        with pytest.raises(ValueError, match=match):
            cfg.replace(**bad)


def test_the_programs_tree_is_the_familys(setup):
    """`decode.stacked_param_shapes` (what the program reads, and what a
    checkpoint is imported into) is the family's tree, leaf for leaf:
    no gate matrix for the relu^2 experts, the shared expert its own
    width."""
    model, cfg, _, _, _ = setup
    flat = {tuple(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(
                decode.stacked_param_shapes(cfg))[0]}
    assert flat == {path: shape for path, (shape, _) in
                    family.shapes(model).items()}
    assert flat[('layers', 'ffn', 'moe_mlp', 'shared_up_proj')] == (
        2, 1, 64, 48)


def _sequential(x, dt, a, b, c, s0):
    """The recurrence one row at a time in float64 numpy."""
    x, dt, a, b, c = (np.asarray(t, np.float64) for t in (x, dt, a, b, c))
    s = np.array(s0, np.float64)
    rep = x.shape[2] // b.shape[2]
    ys = []
    for t in range(x.shape[1]):
        bh = np.repeat(b[:, t], rep, axis=1)
        ch = np.repeat(c[:, t], rep, axis=1)
        s = (np.exp(dt[:, t] * a)[..., None, None] * s +
             (dt[:, t, :, None] * x[:, t])[..., None] * bh[:, :, None, :])
        ys.append(np.einsum('bhpn,bhn->bhp', s, ch))
    return (np.stack(ys, 1) if ys else None), s


@pytest.mark.parametrize('n', [5, 8, 13, 21])
def test_chunk_scan_is_the_sequential_recurrence(n):
    """The chunked form (chunks of 8, the rows not a multiple of it)
    gives the sequential recurrence's outputs and end state from a
    state already under way; `state_at` the state after any row."""
    rng = np.random.default_rng(n)
    b, h, p, g, size = 2, 4, 16, 2, 16
    x = rng.normal(size=(b, n, h, p)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, size=(b, n, h)).astype(np.float32)
    a = -rng.uniform(1, 16, size=h).astype(np.float32)
    bb = rng.normal(size=(b, n, g, size)).astype(np.float32)
    cc = rng.normal(size=(b, n, g, size)).astype(np.float32)
    s0 = rng.normal(size=(b, h, p, size)).astype(np.float32)
    y, s_end = mamba.chunk_scan(*map(jnp.asarray, (x, dt, a, bb, cc, s0)),
                                chunk=8)
    # float32 against float64 over up to 21 rows of outputs of ~10: the
    # sums in another order, a few float32 roundings of them.
    want_y, want_s = _sequential(x, dt, a, bb, cc, s0)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s_end), want_s, atol=2e-5,
                               rtol=1e-5)
    for upto in (0, 1, n // 2, n):
        _, at = _sequential(x[:, :upto], dt[:, :upto], a, bb[:, :upto],
                            cc[:, :upto], s0)
        got = mamba.state_at(*map(jnp.asarray, (x, dt, a, bb, s0)),
                             jnp.int32(upto))
        np.testing.assert_allclose(np.asarray(got), at, atol=2e-5,
                                   rtol=1e-5, err_msg=str(upto))


def test_kernel_in_interpret_mode_is_the_jnp_step(monkeypatch):
    """The Pallas kernel, interpreted, updates the state of the given
    layer as the `jnp` step does, leaves every other layer and the empty
    slots' state as they were, and reads out the same y."""
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    m, b, h, p, n, g = 3, 4, 8, 16, 16, 2
    state = jax.random.normal(keys[0], (m, b, h, p, n))
    dtx = jax.random.normal(keys[1], (b, p, h))
    decay = jax.random.uniform(keys[2], (b, 1, h))
    bb = jax.random.normal(keys[3], (b, g, n))
    cc = jax.random.normal(keys[4], (b, g, n))
    active = jnp.asarray([1, 0, 1, 1], jnp.int32)
    got_s, got_y = ssm_decode.ssm_decode_step(state, 1, dtx, decay, bb, cc,
                                              active)
    want_s, want_y = ssm_decode._ssm_decode_reference(  # pylint: disable=protected-access
        state, jnp.int32(1), dtx, decay, bb, cc, active)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_s[1, 1]),
                                  np.asarray(state[1, 1]))
    np.testing.assert_array_equal(np.asarray(got_s[0]), np.asarray(state[0]))
    assert not np.allclose(np.asarray(got_s[1, 0]), np.asarray(state[1, 0]))


# Decode steps of the long-memory test, and where its snapshot is taken.
_LONG_STEPS, _LONG_SNAP = 4096, 3584
# Relative RMS gap of y over the steps after the snapshot: a state kept
# in float32 reads 1.2e-7 here against float64 (each step rounds by
# 2^-24, and a head of dt A = 1e-3 remembers ~1,000 steps); kept in
# bfloat16 between steps it reads 4.1e-3, saved so once 1.7e-4 (2^-9 a
# rounding, the one snapshot's forgotten in part by the end).  The
# limit sits 250x above the first and 5.7x below the least of the
# others.
_LONG_TOL = 3e-5


@pytest.mark.parametrize('kept', ['engine', 'bf16-slots', 'bf16-store'])
def test_the_kept_state_holds_a_long_recurrence(setup, kept):
    """The states as the engine keeps them, at the dtype of the slots'
    `ssm` and of the snapshot store's `ssm_store` (`init_paged_cache`),
    carried through 4,096 decode steps of the state update with Mamba-2's
    initial decay rates and time steps, and through one snapshot saved
    and restored, read out as the float64 recurrence does.  Either kept
    in bfloat16, the same steps leave that limit.  Routing-free: the
    check that the benchmark's served-token comparison cannot make (its
    reference abstains where routing is close, and routing flips
    outweigh the state's rounding where it does not)."""
    _, cfg, _, _, _ = setup
    paged = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, _PAGES, _PAGE, _SLOTS, _MAX_LEN // _PAGE, snapshots=2))
    slot_dtype = jnp.bfloat16 if kept == 'bf16-slots' else paged['ssm'].dtype
    store_dtype = (jnp.bfloat16 if kept == 'bf16-store' else
                   paged['ssm_store'].dtype)
    rng = np.random.default_rng(11)
    h, p, g, n, t = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                     cfg.ssm_state, _LONG_STEPS)
    rate = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=h))
    dt = (rate * np.exp(0.5 * rng.normal(size=(1, t, h)))).astype(np.float32)
    a = -rng.uniform(1.0, 16.0, size=h).astype(np.float32)
    x = rng.normal(size=(1, t, h, p)).astype(np.float32)
    bb = rng.normal(size=(1, t, g, n)).astype(np.float32)
    cc = rng.normal(size=(1, t, g, n)).astype(np.float32)
    want, _ = _sequential(x, dt, a, bb, cc, np.zeros((1, h, p, n)))

    @jax.jit
    def steps(state, x, dt, bb, cc):
        def one(s, row):
            x_t, dt_t, b_t, c_t = row
            s, y = ssm_decode.ssm_decode_step(
                s.astype(jnp.float32), 0,
                jnp.swapaxes(dt_t[:, :, None] * x_t, 1, 2),
                jnp.exp(dt_t * a)[:, None], b_t, c_t,
                jnp.ones((1,), jnp.int32))
            return s.astype(slot_dtype), jnp.swapaxes(y, 1, 2)
        rows = tuple(jnp.swapaxes(r, 0, 1) for r in (x, dt, bb, cc))
        state, ys = jax.lax.scan(one, state, rows)
        return state, jnp.swapaxes(ys, 0, 1)

    state = jnp.zeros((1, 1, h, p, n), slot_dtype)
    cut = lambda r, lo, hi: jnp.asarray(r[:, lo:hi])  # noqa: E731
    state, _ = steps(state, *(cut(r, 0, _LONG_SNAP) for r in (x, dt, bb, cc)))
    # Saved to the store and restored from it, as a prefix hit does.
    state = jnp.asarray(jnp.asarray(state, store_dtype), slot_dtype)
    _, got = steps(state, *(cut(r, _LONG_SNAP, t) for r in (x, dt, bb, cc)))
    tail = want[:, _LONG_SNAP:]
    gap = float(np.sqrt(np.mean((np.asarray(got, np.float64) - tail) ** 2) /
                        np.mean(tail ** 2)))
    if kept == 'engine':
        assert gap < _LONG_TOL
    else:
        assert gap > _LONG_TOL


@pytest.mark.parametrize('n', [1, 9, 30])
def test_prefill_then_decode_matches_reference(setup, n):
    """A prefill (chunks of 8, the prompt not a multiple of it), then
    decode steps: the reference's logits at every position."""
    _, cfg, params, tokens, ref = setup
    logits, cache = decode.prefill(cfg, params, jnp.asarray([tokens[:n]]),
                                   max_len=_MAX_LEN)
    assert cache['ssm'].shape == (3, 1, 4, 16, 16)
    assert cache['xbc'].shape == (3, 1, 3, 128)
    np.testing.assert_allclose(np.asarray(logits[0]), ref[n - 1],
                               atol=_TOL, rtol=0)
    step = jax.jit(lambda t, c: decode.decode_step(cfg, params, t, c))
    for p in range(n, n + 5):
        logits, cache = step(jnp.asarray([[tokens[p]]]), cache)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[p],
                                   atol=_TOL, rtol=0, err_msg=str(p))


def _slots(cfg, params, live):
    """(state, paged, prompts) with the slots of `live` {slot: prompt
    length} decoding, each prefilled alone, pages inserted, its states
    admitted, as the engine leaves them."""
    rng = np.random.default_rng(7)
    paged = decode.init_paged_cache(cfg, _PAGES, _PAGE, _SLOTS,
                                    _MAX_LEN // _PAGE, snapshots=2)
    state = decode.init_engine_state(_SLOTS)
    prompts = {}
    for slot, n in live.items():
        prompt = rng.integers(1, cfg.vocab_size, size=n).tolist()
        prompts[slot] = prompt
        _, cache = decode.prefill(cfg, params, jnp.asarray([prompt[:-1]]),
                                  max_len=_MAX_LEN)
        row = np.zeros(_MAX_LEN // _PAGE, np.int32)
        row[:] = 1 + 4 * slot + np.arange(4)
        paged = decode.insert_prefill_pages(
            paged, cache, jnp.asarray(row[:-(-(n - 1) // _PAGE)]),
            first_page=0)
        paged = decode.paged_admit_slot(
            paged, slot, row, n - 1,
            states={key: cache[key] for key in ('ssm', 'xbc')})
        state = decode.admit_slot_state(
            state, slot, prompt[-1], 9, np.full(16, -1),
            jax.random.PRNGKey(slot), 0.0, 0)
    return state, paged, prompts


def _reference(model, params, seq):
    """The reference's logits at every position of `seq`, run over the
    sequence padded to `_MAX_LEN` so that one shape compiles (the model
    is causal: padding behind a position cannot reach it)."""
    padded = list(seq) + [0] * (_MAX_LEN - len(seq))
    return np.asarray(family.logits(model, params, padded, 0,
                                    _MAX_LEN))[:len(seq)]


@pytest.mark.parametrize('kernel', ['gather', 'pallas'])
def test_paged_tick_matches_reference(setup, monkeypatch, kernel):
    """Prefill, then ticks through the pool with each slot's states
    advanced by the tick (the attention and state kernels interpreted,
    or the gather path and the `jnp` step): the reference's logits; the
    empty slot's state stays zero."""
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    model, cfg, params, _, _ = setup
    state, paged, prompts = _slots(cfg, params, {0: 9, 2: 21})
    step = jax.jit(lambda s, p: decode.paged_batched_step(
        cfg, params, s['tokens'][:, None], p, s['active'], kernel=kernel))
    seqs = {slot: list(p) for slot, p in prompts.items()}
    rows = {slot: [] for slot in seqs}
    for _ in range(4):
        logits, paged, _, _ = step(state, paged)
        nxt = np.asarray(jnp.argmax(logits, -1))
        for slot, seq in seqs.items():
            rows[slot].append(np.asarray(logits[slot]))
            seq.append(int(nxt[slot]))
        state = dict(state, tokens=jnp.asarray(nxt, jnp.int32))
    for slot, seq in seqs.items():
        np.testing.assert_allclose(
            np.stack(rows[slot]), _reference(model, params, seq)[-5:-1],
            atol=_TOL, rtol=0)
    assert not np.any(np.asarray(paged['ssm'][:, 1]))
    assert not np.any(np.asarray(paged['xbc'][:, 1]))


@pytest.mark.parametrize('later,take,snap_at', [(False, 13, 5),
                                                (True, 10, 7)])
def test_a_riding_chunk_keeps_its_own_state(setup, later, take, snap_at):
    """A chunk fused into the tick beside live slots: the tick gives
    what the plain tick gives (no slot reads the chunk's state), the
    chunk's states after its real rows (a padded piece's pad rows move
    nothing) are what a standalone prefill leaves, and its snapshot is
    the states after its first `snap_at` rows."""
    _, cfg, params, _, _ = setup
    state, paged, _ = _slots(cfg, params, {0: 9, 1: 17})
    prompt = np.random.default_rng(9).integers(1, 256, size=40).tolist()
    start = 20 if later else 0
    cache = None
    if later:
        _, cache = decode.prefill(cfg, params, jnp.asarray([prompt[:start]]),
                                  max_len=_MAX_LEN)
        cache = dict(cache, snap=decode._ssm_states(cfg, 1))  # pylint: disable=protected-access
    piece = prompt[start:start + take] + [0] * (16 - take)
    plain = decode.paged_engine_step(cfg, params, state, paged)
    fused = decode.paged_engine_step_with_chunk(
        cfg, params, state, paged, jnp.asarray([piece]), cache,
        jnp.asarray(take, jnp.int32), jnp.asarray(snap_at, jnp.int32),
        max_len=_MAX_LEN)
    for a, b in zip(jax.tree.leaves(plain[:4]), jax.tree.leaves(fused[:4])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=_TOL)
    private = fused[5]
    for upto, got in ((start + take, private),
                      (start + snap_at, private['snap'])):
        _, alone = decode.prefill(cfg, params, jnp.asarray([prompt[:upto]]),
                                  max_len=_MAX_LEN)
        for key in ('ssm', 'xbc'):
            np.testing.assert_allclose(np.asarray(got[key]),
                                       np.asarray(alone[key]), atol=_TOL,
                                       err_msg=f'{key} after {upto}')


# ---------------------------------------------------------- the engine


def _engine(cfg, params, **kw):
    kw.setdefault('slots', 3)
    kw.setdefault('kv_pages', 48)
    return batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=64, prefill_chunk=8, page_size=4, **kw)


def _gap(model, params, prompt, served):
    """How far below the reference's best logit each served token's
    lies: 0 where the engine's greedy token is the reference's."""
    rows = _reference(model, params, prompt + served[:-1])[len(prompt) - 1:]
    return float(np.max(rows.max(-1) - rows[np.arange(len(served)),
                                            served]))


def test_engine_prefix_hits_restore_the_snapshot(setup):
    """Requests on one document.  The first shares nothing: its state at
    its deepest boundary of whole chunks is saved.  The second resumes
    there, prefills the rest of the document again and saves the state
    at the document's end, its branch point.  The later ones restore
    that, and one whose whole prefilled prompt is cached takes no
    prefill at all.  Each answer is the reference's and what a cold
    engine without the prefix cache gives."""
    model, cfg, params, _, _ = setup
    rng = np.random.default_rng(6)
    doc = rng.integers(1, 256, size=20).tolist()
    prompts = [doc + rng.integers(1, 256, size=n).tolist()
               for n in (3, 7, 2, 11)] + [doc + [5]]
    eng = _engine(cfg, params)
    try:
        outs = [eng.generate(p, 10, timeout=300) for p in prompts]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, o in zip(prompts, outs):
        assert _gap(model, params, p, o) <= _TOL, len(p)
    ssm = stats['ssm']
    # Pages of 4, chunks of 8: the first prompt's 22 prefilled tokens
    # fill 5 pages and save at 4 (16 tokens, two chunks); the second
    # restores 16, recomputes the document's last page and saves at 5
    # (20 tokens); the others restore 20.
    assert ssm['prompt_tokens'] == sum(len(p) for p in prompts)
    assert ssm['snapshots_restored'] == 4
    assert ssm['restored_tokens'] == 16 + 3 * 20
    assert ssm['recomputed_tokens'] == 4
    assert ssm['snapshots_saved'] == 2 and ssm['snapshots_evicted'] == 0
    assert ssm['state_bytes'] == 3 * 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert ssm['snapshot_rows'] == 6
    cold = _engine(cfg, params, prefix_caching=False)
    try:
        assert [cold.generate(p, 10, timeout=300) for p in prompts] == outs
    finally:
        cold.stop()


def test_engine_hit_without_a_snapshot_recomputes(setup):
    """A document whose pages are cached but whose state at its end is
    not (the first prompt's snapshot is at 24 tokens, its last whole
    chunk, past the document): the next prompt on it adopts the pages, prefills the
    document again for its state, and saves the state at the branch
    point; the one after restores it.  Every answer is the
    reference's."""
    model, cfg, params, _, _ = setup
    rng = np.random.default_rng(11)
    doc = rng.integers(1, 256, size=22).tolist()
    prompts = [doc + rng.integers(1, 256, size=n).tolist()
               for n in (9, 4, 6)]
    eng = _engine(cfg, params)
    try:
        outs, stats = [], []
        for p in prompts:
            outs.append(eng.generate(p, 8, timeout=300))
            stats.append(dict(eng.stats()['ssm']))
    finally:
        eng.stop()
    for p, o in zip(prompts, outs):
        assert _gap(model, params, p, o) <= _TOL, len(p)
    # Pages of 4: the document's 22 tokens fill 5 pages (20 tokens).
    assert [s['recomputed_tokens'] for s in stats] == [0, 20, 20]
    assert [s['restored_tokens'] for s in stats] == [0, 0, 20]
    assert [s['snapshots_saved'] for s in stats] == [1, 2, 2]
    assert [s['snapshots_restored'] for s in stats] == [0, 0, 1]


def test_a_reused_slot_starts_from_zero_state(setup):
    """One slot serves a long request and then a one-token prompt (no
    prefill: the slot's state is whatever admission sets): the second
    answer is the reference's, not a continuation of the first's state;
    `paged_admit_slot` without a state zeroes the slot's."""
    model, cfg, params, _, _ = setup
    rng = np.random.default_rng(12)
    first = rng.integers(1, 256, size=30).tolist()
    eng = _engine(cfg, params, slots=1)
    try:
        eng.generate(first, 6, timeout=300)
        out = eng.generate([17], 6, timeout=300)
    finally:
        eng.stop()
    assert _gap(model, params, [17], out) <= _TOL
    paged = decode.init_paged_cache(cfg, _PAGES, _PAGE, _SLOTS, 4,
                                    snapshots=1)
    paged = dict(paged, ssm=paged['ssm'] + 1.0, xbc=paged['xbc'] + 1.0)
    paged = decode.paged_admit_slot(paged, 1, np.zeros(4, np.int32), 0)
    assert not np.any(np.asarray(paged['ssm'][:, 1]))
    assert not np.any(np.asarray(paged['xbc'][:, 1]))
    assert np.all(np.asarray(paged['ssm'][:, 0]) == 1.0)


def test_the_experts_shares_sum_to_the_uncut_layer(setup):
    """The eight one-expert shares of an expert layer, each with the
    shared expert, less the shared expert seven times over, are the
    layer that holds all eight (the routed sum is a sum of the held
    experts' parts, gates normalised over the whole top-k, scaled by
    `routed_scaling_factor`); and that layer is the reference's."""
    model, cfg, params, _, _ = setup
    mp = jax.tree.map(lambda a: a[0], params['layers']['ffn']['moe_mlp'])
    x = jax.random.normal(jax.random.PRNGKey(2), (7, 64))
    whole, _, _ = moe.moe_apply(x, mp, cfg)
    parts = []
    for e in range(8):
        part = dict(mp, up_proj=mp['up_proj'][e:e + 1],
                    down_proj=mp['down_proj'][e:e + 1])
        parts.append(moe.moe_apply(x, part,
                                   cfg.replace(experts_held=(e, 1)))[0])
    shared = moe._expert_products(  # pylint: disable=protected-access
        x, jnp.ones((7, 1)), None, mp['shared_up_proj'],
        mp['shared_down_proj'], cfg)
    np.testing.assert_allclose(np.asarray(sum(parts) - 7 * shared),
                               np.asarray(whole), atol=1e-5)
    norm = params['layers']['ffn']['mlp_norm']['scale'][0]
    layer, _ = family.layer(model, params, 1, x)
    np.testing.assert_allclose(
        np.asarray(layer - x),
        np.asarray(moe.moe_apply(decode._norm(x, norm, cfg), mp, cfg)[0]),  # pylint: disable=protected-access
        atol=1e-5)


def test_the_snapshot_store_evicts_unused_snapshots_first():
    """Probation before the restored ones, oldest first; a pinned entry
    is never evicted; a prefix with a snapshot gets no second."""
    store = cache_manager.StateSnapshots(3)
    a, b, c = (store.reserve(h) for h in (10, 20, 30))
    assert sorted((a, b, c)) == [0, 1, 2] and store.reserve(10) is None
    store.release(10, store.acquire(10))          # 10 restored: kept
    pinned = store.acquire(20)
    assert store.reserve(40) == c and store.evicted == 1   # 30 went
    assert store.depth([1, 20, 3, 40], 4) == 4
    assert store.depth([1, 20, 3, 5], 3) == 2
    # 20 is pinned: 40, unrestored, goes before 10, restored.
    assert store.reserve(50) == c and store.entry(40) is None
    assert store.entry(20) == pinned and store.entry(10) == a
    store.release(20, pinned)
    assert store.reserve(60) == c and store.evicted == 3
    assert store.entry(10) == a and store.entry(20) == pinned


def test_refused_where_the_state_cannot_follow(setup):
    """Where state would be lost or left wrong, refused by mechanism:
    the speculative tick (a rejected draft stays in the state), the
    ring prefill and a slice replica (no placement for it), the KV
    handoff (the wire carries pages only), an engine over a mesh."""
    _, cfg, params, tokens, _ = setup
    with pytest.raises(ValueError, match='recurrent state'):
        decode.paged_spec_engine_step(
            cfg, params, decode.init_engine_state(2),
            decode.init_paged_cache(cfg, 9, 16, 2, 4), jnp.zeros((2, 2),
                                                               jnp.int32))
    with pytest.raises(ValueError, match='mamba'):
        decode.prefill_sp(cfg, params, jnp.asarray([tokens[:8]]), mesh=None,
                          max_len=64)
    with pytest.raises(handoff.HandoffError, match='mamba'):
        handoff.check_model(cfg)
    from skypilot_tpu.serve import slice_replica
    with pytest.raises(ValueError, match='mamba'):
        slice_replica.SliceReplicaEngine(cfg, params, num_hosts=1)
    with pytest.raises(ValueError, match='recurrent state'):
        _engine(cfg, params, spec_tokens=2)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ('data',))
    with pytest.raises(ValueError, match='recurrent state'):
        _engine(cfg, params, mesh=mesh)


def test_the_checkpoint_names_cover_the_tree():
    """`import_weights` reads the benchmark file's keys (as a checkpoint's
    config.json) into the config the family gives, and names a
    checkpoint tensor for every leaf of the tree, with the shapes they
    stack to."""
    model = _load('configs', 'nemotron-3-nano-30b-a3b-e16.json')
    hf = dict(model, n_routed_experts=model['published']['n_routed_experts'])
    cfg, name = import_weights.config_from_hf(hf)
    want = family.program_config(model, 8704)
    assert name == 'nemotron_h'
    for field in ('layer_pattern', 'ssm_heads', 'ssm_head_dim', 'ssm_groups',
                  'ssm_state', 'ssm_chunk', 'conv_kernel', 'd_ff',
                  'shared_d_ff', 'n_experts', 'expert_top_k', 'expert_scale',
                  'expert_gate_eps', 'mlp_act', 'tie_embeddings',
                  'head_dim', 'n_heads', 'n_kv_heads', 'norm_eps'):
        assert getattr(cfg, field) == getattr(want, field), field
    plan = import_weights._plan_for(want, name)  # pylint: disable=protected-access
    tree = import_weights.expected_tree(want)
    flat = {tuple(k.key for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert set(plan) == flat
    names = {template for template, *_ in plan.values()}
    assert {'backbone.layers.{i}.mixer.A_log',
            'backbone.layers.{i}.mixer.gate.e_score_correction_bias',
            'backbone.layers.{i}.mixer.experts.{e}.up_proj.weight',
            'backbone.layers.{i}.mixer.shared_experts.down_proj.weight',
            'backbone.norm_f.weight', 'lm_head.weight'} <= names
    kinds = want.layer_names()
    for path, entry in plan.items():
        if len(entry) == 3:
            stack = {'mamba': 'mamba', 'attn': 'attn_nope',
                     'ffn': 'experts'}[path[1]]
            assert [kinds[i] for i in entry[2]] == [stack] * len(entry[2])


# The program text of the other stacked and patterned models' decode
# programs at their tiny twins (the plain tick, the tick with a fresh
# and a later chunk riding it, a prefill), as the parent of the change
# that brought mamba layers and runs of periods lowered them.
_TEXTS = {
    'tiny-conv-moe': ('07f92fd838dbfcf2', '374435148f50fdfc',
                      'cb6807eeab57563e', '7ab888f2dc297262'),
    'tiny-window-moe': ('1509e84e7dbf4301', 'b16a1e2b5b1d3c2c',
                        '72374b1ffc3b236a', 'a2eb572f8b1e0e43'),
}


@pytest.mark.parametrize('twin', sorted(_TEXTS))
def test_other_models_lower_to_the_text_they_had(twin):
    """LFM2's (conv layers stacked by kind) and command-a's (a window
    pattern, a parallel block, SwiGLU experts) programs are untouched by
    the new kinds, the runs and the relu^2 experts: their lowered text
    hashes to what it did before them."""
    from benchmarks import families
    model = _load('tests', f'{twin}.json')
    fam = families.of(model)
    cfg = fam.program_config(model, 64)
    params = {}
    for path, (shape, _) in fam.shapes(model).items():
        node = params
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = jax.ShapeDtypeStruct(shape, jnp.float32)
    params = jax.eval_shape(lambda p: decode.serving_params(cfg, p), params)
    paged = jax.eval_shape(
        lambda: decode.init_paged_cache(cfg, 13, 16, 3, 4))
    state = jax.eval_shape(lambda: decode.init_engine_state(3))
    tok = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    chunk = jax.jit(decode.bind(decode.paged_engine_step_with_chunk, cfg,
                                max_len=64))
    cache = jax.eval_shape(lambda: decode._private_cache(cfg, 64, 16))  # pylint: disable=protected-access
    texts = (
        jax.jit(decode.bind(decode.paged_engine_step, cfg)).lower(
            params, state, paged).as_text(),
        chunk.lower(params, state, paged, tok, None,
                    jnp.int32(5)).as_text(),
        chunk.lower(params, state, paged, tok, cache,
                    jnp.int32(5)).as_text(),
        jax.jit(decode.bind(decode.prefill, cfg, max_len=64)).lower(
            params, tok).as_text())
    assert tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in texts) == _TEXTS[twin]
