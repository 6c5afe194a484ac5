"""The parallel block of window and position-free full attention beside
sigmoid-routed and averaged shared experts, against its plain reference
(`benchmarks/families/cohere2_moe.py`, the one reference in the repo:
float32 `jax.numpy`, nothing of the program in it).

At a tiny size with the served model's structure (`benchmarks/tests/
tiny-window-moe.json`: 8 layers = two periods of window, window,
window, full; 16 experts top-4, 2 shared; window 8; 4 query heads a KV
head), in float32 on the CPU.  The tolerance, `_TOL` = 2e-5 on logits
of standard deviation 0.16: program and reference do the same float32
arithmetic in another order (fused products, online softmax, blocks),
which reads 4e-7 here; the reference with its matrix products rounded
to int8, the control, reads 0.17, and a top-k that differed in its
last place would read about 1e-2.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import cohere2_moe as family
from benchmarks.layouts import single
from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models import moe
from skypilot_tpu.ops import paged_attention
from skypilot_tpu.serve import batching_engine

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_TOL = 2e-5
_WINDOW = 8


@pytest.fixture(scope='module')
def setup():
    with open(os.path.join(_ROOT, 'benchmarks', 'tests',
                           'tiny-window-moe.json'), encoding='utf-8') as f:
        model = json.load(f)
    model['torch_dtype'] = 'float32'
    _, params = single.build(model, jax.devices()[:1], 1234)
    cfg = family.program_config(model, 64)
    tokens = np.random.default_rng(0).integers(1, 256, size=48).tolist()
    ref = np.asarray(family.logits(model, params, tokens, 0, 48))
    return model, cfg, params, tokens, ref


def test_program_config_of_the_twin(setup):
    """The family's `program_config` reads the file's keys into the
    block's settings, and the published list's kinds into one period;
    the config survives its JSON form (a converted checkpoint's
    model_config.json)."""
    model, cfg, _, _, _ = setup
    assert (cfg.norm_type, cfg.parallel_block, cfg.tie_embeddings,
            cfg.expert_score_fn, cfg.shared_expert_combine) == (
                'layernorm', True, True, 'sigmoid', 'average')
    assert (cfg.n_experts, cfg.expert_top_k, cfg.n_shared_experts,
            cfg.held_experts, cfg.n_heads // cfg.n_kv_heads) == (
                16, 4, 2, (0, 16), 4)
    assert configs.config_from_json_dict(
        json.loads(json.dumps(cfg.to_json_dict()))) == cfg
    assert cfg.layer_kinds() == (
        ((True, _WINDOW),) * 3 + ((False, 0),)) * 2
    assert family.layer_types(model)[3] == 'full_attention'


# Contexts under (5), at (8) and over (9, 20, 33) the window of 8.
@pytest.mark.parametrize('n', [5, 8, 9, 20, 33])
def test_prefill_logits_match_reference(setup, n):
    _, cfg, params, tokens, ref = setup
    logits, cache = decode.prefill(cfg, params,
                                   jnp.asarray([tokens[:n]]), max_len=64)
    assert int(cache['index']) == n
    np.testing.assert_allclose(np.asarray(logits[0]), ref[n - 1],
                               atol=_TOL, rtol=0)


def test_cached_decode_matches_reference(setup):
    """Prefill of 6 (under the window), then one token a step through
    the cache to position 30: at, and then far over, the window."""
    _, cfg, params, tokens, ref = setup
    _, cache = decode.prefill(cfg, params, jnp.asarray([tokens[:6]]),
                              max_len=64)
    step = jax.jit(lambda t, c: decode.decode_step(cfg, params, t, c))
    for p in range(6, 30):
        logits, cache = step(jnp.asarray([[tokens[p]]]), cache)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[p],
                                   atol=_TOL, rtol=0, err_msg=str(p))


# Chunk boundaries inside the window (6|3), across it (6|13, 19|2) and
# a padded chunk (width 16 holding 11 tokens; the pad rows' keys lie
# behind every real query's horizon).
@pytest.mark.parametrize('cuts,pad', [((6, 9), 0), ((6, 19, 21), 0),
                                      ((4, 15), 5)])
def test_chunked_prefill_matches_reference(setup, cuts, pad):
    _, cfg, params, tokens, ref = setup
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
    sequence with one token changed, both read orders of magnitude
    over `_TOL`."""
    model, _, params, tokens, ref = setup
    low = np.asarray(family.logits(model, params, tokens, 0, 48,
                                   precision='int8'))
    assert np.max(np.abs(low - ref)) > 1000 * _TOL
    other = list(tokens)
    other[40] = (other[40] + 1) % 256 or 1
    alt = np.asarray(family.logits(model, params, other, 0, 48))
    np.testing.assert_allclose(alt[:40], ref[:40], atol=_TOL, rtol=0)
    assert np.max(np.abs(alt[40:] - ref[40:])) > 1000 * _TOL


# --------------------------------------------------------- the window


def _one_layer(setup, kind):
    model, cfg, params, _, _ = setup
    i = {'window': 0, 'full': 3}[kind]
    lp = jax.tree.map(lambda a: a[i], params['layers']['layer'])
    return cfg, lp, cfg.layer_kinds()[i]


def _attend(cfg, lp, kind, x, positions):
    """One layer of the program over x [1, s, d] at `positions` [s],
    through the masked path, K and V as the scan's body writes them."""
    rope_on, window = kind
    h = decode._norm(x, lp['attn_norm']['scale'], cfg)
    k = decode._rope_if(jnp.asarray(rope_on),
                        decode._attn_proj(h, lp['attn']['k_proj'],
                                          cfg.n_kv_heads, cfg.head_dim),
                        positions, cfg)
    v = decode._attn_proj(h, lp['attn']['v_proj'], cfg.n_kv_heads,
                          cfg.head_dim)
    # The cache is indexed by key position: place the keys there.
    size = int(positions[-1]) + 1
    cache = lambda a: jnp.zeros(
        a.shape[:2] + (size, a.shape[3]), a.dtype).at[
            :, :, positions].set(a)
    y, _ = decode._layer_forward(
        x, lp, cfg, positions, cache(k), cache(v), use_flash=False,
        rope_on=jnp.asarray(rope_on),
        window=jnp.asarray(window or decode._NO_WINDOW, jnp.int32))
    return np.asarray(y[0])


def test_window_edge(setup):
    """On a window layer a key at p - window changes nothing at p, a
    key at p - window + 1 does; on a full layer both do."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 20, 64)), jnp.float32)
    # Not a constant: the norm subtracts a row's mean.
    bump = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    positions = jnp.arange(20)
    p = 19
    for kind_name, outside_moves in (('window', False), ('full', True)):
        cfg, lp, kind = _one_layer(setup, kind_name)
        base = _attend(cfg, lp, kind, x, positions)[p]
        outside = _attend(cfg, lp, kind,
                          x.at[0, p - _WINDOW].add(bump), positions)[p]
        inside = _attend(cfg, lp, kind,
                         x.at[0, p - _WINDOW + 1].add(bump), positions)[p]
        assert (np.max(np.abs(outside - base)) > 1e-4) == outside_moves
        assert np.max(np.abs(inside - base)) > 1e-4


def test_full_layer_has_no_position(setup):
    """A full layer knows the order of its keys and nothing else of
    their positions: the last query's output does not change when the
    tokens before it change places.  A window layer's does (rotary,
    and which keys its window holds).  (A shift of ALL positions by one
    amount would move neither: rotary scores depend on differences of
    positions only.  So the test permutes.)"""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, 12, 64)), jnp.float32)
    order = np.concatenate([rng.permutation(11), [11]])
    positions = jnp.arange(12)
    for kind_name, moves in (('full', False), ('window', True)):
        cfg, lp, kind = _one_layer(setup, kind_name)
        a = _attend(cfg, lp, kind, x, positions)[-1]
        b = _attend(cfg, lp, kind, x[:, order], positions)[-1]
        assert (np.max(np.abs(a - b)) > 1e-4) == moves, kind_name
        if not moves:
            np.testing.assert_allclose(a, b, atol=_TOL, rtol=0)


@pytest.mark.parametrize('lengths,window', [
    ((5, 40, 17), 8), ((31, 8, 0), 8), ((63, 20, 9), 16),
    ((40, 40, 40), 1 << 30)])
def test_paged_kernel_window_matches_reference(monkeypatch, lengths,
                                               window):
    """The Pallas kernel (interpreted) walking from the window's first
    page against the gather reference, lengths under, at and over the
    window; a window that cuts nothing reads the kernel without one."""
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    rng = np.random.default_rng(3)
    b, h_q, h_kv, d, ps, rows = len(lengths), 8, 2, 16, 4, 16
    q = jnp.asarray(rng.normal(size=(b, h_q, 1, d)), jnp.float32)
    pool_k = jnp.asarray(rng.normal(size=(b * rows + 1, h_kv, ps, d)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(b * rows + 1, h_kv, ps, d)),
                         jnp.float32)
    tables = jnp.asarray(
        rng.permutation(b * rows).reshape(b, rows) + 1, jnp.int32)
    args = (q, pool_k, pool_v, tables, jnp.asarray(lengths, jnp.int32))
    kw = dict(sm_scale=d ** -0.5, window=jnp.asarray(window, jnp.int32))
    want = paged_attention._paged_attention_reference(*args, **kw)
    got = paged_attention._paged_attention_pallas(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    if window == 1 << 30:
        plain = paged_attention._paged_attention_pallas(
            *args, sm_scale=d ** -0.5)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))


@pytest.mark.parametrize('s_q', [1, 3], ids=['tick', 'verify3'])
def test_tick_on_the_whole_pool_with_two_kinds_of_layer(monkeypatch, setup,
                                                        s_q):
    """One write-then-attend forward over a pool of seeded noise,
    depths under, at and over the window: the kernel given the whole
    pool, the layer's index and the layer's window (all three ride the
    layer scan) against the gather view.  Logits agree; both leave the
    same pool, in which each of the 8 layers (window, window, window,
    full, twice) got its own rows and nothing else moved."""
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    _, cfg, params, _, _ = setup
    rng = np.random.default_rng(9)
    slots, ps, rows = 3, 4, 10
    paged = decode.init_paged_cache(cfg, 1 + slots * rows, ps, slots, rows)
    noise = lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype)
    paged = dict(
        paged, k=noise(paged['k']), v=noise(paged['v']),
        block_tables=jnp.asarray(
            1 + rng.permutation(slots * rows).reshape(slots, rows),
            jnp.int32),
        lengths=jnp.asarray([5, _WINDOW, 33], jnp.int32))
    tokens = jnp.asarray(rng.integers(1, 256, (slots, s_q)), jnp.int32)
    run = lambda kernel: jax.jit(lambda t, p: decode._paged_forward(
        cfg, params, t, p, kernel=kernel, all_positions=True))(
            tokens, paged)
    logits_g, k_g, v_g, counts_g, _, _ = run('gather')
    logits_p, k_p, v_p, counts_p, _, _ = run('pallas')
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_g),
                               atol=_TOL, rtol=0)
    np.testing.assert_array_equal(np.asarray(counts_p),
                                  np.asarray(counts_g))
    for got, same, old in ((k_p, k_g, paged['k']), (v_p, v_g, paged['v'])):
        # (The rows of later layers carry the two paths' rounding.)
        np.testing.assert_allclose(np.asarray(got), np.asarray(same),
                                   atol=_TOL, rtol=0)
        moved = np.argwhere((np.asarray(got) != np.asarray(old)).any(-1))
        # [layer, page, head, offset]: s_q rows a slot in every layer
        # and head, at the slots' own (page, offset).
        assert len(moved) == cfg.n_layers * cfg.n_kv_heads * slots * s_q
        tables = np.asarray(paged['block_tables'])
        pos = np.asarray(paged['lengths'])[:, None] + np.arange(s_q)
        assert {(int(p), int(o)) for _, p, _, o in moved} == {
            (int(tables[b, q // ps]), int(q % ps))
            for b in range(slots) for q in pos[b]}


# -------------------------------------------------------- the experts


def test_sigmoid_gates_sum_to_one_and_unheld_is_shared_only(setup):
    """Gates are the top-k sigmoid scores renormalised; a token none of
    whose experts is held gets the shared experts' average only."""
    _, cfg, params, _, _ = setup
    mp = jax.tree.map(lambda a: a[0],
                      params['layers']['layer']['moe_mlp'])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(12, 64)),
                    jnp.float32)
    scores, gates, idx = moe.route(x, mp['router']['kernel'], cfg)
    np.testing.assert_allclose(np.asarray(jnp.sum(gates, -1)), 1.0,
                               atol=1e-6)
    top = np.sort(np.asarray(scores), axis=-1)[:, -cfg.expert_top_k:]
    np.testing.assert_allclose(
        np.sort(np.asarray(gates), -1), top / top.sum(-1, keepdims=True),
        atol=1e-6)
    # Hold one expert that token 0 did not choose, alone.
    absent = next(e for e in range(16) if e not in np.asarray(idx[0]))
    one = cfg.replace(experts_held=(absent, 1))
    mp_one = dict(mp, **{k: mp[k][absent:absent + 1]
                         for k in ('gate_proj', 'up_proj', 'down_proj')})
    out, _, counts = moe.moe_apply(x, mp_one, one)
    shared = sum(
        (jax.nn.silu(x @ mp['shared_gate_proj'][k]) *
         (x @ mp['shared_up_proj'][k])) @ mp['shared_down_proj'][k]
        for k in range(2)) / 2
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(shared[0]),
                               atol=1e-5)
    held_rows = int(np.sum(np.asarray(idx) == absent))
    assert [int(c) for c in counts] == [12, held_rows, held_rows]


def test_the_shares_add_up(setup):
    """The 16 experts held as 4 shares of 4: the four routed parts,
    with attention and the shared experts counted once, equal the
    uncut reference's layer output."""
    model, cfg, params, _, _ = setup
    stacked = params['layers']['layer']
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 12, 64)), jnp.float32)
    want = np.asarray(family.layer(model, stacked, 0, x[0]))
    lp = jax.tree.map(lambda a: a[0], stacked)
    mp = lp['moe_mlp']
    h = decode._norm(x, lp['attn_norm']['scale'], cfg)[0]

    def share(j):
        held = {k: mp[k][4 * j:4 * j + 4]
                for k in ('gate_proj', 'up_proj', 'down_proj')}
        return cfg.replace(experts_held=(4 * j, 4)), dict(mp, **held)

    # Share 0 runs the whole layer: attention, shared experts, its part.
    cfg0, mp0 = share(0)
    got = _attend(cfg0, dict(lp, moe_mlp=mp0), cfg.layer_kinds()[0], x,
                  jnp.arange(12))
    pairs = 0
    for j in range(1, 4):
        cfg_j, mp_j = share(j)
        part, _, counts = moe.moe_apply(
            h, mp_j, cfg_j.replace(n_shared_experts=0))
        got = got + np.asarray(part)
        pairs += int(counts[1])
    np.testing.assert_allclose(got, want, atol=_TOL, rtol=0)
    # Every (token, expert) pair fell to exactly one share.
    _, _, counts0 = moe.moe_apply(h, mp0, cfg0)
    assert pairs + int(counts0[1]) == 12 * cfg.expert_top_k


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


@pytest.mark.parametrize('kernel', ['gather', 'pallas'])
def test_paged_engine_matches_reference(setup, kernel):
    """The paged engine, kernel and gather paths: prefix miss and hit,
    padded tail chunks, requests batched beside each other and alone,
    contexts from under to four times the window."""
    model, cfg, params, _, _ = setup
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
        # Alone or beside others, served from cached pages or not: the
        # same tokens (what the deleted branches guarded).
        alone = eng.generate(prompts[0], 12, timeout=300)
        assert alone == outs[0]
    finally:
        eng.stop()


def test_engine_counters_by_hand(setup):
    """stats()['moe'] and 'walked_pages' on a three-request script, one
    slot, one request after another (page size 4, window 8, 8 layers of
    which 6 have the window; all 16 experts held, top-4)."""
    _, cfg, params, _, _ = setup
    eng = _engine(cfg, params, 'gather', slots=1)
    script = [([5, 6, 7], 4), (list(range(1, 12)), 3),
              (list(range(20, 42)), 2)]
    try:
        for prompt, n in script:
            assert len(eng.generate(prompt, n, timeout=300)) == n
        stats = eng.stats()
    finally:
        eng.stop()
    # A request of n answers is live ON THE DEVICE for n ticks (its
    # last prompt token rides the first), each a row through 8 expert
    # layers.
    rows = sum(n for _, n in script)
    assert stats['moe']['tokens'] == 8 * rows
    assert stats['moe']['held_pairs'] == 8 * rows * 4
    # One row a tick: the fullest expert of a layer holds 1.
    assert stats['moe']['max_expert_tokens'] == 8 * rows
    # Tick j of a prompt of p tokens runs at depth p - 1 + j: it holds
    # ceil((depth + 1) / 4) pages; a window layer walks from the page
    # of key depth - 7.  The host counts what it dispatches: ticks run
    # one ahead of the reads, so a request's slot rides one tick more
    # (frozen on the device) before the host learns it has finished.
    live = walked = 0
    for prompt, n in script:
        for j in range(n + 1):
            depth = len(prompt) - 1 + j
            pages = -(-(depth + 1) // 4)
            live += pages
            walked += 2 * pages + 6 * (pages - max(depth - 7, 0) // 4)
    assert stats['paged_kernel']['live_pages'] == live
    assert stats['paged_kernel']['walked_pages'] == walked
    assert walked < 8 * live


@pytest.mark.parametrize('kernel', ['gather', 'pallas'])
def test_engine_on_reformed_kernels_equals_generate(setup, kernel):
    """The parallel block with two kinds of layer, 16 query heads on 2
    KV heads: the engine serves on q/k/v kernels it re-formed
    (`decode.serving_params`) the tokens `decode.generate` gives on
    the caller's training-layout tree, which it leaves whole."""
    _, cfg, params, tokens, _ = setup
    prompts = [tokens[:19], tokens[5:16]]
    want = [np.asarray(decode.generate(
        cfg, params, jnp.asarray([p], jnp.int32), max_new_tokens=10,
        max_len=64)[1])[0].tolist() for p in prompts]
    eng = _engine(cfg, params, kernel, slots=2)
    try:
        requests = [eng.submit(p, 10) for p in prompts]
        assert [r.result(timeout=300) for r in requests] == want
        attn = eng.params['layers']['layer']['attn']
        assert attn['q_proj']['kernel'].shape == (
            cfg.n_layers, cfg.d_model, cfg.n_heads * cfg.head_dim)
        assert attn['k_proj']['kernel'].shape == (
            cfg.n_layers, cfg.d_model, cfg.n_kv_heads * cfg.head_dim)
        assert eng.stats()['weights']['reformed_bytes'] == sum(
            params['layers']['layer']['attn'][name]['kernel'].nbytes
            for name in ('q_proj', 'k_proj', 'v_proj'))
    finally:
        eng.stop()
    assert params['layers']['layer']['attn']['q_proj']['kernel'].shape == (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(params))
