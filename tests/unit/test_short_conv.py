"""Gated short-convolution layers with per-sequence tails beside a page
pool of the attention layers alone, leading dense layers, and experts
chosen by score + bias, against the plain reference
(`benchmarks/families/lfm2_moe.py`: float32 `jax.numpy`, nothing of the
program in it).

At a tiny size with the served block's structure (`benchmarks/tests/
tiny-conv-moe.json`: two dense conv layers, then one period of
attention, conv, conv, conv; hidden 64, 4 query heads on 2 KV heads of
16; 8 experts top-2 selected by score + bias; a conv kernel of 3), in
float32 on the CPU.  The tolerance, `_TOL` = 2e-5 on logits of
standard deviation 0.17: program and reference do the same float32
arithmetic in another order (fused products, the online softmax, the
taps summed in another order), which reads 1e-6 here; a tail one row
off or another sequence's row in it reads 1e-2 or more, and the
reference with its matrix products rounded to int8, the control, 0.1.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import lfm2_moe as family
from benchmarks.layouts import single
from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models import import_weights
from skypilot_tpu.models import moe
from skypilot_tpu.models import transformer
from skypilot_tpu.serve import batching_engine
from skypilot_tpu.serve import handoff

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_TOL = 2e-5
_MAX_LEN, _PAGE, _SLOTS, _PAGES = 64, 16, 3, 13


def _twin():
    with open(os.path.join(_ROOT, 'benchmarks', 'tests',
                           'tiny-conv-moe.json'), encoding='utf-8') as f:
        return json.load(f)


@pytest.fixture(scope='module')
def setup():
    model = _twin()
    _, params = single.build(model, jax.devices()[:1], 1234)
    cfg = family.program_config(model, _MAX_LEN)
    tokens = np.random.default_rng(0).integers(1, 256, size=48).tolist()
    ref = np.asarray(family.logits(model, params, tokens, 0, 48))
    return model, cfg, params, tokens, ref


def test_config_of_the_twin(setup):
    """The block's settings from the file; a conv layer has no cache
    layer; the config survives its JSON form; what does not add up is
    refused."""
    _, cfg, params, _, _ = setup
    assert cfg.layer_names() == ('conv', 'conv', 'full', 'conv', 'conv',
                                 'conv')
    assert (cfg.conv_layers, cfg.cache_layers, cfg.layer_kinds()) == (
        5, 1, ((True, 0),))
    assert cfg.stacked_by_kind and not configs.TINY.stacked_by_kind
    assert configs.TINY.cache_layers == configs.TINY.n_layers
    assert configs.config_from_json_dict(
        json.loads(json.dumps(cfg.to_json_dict()))) == cfg
    assert set(params) == {'embed', 'final_norm', 'lead', 'layers'}
    assert set(params['lead']) == {'conv', 'ffn'}
    for bad, match in ((dict(conv_kernel=1), 'conv_kernel'),
                       (dict(dense_d_ff=0), 'dense_d_ff'),
                       (dict(n_layers=7), 'whole periods'),
                       (dict(post_norms=True), 'stacked by kind')):
        with pytest.raises(ValueError, match=match):
            cfg.replace(**bad)


def test_the_programs_tree_is_the_familys(setup):
    """`decode.stacked_param_shapes` (what the program reads, and what a
    checkpoint is imported into) is the family's tree, leaf for leaf."""
    model, cfg, _, _, _ = setup
    tree = decode.stacked_param_shapes(cfg)
    flat = {path: leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    flat = {tuple(k.key for k in path): shape
            for path, shape in flat.items()}
    assert flat == {path: shape for path, (shape, _) in
                    family.shapes(model).items()}


@pytest.mark.parametrize('n', [1, 2, 3, 20, 33])
def test_prefill_matches_reference(setup, n):
    _, cfg, params, tokens, ref, = setup[:5]
    logits, cache = decode.prefill(cfg, params, jnp.asarray([tokens[:n]]),
                                   max_len=_MAX_LEN)
    assert cache['k'].shape[0] == 1
    assert cache['conv'].shape == (5, 1, 2, 64)
    np.testing.assert_allclose(np.asarray(logits[0]), ref[n - 1],
                               atol=_TOL, rtol=0)


# Two and three chunks, the cuts at every offset mod 3 of the kernel.
@pytest.mark.parametrize('cuts', [(6, 9, 30), (7, 10, 30), (8, 11, 30),
                                  (1, 2, 30), (2, 4, 5, 30)])
def test_chunked_prefill_then_decode_matches_reference(setup, cuts):
    """A chunk's first rows take the tail the chunk before left: the
    cut at any offset of the kernel gives the logits of one prefill,
    and decode after them the reference's."""
    _, cfg, params, tokens, ref = setup
    _, cache = decode.prefill(cfg, params, jnp.asarray([tokens[:cuts[0]]]),
                              max_len=_MAX_LEN)
    for a, b in zip(cuts, cuts[1:]):
        logits, cache = decode.prefill_chunk(
            cfg, params, jnp.asarray([tokens[a:b]]), cache)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[b - 1],
                                   atol=_TOL, rtol=0, err_msg=str((a, b)))
    step = jax.jit(lambda t, c: decode.decode_step(cfg, params, t, c))
    for p in range(cuts[-1], cuts[-1] + 6):
        logits, cache = step(jnp.asarray([[tokens[p]]]), cache)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[p],
                                   atol=_TOL, rtol=0, err_msg=str(p))


def _slots(cfg, params, live, head_lanes=0):
    """(state, paged, prompts) with the slots of `live` {slot: prompt
    length} decoding, each prefilled alone, pages inserted, its tails
    admitted, as the engine leaves them."""
    rng = np.random.default_rng(7)
    paged = decode.init_paged_cache(cfg, _PAGES, _PAGE, _SLOTS,
                                    _MAX_LEN // _PAGE,
                                    head_lanes=head_lanes)
    state = decode.init_engine_state(_SLOTS)
    prompts = {}
    for slot, n in live.items():
        prompt = rng.integers(1, cfg.vocab_size, size=n).tolist()
        prompts[slot] = prompt
        _, cache = decode.prefill(cfg, params,
                                  jnp.asarray([prompt[:-1]]),
                                  max_len=_MAX_LEN)
        cache = dict(cache, snap=decode._conv_tails(  # pylint: disable=protected-access
            cfg, _MAX_LEN // _PAGE))
        row = np.zeros(_MAX_LEN // _PAGE, np.int32)
        row[:] = 1 + 4 * slot + np.arange(4)
        paged = decode.insert_prefill_pages(
            paged, cache, jnp.asarray(row[:-(-(n - 1) // _PAGE)]),
            first_page=0)
        paged = decode.paged_admit_slot(paged, slot, row, n - 1,
                                        tail=cache['conv'][:, 0])
        state = decode.admit_slot_state(
            state, slot, prompt[-1], 9, np.full(16, -1),
            jax.random.PRNGKey(slot), 0.0, 0)
    return state, paged, prompts


def _reference_row(model, params, seq):
    return np.asarray(family.logits(model, params, seq, 0, len(seq)))[-1]


@pytest.mark.parametrize('kernel,lanes', [('gather', 0), ('pallas', 0),
                                          ('pallas', 128), ('pallas', 32),
                                          ('gather', 32)],
                         ids=['gather', 'pallas', 'pallas-lanes',
                              'pallas-packed', 'gather-packed'])
def test_paged_tick_matches_reference(setup, monkeypatch, kernel, lanes):
    """Prefill, then ticks through the pool with each slot's tails
    carried: the gather path, the kernel (interpret mode), the kernel
    on a pool of lane-padded rows (what a TPU's engine holds where the
    heads cannot fill a row: 2 heads of 16 in 128 lanes) and both on a
    pool whose rows hold two heads side by side (rows of 32 lanes here,
    as a TPU's engine packs two heads of 64 to 128) give the
    reference's logits."""
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    model, cfg, params, _, _ = setup
    state, paged, prompts = _slots(cfg, params, {0: 9, 2: 21},
                                   head_lanes=lanes)
    if lanes:
        # 2 kv heads of 16: padded to 128 lanes a head, or one row.
        assert paged['k'].shape[2:] == ((2, _PAGE, 128) if lanes == 128
                                        else (1, _PAGE, 32))
    step = jax.jit(lambda s, p: decode.paged_batched_step(
        cfg, params, s['tokens'][:, None], p, s['active'],
        kernel=kernel))
    seqs = {slot: list(p) for slot, p in prompts.items()}
    for _ in range(4):
        logits, paged, _, _ = step(state, paged)
        nxt = np.asarray(jnp.argmax(logits, -1))
        for slot, seq in seqs.items():
            np.testing.assert_allclose(
                np.asarray(logits[slot]), _reference_row(model, params, seq),
                atol=_TOL, rtol=0)
            seq.append(int(nxt[slot]))
        state = dict(state, tokens=jnp.asarray(nxt, jnp.int32))


@pytest.mark.parametrize('later,take', [(False, 16), (False, 13),
                                        (True, 16), (True, 10)])
def test_a_riding_chunk_keeps_to_its_own_rows(setup, later, take):
    """A chunk fused into the tick beside live slots: the tick gives
    what the plain tick gives (no slot's convolution reads a row of the
    chunk), and the chunk's private cache, its tails after its real rows
    (a padded piece's pad rows take no tail) and the snapshots of the
    pages it completed, what a standalone prefill gives."""
    model, cfg, params, _, _ = setup
    state, paged, _ = _slots(cfg, params, {0: 9, 1: 17})
    prompt = np.random.default_rng(9).integers(1, 256, size=40).tolist()
    start = 20 if later else 0
    cache = None
    if later:
        _, cache = decode.prefill(cfg, params, jnp.asarray([prompt[:start]]),
                                  max_len=_MAX_LEN)
        cache = dict(cache, snap=decode._conv_tails(  # pylint: disable=protected-access
            cfg, _MAX_LEN // _PAGE))
    piece = prompt[start:start + take] + [0] * (16 - take)
    plain = decode.paged_engine_step(cfg, params, state, paged)
    fused = decode.paged_engine_step_with_chunk(
        cfg, params, state, paged, jnp.asarray([piece]), cache,
        jnp.asarray(take, jnp.int32), max_len=_MAX_LEN)
    for a, b in zip(jax.tree.leaves(plain[:4]), jax.tree.leaves(fused[:4])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=_TOL)
    private = fused[5]
    _, alone = decode.prefill(cfg, params,
                              jnp.asarray([prompt[:start + take]]),
                              max_len=_MAX_LEN)
    np.testing.assert_allclose(np.asarray(private['conv']),
                               np.asarray(alone['conv']), atol=_TOL)
    # The page that ends at position 16 is complete once the prompt's
    # rows reach it: its snapshot is the tails after 16 rows.
    _, at16 = decode.prefill(cfg, params, jnp.asarray([prompt[:16]]),
                             max_len=_MAX_LEN)
    snap = np.asarray(private['snap'][:, 0])
    if start + take >= 16 and start < 16:
        np.testing.assert_allclose(snap, np.asarray(at16['conv'][:, 0]),
                                   atol=_TOL)
    else:
        assert not later or np.all(snap == 0)


# ---------------------------------------------------------- the engine


def _engine(cfg, params, **kw):
    kw.setdefault('slots', 3)
    return batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=64, prefill_chunk=8, kv_pages=48, page_size=4,
        **kw)


def _gap(model, params, prompt, served):
    """How far below the reference's best logit each served token's
    lies: 0 where the engine's greedy token is the reference's."""
    seq = prompt + served[:-1]
    ref = np.asarray(family.logits(model, params, seq, 0, len(seq)))
    rows = ref[len(prompt) - 1:]
    return float(np.max(rows.max(-1) - rows[np.arange(len(served)),
                                            served]))


def test_engine_prefix_hit_restores_the_tails(setup):
    """Requests on one document, batched and alone: the later ones hit
    its cached pages, whose snapshots give the tails (a hit ending at a
    page boundary of the prompt, a partial one, and one whole prompt
    cached); each answer is the reference's and what a cold engine
    without the prefix cache gives."""
    model, cfg, params, _, _ = setup
    rng = np.random.default_rng(6)
    doc = rng.integers(1, 256, size=21).tolist()
    prompts = [doc + rng.integers(1, 256, size=n).tolist()
               for n in (3, 7, 2, 11)] + [doc[:13]]
    eng = _engine(cfg, params)
    try:
        first = eng.submit(prompts[0], 10)
        outs = [first.result(timeout=300)]
        rest = [eng.submit(p, 10) for p in prompts[1:]]
        outs += [r.result(timeout=300) for r in rest]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, o in zip(prompts, outs):
        assert _gap(model, params, p, o) <= _TOL, len(p)
    assert first.span.prefix_hit_pages == 0
    assert [r.span.prefix_hit_pages for r in rest] == [5, 5, 5, 3]
    conv = stats['conv']
    assert conv['snapshots_restored'] == 4
    assert conv['restored_tokens'] == 4 * (5 + 5 + 5 + 3)
    assert conv['prompt_tokens'] == sum(len(p) for p in prompts)
    assert conv['layers'] == 5 and conv['snapshots_saved'] >= 5
    assert conv['state_bytes'] == 5 * 3 * 2 * 64 * 4
    cold = _engine(cfg, params, prefix_caching=False)
    try:
        assert [cold.generate(p, 10, timeout=300) for p in prompts] == outs
    finally:
        cold.stop()


def test_engine_on_packed_rows_gives_the_unpadded_tokens(setup,
                                                         monkeypatch):
    """The engine through the kernel (interpret mode) on a pool whose
    rows hold both kv heads side by side, as a TPU's holds LFM2's heads
    of 64 two to a row: requests on one document, the later ones prefix
    hits, give the tokens of the pool of one head a row, and say so."""
    from skypilot_tpu.ops import paged_attention
    model, cfg, params, _, _ = setup
    monkeypatch.setenv('SKYTPU_PALLAS_INTERPRET', '1')
    rng = np.random.default_rng(8)
    doc = rng.integers(1, 256, size=21).tolist()
    prompts = [doc + rng.integers(1, 256, size=n).tolist() for n in (3, 6)]
    outs, stats = {}, {}
    for lanes in (0, 32):
        monkeypatch.setattr(paged_attention, 'pool_head_lanes',
                            lambda kernel, lanes=lanes: lanes)
        eng = _engine(cfg, params, slots=2)
        try:
            outs[lanes] = [eng.generate(prompts[0], 8, timeout=300)]
            outs[lanes] += [r.result(timeout=300) for r in
                            [eng.submit(p, 8) for p in prompts]]
            stats[lanes] = eng.stats()
            assert eng._cache['k'].shape[2:] == (  # pylint: disable=protected-access
                (2, 4, 16) if lanes == 0 else (1, 4, 32))
        finally:
            eng.stop()
    assert outs[32] == outs[0]
    for p, o in zip(prompts[:1] + prompts, outs[32]):
        assert _gap(model, params, p, o) <= _TOL, len(p)
    assert [stats[lanes]['paged_kernel']['heads_per_row']
            for lanes in (0, 32)] == [1, 2]
    assert stats[32]['paged_kernel']['kernel_writes'] > 0
    assert stats[32]['conv']['snapshots_restored'] == 2


def test_engine_reuses_a_released_slot_without_its_tails(setup):
    """One slot, one request after another: each admission sets the
    slot's tails (zeros, a snapshot, or the prompt's), so none reads
    its predecessor's; one-token prompts included.  The expert counts
    by hand: a row a tick through 4 expert layers, 2 experts a row."""
    model, cfg, params, _, _ = setup
    script = [([5, 6, 7, 8, 9], 4), ([3], 3), (list(range(1, 12)), 5),
              ([3], 2)]
    eng = _engine(cfg, params, slots=1)
    try:
        outs = [eng.generate(p, n, timeout=300) for p, n in script]
        stats = eng.stats()
    finally:
        eng.stop()
    for (p, _), o in zip(script, outs):
        assert _gap(model, params, p, o) <= _TOL
    rows = sum(n for _, n in script)
    assert stats['moe']['tokens'] == 4 * rows
    assert stats['moe']['held_pairs'] == 4 * rows * 2
    # One row a tick: the two experts it took are all that is touched.
    assert stats['moe']['touched'] == 4 * rows * 2


def test_the_bias_chooses_and_never_weights():
    """`moe.route` with a selection bias: the top-k is of score + bias,
    the gates are the chosen experts' scores over their sum + eps."""
    cfg = configs.TINY_MOE.replace(n_experts=8, expert_top_k=2,
                                   expert_score_fn='sigmoid',
                                   expert_bias=True, expert_gate_eps=1e-6)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)) / 4, jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.1, jnp.float32)
    scores, gates, idx = moe.route(x, w, cfg, bias)
    _, _, plain_idx = moe.route(x, w, cfg.replace(expert_bias=False))
    assert np.any(np.asarray(idx) != np.asarray(plain_idx))
    want = np.argsort(-(np.asarray(scores) + np.asarray(bias)), -1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want, -1))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(gates), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)


# ------------------------------------------------ what cannot carry tails


def test_what_cannot_carry_the_tails_refuses_them(setup):
    """Speculation, sequence-parallel prefill, the slice engine, the
    handoff wire, the training module and a mesh refuse a model with
    conv layers by name, before any state is made."""
    _, cfg, params, tokens, _ = setup
    with pytest.raises(ValueError, match='conv'):
        _engine(cfg, params, spec_tokens=2)
    with pytest.raises(ValueError, match='conv'):
        decode.paged_spec_engine_step(
            cfg, params, decode.init_engine_state(2),
            decode.init_paged_cache(cfg, 9, 4, 2, 4),
            jnp.zeros((2, 2), jnp.int32))
    with pytest.raises(ValueError, match='conv'):
        decode.prefill_sp(cfg, params, jnp.asarray([tokens[:8]]), mesh=None,
                          max_len=64)
    with pytest.raises(handoff.HandoffError, match='conv'):
        handoff.check_model(cfg)
    from skypilot_tpu.serve import slice_replica
    with pytest.raises(ValueError, match='conv'):
        slice_replica.SliceReplicaEngine(cfg, params, num_hosts=1)
    with pytest.raises(ValueError, match='conv layers'):
        transformer.Transformer(cfg).init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32))
    eng = _engine(cfg, params, slots=1)
    try:
        with pytest.raises(handoff.HandoffError, match='conv'):
            eng.export_prefill(tokens[:20])
        with pytest.raises(handoff.HandoffError, match='conv'):
            eng.export_prefix_pages()
    finally:
        eng.stop()


# ------------------------------------------------ importing a checkpoint


def _hf_config(model):
    return {k: v for k, v in model.items()
            if k not in ('family', 'layout', 'engine', 'published',
                         'reduced', 'name', 'source', 'torch_dtype')}


def test_config_from_hf_reads_the_block():
    model = _twin()
    cfg, name = import_weights.config_from_hf(_hf_config(model))
    assert name == 'lfm2_moe'
    want = family.program_config(model, _MAX_LEN)
    for key in ('n_layers', 'dense_layers', 'layer_pattern', 'conv_kernel',
                'dense_d_ff', 'd_ff', 'n_experts', 'expert_top_k',
                'expert_bias', 'expert_gate_eps', 'qk_norm', 'head_dim',
                'tie_embeddings', 'rope_theta', 'norm_eps'):
        assert getattr(cfg, key) == getattr(want, key), key
    # The published 40 layers: 38 after the dense two, not whole periods
    # of four, are one run of kinds.
    whole = dict(_hf_config(model), num_hidden_layers=40,
                 layer_types=['conv', 'conv'] + (
                     ['full_attention'] + ['conv'] * 3) * 9 +
                 ['full_attention', 'conv'])
    cfg40, _ = import_weights.config_from_hf(whole)
    assert len(cfg40.layer_pattern) == 38 and cfg40.conv_layers == 30
    with pytest.raises(ValueError, match='conv_bias'):
        import_weights.config_from_hf(dict(_hf_config(model),
                                           conv_bias=True))


def test_name_mapping_on_a_fake_state_dict():
    """The plan over a fake state dict in the checkpoint's names (torch
    layouts: Linear [out, in], Conv1d [d, 1, K]) gives the tree the
    program reads, leaf for leaf; the q/k norm scales are permuted as q
    and k are."""
    model = _twin()
    cfg, _ = import_weights.config_from_hf(_hf_config(model))
    plan = import_weights._plan_for(cfg, 'lfm2_moe')  # pylint: disable=protected-access
    templates = {entry[0] for entry in plan.values()}
    assert {'model.layers.{i}.conv.conv.weight',
            'model.layers.{i}.operator_norm.weight',
            'model.layers.{i}.ffn_norm.weight',
            'model.layers.{i}.self_attn.q_layernorm.weight',
            'model.layers.{i}.feed_forward.gate.weight',
            'model.layers.{i}.feed_forward.expert_bias',
            'model.layers.{i}.feed_forward.experts.{e}.w2.weight',
            'model.layers.{i}.feed_forward.w1.weight',
            'model.embedding_norm.weight'} <= templates
    d, hd, f, fe, v, e = 64, 16, 96, 32, 256, 8
    shapes = {'embed_tokens': (v, d), 'embedding_norm': (d,),
              'in_proj': (3 * d, d), 'conv.conv': (d, 1, 3),
              'out_proj': (d, d), 'q_proj': (4 * hd, d),
              'k_proj': (2 * hd, d), 'v_proj': (2 * hd, d),
              'layernorm': (hd,), 'norm.weight': (d,),
              'feed_forward.gate.': (e, d), 'expert_bias': (e,),
              'experts.{e}.w2': (d, fe), 'experts.{e}.w': (fe, d),
              'feed_forward.w2': (d, f), 'feed_forward.w': (f, d)}
    rng = np.random.default_rng(0)

    def fake(name):
        key = next(k for k in shapes if k in name)
        return rng.normal(size=shapes[key]).astype(np.float32)

    tree = {}
    for path, (template, transform, *stack) in plan.items():
        if '{i}' not in template:
            tree[path] = transform(fake(template)).shape
            continue
        one = (np.stack([transform(fake(template)) for _ in range(e)])
               if '{e}' in template else transform(fake(template)))
        tree[path] = (len(stack[0]),) + one.shape
    assert tree == {path: shape for path, (shape, _) in
                    family.shapes(model).items()}
    conv = rng.normal(size=(d, 1, 3)).astype(np.float32)
    np.testing.assert_array_equal(
        plan[('lead', 'conv', 'conv', 'kernel')][1](conv), conv[:, 0].T)
    scale = np.arange(hd, dtype=np.float32)
    np.testing.assert_array_equal(
        plan[('layers', 'attn', 'attn', 'q_norm', 'scale')][1](scale),
        np.stack([scale[:hd // 2], scale[hd // 2:]], -1).reshape(hd))
    assert plan[('layers', 'ffn', 'mlp_norm', 'scale')][2] == [2, 3, 4, 5]
    assert plan[('lead', 'conv', 'conv', 'kernel')][2] == [0, 1]
