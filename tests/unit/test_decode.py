"""KV-cache decoding parity: prefill + incremental decode must produce
exactly the tokens a naive full re-forward would (models/decode.py)."""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models.transformer import Transformer


@pytest.fixture(scope='module')
def setup():
    cfg = configs.get_config('tiny')
    model = Transformer(cfg)
    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    params = nn.meta.unbox(model.init(rng, prompt)['params'])
    return cfg, model, params, prompt


def _naive_generate(model, params, prompt, n):
    """Greedy continuation by full re-forward each step."""
    tokens = prompt
    for _ in range(n):
        logits = model.apply({'params': params}, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return tokens


def test_prefill_logits_match_full_forward(setup):
    cfg, model, params, prompt = setup
    logits, cache = decode.prefill(cfg, params, prompt, max_len=32)
    full = model.apply({'params': params}, prompt)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)
    assert int(cache['index']) == prompt.shape[1]


def test_decode_step_matches_full_forward(setup):
    cfg, model, params, prompt = setup
    logits, cache = decode.prefill(cfg, params, prompt, max_len=32)
    nxt = jnp.argmax(logits, axis=-1)
    step_logits, cache = decode.decode_step(cfg, params, nxt[:, None],
                                            cache)
    extended = jnp.concatenate([prompt, nxt[:, None]], axis=1)
    full = model.apply({'params': params}, extended)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_greedy_generation_parity(setup):
    cfg, model, params, prompt = setup
    tokens, new = decode.generate(cfg, params, prompt,
                                  max_new_tokens=6, max_len=32)
    naive = _naive_generate(model, params, prompt, 6)
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(naive))
    assert new.shape == (2, 6)


def test_sampling_controls(setup):
    cfg, _, params, prompt = setup
    del params, prompt
    logits = jnp.array([[0.0, 5.0, 1.0]])
    greedy = decode.sample(logits, jax.random.PRNGKey(0),
                           decode.SamplingConfig())
    assert int(greedy[0]) == 1
    # top_k=1 is greedy regardless of temperature.
    topk = decode.sample(logits, jax.random.PRNGKey(0),
                         decode.SamplingConfig(temperature=2.0, top_k=1))
    assert int(topk[0]) == 1


def test_max_len_validation(setup):
    cfg, _, params, prompt = setup
    with pytest.raises(ValueError, match='max_len'):
        decode.generate(cfg, params, prompt, max_new_tokens=10,
                        max_len=12)


def test_moe_greedy_generation_parity():
    """MoE decode through the cache matches the training module's full
    re-forward: both run the one expert layer without drops
    (`moe.moe_apply`), so whatever the routing, a token's result is the
    same alone in a tick and among the prompt's other tokens."""
    cfg = configs.get_config('tiny-moe')
    model = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(2),
                                      prompt)['params'])
    tokens, _ = decode.generate(cfg, params, prompt, max_new_tokens=4,
                                max_len=16)
    naive = _naive_generate(model, params, prompt, 4)
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(naive))


def test_generate_is_jittable(setup):
    """The whole generate (prefill + scan of steps) compiles once."""
    cfg, _, params, prompt = setup
    fn = jax.jit(lambda p, t: decode.generate(
        cfg, p, t, max_new_tokens=4, max_len=16)[1])
    out = fn(params, prompt)
    assert out.shape == (2, 4)


@pytest.mark.parametrize('preset', ['tiny-gemma', 'tiny-qwen'])
def test_family_variants_generation_parity(preset):
    """Gemma-style (tied embeddings, GeGLU, +1 norms, scaled embed) and
    Qwen-style (qkv bias) models decode identically to a full
    re-forward."""
    cfg = configs.get_config(preset)
    model = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(4),
                                      prompt)['params'])
    if cfg.tie_embeddings:
        assert 'lm_head' not in params
    if cfg.qkv_bias:
        assert 'bias' in params['layers']['layer']['attn']['q_proj']
    tokens, _ = decode.generate(cfg, params, prompt, max_new_tokens=4,
                                max_len=32)
    ref = _naive_generate(model, params, prompt, 4)
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(ref))


class TestChunkedPrefill:

    def test_chunk_boundary_logits_match_full_prefill(self, setup):
        """prefill_chunk continuations at index > 0 (per-position
        causal mask) must reproduce the one-shot flash prefill's
        last-token logits at every chunk boundary."""
        cfg, model, params, _ = setup
        del model
        prompt = jax.random.randint(jax.random.PRNGKey(7), (1, 12), 0,
                                    cfg.vocab_size, dtype=jnp.int32)
        for split in (4, 5, 8):
            full_logits, full_cache = decode.prefill(
                cfg, params, prompt, max_len=32)
            _, cache = decode.prefill(cfg, params, prompt[:, :split],
                                      max_len=32)
            chunk_logits, cache = decode.prefill_chunk(
                cfg, params, prompt[:, split:], cache)
            np.testing.assert_allclose(np.asarray(chunk_logits),
                                       np.asarray(full_logits),
                                       rtol=2e-4, atol=2e-4)
            assert int(cache['index']) == int(full_cache['index'])
            # And greedy continuation stays exact from either cache.
            nxt = jnp.argmax(chunk_logits, axis=-1)[:, None]
            ref_nxt = jnp.argmax(full_logits, axis=-1)[:, None]
            step_a, _ = decode.decode_step(cfg, params, nxt, cache)
            step_b, _ = decode.decode_step(cfg, params, ref_nxt,
                                           full_cache)
            np.testing.assert_allclose(np.asarray(step_a),
                                       np.asarray(step_b),
                                       rtol=2e-4, atol=2e-4)

    def test_multi_chunk_sequence(self, setup):
        """Three successive chunk continuations equal one prefill."""
        cfg, _, params, _ = setup
        prompt = jax.random.randint(jax.random.PRNGKey(8), (1, 16), 0,
                                    cfg.vocab_size, dtype=jnp.int32)
        full_logits, _ = decode.prefill(cfg, params, prompt, max_len=32)
        _, cache = decode.prefill(cfg, params, prompt[:, :4],
                                  max_len=32)
        for start in (4, 8, 12):
            logits, cache = decode.prefill_chunk(
                cfg, params, prompt[:, start:start + 4], cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits),
                                   rtol=2e-4, atol=2e-4)


class TestBatchedSampling:

    def test_batched_sample_matches_sample(self, setup):
        """Row-for-row parity with decode.sample: same key + logits ->
        same token, across greedy/temperature/top-k settings (the
        serving engine's on-device selection is pinned to the reference
        sampler)."""
        cfg, *_ = setup
        logits = jax.random.normal(jax.random.PRNGKey(5),
                                   (1, cfg.vocab_size))
        for temperature, top_k in ((0.0, 0), (0.7, 0), (1.3, 5),
                                   (0.4, 50), (2.0, 1)):
            key = jax.random.PRNGKey(11)
            ref = decode.sample(
                logits, key,
                decode.SamplingConfig(temperature=temperature,
                                      top_k=top_k))
            got = decode.batched_sample(
                logits, key[None],
                jnp.asarray([temperature], jnp.float32),
                jnp.asarray([top_k], jnp.int32), max_top_k=64)
            assert int(ref[0]) == int(got[0]), (temperature, top_k)

    def test_batched_sample_per_slot_settings(self, setup):
        """One batch mixing greedy and sampled slots: the greedy slot
        is argmax, the top_k=1 slot is argmax, a hot slot may differ."""
        cfg, *_ = setup
        logits = jax.random.normal(jax.random.PRNGKey(6),
                                   (3, cfg.vocab_size))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3))
        out = decode.batched_sample(
            logits, keys,
            jnp.asarray([0.0, 5.0, 5.0], jnp.float32),
            jnp.asarray([0, 1, 0], jnp.int32), max_top_k=8)
        argmax = jnp.argmax(logits, axis=-1)
        assert int(out[0]) == int(argmax[0])   # greedy slot
        assert int(out[1]) == int(argmax[1])   # top_k=1 slot


class TestEngineStep:
    """The tick's tail (`_select_and_bookkeep`): freeze, countdown and
    stop, on a pool of 4-token pages with slot 0 on pages 1-4."""

    def _setup_state(self, cfg, params, slots=2, max_len=16):
        prompt = jax.random.randint(jax.random.PRNGKey(9), (1, 4), 0,
                                    cfg.vocab_size, dtype=jnp.int32)
        logits, pre = decode.prefill(cfg, params, prompt, max_len=max_len)
        ps = 4
        rows = max_len // ps
        cache = decode.init_paged_cache(cfg, slots * rows + 1, ps, slots,
                                        rows)
        row = np.arange(1, rows + 1, dtype=np.int32)
        cache = decode.insert_prefill_pages(cache, pre, row[:1],
                                            first_page=0)
        cache = decode.paged_admit_slot(cache, 0, row, prompt.shape[1])
        state = decode.init_engine_state(slots)
        state = decode.admit_slot_state(
            state, 0, int(jnp.argmax(logits[0])), 3,
            jnp.full((16,), -1, jnp.int32), jax.random.PRNGKey(0),
            0.0, 0)
        return state, cache

    def test_inactive_slots_freeze(self, setup):
        cfg, _, params, _ = setup
        state, cache = self._setup_state(cfg, params)
        before_tok = int(state['tokens'][1])
        before_len = int(cache['lengths'][1])
        state, cache, finished, _, _ = decode.paged_engine_step(
            cfg, params, state, cache)
        assert bool(state['active'][0])
        assert not bool(state['active'][1])
        assert int(state['tokens'][1]) == before_tok
        assert int(cache['lengths'][1]) == before_len
        assert int(cache['lengths'][0]) == 5
        assert not bool(finished[1])

    def test_remaining_counter_finishes(self, setup):
        cfg, _, params, _ = setup
        state, cache = self._setup_state(cfg, params)
        fins = []
        for _ in range(4):
            state, cache, finished, _, _ = decode.paged_engine_step(
                cfg, params, state, cache)
            fins.append(bool(finished[0]))
        # remaining=3 -> exactly the third tick finishes the slot, and
        # the device keeps it frozen afterwards.
        assert fins == [False, False, True, False]
        assert not bool(state['active'][0])

    def test_stop_id_finishes_on_device(self, setup):
        cfg, _, params, _ = setup
        state, cache = self._setup_state(cfg, params)
        # Run one step to learn the next token, then rerun with that
        # token as a stop id: the step itself must flag fin.
        probe_state, _, _, _, _ = decode.paged_engine_step(
            cfg, params, dict(state),
            jax.tree.map(jnp.copy, cache))
        stop = int(probe_state['tokens'][0])
        state = dict(state, stop_ids=state['stop_ids'].at[0, 0].set(stop))
        state, cache, finished, _, _ = decode.paged_engine_step(
            cfg, params, state, cache)
        assert bool(finished[0])
        assert not bool(state['active'][0])


class TestServingForm:
    """The serving programs read q/k/v kernels held as
    [.., d_model, heads * hd] (`decode.serving_params`); `generate`,
    tests and the benchmark's callers keep the training layout.  One
    helper, `_attn_proj`, takes both, told by the kernel's rank."""

    @pytest.mark.parametrize('bias', [False, True],
                             ids=['no-bias', 'bias'])
    @pytest.mark.parametrize('heads', [4, 2], ids=['q', 'kv'])
    @pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
    def test_attn_proj_flat_equals_three_dimensional(self, dtype, heads,
                                                     bias):
        """The same products on the same operands.  In bfloat16, the
        dtype every deployment serves in, the two forms agree to the
        bit; in float32 the CPU backend orders the partial sums of the
        two contractions differently, so they agree to a few units in
        the last place."""
        d, hd = 64, 16
        keys = jax.random.split(jax.random.PRNGKey(3), 3)
        x = jax.random.normal(keys[0], (2, 5, d), dtype)
        proj = {'kernel': jax.random.normal(keys[1], (d, heads, hd),
                                            dtype)}
        if bias:
            proj['bias'] = jax.random.normal(keys[2], (heads, hd), dtype)
        flat = jax.tree.map(
            lambda leaf: leaf.reshape(leaf.shape[:-2] + (-1,)), proj)
        want = np.asarray(decode._attn_proj(x, proj, heads, hd),
                          np.float32)
        got = np.asarray(decode._attn_proj(x, flat, heads, hd),
                         np.float32)
        assert got.shape == (2, heads, 5, hd)
        if dtype == 'bfloat16':
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-5)

    @staticmethod
    def _tree(preset, scan_layers, quantized):
        cfg = configs.get_config(preset).replace(scan_layers=scan_layers)
        prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0), prompt)['params'])
        if quantized:
            from skypilot_tpu.models import quantize
            params = jax.device_put(quantize.quantize_params(params))
        return cfg, params, prompt

    @pytest.mark.parametrize('preset,scan_layers,quantized', [
        ('tiny', True, False), ('tiny', False, False),
        ('tiny-qwen', True, False), ('tiny-qwen', False, False),
        ('tiny', True, True), ('tiny', False, True),
        ('tiny-gemma', True, False),
    ], ids=['scanned', 'unscanned', 'bias', 'bias-unscanned', 'int8',
            'int8-unscanned', 'tied'])
    def test_serving_params_reforms_qkv_and_nothing_else(
            self, preset, scan_layers, quantized):
        cfg, params, prompt = self._tree(preset, scan_layers, quantized)
        before = jax.tree.map(lambda leaf: leaf.shape, params)
        serving = decode.serving_params(cfg, params)
        # The caller's tree is as it was, and every array of it is
        # still there to read: nothing was donated.
        assert jax.tree.map(lambda leaf: leaf.shape, params) == before
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(params))
        lead = 1 if scan_layers else 0
        groups = ([(params['layers']['layer'],
                    serving['layers']['layer'])] if scan_layers else
                  [(params[f'layer_{i}'], serving[f'layer_{i}'])
                   for i in range(cfg.n_layers)])
        held = 0
        for mine, theirs in groups:
            for name, heads in (('q_proj', cfg.n_heads),
                                ('k_proj', cfg.n_kv_heads),
                                ('v_proj', cfg.n_kv_heads)):
                flat = heads * cfg.head_dim
                got = jax.tree.leaves(theirs['attn'][name])
                want = jax.tree.leaves(mine['attn'][name])
                assert len(got) == len(want) == (
                    1 + quantized + cfg.qkv_bias)
                for g, w in zip(got, want):
                    assert g.shape == w.shape[:-2] + (flat,)
                    assert g.dtype == w.dtype
                    np.testing.assert_array_equal(
                        np.asarray(g), np.asarray(w).reshape(g.shape))
                    held += g.nbytes
                kernel = theirs['attn'][name]['kernel']
                if quantized:
                    assert kernel['qvalue'].shape[lead:] == (cfg.d_model,
                                                             flat)
                    assert kernel['scale'].shape[lead:] == (1, flat)
                else:
                    assert kernel.shape[lead:] == (cfg.d_model, flat)
            # o_proj and every other leaf are the caller's own arrays.
            assert jax.tree.leaves(theirs['attn']['o_proj'])[0] is (
                jax.tree.leaves(mine['attn']['o_proj'])[0])
        shared = {id(leaf) for leaf in jax.tree.leaves(params)}
        fresh = [leaf for leaf in jax.tree.leaves(serving)
                 if id(leaf) not in shared]
        assert sum(leaf.nbytes for leaf in fresh) == held
        assert decode.serving_form_bytes(cfg, serving) == held > 0
        assert decode.serving_form_bytes(cfg, params) == 0
        # A tree already in the serving form comes back as it is.
        assert decode.serving_params(cfg, serving) is serving
        # And the programs give on it what they give on the caller's.
        want, _ = decode.generate(cfg, params, prompt, max_new_tokens=5,
                                  max_len=32)
        got, _ = decode.generate(cfg, serving, prompt, max_new_tokens=5,
                                 max_len=32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize('program', ['prefill', 'decode_step',
                                         'prefill_chunk'])
    def test_programs_agree_on_both_forms(self, setup, program):
        cfg, _, params, prompt = setup
        serving = decode.serving_params(cfg, params)

        def run(tree):
            logits, cache = decode.prefill(cfg, tree, prompt, max_len=32)
            if program == 'decode_step':
                logits, cache = decode.decode_step(
                    cfg, tree, jnp.argmax(logits, -1)[:, None], cache)
            elif program == 'prefill_chunk':
                logits, cache = decode.prefill_chunk(
                    cfg, tree, prompt[:, :4], cache)
            return logits, cache['k']

        for got, want in zip(run(serving), run(params)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)
