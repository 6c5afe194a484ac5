"""Weight-only int8 quantization tests (models/quantize.py)."""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models import quantize
from skypilot_tpu.models.transformer import Transformer


def _params(preset='tiny', seed=0):
    cfg = configs.get_config(preset)
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    return cfg, nn.meta.unbox(
        model.init(jax.random.PRNGKey(seed), tokens)['params'])


class TestQuantizeParams:

    def test_kernels_quantized_rest_untouched(self):
        _, params = _params()
        q = quantize.quantize_params(params)
        layer = q['layers']['layer']
        assert quantize.is_quantized_leaf(layer['attn']['q_proj']['kernel'])
        assert quantize.is_quantized_leaf(layer['mlp']['down_proj']['kernel'])
        assert quantize.is_quantized_leaf(q['lm_head']['kernel'])
        assert layer['attn']['q_proj']['kernel']['qvalue'].dtype == jnp.int8
        # Norms + embeddings stay full precision.
        assert not quantize.is_quantized_leaf(q['embed']['embedding'])
        assert not quantize.is_quantized_leaf(
            layer['attn_norm']['scale'])

    def test_moe_experts_quantized_router_not(self):
        """Expert stacks go int8, the router stays as it is, and the
        expert layer without drops reads both (`moe.moe_apply` through
        `maybe_dequant`): the same experts chosen, a result close to
        the full-precision one."""
        from skypilot_tpu.models import moe as moe_lib
        cfg, params = _params('tiny-moe')
        q = quantize.quantize_params(params)
        moe = q['layers']['layer']['moe_mlp']
        assert quantize.is_quantized_leaf(moe['gate_proj'])
        assert quantize.is_quantized_leaf(moe['down_proj'])
        assert not quantize.is_quantized_leaf(moe['router']['kernel'])
        x = jax.random.normal(jax.random.PRNGKey(7), (6, cfg.d_model))
        first = lambda tree: jax.tree.map(lambda a: a[0], tree)
        want, _, counts = moe_lib.moe_apply(
            x, first(params['layers']['layer']['moe_mlp']), cfg)
        got, _, counts_q = moe_lib.moe_apply(x, first(moe), cfg)
        assert [int(c) for c in counts] == [int(c) for c in counts_q]
        err = float(jnp.max(jnp.abs(got - want)))
        assert 0 < err < 0.05 * float(jnp.max(jnp.abs(want))), err

    def test_per_channel_exactness_on_channel_scaled_matrix(self):
        """A matrix whose rows are +-multiples of one channel scale is
        exactly representable: quantization must round-trip it."""
        # Entries are integer multiples (|k| <= 127) of one scale per
        # output channel -> exactly representable.
        ints = np.concatenate([np.arange(-127, 0), np.arange(1, 38)])
        w = np.outer(ints, np.linspace(0.5, 2.0, 16)).astype(np.float32)
        q = quantize._quantize_array(w, (0,))  # pylint: disable=protected-access
        deq = np.asarray(quantize.maybe_dequant(q, jnp.float32))
        np.testing.assert_allclose(deq, w, rtol=1e-6, atol=1e-6)

    def test_relative_error_bounded(self):
        _, params = _params()
        kernel = params['layers']['layer']['attn']['q_proj']['kernel']
        q = quantize.quantize_params(params)
        deq = np.asarray(quantize.maybe_dequant(
            q['layers']['layer']['attn']['q_proj']['kernel'], jnp.float32))
        w = np.asarray(kernel)
        # Scan-stacked kernel [L, d, h, hd]: contraction axis is 1.
        # Symmetric absmax int8: error <= scale/2 = absmax/254 per
        # channel.
        absmax = np.max(np.abs(w), axis=1, keepdims=True)
        assert np.all(np.abs(deq - w) <= absmax / 254 + 1e-7)

    def test_report_ratio(self):
        _, params = _params()
        q = quantize.quantize_params(params)
        report = quantize.quantization_report(q)
        assert report['ratio'] < 0.7  # most weights in int8


class TestQuantizedDecode:

    @pytest.mark.parametrize('preset', ['tiny', 'tiny-moe', 'tiny-qwen'])
    def test_generation_close_to_fp(self, preset):
        """Greedy generation from int8 weights matches full precision
        on a tiny model (logits gaps are large vs quantization noise at
        random init is NOT guaranteed — so compare prefill logits
        numerically instead of token-exactness, then sanity-run the
        generation loop)."""
        cfg, params = _params(preset)
        qparams = quantize.quantize_params(params)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                    cfg.vocab_size, dtype=jnp.int32)
        logits_fp, _ = decode.prefill(cfg, params, prompt, max_len=32)
        logits_q, _ = decode.prefill(cfg, qparams, prompt, max_len=32)
        # int8 per-channel keeps logits within a few percent of fp.
        err = np.max(np.abs(np.asarray(logits_q) - np.asarray(logits_fp)))
        spread = np.max(np.abs(np.asarray(logits_fp))) + 1e-6
        assert err / spread < 0.1, (err, spread)
        tokens, new = decode.generate(cfg, qparams, prompt,
                                      max_new_tokens=4, max_len=32)
        assert tokens.shape == (2, 12) and new.shape == (2, 4)

    def test_tied_embeddings_not_quantized_path(self):
        cfg, params = _params('tiny-gemma')
        qparams = quantize.quantize_params(params)
        assert 'lm_head' not in qparams
        prompt = jnp.ones((1, 4), jnp.int32)
        logits, _ = decode.prefill(cfg, qparams, prompt, max_len=16)
        assert logits.shape == (1, cfg.vocab_size)
