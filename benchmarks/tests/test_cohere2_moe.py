"""CPU rehearsal of the family `cohere2_moe`: what the family counts at
the configuration's sizes, its weight tree against the program's, and
`run.py --dry-run` over a tiny twin of the configuration and of the
long-document traffic (`tiny-window-moe.json`, traffic
`dryrun-window`), with the two per-layer readers the cell brings; where
the reference abstains (PERF.md, findings 21 and 25); the cell's entries
in `BENCHMARK.json`.  Nothing here is a measurement.  (The reference
against the program's prefill, chunked prefill, cached decode and paged
engine is tier-1's: `tests/unit/test_window_moe.py`.)
"""
import json
import math
import os
import sys

import pytest

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks import run as run_lib  # noqa: E402
from benchmarks.families import cohere2_moe  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_CONFIG = 'command-a-plus-05-2026-l4-e16'
_CELL = 'tiny-window-moe.dryrun-window'


def _load(*parts):
    with open(os.path.join(_ROOT, 'benchmarks', *parts),
              encoding='utf-8') as f:
        return json.load(f)


def test_parameter_counts_and_work():
    model = _load('configs', f'{_CONFIG}.json')
    counts = cohere2_moe.param_counts(model)
    assert round(counts['total'] / 1e6) == 4733
    assert sum(math.prod(s) for s, _ in
               cohere2_moe.shapes(model).values()) == counts['total']
    # A layer: 142.6 M of attention, 201.3 M of shared experts, 0.5 M
    # of router, 16 routed experts of 50.3 M.
    assert round(counts['layer'] / 1e6, 1) == 1149.8
    # One routed expert a token in expectation (8 x 16 / 128).
    assert counts['layer_active'] == pytest.approx(
        counts['layer_matmul'] - 15 * 3 * 4096 * 4096)
    # Caches: 4 KiB a key a layer; window layers stop at 4096 keys.
    kib = 1024
    assert cohere2_moe.decode_cache_bytes(model, 1000, 'bfloat16') == \
        4 * 4 * kib * 1000
    assert cohere2_moe.decode_cache_bytes(model, 8400, 'bfloat16') == \
        4 * kib * (3 * 4096 + 8400)
    assert cohere2_moe.decode_flops(model, 8400) - \
        cohere2_moe.decode_flops(model, 0) == \
        cohere2_moe.decode_attention_flops(model, 8400)
    # Prefilling in two pieces needs what prefilling in one does.
    assert cohere2_moe.prefill_flops(model, 8192, 64) + \
        cohere2_moe.prefill_flops(model, 8256, 64) == pytest.approx(
            cohere2_moe.prefill_flops(model, 8192, 128))


def test_the_file_keeps_every_published_width():
    """Every number of the catalog row's config under its own key; the
    three cuts, and they alone, beside their published values."""
    model = _load('configs', f'{_CONFIG}.json')
    published = {
        'hidden_size': 4096, 'num_attention_heads': 128,
        'num_key_value_heads': 8, 'head_dim': 128,
        'intermediate_size': 4096, 'num_experts_per_tok': 8,
        'num_shared_experts': 4, 'sliding_window': 4096,
        'rope_theta': 50000, 'layer_switch': 4, 'logit_scale': 1,
        'layer_norm_eps': 1e-05, 'max_position_embeddings': 200000,
        'first_k_dense_replace': 0, 'rotary_pct': 1,
        'prefix_dense_intermediate_size': 16384,
        'prefix_dense_sliding_window_pattern': 1}
    assert {k: model[k] for k in published} == published
    assert model['reduced'] == ['num_hidden_layers', 'num_experts',
                                'vocab_size']
    assert model['published'] == {'num_hidden_layers': 32,
                                  'num_experts': 128,
                                  'vocab_size': 262144}
    assert (model['num_hidden_layers'], model['num_experts'],
            model['vocab_size']) == (4, 16, 32768)
    assert len(model['layer_types']) == 32
    assert cohere2_moe.router_width(model) == 128
    assert 'eight' in model['deployment']
    cfg = cohere2_moe.program_config(model, model['engine']['max_len'])
    assert (cfg.n_experts, cfg.experts_held, cfg.sliding_window) == \
        (128, (0, 16), 4096)


def test_shapes_are_the_tree_the_program_reads():
    """The family's weight tree is what the program's own modules make
    for the family's `program_config`: the expert layer's parameters
    (`MoEMLP`), the attention projections, one norm a layer, no head."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from skypilot_tpu.models import moe, transformer

    model = _load('tests', 'tiny-window-moe.json')
    cfg = cohere2_moe.program_config(model, 64)
    x = jnp.zeros((1, 4, cfg.d_model), cfg.dtype)
    made = {
        'moe_mlp': jax.eval_shape(moe.MoEMLP(cfg).init,
                                  jax.random.PRNGKey(0), x),
        'attn': jax.eval_shape(
            transformer.Attention(cfg).init, jax.random.PRNGKey(0), x,
            jnp.arange(4)),
    }
    want = {}
    for name, tree in made.items():
        flat = jax.tree_util.tree_flatten_with_path(
            nn.meta.unbox(tree)['params'])[0]
        for path, leaf in flat:
            keys = tuple(p.key for p in path)
            want[('layers', 'layer', name) + keys] = leaf.shape
    n = model['num_hidden_layers']
    got = {path: shape for path, (shape, _) in
           cohere2_moe.shapes(model).items()}
    assert {k: (n,) + v for k, v in want.items()} == \
        {k: v for k, v in got.items() if k[:3] in
         {p[:3] for p in want}}
    assert set(got) - {('layers', 'layer', *k[2:]) for k in want} == {
        ('embed', 'embedding'), ('final_norm', 'scale'),
        ('layers', 'layer', 'attn_norm', 'scale')}


# ------------------------------------------ where the reference abstains


def test_margin_is_a_held_experts_distance_from_the_edge():
    """Router logits 5, 4, 3, 2.99, 1, 0 under top-3: expert 2 is in by
    0.01 and expert 3 out by 0.01; experts 0 and 1 are in by 2.01 and
    1.01; experts 4 and 5 are out by 2 and 3."""
    import jax.numpy as jnp
    h = jnp.asarray([[1.0, 0.0]])
    w = jnp.asarray([[5.0, 4.0, 3.0, 2.99, 1.0, 0.0], [0.0] * 6])
    for lo, n, want in ((2, 2, 0.01), (0, 2, 1.01), (4, 2, 2.0),
                        (0, 6, 0.01)):
        gates, margin = cohere2_moe._gates(h, w, top_k=3, lo=lo, n_held=n,
                                           precision='float32')
        assert float(margin[0]) == pytest.approx(want, abs=1e-5)
        assert gates.shape == (1, n)
    # Gates over the whole top-3, whichever of it is held.
    gates, _ = cohere2_moe._gates(h, w, top_k=3, lo=0, n_held=6,
                                  precision='float32')
    assert float(gates.sum()) == pytest.approx(1.0)
    assert [float(g) > 0 for g in gates[0]] == [True] * 3 + [False] * 3


def test_the_reference_abstains_on_undecided_rows_and_only_there():
    """A row whose margin is under the stated dtype's width is all
    zeros in the float32 logits, so any token reads a gap of 0 there;
    every other row is the plain forward's; the control answers every
    row; a float32 configuration abstains nowhere."""
    import jax
    import numpy as np
    from benchmarks.layouts import single
    model = _load('tests', 'tiny-window-moe.json')
    _, params = single.build(model, jax.devices()[:1], 11)
    tokens = [int(t) for t in
              np.random.default_rng(3).integers(0, 256, 64)]
    plain, margin = (np.asarray(a) for a in
                     cohere2_moe.forward(model, params, tokens, 8, 56))
    assert plain.shape == (56, 256) and margin.shape == (56,)
    assert cohere2_moe.undecided_margin(model) < 1e-6 < margin.min()
    np.testing.assert_array_equal(
        np.asarray(cohere2_moe.logits(model, params, tokens, 8, 56)), plain)
    stated = dict(model, torch_dtype='bfloat16')
    width = cohere2_moe.undecided_margin(stated)
    assert width == pytest.approx(3 * 2.0 ** -7)
    out = np.asarray(cohere2_moe.logits(stated, params, tokens, 8, 56))
    undecided = margin < width
    assert 0 < undecided.sum() < 56
    assert not out[undecided].any()
    np.testing.assert_array_equal(out[~undecided], plain[~undecided])
    low = np.asarray(cohere2_moe.logits(stated, params, tokens, 8, 56,
                                        precision='int8'))
    assert np.abs(low).max(axis=1).min() > 0


def test_benchmark_json_has_the_cell():
    bench = _load('..', 'BENCHMARK.json')
    cell = f'{_CONFIG}.docs-window'
    config = [c for c in bench['configs'] if c['name'] == _CONFIG][-1]
    assert bench['configs'][-1] is config
    assert config['reduced'] == _load('configs', f'{_CONFIG}.json')['reduced']
    assert config['source'] == _load('configs', f'{_CONFIG}.json')['source']
    assert bench['workloads'][-1] == {
        'name': cell, 'config': _CONFIG, 'traffic': 'docs-window',
        'chips': 1, 'why': bench['workloads'][-1]['why']}
    assert [m['name'] for m in bench['per_layer'][-2:]] == [
        'expert_tokens_per_tick', 'kv_walked_share']
    for metric in bench['per_layer'][-2:]:
        assert metric['workloads'] == [cell]
        assert metric['moves'] == 'out_tok_per_s'
    limits = _load('limits', f'{cell}.json')
    assert set(limits) == {'logit_gap_max', 'sampled_tokens_min'}
    assert limits['sampled_tokens_min'] == 600


# ------------------------------------------- the cell on its tiny twin


@pytest.fixture
def twin_cell(monkeypatch):
    """`dryrun.json` with the twin's cell and the two per-layer metrics
    beside its own: the file is the accepted benchmark's, so the
    entries are laid over it here."""
    load = run_lib._load_json

    def patched(path):
        data = load(path)
        if os.path.basename(path) == 'dryrun.json':
            data['configs'].append({
                'name': 'tiny-window-moe', 'source': 'none',
                'file': 'benchmarks/tests/tiny-window-moe.json',
                'reduced': [], 'why': 'rehearsal'})
            data['workloads'].append({
                'name': _CELL, 'config': 'tiny-window-moe',
                'traffic': 'dryrun-window', 'chips': 1,
                'why': 'rehearsal'})
            for name, unit, better, layer in (
                    ('expert_tokens_per_tick', 'tokens', 'higher',
                     'expert layer'),
                    ('kv_walked_share', '%', 'lower', 'kernels')):
                data['per_layer'].append({
                    'name': name, 'unit': unit, 'better': better,
                    'source': 'program_counter', 'layer': layer,
                    'moves': 'out_tok_per_s', 'workloads': [_CELL]})
        return data

    monkeypatch.setattr(run_lib, '_load_json', patched)


def _dry_run(capsys, *extra):
    rc = run_lib.main(['--dry-run', '--workload', _CELL, '--seed',
                       str(2**31 + 4321), '--seconds', '3', *extra])
    captured = capsys.readouterr()
    return rc, json.loads(captured.out.strip().splitlines()[-1])


def test_dry_run_of_the_cell(capsys, twin_cell):
    """The cell's whole path on the CPU: shared documents eight windows
    deep served from cached pages, correct against the reference, and
    the two new per-layer metrics read from the program's counters."""
    rc, line = _dry_run(capsys, '--trace', '1')
    assert rc == 0
    assert line['correct'] is True and line['failed'] == 0
    metrics = {k: v['value'] for k, v in line['metrics'].items()}
    # 16 of 16 experts held, top-4: four pairs a live row.
    assert 0 < metrics['expert_tokens_per_tick'] <= 4 * 4
    assert metrics['expert_tokens_per_tick'] == pytest.approx(
        4 * metrics['tokens_per_tick'], rel=0.2)
    # Contexts of 70-100 under a window of 8 and pages of 16: a window
    # layer walks one or two of five to seven pages; 6 of 8 layers.
    assert 25 < metrics['kv_walked_share'] < 60
    assert 'ttft_p50_ms' not in metrics


def test_untraced_dry_run_reports_the_cells_end_to_end(capsys, twin_cell):
    rc, line = _dry_run(capsys, '--trace', '0')
    assert rc == 0 and line['correct'] is True
    assert set(line['metrics']) == {'itl_p95_ms', 'out_tok_per_s',
                                    'setup_s'}


def test_the_control_comes_out_not_correct(capsys, twin_cell):
    rc, line = _dry_run(capsys, '--trace', '0', '--control', 'int8')
    assert rc == 0 and line['correct'] is False
    gap = line['checks']['logit_gap_max']
    assert gap['value'] > gap['limit']


def test_an_altered_token_comes_out_not_correct(capsys, twin_cell,
                                                monkeypatch):
    from skypilot_tpu.serve import scheduler
    push = scheduler.Request._push
    count = [0]

    def broken(self, token):
        count[0] += 1
        push(self, (token + 1) % 256 if count[0] % 5 == 0 else token)

    monkeypatch.setattr(scheduler.Request, '_push', broken)
    rc, line = _dry_run(capsys, '--trace', '0')
    assert rc == 0 and line['correct'] is False


def test_a_program_without_the_counters_reports_nothing():
    """On the parent's program `stats()` has no `moe` and no
    `walked_pages`: the readers return nothing and do not raise."""
    import types
    from benchmarks.layers import expert_tokens_per_tick, kv_walked_share
    old = {'ticks': 0, 'paged_kernel': {'live_pages': 0,
                                        'table_pages': 0}}
    new = {'ticks': 9, 'paged_kernel': {'live_pages': 90,
                                        'table_pages': 900}}
    run = types.SimpleNamespace(stats0=old, stats1=new,
                                model={'num_hidden_layers': 4})
    assert expert_tokens_per_tick.compute(run) is None
    assert kv_walked_share.compute(run) is None
    old['paged_kernel']['walked_pages'] = 0
    new['paged_kernel']['walked_pages'] = 180
    old['moe'] = {'held_pairs': 0}
    new['moe'] = {'held_pairs': 72}
    assert kv_walked_share.compute(run) == pytest.approx(50.0)
    assert expert_tokens_per_tick.compute(run) == pytest.approx(2.0)
