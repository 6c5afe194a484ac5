"""CPU rehearsal of the family `nemotron_h`: what the family counts at the
configuration's sizes and at the published ones, its weight tree
against what the program reads, its reference against a direct
transcription of the equations, the configuration file against the
catalog's row, and `run.py --dry-run` over a tiny twin of the
configuration and of the docs-window traffic (`tiny-ssm-moe.json`,
traffic `dryrun-window`) with the two readers the cell brings, and
the cell's entries in `BENCHMARK.json`.  Nothing here is a
measurement.  (The reference against the program's prefill, chunks,
fused chunk step, prefix hits and paged engine is tier-1's:
`tests/unit/test_mamba.py`.)
"""
import json
import math
import os
import sys
import types

import numpy as np
import pytest

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks import run as run_lib  # noqa: E402
from benchmarks.families import nemotron_h  # noqa: E402
from benchmarks.layers import ssm_hit_share  # noqa: E402
from benchmarks.layers import ssm_step_roofline  # noqa: E402

_CONFIG = 'nemotron-3-nano-30b-a3b-e16'
_CELL = 'tiny-ssm-moe.dryrun-window'
_METRICS = ('ssm_hit_share', 'ssm_step_roofline')


def _load(*parts):
    with open(os.path.join(_ROOT, 'benchmarks', *parts),
              encoding='utf-8') as f:
        return json.load(f)


def test_parameter_counts_and_work():
    """The configuration's arithmetic: 38.74 M a Mamba layer, 23.40 M an
    attention layer, 9.98 M an expert and 19.96 M the shared one; 31.578
    B at the published sizes, 5.258 B (10.52 GB in bf16) at the cut;
    46.8 MiB of state a sequence; 6 KiB of keys a token."""
    model = _load('configs', f'{_CONFIG}.json')
    counts = nemotron_h.param_counts(model)
    published = nemotron_h.param_counts(dict(model, **model['published']))
    assert counts['mamba'] == 38_744_896
    assert counts['attention'] == 23_399_040
    assert counts['expert'] == 9_977_856
    assert published['total'] == 31_577_940_288
    assert counts['total'] == 5_258_420_544
    assert round(counts['total'] * 2 / 1e9, 2) == 10.52
    assert sum(math.prod(s) for s, _ in
               nemotron_h.shapes(model).values()) == counts['total']
    assert nemotron_h.expert_bytes(model) == 2 * 2688 * 1856 * 2
    assert nemotron_h.decode_cache_bytes(model, 1, 'bfloat16') == 6 * 1024
    assert nemotron_h.decode_state_bytes(model) == 23 * (
        64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert round(nemotron_h.decode_state_bytes(model) / 2**20, 1) == 46.8
    # Per layer: S read and written (4 MiB) and 4 KiB a head of
    # operands and result.
    assert nemotron_h.ssm_step_bytes(model) == 23 * 4 * (
        2 * 64 * 64 * 128 + 2 * 64 * 64 + 64 + 2 * 8 * 128)
    per_token = 2 * (counts['active'] + counts['head'])
    assert nemotron_h.decode_flops(model, 0) - per_token == \
        nemotron_h._ssm_flops(model)  # pylint: disable=protected-access
    assert nemotron_h.prefill_flops(model, 0, 32) + \
        nemotron_h.prefill_flops(model, 32, 32) == pytest.approx(
            nemotron_h.prefill_flops(model, 0, 64))


def test_the_file_keeps_every_published_size():
    """Every key of the catalog row's config under its own name; the
    held experts and the vocabulary cut, and nothing else; what
    config.json does not itself state listed under `assumed`."""
    model = _load('configs', f'{_CONFIG}.json')
    # MODEL_CATALOG names a JSON-lines catalog of published configs.
    catalog = os.environ.get('MODEL_CATALOG', '')
    if catalog and os.path.exists(catalog):
        with open(catalog, encoding='utf-8') as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'NVIDIA-Nemotron-3-Nano-30B-A3B-BF16')
        changed = {k for k in row['config'] if model[k] != row['config'][k]}
        assert changed == {'n_routed_experts', 'vocab_size'}
        assert model['source'] == row['source_url']
        assert model['published'] == {
            k: row['config'][k] for k in ('n_routed_experts', 'vocab_size')}
    assert model['reduced'] == ['n_routed_experts', 'vocab_size']
    assert model['experts_held'] == [0, 16]
    assert len(model['hybrid_override_pattern']) == 52
    stated = ' '.join(model['assumed'])
    for word in ('no rotary', 'float32', 'MambaRMSNormGated',
                 'routed_scaling_factor', 'e_score_correction_bias',
                 'backbone.layers', 'A_log', 'bfloat16'):
        assert word in stated, word
    assert 'v5e-8' in model['deployment']
    cfg = nemotron_h.program_config(model, model['engine']['max_len'])
    assert (cfg.n_layers, cfg.mamba_layers, cfg.cache_layers,
            cfg.head_dim, cfg.d_ff, cfg.shared_d_ff, cfg.n_experts,
            cfg.held_experts, cfg.expert_top_k, cfg.expert_scale,
            cfg.ssm_inner, cfg.ssm_conv_dim) == (
                52, 23, 6, 128, 1856, 3712, 128, (0, 16), 6, 2.5, 4096,
                6144)
    assert [(len(kinds), n) for kinds, n in cfg.runs()] == [
        (6, 1), (7, 4), (9, 1), (9, 1)]
    assert model['engine'] == {
        'slots': 64, 'max_len': 8704, 'prefill_chunk': 256,
        'page_size': 16, 'kv_pages': 6144, 'prefix_caching': True}


def test_the_traffic_fits_the_pool_and_the_chip():
    """The documents and every slot's longest tail at once, with pages
    to spare; weights, slot states and pool within the chip's 16 GiB
    with room for a store of snapshots."""
    from benchmarks import traffic
    model = _load('configs', f'{_CONFIG}.json')
    spec = _load('workloads', 'docs-window.json')
    engine = model['engine']
    assert spec['clients'] == engine['slots']
    doc_pages = spec['shared_prefix']['count'] * (
        spec['shared_prefix']['tokens'] // engine['page_size'])
    tail = -(-(spec['prompt_tokens']['max'] + spec['output_tokens']['max'])
             // engine['page_size'])
    assert doc_pages + engine['slots'] * tail < engine['kv_pages'] - 1
    longest = spec['shared_prefix']['tokens'] + \
        spec['prompt_tokens']['max'] + spec['output_tokens']['max']
    assert longest <= engine['max_len']
    weights = 2 * nemotron_h.param_counts(model)['total']
    states = engine['slots'] * nemotron_h.decode_state_bytes(model)
    pool = engine['kv_pages'] * 16 * nemotron_h.decode_cache_bytes(
        model, 1, 'bfloat16')
    assert weights + states + pool < 0.85 * 2**34
    mix = traffic.Mix(spec, 2**31 + 7, model['vocab_size'])
    assert max(mix.prompt_levels) <= spec['prompt_tokens']['max']


def test_shapes_are_the_tree_the_program_reads():
    """`decode.prefill` runs on a tree of exactly the family's leaves,
    and reads every one: with a leaf taken away it fails."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import decode
    model = _load('tests', 'tiny-ssm-moe.json')
    cfg = nemotron_h.program_config(model, 32)
    spec = nemotron_h.shapes(model)

    def tree(without=None):
        out = {}
        for path, (shape, _) in spec.items():
            if path == without:
                continue
            node = out
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = jax.ShapeDtypeStruct(shape, jnp.float32)
        return out

    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    run = lambda params: jax.eval_shape(
        lambda p, t: decode.prefill(cfg, p, t, max_len=32), params, tokens)
    logits, cache = run(tree())
    assert logits.shape == (1, 256)
    assert cache['k'].shape == (1, 1, 2, 32, 16)      # one attention layer
    assert cache['ssm'].shape == (3, 1, 4, 16, 16)    # three mamba layers
    for path in spec:
        with pytest.raises(KeyError):
            run(tree(without=path))


def test_runs_of_the_pattern():
    """The pattern cut after each attention layer, equal neighbours
    merged: the four runs the program scans."""
    model = _load('configs', f'{_CONFIG}.json')
    runs = nemotron_h.program_config(model, 8704).runs()
    letters = {'mamba': 'M', 'experts': 'E', 'attn_nope': '*'}
    assert [(''.join(letters[k] for k in kinds), n)
            for kinds, n in runs] == [('MEMEM*', 1), ('EMEMEM*', 4),
                                      ('EMEMEMEM*', 1), ('EMEMEMEME', 1)]
    assert ''.join(''.join(letters[k] for k in kinds) * n
                   for kinds, n in runs) == model['hybrid_override_pattern']


def _transcribed(model, params, tokens):
    """The module docstring's equations, written out once more in
    numpy float64, layer by layer, position by position: the check on
    the reference's own arithmetic (its scan, its blocks, its expert
    loop)."""
    eps = model['norm_eps']
    g = lambda *path: np.asarray(_get(params, path), np.float64)
    rms = lambda x, w: w * x / np.sqrt(np.mean(x * x, -1, keepdims=True) +
                                       eps)
    x = g('embed', 'embedding')[np.asarray(tokens)]
    s = len(tokens)
    heads, p = model['mamba_num_heads'], model['mamba_head_dim']
    groups, size = model['n_groups'], model['ssm_state_size']
    inner = heads * p
    seen = {'M': 0, '*': 0, 'E': 0}
    for letter in model['hybrid_override_pattern']:
        i = seen[letter]
        seen[letter] += 1
        if letter == 'M':
            w = lambda *path: g('layers', 'mamba', *path)[i]
            u = rms(x, w('mamba_norm', 'scale'))
            proj = u @ w('mamba', 'in_proj', 'kernel')
            z, xbc, dt = (proj[:, :inner], proj[:, inner:-heads],
                          proj[:, -heads:])
            k = w('mamba', 'conv_kernel')
            pad = np.concatenate([np.zeros((k.shape[0] - 1, xbc.shape[1])),
                                  xbc])
            conv = np.stack([sum(k[j] * pad[t + j] for j in range(k.shape[0]))
                             for t in range(s)]) + w('mamba', 'conv_bias')
            xbc = conv / (1 + np.exp(-conv))
            xs = xbc[:, :inner].reshape(s, heads, p)
            bs = xbc[:, inner:inner + groups * size].reshape(s, groups, size)
            cs = xbc[:, inner + groups * size:].reshape(s, groups, size)
            dt = np.log1p(np.exp(dt + w('mamba', 'dt_bias')))
            a = -np.exp(w('mamba', 'A_log'))
            state = np.zeros((heads, p, size))
            y = np.zeros((s, heads, p))
            for t in range(s):
                for h in range(heads):
                    grp = h // (heads // groups)
                    state[h] = (np.exp(dt[t, h] * a[h]) * state[h] +
                                dt[t, h] * np.outer(xs[t, h], bs[t, grp]))
                    y[t, h] = state[h] @ cs[t, grp] + w('mamba', 'D')[h] * \
                        xs[t, h]
            gated = y.reshape(s, inner) * z / (1 + np.exp(-z))
            gated = gated.reshape(s, groups, inner // groups)
            gated = gated / np.sqrt(np.mean(gated * gated, -1, keepdims=True)
                                    + eps)
            x = x + (gated.reshape(s, inner) * w('mamba', 'norm', 'scale')) \
                @ w('mamba', 'out_proj', 'kernel')
        elif letter == '*':
            w = lambda *path: g('layers', 'attn', *path)[i]
            u = rms(x, w('attn_norm', 'scale'))
            q = np.einsum('sd,dhk->shk', u, w('attn', 'q_proj', 'kernel'))
            kk = np.einsum('sd,dhk->shk', u, w('attn', 'k_proj', 'kernel'))
            v = np.einsum('sd,dhk->shk', u, w('attn', 'v_proj', 'kernel'))
            h_q, hd = q.shape[1:]
            rep = h_q // kk.shape[1]
            out = np.zeros((s, h_q, hd))
            for t in range(s):
                for h in range(h_q):
                    sc = kk[:t + 1, h // rep] @ q[t, h] / np.sqrt(hd)
                    pr = np.exp(sc - sc.max())
                    out[t, h] = (pr / pr.sum()) @ v[:t + 1, h // rep]
            x = x + np.einsum('shk,hkd->sd', out,
                              w('attn', 'o_proj', 'kernel'))
        else:
            w = lambda *path: g('layers', 'ffn', *path)[i]
            f = rms(x, w('mlp_norm', 'scale'))
            sc = 1 / (1 + np.exp(-(f @ w('moe_mlp', 'router', 'kernel'))))
            chosen = np.argsort(-(sc + w('moe_mlp', 'expert_bias')),
                                -1)[:, :model['num_experts_per_tok']]
            relu2 = lambda t: np.maximum(t, 0) ** 2
            y = relu2(f @ w('moe_mlp', 'shared_up_proj')[0]) @ \
                w('moe_mlp', 'shared_down_proj')[0]
            for t in range(s):
                gates = sc[t, chosen[t]]
                gates = gates / gates.sum() * model['routed_scaling_factor']
                for e, gate in zip(chosen[t], gates):
                    y[t] += gate * (relu2(f[t] @ w('moe_mlp', 'up_proj')[e])
                                    @ w('moe_mlp', 'down_proj')[e])
            x = x + y
    x = rms(x, g('final_norm', 'scale'))
    return x @ g('lm_head', 'kernel')


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


@pytest.fixture(scope='module')
def twin():
    import jax
    from benchmarks.layouts import single_seeded
    model = _load('tests', 'tiny-ssm-moe.json')
    _, params = single_seeded.build(model, jax.devices()[:1], 99)
    tokens = np.random.default_rng(3).integers(1, 256, size=19).tolist()
    return model, params, tokens


def test_reference_is_the_equations(twin):
    """The reference against the transcription, at every position
    (float32 against float64: 1e-4 on logits of standard deviation
    ~1), and its margins: the twin routes nowhere near a tie at
    float32's width, so it abstains on no row.  The seeded decay rates
    and time steps are Mamba-2's ranges."""
    model, params, tokens = twin
    out, margin = nemotron_h.forward(model, params, tokens, 0, len(tokens))
    want = _transcribed(model, params, tokens)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4, rtol=0)
    assert float(np.min(margin)) > nemotron_h.undecided_margin(model)
    np.testing.assert_array_equal(
        np.asarray(nemotron_h.logits(model, params, tokens, 0,
                                     len(tokens))), np.asarray(out))
    part = nemotron_h.logits(model, params, tokens, 7, 5)
    np.testing.assert_allclose(np.asarray(part), np.asarray(out)[7:12],
                               atol=1e-6, rtol=0)
    mixer = params['layers']['mamba']['mamba']
    a = np.exp(np.asarray(mixer['A_log']))
    dt = np.log1p(np.exp(np.asarray(mixer['dt_bias'])))
    assert a.min() >= 1 and a.max() <= 16
    assert dt.min() >= 0.99e-3 and dt.max() <= 0.101


def test_the_controls_are_far(twin):
    """The int8 control is far from rounding and from the program's
    tolerance; with the bias dropped the reference is another model;
    the state rounded to bf16 at every step moves the logits too."""
    import jax
    model, params, tokens = twin
    out = np.asarray(nemotron_h.logits(model, params, tokens, 0, 19))
    low = np.asarray(nemotron_h.logits(model, params, tokens, 0, 19,
                                       precision='int8'))
    assert np.max(np.abs(low - out)) > 5e-2
    state = np.asarray(nemotron_h.logits(model, params, tokens, 0, 19,
                                         precision='bf16_state'))
    assert np.max(np.abs(state - out)) > 1e-3
    unbiased = jax.tree.map(lambda a: a, params)
    unbiased['layers']['ffn']['moe_mlp']['expert_bias'] = \
        0 * params['layers']['ffn']['moe_mlp']['expert_bias']
    other = np.asarray(nemotron_h.logits(model, unbiased, tokens, 0, 19))
    assert np.max(np.abs(other - out)) > 1e-2


# ------------------------------------------------------ the readers


def test_the_readers_on_counts_by_hand_and_on_a_program_without():
    """ssm_hit_share: 8,192 of 8,272 prompt tokens restored a request
    = 99.03%; ssm_step_roofline: 10,000 tokens in a 4 s span whose
    kernel took 1.3 s of 4.1 s of device window.  On the parent's
    program `stats()` has no `ssm` and the trace no `%ssm_decode_step`:
    nothing, and no raise."""
    model = _load('configs', f'{_CONFIG}.json')
    stats = lambda restored, prompt: {'ssm': {'restored_tokens': restored,
                                              'prompt_tokens': prompt}}
    requests = [types.SimpleNamespace(prompt=[0] * 100,
                                      token_s=[0.5 + i * 0.0003
                                               for i in range(10_000)])]
    run = types.SimpleNamespace(
        stats0=stats(0, 0), stats1=stats(8192 * 10, 8272 * 10),
        family=nemotron_h, model=model, peak={'hbm_bytes_per_s': 819e9},
        trace={'by_name': {'tick/%ssm_decode_step.3 custom-call': 1.3,
                           'tick/%fusion.1': 2.0},
               'devices': 1, 'window_s': 4.1},
        trace_span=(0.0, 4.0), requests=requests)
    assert ssm_hit_share.compute(run) == pytest.approx(100 * 8192 / 8272)
    needed = 10_000 * nemotron_h.ssm_step_bytes(model) / 819e9 / 4.0
    assert ssm_step_roofline.compute(run) == pytest.approx(
        100 * needed / (1.3 / 4.1))
    run.stats0 = run.stats1 = {'tick_loop': {'loop_s': 1.0}}
    run.trace = {'by_name': {'tick/%fusion.1': 2.0}, 'devices': 1,
                 'window_s': 4.1}
    assert ssm_hit_share.compute(run) is None
    assert ssm_step_roofline.compute(run) is None
    run.family = types.SimpleNamespace()
    assert ssm_step_roofline.compute(run) is None


def test_benchmark_json_has_the_cell():
    """The cell's four entries, found by name (a later PR appends its
    own behind them: nothing here pins the tail), each behind the
    entries the benchmark had; the cell's limits file."""
    bench = _load('..', 'BENCHMARK.json')
    cell = f'{_CONFIG}.docs-window'
    model = _load('configs', f'{_CONFIG}.json')

    def entry(section, name):
        names = [e['name'] for e in bench[section]]
        assert names.count(name) == 1
        return names.index(name), bench[section][names.index(name)]

    at, config = entry('configs', _CONFIG)
    assert at >= 5
    assert config == {
        'name': _CONFIG, 'source': model['source'],
        'file': f'benchmarks/configs/{_CONFIG}.json',
        'reduced': model['reduced'], 'why': config['why']}
    at, workload = entry('workloads', cell)
    assert at >= 6
    assert workload == {
        'name': cell, 'config': _CONFIG, 'traffic': 'docs-window',
        'chips': 1, 'why': workload['why']}
    assert len(workload['why']) <= 200 and len(config['why']) <= 200
    layers = {'ssm_step_roofline': ('kernels', 'device_trace'),
              'ssm_hit_share': ('page pool and prefix cache',
                                'program_counter')}
    for name, (layer, source) in layers.items():
        at, metric = entry('per_layer', name)
        assert at >= 19
        assert metric == {
            'name': name, 'unit': '%', 'better': 'higher',
            'source': source, 'layer': layer,
            'moves': 'out_tok_per_s', 'workloads': [cell]}
    limits = _load('limits', f'{cell}.json')
    assert set(limits) == {'logit_gap_max', 'sampled_tokens_min'}


# ------------------------------------------- the cell on its tiny twin


@pytest.fixture
def twin_cell(monkeypatch):
    """`dryrun.json` with the twin's cell and the two per-layer metrics
    beside its own: the file is the accepted benchmark's, so the
    entries are laid over it here.  The dry run has no table of peaks;
    the readers are given the v5e's, so that they have something to
    read."""
    load = run_lib._load_json

    def patched(path):
        data = load(path)
        if os.path.basename(path) == 'dryrun.json':
            data['configs'].append({
                'name': 'tiny-ssm-moe', 'source': 'none',
                'file': 'benchmarks/tests/tiny-ssm-moe.json',
                'reduced': [], 'why': 'rehearsal'})
            data['workloads'].append({
                'name': _CELL, 'config': 'tiny-ssm-moe',
                'traffic': 'dryrun-window', 'chips': 1,
                'why': 'rehearsal'})
            for name in _METRICS:
                data['per_layer'].append({
                    'name': name, 'unit': '%', 'better': 'higher',
                    'source': 'program_counter', 'layer': 'rehearsal',
                    'moves': 'out_tok_per_s', 'workloads': [_CELL]})
        return data

    monkeypatch.setattr(run_lib, '_load_json', patched)
    find = run_lib.find_devices

    def with_peaks(chips, dry_run):
        devices, _, attach_s = find(chips, dry_run)
        return devices, {'flops_bf16': 197e12,
                         'hbm_bytes_per_s': 819e9}, attach_s

    monkeypatch.setattr(run_lib, 'find_devices', with_peaks)


def _dry_run(capsys, *extra):
    rc = run_lib.main(['--dry-run', '--workload', _CELL, '--seed',
                       str(2**31 + 4321), '--seconds', '3', *extra])
    captured = capsys.readouterr()
    return rc, json.loads(captured.out.strip().splitlines()[-1])


def test_dry_run_of_the_cell(capsys, twin_cell):
    """The cell's whole path on the CPU: correct against the reference
    with nothing compiled in the window, and the hit share read from the
    program's counters (the dry run's trace has no device plane, so the
    kernel's roofline reads nothing)."""
    rc, line = _dry_run(capsys, '--trace', '1')
    assert rc == 0
    assert line['correct'] is True and line['failed'] == 0
    assert line['checks']['window_compiles']['value'] == 0
    metrics = {k: v['value'] for k, v in line['metrics'].items()}
    assert 50 < metrics['ssm_hit_share'] < 100


@pytest.mark.parametrize('how', ['control', 'altered'])
def test_what_must_come_out_not_correct(capsys, twin_cell, monkeypatch,
                                        how):
    """The int8 control, and the program with every fifth served token
    altered."""
    extra = ()
    if how == 'control':
        extra = ('--control', 'int8')
    else:
        from skypilot_tpu.serve import scheduler
        push = scheduler.Request._push
        count = [0]

        def broken(self, token):
            count[0] += 1
            push(self, (token + 1) % 256 if count[0] % 5 == 0 else token)

        monkeypatch.setattr(scheduler.Request, '_push', broken)
    rc, line = _dry_run(capsys, '--trace', '0', *extra)
    assert rc == 0 and line['correct'] is False
    gap = line['checks']['logit_gap_max']
    assert gap['value'] > gap['limit']
