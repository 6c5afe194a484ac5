"""CPU rehearsal of the family `ouro`: what the family counts at the
configuration's sizes, its weight tree against what the program reads,
the configuration file against the catalog's row, and `run.py
--dry-run` over a tiny twin of the configuration and of the
short-problem traffic (`tiny-looped.json`, traffic `dryrun-reason`)
with the reader the cell brings; the cell's entries in
`BENCHMARK.json`.  Nothing here is a measurement.  (The reference
against the program's prefill, chunked prefill, cached decode, paged
engine and speculative tick is tier-1's: `tests/unit/test_looped.py`.)
"""
import json
import math
import os
import sys
import types

import pytest

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks import run as run_lib  # noqa: E402
from benchmarks.families import ouro  # noqa: E402
from benchmarks.layers import loop_weight_roofline  # noqa: E402

_CONFIG = 'ouro-2.6b'
_CELL = 'tiny-looped.dryrun-reason'


def _load(*parts):
    with open(os.path.join(_ROOT, 'benchmarks', *parts),
              encoding='utf-8') as f:
        return json.load(f)


def test_parameter_counts_and_work():
    model = _load('configs', f'{_CONFIG}.json')
    counts = ouro.param_counts(model)
    # 4 x 2048 x 2048 + 3 x 2048 x 5632 + 4 norm scales a layer.
    assert counts['layer'] == 51_388_416
    assert round(counts['total'] / 1e6, 1) == 2668.0
    assert sum(math.prod(s) for s, _ in
               ouro.shapes(model).values()) == counts['total']
    assert round(counts['total'] * 2 / 1e9, 3) == 5.336      # bf16
    # What a pass must read: the 48 layers' matrices, 4.93 GB.
    assert ouro.loop_stack_bytes(model) == 48 * counts['layer_matmul'] * 2
    assert round(ouro.loop_stack_bytes(model) / 1e9, 2) == 4.93
    # Caches: 192 cache layers of 16 KV heads of 128: 1.5 MiB a token.
    assert ouro.cache_layers(model) == 192
    assert ouro.decode_cache_bytes(model, 1, 'bfloat16') == 3 * 2**19
    assert ouro.decode_cache_bytes(model, 240, 'bfloat16') == \
        240 * 3 * 2**19
    # Every layer product four times, the gate four times, the head once.
    assert ouro.decode_flops(model, 0) == 2 * (
        4 * (48 * counts['layer_matmul'] + 2048) + counts['head'])
    assert ouro.decode_flops(model, 300) - ouro.decode_flops(model, 0) == \
        ouro.decode_attention_flops(model, 300) == 4 * 16 * 128 * 192 * 300
    # Prefilling in two pieces needs what prefilling in one does.
    assert ouro.prefill_flops(model, 0, 32) + \
        ouro.prefill_flops(model, 32, 32) == pytest.approx(
            ouro.prefill_flops(model, 0, 64))


def test_the_file_keeps_every_published_size():
    """Every key of the catalog row's config under its own name and
    with its value; nothing cut; what config.json does not itself state
    listed under `assumed`."""
    model = _load('configs', f'{_CONFIG}.json')
    published = {
        'num_hidden_layers': 48, 'hidden_size': 2048,
        'num_attention_heads': 16, 'num_key_value_heads': 16,
        'head_dim': 128, 'intermediate_size': 5632, 'vocab_size': 49152,
        'rope_theta': 1000000, 'rms_norm_eps': 1e-06,
        'max_position_embeddings': 65536, 'total_ut_steps': 4,
        'early_exit_threshold': 1, 'hidden_act': 'silu',
        'tie_word_embeddings': False, 'model_type': 'ouro',
        'max_window_layers': 48, 'sliding_window': None,
        'use_sliding_window': False, 'rope_scaling': None}
    assert {k: model[k] for k in published} == published
    assert model['layer_types'] == ['full_attention'] * 48
    catalog = os.path.join('/opt/skills/guides/model-configs',
                           'architectures.jsonl')
    if os.path.exists(catalog):
        with open(catalog, encoding='utf-8') as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'Ouro-2.6B')
        assert {k: model[k] for k in row['config']} == row['config']
        assert model['source'] == row['source_url']
    assert model['reduced'] == [] and model['published'] == {}
    assert 'nothing is cut' in model['deployment']
    stated = ' '.join(model['assumed'])
    for word in ('modeling_ouro.py', 'input_layernorm_2',
                 'post_attention_layernorm_2', 'early_exit_gate',
                 'float32', 'adjacent', 'bfloat16', 'seeded'):
        assert word in stated, word
    cfg = ouro.program_config(model, model['engine']['max_len'])
    assert (cfg.n_layers, cfg.loop_passes, cfg.cache_layers,
            cfg.post_norms, cfg.exit_threshold, cfg.head_dim) == (
                48, 4, 192, True, 1.0, 128)
    assert model['engine'] == {
        'slots': 8, 'max_len': 512, 'prefill_chunk': 256, 'page_size': 16,
        'kv_pages': 264, 'prefix_caching': True}


def test_the_traffic_fits_the_pool():
    """The longest request is inside `max_len`, and every slot's
    longest request is resident at once, with pages to spare."""
    from benchmarks import traffic
    model = _load('configs', f'{_CONFIG}.json')
    spec = _load('workloads', 'reason-closed.json')
    engine = model['engine']
    mix = traffic.Mix(spec, 2**31 + 7, model['vocab_size'])
    assert (min(mix.prompt_levels), max(mix.prompt_levels)) == (48, 96)
    assert (min(mix.output_levels), max(mix.output_levels)) == (288, 416)
    longest = max(sum(mix.pair(i)) for i in range(mix.cycle))
    assert longest <= spec['prompt_tokens']['max'] + \
        spec['output_tokens']['max'] == engine['max_len']
    assert spec['clients'] == engine['slots']
    rows = engine['max_len'] // engine['page_size']
    assert engine['slots'] * rows + 1 + 7 == engine['kv_pages']


def test_shapes_are_the_tree_the_program_reads():
    """`decode.prefill` runs on a tree of exactly the family's leaves,
    and reads every one: with a leaf taken away it fails."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import decode
    model = _load('tests', 'tiny-looped.json')
    cfg = ouro.program_config(model, 32)
    spec = ouro.shapes(model)

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
    assert cache['k'].shape == (9, 1, 4, 32, 16)
    for path in spec:
        with pytest.raises(KeyError):
            run(tree(without=path))


def test_benchmark_json_has_the_cell():
    """The cell's three entries, found by name (a later PR appends its
    own behind them: nothing here pins the tail), each behind the
    entries the benchmark had."""
    bench = _load('..', 'BENCHMARK.json')
    cell = f'{_CONFIG}.reason-closed'
    model = _load('configs', f'{_CONFIG}.json')

    def entry(section, name):
        names = [e['name'] for e in bench[section]]
        assert names.count(name) == 1
        return names.index(name), bench[section][names.index(name)]

    at, config = entry('configs', _CONFIG)
    assert at >= 3
    assert config == {
        'name': _CONFIG, 'source': model['source'],
        'file': f'benchmarks/configs/{_CONFIG}.json', 'reduced': [],
        'why': config['why']}
    at, workload = entry('workloads', cell)
    assert at >= 4
    assert workload == {
        'name': cell, 'config': _CONFIG, 'traffic': 'reason-closed',
        'chips': 1, 'why': workload['why']}
    assert len(workload['why']) <= 200
    at, metric = entry('per_layer', 'loop_weight_roofline')
    assert at >= 12
    assert metric == {
        'name': 'loop_weight_roofline', 'unit': '%', 'better': 'higher',
        'source': 'program_counter', 'layer': 'model step',
        'moves': 'out_tok_per_s', 'workloads': [cell]}
    limits = _load('limits', f'{cell}.json')
    assert set(limits) == {'logit_gap_max', 'sampled_tokens_min'}
    assert limits['sampled_tokens_min'] == 1000


# ------------------------------------------------------ the reader


def test_the_reader_on_counts_by_hand_and_on_a_program_without():
    """1,200 ticks of 4 passes of 4.93 GB in 50 s of the engine's loop
    at 819 GB/s: 57.8%.  On the parent's program `stats()` has no
    `loop`, and a family of one pass has no `loop_stack_bytes`:
    nothing, and no raise."""
    model = _load('configs', f'{_CONFIG}.json')
    stats = lambda passes, loop_s: {
        'loop': {'steps': 4, 'cache_layers': 192, 'passes': passes,
                 'exit_mass': []},
        'tick_loop': {'loop_s': loop_s}}
    run = types.SimpleNamespace(
        stats0=stats(80, 7.0), stats1=stats(4880, 57.0), family=ouro,
        model=model, peak={'hbm_bytes_per_s': 819e9}, seconds=50.0)
    assert loop_weight_roofline.compute(run) == pytest.approx(
        100 * 4800 * 4932501504 / 819e9 / 50)
    assert 57 < loop_weight_roofline.compute(run) < 58
    # Counters read late, while the slots drain: passes and the loop's
    # seconds are read together, the window's length is not used.
    run.stats1 = stats(4880 + 1200, 57.0 + 12.5)
    assert 57 < loop_weight_roofline.compute(run) < 58
    run.stats0, run.stats1 = ({'ticks': 0, 'tick_loop': {'loop_s': 0.0}},
                              {'ticks': 9, 'tick_loop': {'loop_s': 1.0}})
    assert loop_weight_roofline.compute(run) is None
    from benchmarks.families import dense
    run.stats0, run.stats1, run.family = stats(0, 0.0), stats(8, 1.0), dense
    assert loop_weight_roofline.compute(run) is None
    run.family, run.peak = ouro, None            # the dry run
    assert loop_weight_roofline.compute(run) is None


# ------------------------------------------- the cell on its tiny twin


@pytest.fixture
def twin_cell(monkeypatch):
    """`dryrun.json` with the twin's cell and the new per-layer metric
    beside its own: the file is the accepted benchmark's, so the
    entries are laid over it here.  The dry run has no table of peaks;
    the reader is given the v5e's, so that it has something to read."""
    load = run_lib._load_json

    def patched(path):
        data = load(path)
        if os.path.basename(path) == 'dryrun.json':
            data['configs'].append({
                'name': 'tiny-looped', 'source': 'none',
                'file': 'benchmarks/tests/tiny-looped.json',
                'reduced': [], 'why': 'rehearsal'})
            data['workloads'].append({
                'name': _CELL, 'config': 'tiny-looped',
                'traffic': 'dryrun-reason', 'chips': 1,
                'why': 'rehearsal'})
            data['per_layer'].append({
                'name': 'loop_weight_roofline', 'unit': '%',
                'better': 'higher', 'source': 'program_counter',
                'layer': 'model step', 'moves': 'out_tok_per_s',
                'workloads': [_CELL]})
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
    """The cell's whole path on the CPU: correct against the reference,
    and the new per-layer metric read from the program's counter."""
    rc, line = _dry_run(capsys, '--trace', '1')
    assert rc == 0
    assert line['correct'] is True and line['failed'] == 0
    metrics = {k: v['value'] for k, v in line['metrics'].items()}
    assert metrics['loop_weight_roofline'] > 0
    assert 0 < metrics['tokens_per_tick'] <= 4
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
