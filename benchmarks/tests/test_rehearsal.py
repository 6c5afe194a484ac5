"""CPU rehearsal of the benchmark harness: seconds in all.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Nothing here is a measurement.  The rehearsal drives `run.py --dry-run`
(the same path as a run, cells from `dryrun.json`, the `tiny` toy) and
checks the pieces of the yardstick one by one.
"""
import hashlib
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

from benchmarks import cost  # noqa: E402
from benchmarks import reduce as reduce_lib  # noqa: E402
from benchmarks import run as run_lib  # noqa: E402
from benchmarks import traffic  # noqa: E402
from benchmarks.families import dense  # noqa: E402
from benchmarks.tests import dense_tied  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_KEYS = ['correct', 'attempted', 'failed', 'metrics', 'device']


def _config(name):
    with open(os.path.join(_ROOT, 'benchmarks', 'configs',
                           f'{name}.json'), encoding='utf-8') as f:
        return json.load(f)


def _tiny(dtype='bfloat16'):
    with open(os.path.join(_HERE, 'tiny.json'), encoding='utf-8') as f:
        return dict(json.load(f), torch_dtype=dtype)


def _dry_run(capsys, *extra):
    rc = run_lib.main(['--dry-run', '--seed', str(2**31 + 12345),
                       '--seconds', '2', *extra])
    captured = capsys.readouterr()
    return rc, json.loads(captured.out.strip().splitlines()[-1]), \
        captured.err


@pytest.mark.parametrize('cell,trace', [('tiny.dryrun-open', '0'),
                                        ('tiny.dryrun-shared', '1')])
def test_dry_run_prints_the_contract_line(capsys, cell, trace):
    rc, line, err = _dry_run(capsys, '--workload', cell, '--trace', trace)
    assert rc == 0
    assert list(line)[:len(_KEYS)] == _KEYS
    assert list(line)[-1] == 'checks'
    assert line['device']['platform'] == 'cpu'
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] > 0
    for name, c in line['checks'].items():
        assert f'check {name}:' in err
    if trace == '0':
        assert set(line['metrics']) == {'ttft_p95_ms', 'ttft_p50_ms',
                                        'itl_p95_ms', 'out_tok_per_s',
                                        'setup_s'}
    else:
        # Counters and spans read on the CPU; what needs a device trace
        # or a peak returns nothing and is left out, never 0.
        assert line['metrics']['prefix_hit_share']['value'] > 50
        assert line['metrics']['tokens_per_tick']['value'] > 0
        assert 'device_idle' not in line['metrics']
        assert 'step_mfu' not in line['metrics']
    for m in line['metrics'].values():
        assert set(m) == {'value', 'unit'}


def test_without_dry_run_no_chip_is_an_error(capsys):
    with pytest.raises(SystemExit) as e:
        run_lib.main(['--workload', 'mistral-7b-v0.3-l16.chat-open',
                      '--seed', '1', '--seconds', '1', '--trace', '0'])
    assert "platform 'cpu'" in str(e.value)
    assert capsys.readouterr().out == ''


def test_control_comes_out_not_correct(capsys):
    """The reference in int8, put in the program's place."""
    rc, line, _ = _dry_run(capsys, '--workload', 'tiny.dryrun-open',
                           '--trace', '0', '--control', 'int8')
    assert rc == 0
    assert line['correct'] is False
    gap = line['checks']['logit_gap_max']
    assert gap['value'] > gap['limit']


def test_an_altered_token_comes_out_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: every fifth token is altered
    where the engine hands it out."""
    from skypilot_tpu.serve import scheduler
    push = scheduler.Request._push
    count = [0]

    def broken(self, token):
        count[0] += 1
        push(self, (token + 1) % 256 if count[0] % 5 == 0 else token)

    monkeypatch.setattr(scheduler.Request, '_push', broken)
    rc, line, _ = _dry_run(capsys, '--workload', 'tiny.dryrun-open',
                           '--trace', '0')
    assert rc == 0
    assert line['correct'] is False
    assert line['checks']['logit_gap_max']['value'] > 0.5


@pytest.fixture
def tied_family(monkeypatch):
    """The tests' own family, put where `families.of` finds it."""
    monkeypatch.setitem(sys.modules, 'benchmarks.families.dense_tied',
                        dense_tied)


@pytest.mark.parametrize('control', [None, 'int8'])
def test_a_second_family_runs_with_no_file_outside_the_tests(
        capsys, tied_family, control):
    """The dense block with a tied head: `tie_embeddings=True` to the
    program, no `lm_head` leaf, logits through the embedding's
    transpose.  It reaches `correct`, and its control does not."""
    rc, line, _ = _dry_run(capsys, '--workload', 'tiny-tied.dryrun-open',
                           '--trace', '0',
                           *(['--control', control] if control else []))
    assert rc == 0 and line['failed'] == 0
    assert line['correct'] is (control is None)
    assert line['checks']['sampled_tokens']['value'] >= 100
    for root, _, files in os.walk(os.path.join(_ROOT, 'benchmarks')):
        if 'tests' in root.split(os.sep) or '__pycache__' in root:
            continue
        for name in files:
            with open(os.path.join(root, name), encoding='utf-8') as f:
                assert 'dense_tied' not in f.read(), name


# Taken from the parent (PR 27) before `shapes` moved to the family.
_PARENT_WEIGHTS = {
    3: '38a3da94abccfac85b64b5fe590a3ea1ee47eceab5350f06a56c386d486d4928',
    2**31 + 12345:
        '42885e70d18ec03c1fb36ad19ed857e6233a90d661e78dfcf1209c14043bed8c',
}


@pytest.mark.parametrize('seed', sorted(_PARENT_WEIGHTS))
def test_the_dense_weights_are_the_parents_bit_for_bit(seed):
    import jax
    import numpy as np
    from benchmarks.layouts import single
    _, params = single.build(_tiny(), jax.devices(), seed)
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(params),
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(leaf.dtype).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == _PARENT_WEIGHTS[seed]


def test_blocks_cover_an_answer_of_any_length():
    # An answer that fits one block is read as before: one block from
    # the prompt's last row, or the sequence's last `_ROWS` rows.
    rows = run_lib._ROWS
    assert list(run_lib._blocks(100, 200, 1024)) == [(99, 0, 0, 200)]
    assert list(run_lib._blocks(400, 113, 512)) == [(0, 399, 0, 113)]
    # A longer one: every served token in exactly one block's rows.
    n, m = 200, 1300
    blocks = list(run_lib._blocks(n, m, 1536))
    assert len(blocks) == 3
    seen = []
    for first, lo, j, k in blocks:
        assert 0 <= first <= 1536 - rows and lo + k <= rows
        seen += [(first + lo + i, j + i) for i in range(k)]
    assert seen == [(n - 1 + j, j) for j in range(m)]


@pytest.mark.parametrize('altered', [False, True])
def test_a_long_answer_is_compared_over_every_block(capsys, monkeypatch,
                                                    altered):
    """Blocks of 32 rows, so that the toy's answers (16 to 80 tokens) need
    up to three; with `altered`, one token of every request's second
    block is changed where the engine hands it out."""
    from skypilot_tpu.serve import scheduler
    monkeypatch.setattr(run_lib, '_ROWS', 32)
    monkeypatch.setattr(run_lib, '_SEQ_BUCKET', 32)
    calls = []
    logits = dense.logits
    monkeypatch.setattr(dense, 'logits', lambda model, params, seq, first,
                        rows, **kw: calls.append((len(seq), first, rows))
                        or logits(model, params, seq, first, rows, **kw))
    push = scheduler.Request._push

    def broken(self, token):
        # Served token 40 is row 8 of the second block.
        push(self, (token + 1) % 256 if len(self.tokens) == 40 else token)

    if altered:
        monkeypatch.setattr(scheduler.Request, '_push', broken)
    rc, line, _ = _dry_run(capsys, '--workload', 'tiny.dryrun-open',
                           '--trace', '0')
    assert rc == 0
    assert {rows for _, _, rows in calls} == {32}
    tokens = line['checks']['sampled_tokens']['value']
    assert tokens > 32 * run_lib._SAMPLE_REQUESTS     # answers past a block
    assert -(-tokens // 32) <= len(calls) <= tokens // 32 + \
        2 * run_lib._SAMPLE_REQUESTS
    assert line['correct'] is not altered
    if altered:
        assert line['checks']['logit_gap_max']['value'] > 0.5


def test_traffic_is_a_pure_function_of_the_seed():
    with open(os.path.join(_ROOT, 'benchmarks', 'workloads',
                           'chat-open.json'), encoding='utf-8') as f:
        spec = json.load(f)
    seed = 2**31 + 7
    # The cell offers the same requests in the same order whatever the
    # seed, each due up to `dither_ms` later than the one fixed trace.
    dither = spec.pop('dither_ms') / 1e3
    fixed = [[(r.due_s, len(r.prompt), r.max_new) for r in traffic.Mix(
        spec, s, 32768).open_schedule(40.0)] for s in (seed, seed + 1)]
    assert fixed[0] == fixed[1]
    spec['dither_ms'] = dither * 1e3
    late = [traffic.Mix(spec, s, 32768).open_schedule(40.0)
            for s in (seed, seed, seed + 1)]
    assert [r.due_s for r in late[0]] == [r.due_s for r in late[1]]
    assert [r.due_s for r in late[0]] != [r.due_s for r in late[2]]
    for sched in late:
        assert [r.due_s for r in sched] == sorted(r.due_s for r in sched)
        by_index = sorted(sched, key=lambda r: r.index)
        assert [(len(r.prompt), r.max_new) for r in by_index] == \
            [(n, m) for _, n, m in fixed[0]]
        assert all(0.0 <= r.due_s - due < dither
                   for r, (due, _, _) in zip(by_index, fixed[0]))
    assert 0.05 < max(r.due_s - due for r, (due, _, _) in zip(
        sorted(late[0], key=lambda r: r.index), fixed[0]))
    spec.pop('dither_ms')
    spec.pop('order_seed')
    a = traffic.Mix(spec, seed, 32768).open_schedule(40.0)
    b = traffic.Mix(spec, seed, 32768).open_schedule(40.0)
    c = traffic.Mix(spec, seed + 1, 32768).open_schedule(40.0)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    p, o = spec['prompt_tokens'], spec['output_tokens']
    for r in a:
        assert p['min'] <= len(r.prompt) <= p['max']
        assert o['min'] <= r.max_new <= o['max']
        assert 0 <= r.due_s < 40.0
        assert all(0 < t < 32768 for t in r.prompt)
    assert len(a) == round(spec['rate_per_s'] * 40.0)
    # Another seed offers the same work in another order.
    n = len(a) - len(a) % p['levels']
    assert sorted((len(r.prompt), r.max_new) for r in a[:n]) == \
        sorted((len(r.prompt), r.max_new) for r in c[:n])
    assert sorted(round(y.due_s - x.due_s, 9) for x, y in zip(a, a[1:])) \
        == sorted(round(y.due_s - x.due_s, 9) for x, y in zip(c, c[1:]))
    # Set-up warms every prompt length the window can send.
    warm = {len(r.prompt) for phase in traffic.Mix(
        spec, seed, 32768).warmup() for r in phase}
    assert {len(r.prompt) for r in a} <= warm


def _synthetic_trace(tmp_path, second_kernel=False):
    """Two ticks on one device: a `while` of 8 us holding a 2 us kernel
    twice, then a 1 us fusion; 5 us idle between the ticks.  With
    `second_kernel` the fusion is another Mosaic call (a scan, say)."""
    from jax.profiler import ProfileData
    call = ('{} = bf16[16,8,4,128]{{3,2,1,0}} custom-call(s32[16,160]{{1,0}} '
            '%copy-done.2), custom_call_target=\\"tpu_custom_call\\", '
            'frontend_attributes={{}}')
    kernel = call.format('%paged_decode_attention.6')
    loop = '%while.1 = (s32[], bf16[16,1,4096]{2,0,1}) while(%tuple.3)'
    last = call.format('%state_scan.2') if second_kernel else \
        '%fusion.7 = f32[16]{0} fusion(%p.1), kind=kLoop'
    events = []
    for base in (0, 14_000):
        events += [(loop, base, 8_000),
                   (kernel, base + 1_000, 2_000),
                   (kernel, base + 4_000, 2_000),
                   (last, base + 8_000, 1_000)]
    names = sorted({e[0] for e in events})
    meta = ''.join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: '
        f'"{n}" }} }} ' for i, n in enumerate(names))
    evs = ''.join(
        f'events {{ metadata_id: {names.index(n) + 1} offset_ps: '
        f'{s * 1000} duration_ps: {d * 1000} }} ' for n, s, d in events)
    meta += ('event_metadata { key: 9 value { id: 9 name: '
             '"jit_paged_engine_step(123)" } } ')
    mods = ''.join(f'events {{ metadata_id: 9 offset_ps: {b * 1000} '
                   f'duration_ps: 9000000 }} ' for b in (0, 14_000))
    text = (f'planes {{ id: 1 name: "/device:TPU:0" {meta} lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 0 {evs} }} lines {{ id: 2 '
            f'name: "XLA Modules" timestamp_ns: 0 {mods} }} }} '
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 name: '
            f'"python" timestamp_ns: 0 }} }}')
    path = tmp_path / 'plugins' / 'profile' / 'x' / 'h.xplane.pb'
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_reduce_gives_the_known_busy_share_and_op_totals(tmp_path):
    trace_dir = _synthetic_trace(tmp_path)
    out = reduce_lib.reduce_trace(reduce_lib.find_xplane(trace_dir), 1)
    assert out['window_s'] == pytest.approx(23e-6)
    assert out['busy_s'] == pytest.approx(18e-6)
    kernel = ('paged_engine_step/%paged_decode_attention.6 custom-call '
              'tpu_custom_call')
    loop = 'paged_engine_step/%while.1 while'
    assert out['by_name'][kernel] == pytest.approx(8e-6)
    assert out['by_name'][loop] == pytest.approx(16e-6)
    assert out['own_by_name'][loop] == pytest.approx(8e-6)
    assert {row[0] for row in out['breakdown']['device_ops'][:2]} == \
        {kernel, loop}
    assert out['breakdown']['idle_gaps'][0][1] == pytest.approx(5e-6)
    from benchmarks.layers import device_idle
    run = types.SimpleNamespace(trace=out)
    assert device_idle.compute(run) == pytest.approx(100 * 5 / 23)
    assert device_idle.compute(types.SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize('second_kernel', [False, True])
def test_the_paged_kernel_is_told_by_its_name(tmp_path, second_kernel):
    """One token decoded at a context of 1,000 keys in a span as long as
    the trace: its K and V at the HBM peak over the kernel's 8 us.
    Another Mosaic call in the tick's program is not the kernel's time."""
    from benchmarks.layers import paged_attn_roofline
    trace_dir = _synthetic_trace(tmp_path, second_kernel)
    out = reduce_lib.reduce_trace(reduce_lib.find_xplane(trace_dir), 1)
    assert sum('tpu_custom_call' in k for k in out['by_name']) == \
        1 + second_kernel
    model = _config('mistral-7b-v0.3-l16')
    request = types.SimpleNamespace(prompt=[1] * 1000, token_s=[10e-6])
    run = types.SimpleNamespace(
        trace=out, trace_span=(0.0, 23e-6), requests=[request],
        model=model, family=dense, kv_dtype='bfloat16',
        peak=cost.peaks('TPU v5 lite'))
    assert paged_attn_roofline.compute(run) == pytest.approx(
        100 * (65536e3 / 819e9) / 8e-6)
    # A trace that does not name the kernel reads nothing, never 0.
    run.trace = dict(out, by_name={
        k.replace('paged_decode_attention', 'closed_call'): v
        for k, v in out['by_name'].items()})
    assert paged_attn_roofline.compute(run) is None


@pytest.mark.parametrize('name,billions', [('mistral-7b-v0.3-l16', 3.76),
                                           ('internlm2-1.8b', 1.89)])
def test_parameter_counts(name, billions):
    model = _config(name)
    assert model['family'] == 'dense'
    assert round(dense.param_counts(model)['total'] / 1e9, 2) == billions
    assert sum(math.prod(s) for s, _ in dense.shapes(model).values()) == \
        dense.param_counts(model)['total']


def test_cost_counts_what_the_traffic_needs():
    model = _config('mistral-7b-v0.3-l16')
    assert dense.decode_cache_bytes(model, 1, 'bfloat16') == 64 * 1024
    assert dense.decode_cache_bytes(model, 1000, 'int8') == 32 * 1024 * 1000
    peak = cost.peaks('TPU v5 lite')
    floor = cost.paged_attention_floor_s(
        dense.decode_cache_bytes(model, 1000, 'bfloat16'),
        dense.decode_attention_flops(model, 1000), peak)
    assert floor['bound'] == 'hbm'
    assert floor['seconds'] == pytest.approx(65536e3 / 819e9)
    assert cost.paged_attention_floor_s(1, 10**9, peak)['bound'] == 'flops'
    # A decoded token: every matmul once, and attention over its context.
    assert dense.decode_flops(model, 1000) - dense.decode_flops(model, 0) \
        == dense.decode_attention_flops(model, 1000)
    # Prefilling in two pieces needs what prefilling in one does.
    whole = dense.prefill_flops(model, 0, 512)
    assert dense.prefill_flops(model, 0, 256) + \
        dense.prefill_flops(model, 256, 256) == pytest.approx(whole)
    with pytest.raises(ValueError):
        cost.peaks('cpu')


def test_reference_and_weights_agree_with_the_program_on_tiny():
    """The plain reference against `models/transformer.Transformer`, in
    float32 on the CPU, on the layout's own seeded weights; and the
    layout's tree is the one `Transformer.init` makes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn
    from benchmarks.layouts import single
    from skypilot_tpu.models import transformer

    model = _tiny('float32')
    _, params = single.build(model, jax.devices(), 2**31 + 5)
    cfg = dense.program_config(model, 64)
    tokens = np.random.default_rng(0).integers(1, 256, size=48)
    net = transformer.Transformer(cfg)
    made = nn.meta.unbox(jax.eval_shape(
        net.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert jax.tree.map(lambda a: a.shape, made['params']) == \
        jax.tree.map(lambda a: a.shape, params)
    with jax.default_matmul_precision('highest'):
        theirs = net.apply({'params': params},
                           jnp.asarray(tokens[None], jnp.int32))[0]
    ours = dense.logits(model, params, tokens.tolist(), 16, 32)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs[16:]),
                               atol=2e-4, rtol=2e-4)
    low = dense.logits(model, params, tokens.tolist(), 16, 32,
                           precision='int8')
    assert float(jnp.max(jnp.abs(low - ours))) > 1e-3


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(_ROOT, 'BENCHMARK.json'), encoding='utf-8') as f:
        bench = json.load(f)
    e2e = {m['name'] for m in bench['end_to_end']}
    cells = {w['name']: w for w in bench['workloads']}
    for c in bench['configs']:
        model = _config(c['name'])
        assert c['file'] == f'benchmarks/configs/{c["name"]}.json'
        assert model['reduced'] == c['reduced']
        assert model['source'] == c['source']
        for where, key in (('families', 'family'), ('layouts', 'layout')):
            assert os.path.exists(os.path.join(
                _ROOT, 'benchmarks', where, f'{model[key]}.py'))
    for w in bench['workloads']:
        assert w['name'] == f'{w["config"]}.{w["traffic"]}'
        for kind, name in (('workloads', w['traffic']),
                           ('limits', w['name'])):
            assert os.path.exists(os.path.join(
                _ROOT, 'benchmarks', kind, f'{name}.json'))

    def reporting(metric):
        return set(metric.get('workloads', cells))

    for m in bench['per_layer']:
        assert os.path.exists(os.path.join(
            _ROOT, 'benchmarks', 'layers', f'{m["name"]}.py'))
        moved = next(x for x in bench['end_to_end']
                     if x['name'] == m['moves'])
        assert reporting(m) <= reporting(moved)
    assert 'setup_s' in e2e
