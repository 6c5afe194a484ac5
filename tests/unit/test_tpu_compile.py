"""Compile the main path's kernels for a TPU v5e that is described,
not attached: Mosaic refuses here, in seconds and without a chip, what
interpret mode lets through (a block or a DMA slice off the tiling, a
reshape it has no layout for, more VMEM than a kernel may use).  It
compiles only — whether the result is right is the chip suite's
(tests/tpu/).

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports this file.
Keep such tests in this one file.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.models import decode
from skypilot_tpu.ops import paged_attention


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    'layers,slots,h_q,h_kv,s_q,rows,n_pages,quantized', [
        (16, 16, 32, 8, 1, 160, 2432, False),  # benchmark: Mistral-7B
        (24, 24, 16, 8, 1, 96, 2048, False),   # benchmark: InternLM2-1.8B
        (16, 16, 32, 8, 4, 160, 2432, False),  # speculative verify, k = 3
        (16, 16, 32, 8, 1, 160, 2432, True),   # int8 pages
        (16, 16, 8, 2, 1, 160, 2432, False),   # one shard of --tensor 4
        (16, 16, 8, 2, 4, 160, 2432, True),    # ... int8, verify
    ], ids=['mistral', 'internlm2', 'verify4', 'int8', 'shard',
            'shard-int8'])
def test_paged_decode_kernel_compiles_for_v5e(
        one_chip, monkeypatch, layers, slots, h_q, h_kv, s_q, rows,
        n_pages, quantized):
    """The kernel as the tick calls it: every layer's pages in one
    operand and the layer a traced scalar, so what Mosaic compiles is
    the copy from `hbm.at[layer, page]`; nothing pool-sized may be
    made on the way to the kernel."""
    monkeypatch.delenv('SKYTPU_PALLAS_INTERPRET', raising=False)
    d, ps = 128, 16

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (
        {'q': arg((layers, n_pages, h_kv, ps, d), jnp.int8),
         'scale': arg((layers, n_pages, h_kv, ps), jnp.float32)}
        if quantized else arg((layers, n_pages, h_kv, ps, d), jnp.bfloat16))
    compiled = jax.jit(
        lambda *a: paged_attention._paged_attention_pallas(
            *a[:-1], sm_scale=d ** -0.5, layer=a[-1])).lower(
                arg((slots, h_q, s_q, d), jnp.bfloat16), pool, pool,
                arg((slots, rows), jnp.int32),
                arg((slots,), jnp.int32), arg((), jnp.int32)).compile()
    assert 'tpu_custom_call' in compiled.as_text()
    # The pools go to the kernel as they came: at most the layer's
    # scales of an int8 pool (3% of it) are sliced out.
    assert compiled.memory_analysis().temp_size_in_bytes < (
        n_pages * h_kv * ps * d * (1 if quantized else 2))


@pytest.mark.parametrize('s_q', [1, 4], ids=['tick', 'verify4'])
def test_windowed_paged_decode_kernel_compiles_for_v5e(
        one_chip, monkeypatch, s_q):
    """The kernel with a layer's window as a fourth prefetched scalar
    (after the layer's index), at the benchmark's expert cell: 4 layers'
    pages, 64 slots, 128 query heads on 8 KV
    heads (16 a KV head: 128 rows a step), tables of 544 rows."""
    monkeypatch.delenv('SKYTPU_PALLAS_INTERPRET', raising=False)
    d, ps, slots, n_pages = 128, 16, 64, 6144

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((4, n_pages, 8, ps, d), jnp.bfloat16)
    compiled = jax.jit(
        lambda *a: paged_attention._paged_attention_pallas(
            *a[:-2], sm_scale=d ** -0.5, window=a[-2],
            layer=a[-1])).lower(
                arg((slots, 128, s_q, d), jnp.bfloat16), pool, pool,
                arg((slots, 544), jnp.int32), arg((slots,), jnp.int32),
                arg((), jnp.int32), arg((), jnp.int32)).compile()
    assert 'tpu_custom_call' in compiled.as_text()


@pytest.mark.parametrize('writes', [False, True], ids=['reads', 'writes'])
def test_packed_rows_reach_the_kernel_and_back_by_moves(one_chip,
                                                        kernel_on_tpu, writes):
    """LFM2-24B-A2B's heads, two of 64 to a 128-lane row, through
    `paged_attention` at the benchmark's shapes (64 slots, tables of 544
    rows, 2 layers of 6,144 pages): no bitcast in the program changes an
    array's element count.  One did (a bf16[64,4,8,128] kernel result
    read as [64,4,2,4,1,64], every head's output taken from lanes 0-63)
    where the wrapper cut each head's lanes out with a plain slice: the
    compiler folded the stack of cuts into that bitcast, and the chip
    computed the second head of each row wrong."""
    slots, pages, rows = 64, 6144, 544

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((2, pages, 4, 16, 128))
    new = (arg((slots, 8, 1, 64)),) * 2 if writes else ()
    hlo = jax.jit(lambda q, k, v, t, n, layer, *new: paged_attention.
                  paged_attention(q, k, v, t, n, layer=layer,
                                  heads_per_row=2,
                                  **dict(zip(('new_k', 'new_v'), new)))
                  ).lower(arg((slots, 32, 1, 64)), pool, pool,
                          arg((slots, rows), jnp.int32),
                          arg((slots,), jnp.int32), arg((), jnp.int32),
                          *new).compile().as_text()
    assert 'paged_decode_attention' in hlo
    assert not _resizing_bitcasts(hlo)


def _resizing_bitcasts(hlo: str):
    """The bitcasts whose result holds another number of elements than
    their operand: a read of an array's padding as data."""
    size, out = {}, []
    for line in hlo.splitlines():
        m = re.match(r'^\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* '
                     r'([\w\-]+)\((%[\w.\-]+)?', line)
        if not m:
            continue
        size[m.group(1)] = math.prod(int(n) for n in m.group(2).split(',')
                                     if n)
        if m.group(3) == 'bitcast' and size.get(m.group(4), size[
                m.group(1)]) != size[m.group(1)]:
            out.append(line.strip())
    return out


def _own_operations(hlo: str):
    """(name, result dims, opcode) of every instruction that runs as an
    operation of its own: those of the computations no fusion calls."""
    fused = set(re.findall(r'kind=k\w+, calls=(%[\w.\-]+)', hlo))
    out, inside = [], None
    for line in hlo.splitlines():
        head = re.match(r'^(?:ENTRY )?(%[\w.\-]+) \(.*\{$', line)
        if head:
            inside = head.group(1)
        m = re.match(r'^\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* '
                     r'([\w\-]+)\(', line)
        if m and inside not in fused:
            out.append((m.group(1),
                        [int(n) for n in m.group(2).split(',') if n],
                        m.group(3)))
    return out


@pytest.mark.parametrize('form', ['serving', 'training'])
@pytest.mark.parametrize('heads', [32, 8, 128],
                         ids=['q', 'kv', 'q-docs-window'])
def test_layer_scan_reads_a_stacked_projection_kernel_in_place(
        one_chip, form, heads):
    """A scan over stacked q/k/v kernels at Mistral's widths (and the
    128 query heads of the expert cell), 16 rows as a tick has.  In the
    serving form `[L, d_model, heads * hd]` the slice of layer l is
    part of the product's fusion: no operation of the loop's body
    results in a whole layer's kernel.  The training form
    `[L, d_model, heads, hd]` is the control: the compiler copies the
    layer's kernel out of the stack first
    (`constant_dynamic-slice_fusion`), which the serving engine's
    re-forming exists to avoid.  Should a later compiler stop copying
    it, this says so, and `decode.serving_params` can go."""
    layers, d, hd, rows = 3, 4096, 128, 16

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def scanned(x, kernels):
        return jax.lax.scan(
            lambda x, kernel: (x, decode._attn_proj(  # pylint: disable=protected-access
                x, {'kernel': kernel}, heads, hd)), x, kernels)[1]

    kernel = (layers, d, heads * hd) if form == 'serving' else (
        layers, d, heads, hd)
    hlo = jax.jit(scanned).lower(arg((1, rows, d)),
                                 arg(kernel)).compile().as_text()
    whole = [(name, dims, op) for name, dims, op in _own_operations(hlo)
             if math.prod(dims) == d * heads * hd]
    if form == 'serving':
        assert not whole, whole
    else:
        assert any('constant_dynamic-slice_fusion' in name
                   for name, _, _ in whole), whole


@pytest.mark.parametrize('by_pages', [True, False],
                         ids=['by-pages', 'gather'])
def test_seeding_from_a_pool_leaf_over_2_gib(one_chip, monkeypatch,
                                             by_pages):
    """A prefix hit on a looped stack's pool (192 cache layers x 264
    pages of 16 KV heads: 3.3 GB a leaf).  Copied out page by page,
    `paged_seed_private` makes nothing but the private cache.  The
    gather is the control: over an operand of 2 GiB or more the compiler
    splits it and copies two thirds of the leaf first, 2.2 GB of
    temporaries, which is why `decode._GATHER_LIMIT_BYTES` exists.
    Should a later compiler stop copying, this says so, and the
    page-by-page path can go."""
    from skypilot_tpu.models import configs
    cfg = configs.ModelConfig(
        vocab_size=49152, d_model=2048, n_layers=48, n_heads=16,
        n_kv_heads=16, d_ff=5632, loop_passes=4, post_norms=True,
        dtype=jnp.bfloat16)
    if not by_pages:
        monkeypatch.setattr(decode, '_GATHER_LIMIT_BYTES', 1 << 62)
    with_sharding = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    paged = with_sharding(jax.eval_shape(
        lambda: decode.init_paged_cache(cfg, 264, 16, 8, 32)))
    assert paged['k'].shape[0] == 192
    ids = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=one_chip)
    memory = jax.jit(
        decode.bind(decode.paged_seed_private, cfg),
        static_argnames=('priv_len',)).lower(
            paged, ids, priv_len=512).compile().memory_analysis()
    assert memory.output_size_in_bytes == pytest.approx(0.805e9, rel=0.01)
    if by_pages:
        assert memory.temp_size_in_bytes < 0.05e9
    else:
        assert memory.temp_size_in_bytes > 2e9


# The benchmark's dense stacks: Mistral-7B's 16 layers, and Ouro-2.6B's
# 48 layers run 4 times (sandwich norms, exit gate); with each the
# engine's slots, pool pages and max_len.
_STACKS = {
    'mistral': (dict(vocab_size=32768, d_model=4096, n_layers=16,
                     n_heads=32, n_kv_heads=8, d_ff=14336), 16, 2432, 2560),
    'looped': (dict(vocab_size=49152, d_model=2048, n_layers=48,
                    n_heads=16, n_kv_heads=16, d_ff=5632, loop_passes=4,
                    post_norms=True, norm_eps=1e-6), 8, 264, 512),
}


def _engine_shapes(stack, one_chip):
    """(cfg, params, state, paged, private cache) of a benchmark stack
    as shapes on the described chip: the weights in bf16 with the q/k/v
    kernels in the serving form, the engine's state and page pool, one
    prompt's private prefill cache."""
    from skypilot_tpu.models import configs
    keys, slots, pages, max_len = _STACKS[stack]
    cfg = configs.ModelConfig(rope_theta=1e6, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16, remat=False,
                              **keys)
    layers, d, f, hd = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.head_dim

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scale = lambda *lead: {'scale': arg(lead + (d,), jnp.float32)}
    proj = lambda heads: {'kernel': arg((layers, d, heads * hd))}
    layer = {
        'attn_norm': scale(layers), 'mlp_norm': scale(layers),
        'attn': {'q_proj': proj(cfg.n_heads), 'k_proj': proj(cfg.n_kv_heads),
                 'v_proj': proj(cfg.n_kv_heads),
                 'o_proj': {'kernel': arg((layers, cfg.n_heads, hd, d))}},
        'mlp': {'gate_proj': {'kernel': arg((layers, d, f))},
                'up_proj': {'kernel': arg((layers, d, f))},
                'down_proj': {'kernel': arg((layers, f, d))}}}
    params = {'embed': {'embedding': arg((cfg.vocab_size, d))},
              'final_norm': scale(),
              'lm_head': {'kernel': arg((d, cfg.vocab_size))},
              'layers': {'layer': layer}}
    if cfg.post_norms:
        layer.update(attn_post_norm=scale(layers),
                     mlp_post_norm=scale(layers))
    if cfg.loop_passes > 1:
        params['exit_gate'] = {'kernel': arg((d, 1)), 'bias': arg((1,))}
    shapes = lambda make: jax.tree.map(
        lambda a: arg(a.shape, a.dtype), jax.eval_shape(make))
    return (cfg, params,
            shapes(lambda: decode.init_engine_state(slots)),
            shapes(lambda: decode.init_paged_cache(cfg, pages, 16, slots,
                                                   max_len // 16)),
            shapes(lambda: decode.init_cache(cfg, 1, max_len)))


@pytest.fixture()
def kernel_on_tpu(monkeypatch):
    """The paged tick takes the Pallas kernel, as on the chip."""
    from skypilot_tpu.ops import attention
    monkeypatch.delenv('SKYTPU_PALLAS_INTERPRET', raising=False)
    monkeypatch.setattr(attention, '_on_tpu', lambda: True)


def test_looped_tick_keeps_the_pool_in_place(one_chip, kernel_on_tpu):
    """The decode tick of a looped stack at the benchmark's shapes
    (48 layers x 4 passes: a pool of 192 cache layers, 6.6 GB): the
    scan over passes around the layer scan carries the pool as the
    layer scan alone does, so the tick aliases it to its result and
    makes no temporary of a pool's or a private cache's size; the
    kernel is in it, given the whole pool, and writes the tick's new
    rows itself: no scatter of the tick results in a pool leaf (PR 38;
    two of them a cache layer before)."""
    cfg, params, state, paged, _ = _engine_shapes('looped', one_chip)
    compiled = jax.jit(
        decode.bind(decode.paged_engine_step, cfg, kernel='pallas'),
        donate_argnums=(2,)).lower(params, state, paged).compile()
    memory = compiled.memory_analysis()
    pool = 2 * 192 * 264 * 16 * 16 * 128 * 2
    assert memory.alias_size_in_bytes >= pool
    assert memory.temp_size_in_bytes < 0.05e9
    hlo = compiled.as_text()
    assert 'paged_decode_attention' in hlo
    leaf = ','.join(map(str, paged['k'].shape))
    assert not re.findall(rf'= \w+\[{leaf}\]\S* scatter\(', hlo)


@pytest.mark.parametrize('stack,later', [
    ('mistral', False), ('mistral', True), ('looped', False),
    ('looped', True)], ids=['mistral-first', 'mistral-later',
                            'looped-first', 'looped-later'])
def test_fused_step_keeps_pool_and_kernels_in_place(one_chip,
                                                    kernel_on_tpu, stack,
                                                    later):
    """A chunk of 128 rows (padded: so many of them the prompt's)
    riding the tick (`paged_engine_step_with_chunk`), a prompt's first
    and a later one:
    the pool (and a later chunk's private cache) is aliased to the
    result as in the plain tick; the temporaries are no more than the
    standalone chunk's; the paged kernel is in it; and no operation of
    the layer loop results in a layer's whole q, k or v kernel: the one
    product for both groups of rows still reads the stacked kernel in
    place (`_attn_proj`)."""
    cfg, params, state, paged, cache = _engine_shapes(stack, one_chip)
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32, sharding=one_chip)
    max_len = cache['k'].shape[3]
    compiled = jax.jit(
        decode.bind(decode.paged_engine_step_with_chunk, cfg,
                    max_len=max_len, kernel='pallas'),
        donate_argnums=(2, 4)).lower(
            params, state, paged, tokens, cache if later else None,
            jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=one_chip)).compile()
    if later:
        alone = jax.jit(decode.bind(decode.prefill_chunk, cfg),
                        donate_argnums=(2,)).lower(params, tokens, cache)
    else:
        alone = jax.jit(decode.bind(decode.prefill, cfg,
                                    max_len=max_len)).lower(params, tokens)
    nbytes = lambda tree: sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree.leaves(tree))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= nbytes(
        (paged['k'], paged['v'])) + later * nbytes(
            (cache['k'], cache['v']))
    assert memory.temp_size_in_bytes <= (
        alone.compile().memory_analysis().temp_size_in_bytes + 0.05e9)
    hlo = compiled.as_text()
    assert 'paged_decode_attention' in hlo
    kernels = {cfg.d_model * heads * cfg.head_dim
               for heads in (cfg.n_heads, cfg.n_kv_heads)}
    whole = [op for op in _own_operations(hlo)
             if math.prod(op[1]) in kernels]
    assert not whole, whole


@pytest.mark.parametrize('stack,text_sha256', [
    ('mistral',
     'ac76ff74366eff3b79a6553440e0bc9424a81f1f73e33cc0c338540ec94dbf68'),
    ('looped',
     'cc5c2140ad76e215e53a0ae3aec67de3755c31bd826ac7a05e2e98613bb3ed37'),
])
def test_plain_tick_lowers_to_the_text_it_had(one_chip, kernel_on_tpu,
                                              stack, text_sha256):
    """A tick without a chunk is the program it was before a chunk
    could ride it (PR 36) once the kernel wrote the tick's rows (PR 38,
    whose parent's text these replace): its lowered text at the
    benchmark's shapes, less the Mosaic kernel's payload (which holds
    the checkout's path), hashes to what the parent commit's did.  A
    change that means to alter the tick lowers it at the commit before,
    sees that this held, and pins its own text here."""
    import hashlib
    cfg, params, state, paged, _ = _engine_shapes(stack, one_chip)
    text = jax.jit(
        decode.bind(decode.paged_engine_step, cfg, kernel='pallas'),
        donate_argnums=(2,)).lower(params, state, paged).as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    assert hashlib.sha256(text.encode()).hexdigest() == text_sha256


@pytest.mark.parametrize('step', ['tick', 'fused'])
def test_layers_stacked_by_kind_read_weights_in_place(one_chip,
                                                      kernel_on_tpu, step):
    """A model stacked by kind at the benchmark's shapes (LFM2-24B-A2B's
    layers 0-9: two dense conv layers, then two periods of attention
    and three conv layers, 64 experts of 2048 x 1536 a layer; 64 slots,
    6,144 pages, `max_len` 8,704; two heads of 64 side by side in a
    row of 128 lanes, as the engine holds them for the kernel): the
    plain tick and a 256-row chunk riding it keep the pool, the slots'
    conv tails and the pages' snapshots in place (aliased to the
    result), make no temporary of a weight stack's size, no operation
    results in a layer's experts or a period's (each layer's weights
    are one dynamic index of its kind's stack, which the product reads
    in place; a slice of a period read by its four layers was copied out
    first, 4.9 GB of temporaries), and none but the kernel's
    results in the pool's shape: the packed rows are not unpacked or
    moved around it; no bitcast reads an array's padding as data."""
    from skypilot_tpu.models import configs
    cfg = configs.ModelConfig(
        vocab_size=65536, d_model=2048, n_layers=10, n_heads=32,
        n_kv_heads=8, d_ff=1536, dense_layers=('conv', 'conv'),
        dense_d_ff=11776, layer_pattern=('full', 'conv', 'conv', 'conv'),
        conv_kernel=3, qk_norm=True, rope_theta=1e6, tie_embeddings=True,
        n_experts=64, expert_top_k=4, expert_score_fn='sigmoid',
        expert_bias=True, expert_gate_eps=1e-6, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, remat=False)
    slots, pages, max_len = 64, 6144, 8704

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: arg(a.shape),
                          decode.stacked_param_shapes(cfg))
    for proj in ('q_proj', 'k_proj', 'v_proj'):   # the serving form
        leaf = params['layers']['attn']['attn'][proj]['kernel']
        params['layers']['attn']['attn'][proj]['kernel'] = arg(
            leaf.shape[:-2] + (leaf.shape[-2] * leaf.shape[-1],))
    shapes = lambda make: jax.tree.map(
        lambda a: arg(a.shape, a.dtype), jax.eval_shape(make))
    state = shapes(lambda: decode.init_engine_state(slots))
    paged = shapes(lambda: decode.init_paged_cache(
        cfg, pages, 16, slots, max_len // 16, head_lanes=128))
    assert paged['k'].shape == (2, pages, 4, 16, 128)
    if step == 'tick':
        compiled = jax.jit(
            decode.bind(decode.paged_engine_step, cfg, kernel='pallas'),
            donate_argnums=(2,)).lower(params, state, paged).compile()
    else:
        compiled = jax.jit(
            decode.bind(decode.paged_engine_step_with_chunk, cfg,
                        max_len=max_len, kernel='pallas'),
            donate_argnums=(2, 4)).lower(
                params, state, paged, arg((1, 256), jnp.int32), None,
                arg((), jnp.int32)).compile()
    nbytes = lambda tree: sum(math.prod(a.shape) * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= nbytes(
        (paged['k'], paged['v'], paged['conv'], paged['conv_snap']))
    assert memory.temp_size_in_bytes < 0.3e9
    hlo = compiled.as_text()
    assert 'paged_decode_attention' in hlo
    stacks = {(64, 2048, 1536), (64, 1536, 2048), (2048, 11776),
              (11776, 2048), (2048, 6144)}
    copied = [op for op in _own_operations(hlo)
              if tuple(op[1][-3:]) in stacks or tuple(op[1][-2:]) in stacks
              if op[2] not in ('parameter', 'get-tuple-element', 'tuple',
                               'bitcast', 'while', 'slice-done',
                               'custom-call')]
    assert not copied, copied
    pool = [op for op in _own_operations(hlo)
            if tuple(op[1]) == paged['k'].shape
            if op[2] not in ('parameter', 'get-tuple-element', 'tuple',
                             'bitcast', 'while', 'custom-call')]
    assert not pool, pool
    assert not _resizing_bitcasts(hlo)


def test_ssm_decode_kernel_compiles_for_v5e_in_place(one_chip, monkeypatch):
    """The decode-state kernel at the benchmark's shapes (Nemotron-3-
    Nano: 23 mamba layers, 64 slots, 64 heads of a [64, 128] float32
    state, 8 groups): every layer's state in one operand and the layer
    a scalar, the state aliased to the result (3.1 GB written in place)
    and nothing else of its size made around the call."""
    from skypilot_tpu.ops import ssm_decode
    monkeypatch.delenv('SKYTPU_PALLAS_INTERPRET', raising=False)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    m, b, h, p, n, g = 23, 64, 64, 64, 128, 8
    compiled = jax.jit(ssm_decode._decode_call(  # pylint: disable=protected-access
        (m, b, h, p, n), g, False), donate_argnums=(2,)).lower(
            arg((1,), jnp.int32), arg((b,), jnp.int32),
            arg((m, b, h, p, n)), arg((b, p, h)), arg((b, 1, h)),
            arg((b, g, n)), arg((b, g, n))).compile()
    assert 'ssm_decode_step' in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= m * b * h * p * n * 4
    assert memory.temp_size_in_bytes < 1e6
