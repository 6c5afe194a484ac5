"""Paged-KV engine tests: parity with `decode.generate` (greedy +
sampled, native + int8 pages), the pool `kv_pages=None` derives, chunked prefill across page boundaries, prefix
reuse with mid-page divergence, pool exhaustion -> 429 backpressure,
and no page leaks across completion/cancel/TTL.

Engines are module-scoped where possible: every engine instance
re-jits the paged step, so tests share one plain and one int8 engine
(using disjoint token ranges so prefix-cache state cannot couple
them) and only pool-accounting tests build their own small pools."""
from __future__ import annotations

import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models.transformer import Transformer
from skypilot_tpu.serve import batching_engine
from skypilot_tpu.serve import cache_manager


@pytest.fixture(scope='module')
def setup():
    cfg = configs.get_config('tiny')
    model = Transformer(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
    return cfg, params


def _reference(cfg, params, prompt_ids, n, max_len=64):
    prompt = jnp.asarray([prompt_ids], jnp.int32)
    _, new = decode.generate(cfg, params, prompt, max_new_tokens=n,
                             max_len=max_len)
    return [int(t) for t in np.asarray(new)[0]]


def _paged_engine(cfg, params, **kw):
    kw.setdefault('max_len', 64)
    kw.setdefault('slots', 2)
    kw.setdefault('prefill_chunk', 8)
    kw.setdefault('kv_pages', 48)
    kw.setdefault('page_size', 8)
    return batching_engine.ContinuousBatchingEngine(cfg, params, **kw)


@pytest.fixture(scope='module')
def plain_engine(setup):
    cfg, params = setup
    eng = _paged_engine(cfg, params)
    yield eng
    eng.stop()


@pytest.fixture(scope='module')
def int8_engine(setup):
    cfg, params = setup
    eng = _paged_engine(cfg, params, quantize_kv=True)
    yield eng
    eng.stop()


class TestPagedParity:

    def test_greedy_parity_vs_generate(self, setup, plain_engine):
        """Greedy decode through the page pool must match the
        single-sequence reference token-for-token (same masked
        attention over the same values, gathered by page index)."""
        cfg, params = setup
        for prompt, n in (([3, 1, 4, 1, 5, 9, 2, 6], 6),
                          ([7], 4),        # single-token prompt
                          ([2, 7], 8),
                          (list(range(1, 25)), 5)):  # multi-page
            got = plain_engine.generate(prompt, n, timeout=180)
            assert got == _reference(cfg, params, prompt, n), prompt

    def test_greedy_parity_int8_kv(self, setup, int8_engine):
        """int8 pages must still agree with the reference on the
        tiny config's logit margins (the acceptance pin)."""
        cfg, params = setup
        for prompt, n in (([3, 1, 4, 1, 5, 9, 2, 6], 6),
                          ([7], 4),
                          (list(range(1, 25)), 5)):
            got = int8_engine.generate(prompt, n, timeout=180)
            assert got == _reference(cfg, params, prompt, n), prompt

    def test_concurrent_requests_exact(self, setup, plain_engine):
        cfg, params = setup
        prompts = [([3, 1, 4, 1, 5], 5), ([2, 7], 8),
                   ([9, 9, 8, 2, 1, 0, 3], 3)]
        requests = [plain_engine.submit(p, n) for p, n in prompts]
        results = [r.result(timeout=180) for r in requests]
        for (p, n), got in zip(prompts, results):
            assert got == _reference(cfg, params, p, n), (p, n)

    def test_sampled_parity_vs_generate(self, setup, plain_engine):
        """Temperature sampling depends only on (logits, key chain):
        the same seed gives the same stream through the pages, and
        greedy through the sampling path matches generate().  (Row
        parity with decode.generate's sampling is pinned in
        test_batching_engine.)"""
        cfg, params = setup
        sampling = decode.SamplingConfig(temperature=0.8, top_k=10,
                                         seed=123)
        prompt = [3, 1, 4, 1, 5, 9, 2]
        a = plain_engine.generate(prompt, 6, sampling=sampling,
                                  timeout=180)
        b = plain_engine.generate(prompt, 6, sampling=sampling,
                                  timeout=180)
        assert a == b          # seed-deterministic through pages
        assert len(a) == 6
        greedy = plain_engine.generate(
            prompt, 5, timeout=180,
            sampling=decode.SamplingConfig(temperature=0.0))
        assert greedy == _reference(cfg, params, prompt, 5)

    def test_chunked_prefill_across_page_boundaries(self, setup):
        """Chunk width (6) deliberately misaligned with page size (8):
        chunk boundaries land mid-page and page boundaries mid-chunk —
        the scatter/gather must stay exact either way."""
        cfg, params = setup
        eng = _paged_engine(cfg, params, prefill_chunk=6)
        try:
            for prompt in (list(range(1, 21)),   # 19 = 3 chunks + tail
                           [7, 9]):
                got = eng.generate(prompt, 5, timeout=180)
                assert got == _reference(cfg, params, prompt, 5), prompt
            assert eng.stats()['prefill_chunks'] >= 3
        finally:
            eng.stop()

    def test_moe_paged_exact(self):
        """MoE + pages: chunked prefill scatters into pages, a second
        prompt with the same first two pages is served from them (the
        expert layer drops no token, so equal prefixes have equal KV),
        and decode stays exact on the miss and on the hit."""
        cfg = configs.get_config('tiny-moe')
        shared = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]
        prompts = [shared + [2, 3, 8], shared + [4, 6]]
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0),
            jnp.asarray([prompts[0]], jnp.int32))['params'])
        eng = _paged_engine(cfg, params)
        try:
            for prompt, hit_pages in zip(prompts, (0, 2)):
                request = eng.submit(prompt, 5)
                assert request.result(timeout=180) == _reference(
                    cfg, params, prompt, 5)
                assert request.span.prefix_hit_pages == hit_pages
            # The prefix cache counts hits in pages.
            assert eng.stats()['prefix_cache_hits'] == 2
        finally:
            eng.stop()


def _seeded_tick_state(cfg, *, quantize_kv, s_q):
    """A pool full of seeded noise (every page of every layer, the null
    page too), tables of disjoint pages, ragged depths (mid-page, on a
    page boundary, one whose last drafted token falls off its table to
    the null page) and the tokens of one tick."""
    slots, ps, rows = 3, 4, 6
    n_pages = 1 + slots * rows + 3
    rng = np.random.default_rng(11)
    paged = decode.init_paged_cache(cfg, n_pages, ps, slots, rows,
                                    quantize_kv=quantize_kv)

    def noise(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)

    paged = dict(paged, k=jax.tree.map(noise, paged['k']),
                 v=jax.tree.map(noise, paged['v']))
    paged['block_tables'] = jnp.asarray(
        1 + rng.permutation(slots * rows).reshape(slots, rows), jnp.int32)
    paged['lengths'] = jnp.asarray(
        [ps + 1, 2 * ps, rows * ps - s_q + (s_q > 1)], jnp.int32)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (slots, s_q)),
                         jnp.int32)
    return paged, tokens


def _tick_layer_by_layer(cfg, params, tokens, paged):
    """The write-then-attend forward as it ran before the pool rode
    the layer loop: a Python loop over the layers, each layer's share
    sliced out of the pool, the new rows scattered into the slice, the
    slice's pages gathered for attention, and the slices stacked back.
    -> (logits [B, S, V], new k, new v)."""
    from skypilot_tpu.models import heads
    lengths, tables = paged['lengths'], paged['block_tables']
    ps = decode._page_size_of(paged)
    b, s_q = tokens.shape
    positions = lengths[:, None] + jnp.arange(s_q)[None, :]
    rows = positions // ps
    pages = jnp.where(
        rows < tables.shape[1],
        jnp.take_along_axis(tables, jnp.minimum(rows, tables.shape[1] - 1),
                            axis=1), 0).reshape(-1)
    offs = (positions % ps).reshape(-1)

    def write(c, new):
        tok = new.transpose(0, 2, 1, 3).reshape(b * s_q, new.shape[1], -1)
        if isinstance(c, dict):
            q, scale = decode._quant_kv(tok)
            return {'q': c['q'].at[pages, :, offs].set(q),
                    'scale': c['scale'].at[pages, :, offs].set(scale)}
        return c.at[pages, :, offs].set(tok.astype(c.dtype))

    def view(c):
        arr = decode._dequant_kv(jax.tree.map(lambda a: a[tables], c),
                                 cfg.dtype)
        bb, p, h, s, d = arr.shape
        return arr.transpose(0, 2, 1, 3, 4).reshape(bb, h, p * s, d)

    x = decode._embed(cfg, params, tokens)
    new_k, new_v = [], []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], decode._layer_params(params, cfg))
        h = decode._norm(x, lp['attn_norm']['scale'], cfg)
        k = decode._rope_if(
            None, decode._attn_proj(h, lp['attn']['k_proj'],
                                    cfg.n_kv_heads, cfg.head_dim),
            positions, cfg)
        v = decode._attn_proj(h, lp['attn']['v_proj'], cfg.n_kv_heads,
                              cfg.head_dim)
        k_l = write(jax.tree.map(lambda a: a[l], paged['k']), k)
        v_l = write(jax.tree.map(lambda a: a[l], paged['v']), v)
        new_k.append(k_l)
        new_v.append(v_l)
        x, _ = decode._layer_forward(x, lp, cfg, positions, view(k_l),
                                     view(v_l), use_flash=False)
    x = decode._norm(x, params['final_norm']['scale'], cfg)
    stack = lambda leaves: jax.tree.map(lambda *a: jnp.stack(a), *leaves)
    return heads.unembed(x, params, cfg), stack(new_k), stack(new_v)


class TestPoolInPlace:
    """The pool rides the tick's layer loop whole: a layer's rows go
    into that layer of it and nothing else of it moves."""

    @pytest.mark.parametrize('s_q', [1, 4], ids=['tick', 'verify4'])
    @pytest.mark.parametrize('quantize_kv', [False, True],
                             ids=['plain', 'int8'])
    def test_tick_equals_the_tick_layer_by_layer(self, setup, quantize_kv,
                                                 s_q):
        """Logits and the whole new pool equal what slicing each
        layer's share out, writing it and stacking it back gives; and
        against the old pool, bit for bit: layer l's new rows are in
        layer l at (page, offset) of each (slot, token), every other
        element of every layer is untouched."""
        cfg, params = setup
        paged, tokens = _seeded_tick_state(cfg, quantize_kv=quantize_kv,
                                           s_q=s_q)
        want_logits, want_k, want_v = _tick_layer_by_layer(
            cfg, params, tokens, paged)
        logits, new_k, new_v, _, _, _ = jax.jit(
            lambda t, p: decode._paged_forward(
                cfg, params, t, p, all_positions=True))(tokens, paged)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(want_logits), atol=1e-5)
        ps = decode._page_size_of(paged)
        pos = np.asarray(paged['lengths'])[:, None] + np.arange(s_q)
        tables = np.asarray(paged['block_tables'])
        written = {(int(tables[b, p // ps]) if p // ps < tables.shape[1]
                    else 0, int(p % ps))
                   for b in range(pos.shape[0]) for p in pos[b]}
        assert (0, 0) in written or s_q == 1   # a draft fell off a table
        for name, new, want in (('k', new_k, want_k), ('v', new_v, want_v)):
            for got, exp, old in zip(jax.tree.leaves(new),
                                     jax.tree.leaves(want),
                                     jax.tree.leaves(paged[name])):
                got, old = np.asarray(got), np.asarray(old)
                # The new rows to float32 rounding (eager against
                # jitted arithmetic; an int8 value may round the other
                # way), everything else exactly: see `moved`.
                np.testing.assert_allclose(
                    got.astype(np.float32), np.asarray(exp, np.float32),
                    atol=1 if got.dtype == np.int8 else 1e-5)
                moved = np.argwhere(
                    (got != old).reshape(got.shape[:4] + (-1,)).any(-1))
                # [layer, page, head, offset] of every row that moved:
                # all layers, all heads, only the written (page, offset).
                assert {(int(p), int(o)) for _, p, _, o in moved} == written
                assert len(moved) == (cfg.n_layers * cfg.n_kv_heads *
                                      len(written))

    @pytest.mark.parametrize('step', ['paged-gather', 'paged-pallas',
                                      'paged-int8', 'verify'])
    def test_caches_ride_the_layer_loop_as_its_carry(self, setup, step):
        """Structural, on the traced program: no scanned input or
        output of a loop in the tick has a cache's rank-5 shape or a
        layer's share of it (rank 4 or 5 with the leading axis 1); the
        stacked caches are in the layer loop's carry.  What the scan
        slices and stacks, the compiled tick copies."""
        cfg, params = setup
        slots = 3
        state = decode.init_engine_state(slots)
        cache = decode.init_paged_cache(
            cfg, 16, 4, slots, 6, quantize_kv=step == 'paged-int8')
        kernel = 'pallas' if step == 'paged-pallas' else 'gather'
        if step == 'verify':
            fn = lambda s, c: decode.paged_spec_engine_step(
                cfg, params, s, c, jnp.zeros((slots, 3), jnp.int32))
        else:
            fn = lambda s, c: decode.paged_engine_step(
                cfg, params, s, c, kernel=kernel)
        cache_shapes = {a.shape for a in jax.tree.leaves(
            {'k': cache['k'], 'v': cache['v']})}
        shares = {s[1:] for s in cache_shapes} | {
            (1,) + s[1:] for s in cache_shapes}

        def scans(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == 'scan':
                    yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from scans(sub)

        carried = 0
        for eqn in scans(jax.make_jaxpr(fn)(state, cache).jaxpr):
            n_fixed = eqn.params['num_consts'] + eqn.params['num_carry']
            xs = [v.aval.shape for v in eqn.invars[n_fixed:]]
            ys = [v.aval.shape
                  for v in eqn.outvars[eqn.params['num_carry']:]]
            for shape in xs + ys:
                assert shape not in cache_shapes and shape not in shares, (
                    f'a cache is sliced or stacked by a scan: {shape}')
            carry = [v.aval.shape
                     for v in eqn.outvars[:eqn.params['num_carry']]]
            carried += sum(shape in cache_shapes for shape in carry)
        assert carried == 2 * len(jax.tree.leaves(cache['k']))


class TestInt8KVBound:

    def test_int8_logits_divergence_bounded(self, setup):
        """int8 KV vs native KV: the step logits may drift but must
        stay within a small relative error of the dense reference —
        the quantization-noise contract behind the greedy-parity pin."""
        cfg, params = setup
        prompt = jnp.asarray([list(range(1, 17))], jnp.int32)
        ref_logits, _ = decode.prefill(cfg, params, prompt, max_len=32)

        ps, n_pages = 8, 8
        paged = decode.init_paged_cache(cfg, n_pages, ps, 1, 4,
                                        quantize_kv=True)
        _, priv = decode.prefill(cfg, params, prompt, max_len=32)
        pages = jnp.asarray([1, 2, 3, 4], jnp.int32)
        paged = decode.insert_prefill_pages(paged, priv, pages,
                                            first_page=0)
        row = jnp.zeros((4,), jnp.int32).at[:4].set(pages)
        paged = decode.paged_admit_slot(paged, 0, row, 15)
        logits, _, _, _ = decode.paged_batched_step(
            cfg, params, prompt[:, -1:], paged)
        ref = np.asarray(ref_logits)[0]
        got = np.asarray(logits)[0]
        rel = np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-9)
        assert rel < 0.05, rel
        # ...and small enough that greedy agrees here.
        assert int(np.argmax(got)) == int(np.argmax(ref))


class TestPrefixReuse:
    # Each test uses its own token range so shared-engine cache state
    # cannot couple tests.

    def test_identical_prompts_hit_and_stay_exact(self, setup,
                                                  plain_engine):
        cfg, params = setup
        eng = plain_engine
        shared = list(range(40, 80))            # 40 tokens -> 4 pages
        a = eng.generate(shared, 5, timeout=180)
        hits0 = eng.stats()['prefix_cache_hits']
        handle = eng.submit(shared, 5)
        b = handle.result(timeout=180)
        assert a == b == _reference(cfg, params, shared, 5)
        stats = eng.stats()
        assert stats['prefix_cache_hits'] == hits0 + 4
        assert stats['prefix_cache_entries'] >= 4
        # The hit is visible on the request's span.
        span = eng.span(handle.request_id)
        assert span['prefix_hit_pages'] == 4
        assert span['prefill_chunks'] <= 2       # seed + tail only

    def test_mid_page_divergence_correct(self, setup, int8_engine):
        """Two sessions share a prefix that ends MID-page: the shared
        full pages reuse, the divergence page is private per session,
        and both decode exactly (int8 pages — the quantized gather
        must honor the same sharing rules)."""
        cfg, params = setup
        eng = int8_engine
        base = list(range(100, 140))            # 40 tokens, ps=8
        s1 = base[:37] + [5, 6, 7]              # diverge at pos 37
        s2 = base[:37] + [8, 9, 1]              # (mid page 5)
        a = eng.generate(s1, 5, timeout=180)
        hits0 = eng.stats()['prefix_cache_hits']
        b = eng.generate(s2, 5, timeout=180)
        assert a == _reference(cfg, params, s1, 5)
        assert b == _reference(cfg, params, s2, 5)
        # s2 shared s1's 4 full pages, not the divergence page.
        assert eng.stats()['prefix_cache_hits'] >= hits0 + 4

    def test_full_hit_skips_prefill_entirely(self, setup,
                                             plain_engine):
        """A page-aligned fully-cached prefix admits with ZERO prefill
        chunks — the TTFT-collapse mechanism."""
        cfg, params = setup
        eng = plain_engine
        prompt = list(range(150, 183))          # n-1 = 32 = 4 pages
        eng.generate(prompt, 4, timeout=180)
        chunks0 = eng.stats()['prefill_chunks']
        handle = eng.submit(prompt, 4)
        got = handle.result(timeout=180)
        assert got == _reference(cfg, params, prompt, 4)
        assert eng.stats()['prefill_chunks'] == chunks0
        assert eng.span(handle.request_id)['prefix_hit_pages'] == 4

    @pytest.mark.parametrize('quantize_kv', [False, True],
                             ids=['float', 'int8'])
    def test_hits_ride_a_live_slots_ticks(self, setup, quantize_kv):
        """Beside a decoding slot, a document is asked about twice: the
        first prompt prefills in four chunks, the second seeds its
        private cache from the cached pages (an iteration of its own,
        with a plain tick) and runs its tail as one chunk.  Every
        chunk rides the live slot's tick, and every answer is
        `decode.generate`'s (int8 pages: the first's only, its keys
        were never read back from the pool)."""
        cfg, params = setup
        eng = _paged_engine(cfg, params, slots=3,
                            quantize_kv=quantize_kv)
        try:
            document = list(range(200, 224))        # 24 tokens: 3 pages
            first, second = document + [5, 6, 7], document + [8, 9, 1, 2]
            running = eng.submit([2, 7, 1, 8], 50)
            a = eng.submit(first, 4)
            got_a = a.result(timeout=240)
            b = eng.submit(second, 4)   # at once: 50 ticks pass quickly
            got = b.result(timeout=240)
            assert got_a == _reference(cfg, params, first, 4)
            if not quantize_kv:
                assert got == _reference(cfg, params, second, 4)
                assert running.result(timeout=240) == _reference(
                    cfg, params, [2, 7, 1, 8], 50)
            assert b.span.prefix_hit_pages == 3
            stats = eng.stats()
            # 26 tokens in chunks of 8: four; the hit's tail of 3: one.
            assert stats['prefill_chunks'] == 1 + 4 + 1
            assert stats['prefill_chunks_fused'] == 4 + 1
        finally:
            eng.stop()

    def test_hit_tail_shorter_than_chunk(self, setup):
        """Regression: a prefix hit seeds the private cache near the
        end of the prompt, so the remaining tail can be far shorter
        than prefill_chunk — with the default chunk (512) wider than
        max_len (128) the continuation piece must be narrowed to fit
        the cache instead of clamping over the seeded prefix."""
        cfg, params = setup
        eng = _paged_engine(cfg, params, max_len=128,
                            prefill_chunk=512, slots=2)
        try:
            shared = list(range(30, 90))        # 60 tokens, ps=8
            a = eng.generate(shared, 5, timeout=180)
            b = eng.generate(shared, 5, timeout=180)  # hit: tail of 3
            assert a == b == _reference(cfg, params, shared, 5,
                                        max_len=128)
        finally:
            eng.stop()

    def test_prefix_cache_disabled(self, setup):
        cfg, params = setup
        eng = _paged_engine(cfg, params, prefix_caching=False,
                            slots=1)
        try:
            shared = list(range(40, 60))
            a = eng.generate(shared, 4, timeout=180)
            b = eng.generate(shared, 4, timeout=180)
            assert a == b == _reference(cfg, params, shared, 4)
            stats = eng.stats()
            assert stats['prefix_cache_hits'] == 0
            assert stats['prefix_cache_entries'] == 0
        finally:
            eng.stop()


class TestPoolAccounting:

    def test_pages_freed_on_completion_cancel_and_ttl(self, setup):
        cfg, params = setup
        eng = _paged_engine(cfg, params, slots=1, queue_ttl=0.05,
                            prefix_caching=False)
        try:
            done = eng.submit(list(range(1, 20)), 20)
            stale = eng.submit([4, 5], 4)        # expires queued (TTL)
            with pytest.raises(batching_engine.QueueExpired):
                stale.result(timeout=60)
            # Cancel the long request mid-decode.
            stream = done.stream(timeout=60)
            next(stream)
            done.cancel()
            assert done.done.wait(30)
            deadline = time.time() + 30
            while (eng.stats()['kv_pages_used'] > 0 and
                   time.time() < deadline):
                time.sleep(0.01)
            assert eng.stats()['kv_pages_used'] == 0
            # The pool is fully reusable afterwards.
            got = eng.generate([4, 5], 3, timeout=60)
            assert got == _reference(cfg, params, [4, 5], 3)
        finally:
            eng.stop()
        assert eng._kv.pool.used_count == 0  # pylint: disable=protected-access

    def test_cancel_mid_prefill_frees_pages(self, setup):
        cfg, params = setup
        eng = _paged_engine(cfg, params, slots=1, prefill_chunk=4,
                            prefix_caching=False)
        try:
            blocker = eng.submit(list(range(1, 25)), 6)
            victim = eng.submit(list(range(1, 20)), 6)
            victim.cancel()
            assert blocker.result(timeout=180) == _reference(
                cfg, params, list(range(1, 25)), 6)
            assert victim.done.wait(60)
            deadline = time.time() + 30
            while (eng.stats()['kv_pages_used'] > 0 and
                   time.time() < deadline):
                time.sleep(0.01)
            assert eng.stats()['kv_pages_used'] == 0
        finally:
            eng.stop()

    def test_exhaustion_backpressures_with_429_class(self, setup):
        """Pool too small for two concurrent requests: the second
        stays queued (not crashed), and a third submit gets QueueFull
        (the HTTP 429 mapping) with Retry-After while the pool is
        exhausted.  Also covers submit-time rejection of requests that
        could NEVER fit."""
        cfg, params = setup
        eng = _paged_engine(cfg, params, kv_pages=6, page_size=8,
                            slots=2, prefix_caching=False)
        try:
            with pytest.raises(ValueError, match='pool capacity'):
                eng.submit(list(range(1, 40)), 20)   # needs 8 of 5
            # 4 pages: 25 prompt + 7 new -> ceil(31/8) = 4 of 5 usable.
            blocker = eng.submit(list(range(1, 26)), 7)
            deadline = time.time() + 30
            while (eng.stats()['kv_pages_used'] < 4 and
                   time.time() < deadline):
                time.sleep(0.005)
            queued = eng.submit(list(range(1, 20)), 8)   # needs 4
            # The worker must DEFER the queued request (pool can't
            # cover it while the blocker holds pages) — poll rather
            # than sleep: first-time compiles can stall the loop.
            deadline = time.time() + 60
            while (eng.stats()['pages_exhausted_deferrals'] < 1 and
                   not queued.done.is_set() and
                   time.time() < deadline):
                time.sleep(0.005)
            if not queued.done.is_set():
                assert eng.stats()['pages_exhausted_deferrals'] >= 1
                with pytest.raises(batching_engine.QueueFull) as err:
                    eng.submit(list(range(1, 20)), 8)
                assert err.value.retry_after >= 1.0
            assert eng.stats()['failed'] is False
            # The blocker finishing frees pages; the queued request
            # must then complete on its own.
            assert blocker.result(timeout=120) == _reference(
                cfg, params, list(range(1, 26)), 7)
            assert queued.result(timeout=120) == _reference(
                cfg, params, list(range(1, 20)), 8)
        finally:
            eng.stop()

    def test_backpressure_counts_the_cached_prefix(self, setup):
        """Submit-time backpressure asks for a request's pages less
        those its prompt already has in the prefix cache: a question
        on a long cached document is not refused because the
        document's pages would not fit a second time."""
        cfg, params = setup
        eng = _paged_engine(cfg, params, kv_pages=10, page_size=8,
                            slots=2)
        try:
            kv = eng._kv
            doc = list(range(1, 34))            # 32 prefilled: 4 pages
            plan = kv.plan_admission(doc, 30)   # 62 positions: 8 pages
            kv.commit(0, plan)
            kv.register_prefix(plan)
            assert kv.pool.free_count == 1
            same_doc = doc[:32] + [77, 78]
            need = kv.pages_needed(len(same_doc), 4)
            assert need == 5 and not kv.can_admit(need)
            assert eng._pool_has_room(same_doc, need)
            other = list(range(100, 134))
            assert not eng._pool_has_room(other, need)
            kv.release(0)
        finally:
            eng.stop()

    def test_prefills_in_flight_bounded_by_the_cache(self, setup):
        """Each prompt mid-prefill holds a private cache of max_len;
        together they may hold the bytes of the engine's own cache, a
        bound the engine works out from its shapes.  Requests past it
        wait in the queue and are served all the same."""
        cfg, params = setup
        # 16 pages of 8 are two max_len of 64, under 4 slots.
        eng = _paged_engine(cfg, params, kv_pages=16, slots=4)
        try:
            assert eng._max_prefills == 2
            prompts = [list(range(k, k + 20)) for k in (1, 31, 61, 91)]
            handles = [eng.submit(p, 4) for p in prompts]
            for p, h in zip(prompts, handles):
                assert h.result(timeout=180) == _reference(cfg, params,
                                                           p, 4)
        finally:
            eng.stop()
        # The derived pool (slots x max_len): every slot may prefill.
        derived = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=3)
        try:
            assert derived._max_prefills == 3
        finally:
            derived.stop()

    @pytest.mark.parametrize('kv_pages', [16, None])
    def test_validation(self, setup, kv_pages):
        """Private prefill caches scatter whole pages: `max_len` is a
        multiple of `page_size`, whether the pool is given or derived."""
        cfg, params = setup
        with pytest.raises(ValueError, match='multiple'):
            batching_engine.ContinuousBatchingEngine(
                cfg, params, max_len=60, kv_pages=kv_pages, page_size=8)


class TestDerivedPool:
    """`kv_pages=None` is a pool size worked out from the geometry,
    not another cache: what every slot needs to hold `max_len` at
    once, and the reserved null page."""

    @pytest.mark.parametrize('slots,max_len,page_size,pages', [
        (1, 32, 16, 2), (3, 64, 8, 24), (4, 64, 64, 4)])
    def test_pool_size_from_the_geometry(self, setup, slots, max_len,
                                         page_size, pages):
        cfg, params = setup
        assert cache_manager.PagedKVManager.pool_pages(
            None, slots, max_len, page_size) == pages + 1
        assert cache_manager.PagedKVManager.pool_pages(
            7, slots, max_len, page_size) == 7
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=max_len, slots=slots,
            page_size=page_size)
        try:
            stats = eng.stats()
            assert stats['kv_pages_total'] == pages
            assert stats['page_size'] == page_size
            assert eng._cache['k'].shape[1] == pages + 1
            assert eng._cache['block_tables'].shape == (
                slots, max_len // page_size)
            assert eng._max_prefills == slots
        finally:
            eng.stop()

    def test_every_slot_admits_max_len_at_once(self, setup):
        """What the slot cache guaranteed: requests of `max_len` on
        every slot at the same time, none refused for pages, and the
        pool drained when they are done."""
        cfg, params = setup
        slots, max_len = 3, 64
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=max_len, slots=slots, page_size=8,
            prefill_chunk=8, prefix_caching=False)
        try:
            prompts = [list(range(k, k + 12)) for k in (1, 41, 81)]
            handles = [eng.submit(p, max_len - len(p)) for p in prompts]
            deadline = time.time() + 120
            stats = eng.stats()
            while stats['busy_slots'] < slots and time.time() < deadline:
                time.sleep(0.01)
                stats = eng.stats()
            assert stats['busy_slots'] == slots
            assert stats['kv_pages_used'] == stats['kv_pages_total']
            for p, h in zip(prompts, handles):
                assert h.result(timeout=180) == _reference(
                    cfg, params, p, max_len - len(p), max_len=max_len)
            stats = eng.stats()
            assert stats['pages_exhausted_deferrals'] == 0
            assert stats['kv_pages_used'] == 0
        finally:
            eng.stop()


class TestStatsAndMetrics:

    def test_paged_stats_and_gauges(self, setup, plain_engine):
        from skypilot_tpu.observability import metrics as metrics_lib
        stats = plain_engine.stats()
        assert stats['kv_pages_total'] == 47
        assert stats['page_size'] == 8
        assert stats['prefix_cache_misses'] >= 0
        text = metrics_lib.expose()
        for name in ('skytpu_engine_kv_pages_total',
                     'skytpu_engine_kv_pages_used',
                     'skytpu_engine_kv_pages_pinned',
                     'skytpu_engine_prefix_cache_hits_total',
                     'skytpu_engine_prefix_cache_misses_total'):
            assert name in text, name
        parsed = metrics_lib.parse_exposition(text)
        assert sum(parsed['skytpu_engine_kv_pages_total']
                   .values()) == 47

    def test_reformed_bytes_in_stats(self, setup, plain_engine):
        """`stats()['weights']['reformed_bytes']`: the q/k/v kernels
        the engine holds in the form the product reads; 0 would say the
        layer scan copies each layer's kernels out of the stack."""
        cfg, _ = setup
        per_layer = cfg.d_model * cfg.head_dim * (
            cfg.n_heads + 2 * cfg.n_kv_heads) * 4          # float32
        assert plain_engine.stats()['weights'] == {
            'reformed_bytes': cfg.n_layers * per_layer}


class TestTensorShardedWeights:

    def test_reformed_kernels_keep_their_placement(self):
        """A `--tensor 2` server on the CPU devices: heads over
        'tensor' become the flat axis over 'tensor', each device
        holding the bytes it held, and the engine serves what the
        unsharded server does."""
        from skypilot_tpu.serve import model_server
        single = model_server.ModelServer('tiny', max_len=32, max_batch=1)
        sharded = model_server.ModelServer(
            'tiny', max_len=32, max_batch=2, tensor=2,
            continuous_batching=True)
        try:
            mine = sharded.params['layers']['layer']['attn']
            held = sharded._engine.params['layers']['layer']['attn']  # pylint: disable=protected-access
            for name in ('q_proj', 'k_proj', 'v_proj'):
                before, after = mine[name]['kernel'], held[name]['kernel']
                assert before.ndim == 4 and after.ndim == 3
                assert tuple(after.sharding.spec) == tuple(
                    before.sharding.spec)[:3]
                assert after.sharding.spec[2] == 'tensor'
                for a, b in zip(before.addressable_shards,
                                after.addressable_shards):
                    assert a.device == b.device
                    np.testing.assert_array_equal(
                        np.asarray(a.data).reshape(b.data.shape),
                        np.asarray(b.data))
            assert held['o_proj']['kernel'] is mine['o_proj']['kernel']
            prompt = [[7, 2, 9]]
            assert sharded.generate(prompt, 4) == single.generate(prompt,
                                                                  4)
        finally:
            sharded.close()


class TestFacadeCompat:

    def test_legacy_names_still_importable(self):
        """The batching_engine facade keeps the pre-split import
        surface (ROADMAP satellite: existing imports keep working)."""
        from skypilot_tpu.serve import sampler
        from skypilot_tpu.serve import scheduler
        assert batching_engine.QueueFull is scheduler.QueueFull
        assert batching_engine.QueueExpired is scheduler.QueueExpired
        assert batching_engine._Request is scheduler.Request  # pylint: disable=protected-access
        assert batching_engine._Slot is scheduler.Slot  # pylint: disable=protected-access
        assert batching_engine._PendingPrefill is scheduler.PendingPrefill  # pylint: disable=protected-access
        assert batching_engine.PagesExhausted is (
            cache_manager.PagesExhausted)
        assert sampler.validate_sampling(None,
                                         max_top_k=4) == (0.0, 0, 0)
