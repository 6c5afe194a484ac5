"""Continuous batching engine tests: exactness vs single-sequence
decode, mid-flight admission, slot reuse, stop tokens."""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import configs
from skypilot_tpu.models import decode
from skypilot_tpu.models.transformer import Transformer
from skypilot_tpu.serve import batching_engine


@pytest.fixture(scope='module')
def setup():
    cfg = configs.get_config('tiny')
    model = Transformer(cfg)
    seed_tokens = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), seed_tokens)['params'])
    return cfg, params


def _reference(cfg, params, prompt_ids, n):
    prompt = jnp.asarray([prompt_ids], jnp.int32)
    _, new = decode.generate(cfg, params, prompt, max_new_tokens=n,
                             max_len=64)
    return [int(t) for t in np.asarray(new)[0]]


@pytest.fixture()
def engine(setup):
    cfg, params = setup
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2)
    yield eng
    eng.stop()


class TestEngine:

    def test_single_request_matches_decode(self, setup, engine):
        cfg, params = setup
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        got = engine.generate(prompt, max_new_tokens=6, timeout=120)
        assert got == _reference(cfg, params, prompt, 6)

    def test_single_token_prompt(self, setup, engine):
        cfg, params = setup
        got = engine.generate([7], max_new_tokens=4, timeout=120)
        assert got == _reference(cfg, params, [7], 4)

    def test_concurrent_requests_exact(self, setup, engine):
        """Different lengths and generation budgets decoded together:
        each must match its own single-sequence reference exactly."""
        cfg, params = setup
        prompts = [([3, 1, 4, 1, 5], 5), ([2, 7], 8),
                   ([9, 9, 8, 2, 1, 0, 3], 3)]
        requests = [engine.submit(p, n) for p, n in prompts]
        results = [r.result(timeout=180) for r in requests]
        for (p, n), got in zip(prompts, results):
            assert got == _reference(cfg, params, p, n), (p, n)

    def test_more_requests_than_slots_reuses(self, setup, engine):
        """5 requests through 2 slots: admission happens as slots free
        (continuous), and every result is still exact."""
        cfg, params = setup
        prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
        requests = [engine.submit(p, 4) for p in prompts]
        for p, r in zip(prompts, requests):
            assert r.result(timeout=240) == _reference(cfg, params, p, 4)

    def test_stop_token(self, setup, engine):
        cfg, params = setup
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        ref = _reference(cfg, params, prompt, 8)
        stop = ref[2]
        got = engine.generate(prompt, max_new_tokens=8, stop_token=stop,
                              timeout=120)
        assert got == ref[:3]  # stops AT the stop token (inclusive)

    def test_stop_token_set(self, setup, engine):
        """A multi-EOS stop set (tokenizer.eos_ids): generation ends at
        the FIRST member produced — instruct checkpoints stop at chat
        turn-end markers, not just the model-level EOS."""
        cfg, params = setup
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        ref = _reference(cfg, params, prompt, 8)
        # Decoy id that never appears + the real 3rd generated token.
        stops = frozenset({ref[2], max(ref) + 1})
        got = engine.generate(prompt, max_new_tokens=8,
                              stop_token=stops, timeout=120)
        assert got == ref[:3]

    def test_validation(self, engine):
        with pytest.raises(ValueError, match='empty'):
            engine.submit([], 4)
        with pytest.raises(ValueError, match='exceeds'):
            engine.submit([1, 2, 3], 100)


class TestEngineRobustness:

    def test_moe_config_exact(self, setup):
        """MoE prompts take the chunked, padded path of every other
        model and stay exact: the expert layer drops no token, so pad
        tokens and chunk boundaries move no real token's result."""
        cfg = configs.get_config('tiny-moe')
        model = Transformer(cfg)
        prompt = [3, 1, 4, 1, 5, 9, 2]
        params = nn.meta.unbox(model.init(
            jax.random.PRNGKey(0),
            jnp.asarray([prompt], jnp.int32))['params'])
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2, prefill_chunk=4)
        try:
            # 6 prefilled tokens: a chunk of 4, then 2 padded to 4.
            got = eng.generate(prompt, max_new_tokens=5, timeout=180)
            assert got == _reference(cfg, params, prompt, 5)
            assert eng.stats()['prefill_chunks'] == 2
            assert eng.stats()['moe']['tokens'] == 5 * cfg.n_layers
        finally:
            eng.stop()

    def test_submit_after_stop_rejected(self, setup):
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=32, slots=1)
        eng.stop()
        with pytest.raises(RuntimeError, match='stopped'):
            eng.submit([1, 2], 2)

    def test_zero_max_new_tokens_rejected(self, engine):
        with pytest.raises(ValueError, match='>= 1'):
            engine.submit([1, 2], 0)

    def test_tick_failure_fails_fast_and_rejects(self, setup,
                                                 monkeypatch):
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=32, slots=1)
        try:
            def boom(*a, **k):
                raise RuntimeError('chip fell over')
            monkeypatch.setattr(eng, '_step', boom)
            request = eng.submit([1, 2, 3], 4)
            with pytest.raises(RuntimeError, match='failed'):
                request.result(timeout=30)
            with pytest.raises(RuntimeError, match='failed'):
                eng.submit([1, 2], 2)
        finally:
            eng.stop()


def test_cancel_frees_slot(setup):
    cfg, params = setup
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=64, slots=1)
    try:
        request = eng.submit([1, 2, 3], 50)
        # Take a couple of tokens then hang up.
        stream = request.stream(timeout=60)
        next(stream)
        request.cancel()
        assert request.done.wait(30)
        # The slot must be free for the next request promptly.
        got = eng.generate([4, 5], 3, timeout=60)
        assert len(got) == 3
        assert len(request.tokens) < 50
    finally:
        eng.stop()


def test_temperature_sweep_no_recompile_storm(setup):
    """Distinct temperatures must reuse one compiled executable
    (temperature is traced, not a static jit key)."""
    cfg, params = setup
    import time as _time
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    sampling0 = decode.SamplingConfig(temperature=0.7)
    t0 = _time.time()
    decode.generate(cfg, params, prompt, max_new_tokens=3, max_len=16,
                    sampling=sampling0)
    first = _time.time() - t0
    t0 = _time.time()
    for i in range(5):
        decode.generate(cfg, params, prompt, max_new_tokens=3,
                        max_len=16,
                        sampling=decode.SamplingConfig(
                            temperature=0.5 + i * 0.01))
    per = (_time.time() - t0) / 5
    assert per < first / 2, (first, per)  # cached, not recompiled


def test_stats(setup):
    cfg, params = setup
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=32, slots=2)
    try:
        stats = eng.stats()
        # The autoscaling contract: these keys feed /health.
        assert stats['slots'] == 2
        assert stats['busy_slots'] == 0
        assert stats['queued_requests'] == 0
        assert stats['tokens_generated'] == 0
        assert stats['failed'] is False
        assert stats['ticks'] == 0
        assert stats['prefill_chunks'] == 0
        assert stats['decode_tokens_per_s'] == 0
        assert sum(stats['queue_wait_hist'].values()) == 0
        eng.generate([1, 2, 3], 4, timeout=120)
        stats = eng.stats()
        assert stats['tokens_generated'] == 4
        assert stats['busy_slots'] == 0
        assert stats['ticks'] > 0
        assert stats['prefill_chunks'] >= 1
        assert stats['decode_tokens_per_s'] > 0
        # Exactly one admission went through the queue-wait histogram.
        assert sum(stats['queue_wait_hist'].values()) == 1
    finally:
        eng.stop()


def test_failed_engine_fails_health_probe(setup, monkeypatch):
    """A dead engine must flip /health to 503 so the replica stops
    being READY (the LB would otherwise black-hole traffic)."""
    import requests as _requests
    from skypilot_tpu.serve import model_server
    server = model_server.ModelServer('tiny', max_len=32, max_batch=1,
                                      continuous_batching=True)
    port, shutdown = model_server.start_background(server)
    try:
        assert _requests.get(f'http://127.0.0.1:{port}/health',
                             timeout=30).status_code == 200

        def boom(*a, **k):
            raise RuntimeError('chip fell over')
        monkeypatch.setattr(server._engine, '_step', boom)
        req = server._engine.submit([1, 2, 3], 4)
        assert req.done.wait(30)
        resp = _requests.get(f'http://127.0.0.1:{port}/health',
                             timeout=30)
        assert resp.status_code == 503
        assert resp.json()['status'] == 'engine_failed'
    finally:
        shutdown()
        server.close()


class TestChunkedPrefill:

    def test_chunked_prefill_exact(self, setup):
        """A long prompt prefilled in 4-token chunks must decode
        token-exact vs decode.generate (the n-1/last-token trick holds
        per chunk; the padded final chunk's garbage positions are
        masked then overwritten)."""
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2, prefill_chunk=4)
        try:
            for prompt in (list(range(1, 21)),   # 19 = 4*4 + 3 partial
                           list(range(5, 22)),   # 16 = exact chunks
                           [7, 9],               # below one chunk
                           [3]):                 # no prefill at all
                got = eng.generate(prompt, 5, timeout=180)
                assert got == _reference(cfg, params, prompt, 5), prompt
            assert eng.stats()['prefill_chunks'] > 4
        finally:
            eng.stop()

    def test_chunked_admission_does_not_corrupt_running(self, setup):
        """A long admission interleaves with a running decode; the
        running request's tokens must stay exact end to end."""
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2, prefill_chunk=4)
        try:
            running = eng.submit([2, 7, 1, 8], 12)
            long_prompt = list(range(1, 25))
            late = eng.submit(long_prompt, 4)
            assert running.result(timeout=180) == _reference(
                cfg, params, [2, 7, 1, 8], 12)
            assert late.result(timeout=180) == _reference(
                cfg, params, long_prompt, 4)
        finally:
            eng.stop()

    def test_chunk_not_dividing_max_len_stays_exact(self, setup):
        """Regression: a continuation chunk whose width would run past
        max_len (chunk 48 from index 48 in a 64-length cache) must be
        narrowed, not clamped backwards by dynamic_update_slice over
        already-prefilled positions."""
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=1, prefill_chunk=48)
        try:
            prompt = list(range(1, 61))       # 59 to prefill: 48 + 11
            got = eng.generate(prompt, 3, timeout=180)
            assert got == _reference(cfg, params, prompt, 3)
        finally:
            eng.stop()

    def test_cancel_mid_prefill_frees_slot(self, setup):
        """Cancelling a request whose prompt is still chunking must
        abandon the remaining chunks and free the slot."""
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=1, prefill_chunk=2)
        try:
            blocker = eng.submit(list(range(1, 31)), 8)
            victim = eng.submit(list(range(1, 25)), 8)
            victim.cancel()
            assert blocker.result(timeout=180) == _reference(
                cfg, params, list(range(1, 31)), 8)
            assert victim.done.wait(60)
            assert victim.error is None
            # Slot is reusable afterwards.
            assert eng.generate([4, 5], 3, timeout=120) == _reference(
                cfg, params, [4, 5], 3)
        finally:
            eng.stop()


class TestChunkRidesTheTick:
    """An iteration with a prefill chunk to run dispatches one program,
    the live slots' tick and the chunk together
    (`decode.paged_engine_step_with_chunk`)."""

    @pytest.mark.parametrize('model', ['tiny', 'tiny-moe'])
    def test_arrivals_beside_a_live_slot_stay_exact(self, model):
        """With a slot decoding, prompts of one, two and four chunks
        arrive; every request's greedy tokens are `decode.generate`'s,
        and every chunk but the first request's (which met an empty
        engine) shared its weight read with a live slot's tick."""
        cfg = configs.get_config(model)
        params = nn.meta.unbox(Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=4, prefill_chunk=4)
        prompts = [([2, 7, 1, 8], 52),            # live throughout
                   (list(range(3, 8)), 3),        # 4 to prefill: 1 chunk
                   (list(range(11, 20)), 4),      # 8: 2 chunks
                   (list(range(20, 36)), 5)]      # 15: 4, the last padded
        try:
            handles = [eng.submit(p, n) for p, n in prompts]
            for (p, n), handle in zip(prompts, handles):
                assert handle.result(timeout=240) == _reference(
                    cfg, params, p, n), p
            stats = eng.stats()
            assert stats['prefill_chunks'] == 8
            assert stats['prefill_chunks_fused'] == 7
            assert stats['ticks'] >= 52
            if cfg.n_experts:
                # The ticks' counts hold the live slots' tokens and
                # nothing of the chunks that rode them.
                assert stats['moe']['tokens'] == (
                    sum(n for _, n in prompts) * cfg.n_layers)
            # The chunk's phase holds the tick's dispatch: one
            # `decode-step` in such an iteration, inside
            # `prefill-chunk`, counting the slots that rode.
            fused = [rec['phases'] for rec in eng.profile()['ring']
                     if any(p[0] == 'prefill-chunk' for p in rec['phases'])]
            assert len(fused) == 8
            for phases in fused[1:]:
                steps = [p for p in phases if p[0] == 'decode-step']
                assert len(steps) == 1 and steps[0][3] >= 1
            # The first met frozen slots only: its own tick follows it.
            assert [p[3] for p in fused[0] if p[0] == 'decode-step'] == [
                0, 1]
        finally:
            eng.stop()

    @pytest.mark.parametrize('how', ['cancel', 'deadline'])
    def test_dropped_mid_prefill_beside_a_live_slot(self, setup, how):
        """A cancel or a passed deadline between two chunks that ride
        a live slot's ticks frees the slot; the live request's tokens
        stay exact."""
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2, prefill_chunk=2)
        try:
            running = eng.submit([2, 7, 1, 8], 40)
            victim = eng.submit(list(range(1, 50)), 8, deadline_ms=6e5)
            while eng.stats()['prefill_chunks_fused'] < 2:
                assert not victim.done.is_set()
                victim.done.wait(0.002)
            if how == 'cancel':
                victim.cancel()
            else:
                victim.deadline = victim.submit_time   # it has passed
            assert victim.done.wait(120)
            if how == 'cancel':
                assert victim.error is None
            else:
                assert isinstance(victim.error,
                                  batching_engine.DeadlineExceeded)
                assert 'mid-prefill' in str(victim.error)
            assert running.result(timeout=180) == _reference(
                cfg, params, [2, 7, 1, 8], 40)
            stats = eng.stats()
            assert 2 <= stats['prefill_chunks_fused'] < 24
            assert stats['busy_slots'] == 0
            assert stats['kv_pages_used'] == stats['kv_pages_pinned']
            # The slot is reusable afterwards.
            assert eng.generate([4, 5], 3, timeout=120) == _reference(
                cfg, params, [4, 5], 3)
        finally:
            eng.stop()

    def test_short_pieces_share_one_width(self, setup):
        """Every width of the fused step is a program of the tick's
        size, so pieces under `_FUSED_MIN_WIDTH` rows are padded to it
        (where `prefill_chunk` allows): prompts of 20, 70 and 200
        tokens take one program between them; the speculative engine's
        standalone chunks keep their buckets."""
        cfg, params = setup
        widths = {}
        for spec_tokens in (0, 2):
            eng = batching_engine.ContinuousBatchingEngine(
                cfg, params, max_len=256, slots=2, prefill_chunk=256,
                prefix_caching=False, spec_tokens=spec_tokens)
            try:
                for n in (20, 70, 200):
                    prompt = [1 + i % 250 for i in range(n)]
                    assert eng.generate(prompt, 3, timeout=240) == [
                        int(t) for t in np.asarray(decode.generate(
                            cfg, params, jnp.asarray([prompt], jnp.int32),
                            max_new_tokens=3, max_len=256)[1])[0]], n
                widths[spec_tokens] = [
                    p[3] for rec in eng.profile()['ring']
                    for p in rec['phases'] if p[0] == 'prefill-chunk']
                compiles = eng.profile()['recompiles']['fns']
                if not spec_tokens:
                    assert compiles['chunk_step']['compiles'] == 1
            finally:
                eng.stop()
        assert widths == {0: [256, 256, 256], 2: [32, 128, 256]}

    def test_speculative_engine_runs_no_fused_step(self, setup):
        """The speculative engine's verify ticks are synchronous: its
        chunks stay programs of their own between them."""
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2, prefill_chunk=4,
            spec_tokens=2)
        try:
            running = eng.submit([2, 7, 1, 8], 30)
            late = eng.submit(list(range(1, 20)), 4)
            assert running.result(timeout=180) == _reference(
                cfg, params, [2, 7, 1, 8], 30)
            assert late.result(timeout=180) == _reference(
                cfg, params, list(range(1, 20)), 4)
            stats = eng.stats()
            assert stats['prefill_chunks'] == 6
            assert stats['prefill_chunks_fused'] == 0
            assert 'chunk_step' not in eng.profile()['recompiles']['fns']
        finally:
            eng.stop()


class TestSampling:

    def test_sampled_deterministic_per_seed(self, setup):
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2)
        try:
            sampling = decode.SamplingConfig(temperature=0.8, top_k=10,
                                             seed=123)
            a = eng.generate([3, 1, 4], 6, sampling=sampling,
                             timeout=120)
            b = eng.generate([3, 1, 4], 6, sampling=sampling,
                             timeout=120)
            assert a == b
            c = eng.generate(
                [3, 1, 4], 6, timeout=120,
                sampling=decode.SamplingConfig(temperature=0.8,
                                               top_k=10, seed=7))
            assert len(c) == 6  # a different seed may (and does) differ
        finally:
            eng.stop()

    def test_sampled_independent_of_other_traffic(self, setup):
        """A request's sample stream depends only on its seed (the
        slot's key chain splits once per generated token), so the same
        seeded request returns the same tokens with or without
        neighbours decoding."""
        cfg, params = setup
        sampling = decode.SamplingConfig(temperature=0.9, seed=42)
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2)
        try:
            alone = eng.generate([5, 3, 2], 6, sampling=sampling,
                                 timeout=120)
            noisy = eng.submit([9, 9, 1, 2, 3], 10)
            crowded = eng.generate([5, 3, 2], 6, sampling=sampling,
                                   timeout=120)
            noisy.result(timeout=120)
            assert alone == crowded
        finally:
            eng.stop()

    def test_greedy_sampling_config_matches_default(self, setup):
        """temperature=0 through the sampling path is exactly the
        greedy default — the existing parity pin is not weakened by
        threading SamplingConfig through submit()."""
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2)
        try:
            prompt = [3, 1, 4, 1, 5]
            explicit = eng.generate(
                prompt, 5, timeout=120,
                sampling=decode.SamplingConfig(temperature=0.0, seed=9))
            assert explicit == _reference(cfg, params, prompt, 5)
        finally:
            eng.stop()

    def test_sampling_validation(self, setup):
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=32, slots=1, max_top_k=8,
            max_stop_ids=2)
        try:
            with pytest.raises(ValueError, match='max_top_k'):
                eng.submit([1, 2], 2, sampling=decode.SamplingConfig(
                    temperature=0.5, top_k=9))
            with pytest.raises(ValueError, match='max_stop_ids'):
                eng.submit([1, 2], 2, stop_token=[1, 2, 3])
        finally:
            eng.stop()


class TestBoundedAdmission:

    def test_queue_full_raises_429_class(self, setup):
        cfg, params = setup
        # max_len 128: the blocker leaves pages free, so it is the
        # queue's bound that refuses, not the pool's.
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=128, slots=1, max_queue=2)
        try:
            blocker = eng.submit([1, 2, 3], 50)
            # Give the worker a moment to move the blocker to a slot.
            import time as _time
            deadline = _time.time() + 30
            while (eng.stats()['busy_slots'] == 0 and
                   _time.time() < deadline):
                _time.sleep(0.01)
            queued = [eng.submit([4, 5], 4) for _ in range(2)]
            with pytest.raises(batching_engine.QueueFull) as err:
                eng.submit([6, 7], 4)
            assert err.value.retry_after >= 1.0
            blocker.cancel()
            for request in queued:
                request.result(timeout=120)
        finally:
            eng.stop()

    def test_queue_ttl_expires_waiting_requests(self, setup):
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=1, queue_ttl=0.05)
        try:
            blocker = eng.submit([1, 2, 3], 60)
            stale = eng.submit([4, 5], 4)
            with pytest.raises(batching_engine.QueueExpired):
                stale.result(timeout=60)
            blocker.cancel()
        finally:
            eng.stop()

    def test_unbounded_queue_by_default(self, setup):
        cfg, params = setup
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=32, slots=1)
        try:
            requests = [eng.submit([1, 2], 2) for _ in range(20)]
            for request in requests:
                assert len(request.result(timeout=240)) == 2
        finally:
            eng.stop()


class TestRoleBudgets:
    """Fractional-role budgets (dynamic co-location): derivation pins,
    version-ordered swaps, and smooth-WRR admission order under
    mid-stream budget flips."""

    def test_budget_derivation_pins(self):
        RoleBudget = batching_engine.RoleBudget
        mixed = RoleBudget.from_split(0.5, slots=8, prefill_chunk=16)
        # The mixed default is BOTH phases unclamped — byte-identical
        # to the pre-budget engine.
        assert (mixed.prefill_tokens, mixed.decode_tokens) == (16, 8)
        prefill = RoleBudget.for_role('prefill', slots=8,
                                      prefill_chunk=16)
        assert (prefill.prefill_tokens, prefill.decode_tokens) == (16, 1)
        dec = RoleBudget.for_role('decode', slots=8, prefill_chunk=16)
        assert (dec.prefill_tokens, dec.decode_tokens) == (1, 8)
        # Budgets throttle, they never deadlock: both floors are 1.
        floor = RoleBudget(prefill_tokens=0, decode_tokens=-3)
        assert (floor.prefill_tokens, floor.decode_tokens) == (1, 1)
        with pytest.raises(ValueError, match='Unknown role'):
            RoleBudget(prefill_tokens=1, decode_tokens=1,
                       role='training')

    def test_role_helpers_pinned(self):
        """Satellite pin: roles.py is the ONE place role strings are
        normalized; every `r.get('role') or 'mixed'` went through it."""
        from skypilot_tpu.serve import roles
        assert roles.ROLES == ('prefill', 'decode', 'mixed')
        assert roles.DEFAULT_ROLE == 'mixed'
        assert roles.normalize(None) == 'mixed'
        assert roles.normalize('') == 'mixed'
        assert roles.normalize('prefill') == 'prefill'
        with pytest.raises(ValueError):
            roles.normalize('training')
        assert roles.role_of({}) == 'mixed'
        assert roles.role_of({'role': None}) == 'mixed'
        assert roles.role_of({'role': 'decode'}) == 'decode'
        assert roles.DEFAULT_SPLITS == {'prefill': 1.0, 'decode': 0.0,
                                        'mixed': 0.5}

    def test_version_ordered_swaps(self):
        from skypilot_tpu.serve import scheduler
        queue = scheduler.AdmissionQueue()
        assert queue.set_role_budget(scheduler.RoleBudget.for_role(
            'decode', slots=4, prefill_chunk=16, version=5))
        # A stale rebalance POST must never undo a newer morph.
        assert not queue.set_role_budget(scheduler.RoleBudget.for_role(
            'prefill', slots=4, prefill_chunk=16, version=3))
        assert queue.role_budget.role == 'decode'
        swaps = queue.budget_swaps
        assert queue.set_role_budget(scheduler.RoleBudget.for_role(
            'mixed', slots=4, prefill_chunk=16, version=5))
        assert queue.budget_swaps == swaps + 1
        # None (unclamp) always applies — the escape hatch is never
        # version-gated.
        assert queue.set_role_budget(None)
        assert queue.role_budget is None
        assert queue.admission_allowed(10**6)
        assert queue.prefill_tokens_per_tick(512) == 512

    def test_admission_gate_and_prefill_clamp(self):
        from skypilot_tpu.serve import scheduler
        queue = scheduler.AdmissionQueue()
        queue.set_role_budget(scheduler.RoleBudget(
            prefill_tokens=4, decode_tokens=2))
        assert queue.admission_allowed(0)
        assert queue.admission_allowed(1)
        assert not queue.admission_allowed(2)  # cap reached
        assert queue.prefill_tokens_per_tick(16) == 4
        # The budget can only SHRINK the configured chunk.
        assert queue.prefill_tokens_per_tick(2) == 2

    def test_wrr_order_survives_midstream_budget_flips(self):
        """Satellite: smooth-WRR admission under mid-stream budget
        flips — every queued request is admitted exactly once (no
        double-admission), both QoS classes keep popping (no
        starvation), and the replayed qos_request journal passes the
        qos_fairness invariant."""
        from skypilot_tpu.chaos import invariants
        from skypilot_tpu.serve import scheduler
        queue = scheduler.AdmissionQueue()
        ids = []
        for cls, prefix in (('interactive', 'i'), ('batch', 'b')):
            for i in range(8):
                rid = f'{prefix}{i}'
                queue.submit(scheduler.Request(
                    [1, 2], 2, None, request_id=rid, qos_class=cls))
                ids.append(rid)
        flips = [scheduler.RoleBudget.for_role('prefill', slots=4,
                                               prefill_chunk=16),
                 scheduler.RoleBudget.for_role('decode', slots=4,
                                               prefill_chunk=16),
                 None]
        popped = []
        events = []
        busy = 0
        for step in range(200):
            if not popped or len(popped) % 3 == 0:
                # Mid-stream flip: a rebalance push lands between
                # admissions; queued requests must neither vanish nor
                # be admitted twice.
                assert queue.set_role_budget(flips[step % 3])
            if not queue.admission_allowed(busy):
                busy = 0  # a tick passes; slots all free
                continue
            request = queue.pop()
            if request is None:
                break
            queue.record_admission(request)
            popped.append((request.request_id, request.qos_class))
            busy += 1
            weight = 4 if request.qos_class == 'interactive' else 1
            events.append({'event': 'qos_request_start', 'ts': step,
                           'request_id': request.request_id,
                           'qos_class': request.qos_class,
                           'weight': weight})
            events.append({'event': 'qos_request_end', 'ts': step,
                           'request_id': request.request_id,
                           'qos_class': request.qos_class,
                           'status': 'ok'})
        # No starvation, no double-admission: all 16 admitted, once.
        assert sorted(r for r, _ in popped) == sorted(ids)
        assert len(popped) == len(set(r for r, _ in popped)) == 16
        # Smooth interleave: the batch class pops well before the
        # interactive backlog drains (4:1 weights, not segregated).
        first_batch = next(i for i, (_, c) in enumerate(popped)
                           if c == 'batch')
        last_interactive = max(i for i, (_, c) in enumerate(popped)
                               if c == 'interactive')
        assert first_batch < 5
        assert first_batch < last_interactive
        assert invariants.check(events, ['qos_fairness']) == []

    def test_engine_token_exact_under_budget_flips(self, setup):
        """Budgets clamp PACING only: flipping prefill->decode->mixed
        mid-stream changes when tokens are produced, never which."""
        cfg, params = setup
        RoleBudget = batching_engine.RoleBudget
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2)
        try:
            prompts = [[i + 1, i + 2, i + 3, i + 4] for i in range(5)]
            requests = [eng.submit(p, 4) for p in prompts]
            for version, role in enumerate(
                    ('decode', 'prefill', 'mixed')):
                assert eng.set_role_budget(RoleBudget.for_role(
                    role, slots=2, prefill_chunk=512,
                    version=version))
            for p, r in zip(prompts, requests):
                assert r.result(timeout=240) == _reference(
                    cfg, params, p, 4)
            stats = eng.stats()
            assert stats['budget_swaps'] >= 3
            assert stats['role_budget']['role'] == 'mixed'
        finally:
            eng.stop()


def test_request_finish_is_idempotent():
    """A _finish race (worker vs stop() vs submit-after-stop) must not
    push two stream sentinels or overwrite a success with an error."""
    from skypilot_tpu.serve.batching_engine import _Request
    req = _Request([1], max_new_tokens=4, stop_token=None)
    req._push(42)
    req._finish()
    req._finish(RuntimeError('late shutdown'))  # loser of the race
    assert req.error is None  # success not overwritten
    assert req.result(timeout=1) == [42]
    # Exactly one sentinel: the stream ends after 42, and a token pushed
    # after finish is dropped rather than appearing past the end.
    req._push(99)
    assert list(req.stream(timeout=1)) == [42]
    assert req.tokens == [42]


# --------------------------------------------- the engine's own weights


def _family(case):
    """-> (cfg, the caller's tree, engine keywords) of one case."""
    preset = {'qkv-bias': 'tiny-qwen', 'tied': 'tiny-gemma',
              'experts': 'tiny-moe'}.get(case, 'tiny')
    cfg = configs.get_config(preset)
    if case == 'unscanned':
        cfg = cfg.replace(scan_layers=False)
    params = nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
    if case == 'int8':
        from skypilot_tpu.models import quantize
        params = jax.device_put(quantize.quantize_params(params))
    return cfg, params, ({'spec_tokens': 3} if case == 'speculative'
                         else {})


@pytest.mark.parametrize('case', ['gqa', 'qkv-bias', 'tied', 'unscanned',
                                  'experts', 'int8', 'speculative'])
def test_engine_serves_the_callers_tree_on_its_own_kernels(case):
    """The engine re-forms the q/k/v kernels it is given
    (`decode.serving_params`) and serves what `decode.generate` gives
    on the caller's tree, which stays whole and readable after the
    engine is built and after it is stopped: nothing is donated, and
    the benchmark runs its reference on that tree once the engine is
    gone.  `stats()['weights']['reformed_bytes']` is what the engine
    holds in re-formed leaves."""
    cfg, params, kw = _family(case)
    shapes = jax.tree.map(lambda leaf: leaf.shape, params)
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8, 2, 8]]
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2, prefill_chunk=4, **kw)
    try:
        requests = [eng.submit(p, 6) for p in prompts]
        got = [r.result(timeout=240) for r in requests]
        qkv = decode._qkv_projs(cfg, params)  # pylint: disable=protected-access
        held = decode._qkv_projs(cfg, eng.params)  # pylint: disable=protected-access
        for path, proj in qkv.items():
            for mine, theirs in zip(jax.tree.leaves(proj),
                                    jax.tree.leaves(held[path])):
                assert theirs.shape == mine.shape[:-2] + (
                    mine.shape[-2] * mine.shape[-1],)
        assert eng.stats()['weights']['reformed_bytes'] == sum(
            leaf.nbytes for leaf in jax.tree.leaves(qkv)) > 0
        if case == 'speculative':
            assert eng.stats()['spec_ticks'] > 0
    finally:
        eng.stop()
    assert jax.tree.map(lambda leaf: leaf.shape, params) == shapes
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(params))
    for prompt, tokens in zip(prompts, got):
        assert tokens == _reference(cfg, params, prompt, 6), case


@pytest.mark.parametrize('case', ['gqa', 'qkv-bias', 'int8'])
def test_swap_params_takes_the_training_layout(case):
    """`swap_params` is given what the constructor is given, the
    training layout (`/weights_swap` restores a checkpoint), and the
    engine serves the new weights on kernels it re-formed itself."""
    cfg, first, _ = _family(case)
    second = jax.tree.map(
        lambda leaf: (leaf * 1.5).astype(leaf.dtype)
        if jnp.issubdtype(leaf.dtype, jnp.floating) else leaf, first)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    want = [_reference(cfg, tree, prompt, 6) for tree in (first, second)]
    assert want[0] != want[1]
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, first, max_len=64, slots=2)
    try:
        assert eng.generate(prompt, 6, timeout=240) == want[0]
        held = eng.stats()['weights']['reformed_bytes']
        assert eng.swap_params(second) == 1
        assert eng.generate(prompt, 6, timeout=240) == want[1]
        assert eng.stats()['weights']['reformed_bytes'] == held > 0
        # A tree that is in the serving form already passes as it is.
        assert eng.swap_params(eng.params) == 2
        assert eng.generate(prompt, 6, timeout=240) == want[1]
    finally:
        eng.stop()
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves((first, second)))
