"""CPU harness for the serving engine: the decode hot loop under
open-loop load, at `tiny` size, behind tests/unit/test_bench_serve.py.

It runs on the CPU backend ONLY and refuses any other: it builds an
engine in this process and then starts replicas and slice-prefill
children as subprocesses pinned to `JAX_PLATFORMS=cpu` — on a chip that
would be a parent holding the TPU while it times CPU children.  Its
numbers are counts and CPU wall-clock of the `tiny` preset; none is a
device metric (ROADMAP Speed 1 builds the on-chip benchmark).

Drives `serve.batching_engine.ContinuousBatchingEngine` directly (no
HTTP in the way) with Poisson arrivals over mixed prompt lengths and
reports the numbers a serving SLO is written in:

- decode tokens/s        (aggregate, across all in-flight requests)
- TTFT p50/p99           (submit -> first token)
- ITL  p50/p99           (gap between consecutive tokens of a request)
- speedup vs the pre-pipeline engine (`pipelined=False`: inline
  full-prompt prefill + one host sync per generated token) on the SAME
  workload — the A/B for the on-device-sampling + pipelined-tick loop.
- chunked-prefill stall probe: while `slots-1` decodes run, admit one
  LONG prompt and measure the worst ITL the running requests suffer;
  with chunked prefill that stall is bounded by ONE chunk's compute
  (reported alongside the unchunked stall for contrast).
- paged-KV capacity probe: at a FIXED cache-memory budget (what the
  dense `[L, slots, h_kv, max_len, d]` cache occupies), size an
  int8-paged pool with the same bytes and run that many requests
  CONCURRENTLY — max concurrent slots at fixed memory is the number
  the paged cache exists to move (dense reserves max_len per slot;
  pages reserve only what a request can touch).
- prefix-cache TTFT probe: a shared system prompt is prefilled cold
  once, then re-requested — the hit adopts the cached pages and
  prefills only the tail chunk, so TTFT collapses (reported as
  hit/cold ratio, with the hit's `prefix_hit_pages` from its span).
- disaggregation A/B: the SAME bursty workload (steady chat SSE
  streams + Poisson long-prompt bursts) through the real routing LB
  over HTTP against two replica fleets — role-blind mixed vs
  prefill+decode with KV page handoff.  The pinned number is the
  chat ITL p99 ratio during bursts (disaggregated / mixed): keeping
  long prefills off decode replicas is THE tail-latency lever under
  mixed traffic, and the handed-off pages land the decode-side
  admission as a prefix hit.
- self-speculative decoding A/B: the SAME repetitive-text workload
  (periodic prompts — greedy decode on the tiny model locks into
  cycles, the regime prompt-lookup drafting exists for) with
  `spec_tokens=0` vs `spec_tokens=3`.  The pinned numbers are the
  ITL p50 speedup (one verify tick emits every accepted token, so
  accepted tokens arrive with near-zero gaps) and the mean
  acceptance length from engine stats; greedy outputs must be
  byte-identical across the two runs (token-exactness is the
  contract, speed is the only variable).
- paged decode-kernel A/B: the same paged int8 workload under
  `SKYTPU_DECODE_KERNEL=gather` (XLA gather reference) vs `pallas`
  (block-table-indexed in-kernel page reads).  The Pallas path runs
  under the interpreter (`SKYTPU_PALLAS_INTERPRET=1`), so the section
  asserts PARITY and presence only — interpret-mode wall-clock is not
  a perf claim.
- --smoke also scrapes `/metrics` (observability/metrics.py exposition
  served on a loopback port) before, during, and after the pipelined
  run, asserts the key engine series are present and monotone (ticks,
  decode tokens), and writes the samples into the JSON — the perf
  trajectory carries an observability signal per change.

Prints ONE JSON line and writes it to --out (BENCH_serve.json;
--smoke uses a seconds-scale config and BENCH_serve_smoke.json — the
tier-1 perf smoke `tests/unit/test_bench_serve.py` runs).
"""
from __future__ import annotations

import argparse
import json
import threading
import time
from typing import Any, Dict, List, Optional


def _percentile(values: List[float], pct: float) -> float:
    if not values:
        return 0.0
    values = sorted(values)
    idx = min(len(values) - 1, int(round(pct / 100.0 * (len(values) - 1))))
    return values[idx]


class _Tracked:
    """One benchmark request: submit time + per-token arrival times."""

    def __init__(self, prompt: List[int], max_new: int) -> None:
        self.prompt = prompt
        self.max_new = max_new
        self.submit_t: float = 0.0
        self.token_times: List[float] = []
        self.handle = None

    def watcher(self, token: Optional[int]) -> None:
        if token is not None:
            self.token_times.append(time.perf_counter())

    @property
    def ttft(self) -> Optional[float]:
        if not self.token_times:
            return None
        return self.token_times[0] - self.submit_t

    @property
    def itls(self) -> List[float]:
        return [b - a for a, b in zip(self.token_times,
                                      self.token_times[1:])]


def _workload(rng, n_requests: int, rate: float, prompt_lens: List[int],
              max_new: int, vocab: int) -> List[Any]:
    """[(arrival_offset_s, _Tracked)] — Poisson arrivals, prompt length
    cycling through the mix with +-25% jitter."""
    out = []
    t = 0.0
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / rate)) if rate > 0 else 0.0
        base = prompt_lens[i % len(prompt_lens)]
        n = max(1, int(base * (0.75 + 0.5 * rng.random())))
        prompt = [int(x) for x in rng.integers(1, vocab - 1, size=n)]
        out.append((t, _Tracked(prompt, max_new)))
    return out


def _run_load(engine, workload) -> Dict[str, Any]:
    """Submit the workload open-loop; wait for every request."""
    t0 = time.perf_counter()

    def submitter():
        for offset, tracked in workload:
            now = time.perf_counter() - t0
            if offset > now:
                time.sleep(offset - now)
            tracked.submit_t = time.perf_counter()
            tracked.handle = engine.submit(tracked.prompt,
                                           tracked.max_new)
            tracked.handle.add_watcher(tracked.watcher)

    thread = threading.Thread(target=submitter)
    thread.start()
    thread.join()
    for _, tracked in workload:
        tracked.handle.result(timeout=600)
    tokens = sum(len(t.token_times) for _, t in workload)
    last = max(t.token_times[-1] for _, t in workload if t.token_times)
    first = min(t.submit_t for _, t in workload)
    span = max(last - first, 1e-9)
    ttfts = [t.ttft for _, t in workload if t.ttft is not None]
    itls = [g for _, t in workload for g in t.itls]
    return {
        'requests': len(workload),
        'tokens': tokens,
        'tokens_per_s': round(tokens / span, 2),
        'ttft_p50_ms': round(_percentile(ttfts, 50) * 1e3, 2),
        'ttft_p99_ms': round(_percentile(ttfts, 99) * 1e3, 2),
        'itl_p50_ms': round(_percentile(itls, 50) * 1e3, 2),
        'itl_p99_ms': round(_percentile(itls, 99) * 1e3, 2),
    }


def _scrape_metrics(port: int) -> Dict[str, Any]:
    """One /metrics scrape over real HTTP -> the counter values the
    smoke asserts on (summed across label sets)."""
    import urllib.request

    from skypilot_tpu.observability import metrics as metrics_lib
    with urllib.request.urlopen(
            f'http://127.0.0.1:{port}/metrics', timeout=10) as resp:
        text = resp.read().decode()
    parsed = metrics_lib.parse_exposition(text)

    def total(name: str) -> float:
        return sum((parsed.get(name) or {}).values())

    return {
        'ticks': total('skytpu_engine_ticks_total'),
        'decode_tokens': total('skytpu_engine_decode_tokens_total'),
        'queue_wait_count':
            total('skytpu_engine_queue_wait_seconds_count'),
        'itl_count': total('skytpu_engine_itl_seconds_count'),
        'histograms_present': all(
            f'skytpu_engine_{h}_seconds_bucket' in parsed
            for h in ('queue_wait', 'itl', 'ttft')),
    }


def _measure_chunk_compute(cfg, params, chunk: int, max_len: int,
                           vocab: int) -> float:
    """Median wall time of ONE jitted prefill-chunk continuation (the
    unit the chunked-prefill stall bound is stated in)."""
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import decode
    fn = jax.jit(lambda p, t, c: decode.prefill_chunk(cfg, p, t, c))
    _, cache = decode.prefill(
        cfg, params, jnp.ones((1, chunk), jnp.int32), max_len=max_len)
    piece = jnp.ones((1, chunk), jnp.int32) % (vocab - 1) + 1
    logits, _ = fn(params, piece, cache)   # compile
    logits.block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        logits, new_cache = fn(params, piece, cache)
        logits.block_until_ready()
        del new_cache
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _stall_probe(cfg, params, *, slots: int, prompt_len: int,
                 chunk: int, max_new_bg: int, vocab: int,
                 pipelined_chunked: bool) -> Dict[str, Any]:
    """Admit a long prompt while slots-1 decodes run; the worst ITL the
    running decodes see during the admission window IS the head-of-line
    stall that admission imposed."""
    import numpy as np

    from skypilot_tpu.serve import batching_engine
    max_len = prompt_len + 2 * max_new_bg + 16
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=max_len, slots=slots,
        prefill_chunk=chunk if pipelined_chunked else max(prompt_len, 16))
    try:
        # Warm every compile on the admission path (tick, the long
        # prompt's chunk-0 bucket, the chunk continuation, insert) so
        # the probe measures the steady-state stall, not XLA.
        eng.generate([1, 2, 3], 2, timeout=600)
        eng.generate(list(range(1, prompt_len + 1)), 2, timeout=600)
        rng = np.random.default_rng(0)
        background = []
        for _ in range(max(1, slots - 1)):
            tracked = _Tracked(
                [int(x) for x in rng.integers(1, vocab - 1, size=8)],
                max_new_bg)
            tracked.submit_t = time.perf_counter()
            tracked.handle = eng.submit(tracked.prompt, tracked.max_new)
            tracked.handle.add_watcher(tracked.watcher)
            background.append(tracked)
        # Steady decode before the admission hits.
        deadline = time.time() + 120
        while (min(len(t.token_times) for t in background) < 5 and
               time.time() < deadline):
            time.sleep(0.005)
        long_prompt = [int(x)
                       for x in rng.integers(1, vocab - 1,
                                             size=prompt_len)]
        t_admit = time.perf_counter()
        handle = eng.submit(long_prompt, 2)
        handle.result(timeout=600)
        t_first = time.perf_counter()
        for t in background:
            t.handle.cancel()
        # Worst gap any running decode saw inside the admission window.
        stall = 0.0
        for t in background:
            times = [x for x in t.token_times
                     if t_admit - 0.5 <= x <= t_first + 0.5]
            stall = max(stall, max(
                (b - a for a, b in zip(times, times[1:])), default=0.0))
        baseline_itls = [g for t in background for g in t.itls
                         if g > 0]
        return {
            'max_itl_during_admission_ms': round(stall * 1e3, 2),
            'baseline_itl_p50_ms': round(
                _percentile(baseline_itls, 50) * 1e3, 2),
        }
    finally:
        eng.stop()


def _kv_bytes_per_position(cfg, quantized: bool) -> int:
    """KV bytes one cache position costs per layer per kv-head (k+v):
    the unit the fixed-memory comparison is stated in."""
    import numpy as np
    if quantized:
        return 2 * (cfg.head_dim * 1 + 4)   # int8 values + f32 scale
    return 2 * cfg.head_dim * np.dtype(cfg.dtype).itemsize


def _capacity_probe(cfg, params, *, dense_slots: int, max_len: int,
                    page_size: int, prompt_len: int, max_new: int,
                    vocab: int, quantize_kv: bool = True,
                    max_concurrency: int = 512) -> Dict[str, Any]:
    """Max concurrent requests at the DENSE cache's memory budget.

    Dense concurrency at this budget IS dense_slots (each slot
    reserves max_len positions no matter what requests need).  The
    paged pool with the same bytes holds n_pages pages; a request
    pins ceil((prompt + max_new - 1)/page_size) of them — the probe
    builds that engine and actually runs the full complement
    concurrently to completion.
    """
    import numpy as np

    from skypilot_tpu.serve import batching_engine
    budget_bytes = (dense_slots * max_len *
                    _kv_bytes_per_position(cfg, quantized=False))
    page_bytes = page_size * _kv_bytes_per_position(cfg, quantize_kv)
    n_pages = budget_bytes // page_bytes
    pages_per_request = -(-(prompt_len + max_new - 1) // page_size)
    paged_slots = min(int(n_pages // pages_per_request),
                      max_concurrency)
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=max_len, slots=paged_slots,
        prefill_chunk=max(page_size, 16), kv_pages=int(n_pages) + 1,
        page_size=page_size, quantize_kv=quantize_kv,
        prefix_caching=False)
    rng = np.random.default_rng(0)
    peak_busy = 0
    try:
        eng.generate([1, 2, 3], 2, timeout=600)  # warm compiles
        handles = [
            eng.submit([int(x) for x in
                        rng.integers(1, vocab - 1, size=prompt_len)],
                       max_new)
            for _ in range(paged_slots)
        ]
        while not all(h.done.is_set() for h in handles):
            peak_busy = max(peak_busy, eng.stats()['busy_slots'])
            time.sleep(0.01)
        for h in handles:
            assert len(h.result(timeout=600)) == max_new
        stats = eng.stats()
    finally:
        eng.stop()
    return {
        'budget_bytes': int(budget_bytes),
        'page_size': page_size,
        'quantize_kv': quantize_kv,
        'kv_pages': int(n_pages),
        'pages_per_request': pages_per_request,
        'prompt_len': prompt_len,
        'max_new_tokens': max_new,
        'max_concurrent_dense': dense_slots,
        'max_concurrent_paged': paged_slots,
        'peak_busy_slots': peak_busy,
        'concurrency_ratio': round(paged_slots / max(dense_slots, 1),
                                   2),
        'pool_drained': stats['kv_pages_used'] == 0,
    }


def _prefix_probe(cfg, params, *, max_len: int, page_size: int,
                  chunk: int, prefix_len: int, vocab: int,
                  trials: int = 3,
                  quantize_kv: bool = True) -> Dict[str, Any]:
    """Shared-prefix TTFT: cold prefill once, then hits that adopt the
    cached pages and prefill only the unmatched tail."""
    import numpy as np

    from skypilot_tpu.serve import batching_engine
    pages_needed = -(-(prefix_len + 8) // page_size) * (trials + 3)
    eng = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=max_len, slots=2, prefill_chunk=chunk,
        kv_pages=pages_needed + 8, page_size=page_size,
        quantize_kv=quantize_kv, prefix_caching=True)
    rng = np.random.default_rng(1)

    def ttft_of(prompt):
        handle = eng.submit(prompt, 4)
        handle.result(timeout=600)
        span = eng.span(handle.request_id)
        return span['ttft_ms'], span['prefix_hit_pages']

    try:
        # Warm EVERY compile on both paths (chunk-0 bucket, chunk
        # continuation, page insert, prefix seed) with a throwaway
        # prompt of the same length, measured afterwards on a prompt
        # the cache has never seen.
        warm = [int(x) for x in rng.integers(1, vocab - 1,
                                             size=prefix_len)]
        ttft_of(warm)
        ttft_of(warm)          # warms the hit path (seed compile)
        shared = [int(x) for x in rng.integers(1, vocab - 1,
                                               size=prefix_len)]
        ttft_cold, _ = ttft_of(shared)
        hits = [ttft_of(shared) for _ in range(trials)]
        hit_ttfts = sorted(t for t, _ in hits)
        ttft_hit = hit_ttfts[len(hit_ttfts) // 2]
        hit_pages = hits[0][1]
    finally:
        eng.stop()
    return {
        'prefix_len': prefix_len,
        'page_size': page_size,
        'prefill_chunk': chunk,
        'quantize_kv': quantize_kv,
        'ttft_cold_ms': round(ttft_cold, 3),
        'ttft_hit_ms': round(ttft_hit, 3),
        'ttft_hit_ratio': round(ttft_hit / max(ttft_cold, 1e-9), 4),
        'prefix_hit_pages': hit_pages,
    }


def _spec_probe(cfg, params, *, smoke: bool, vocab: int, seed: int,
                spec_tokens: int = 3) -> Dict[str, Any]:
    """Self-speculative decoding A/B on repetitive text.

    Periodic prompts push the tiny model's greedy decode into cycles
    — exactly the regime the n-gram prompt-lookup drafter targets.
    The SAME workload runs with drafting off (`spec_tokens=0`) and on
    (`spec_tokens=k`); accepted tokens all land in one verify tick,
    so the per-token gap (ITL) collapses while the token stream stays
    byte-identical (longest-exact-prefix acceptance under greedy)."""
    import numpy as np

    from skypilot_tpu.serve import batching_engine

    n_requests = 3 if smoke else 6
    max_new = 48 if smoke else 160
    prompt_len = 24 if smoke else 48
    page_size = 8
    max_len = -(-(prompt_len + max_new + 2) // page_size) * page_size
    rng = np.random.default_rng(seed)
    prompts = []
    for _ in range(n_requests):
        period = int(rng.integers(2, 5))
        motif = [int(x) for x in
                 rng.integers(1, vocab - 1, size=period)]
        prompts.append((motif * (prompt_len // period + 1))
                       [:prompt_len])

    def run(k: int):
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=max_len, slots=n_requests,
            prefill_chunk=max(prompt_len, 16),
            kv_pages=(n_requests + 1) * (max_len // page_size) + 4,
            page_size=page_size, prefix_caching=False,
            spec_tokens=k)
        try:
            # Warm every compile on the measured path (prefill
            # bucket, page insert, and the plain OR spec tick).
            eng.generate(prompts[0], 4, timeout=600)
            tracked = [_Tracked(p, max_new) for p in prompts]
            t0 = time.perf_counter()
            for t in tracked:
                t.submit_t = time.perf_counter()
                t.handle = eng.submit(t.prompt, t.max_new)
                t.handle.add_watcher(t.watcher)
            outputs = [t.handle.result(timeout=600) for t in tracked]
            wall = time.perf_counter() - t0
            stats = eng.stats()
        finally:
            eng.stop()
        itls = [g for t in tracked for g in t.itls]
        tokens = sum(len(o) for o in outputs)
        return {
            'tokens': tokens,
            'wall_s': round(wall, 3),
            'tokens_per_s': round(tokens / max(wall, 1e-9), 2),
            'itl_p50_ms': round(_percentile(itls, 50) * 1e3, 3),
            'itl_p99_ms': round(_percentile(itls, 99) * 1e3, 3),
        }, outputs, stats

    off, out_off, _ = run(0)
    on, out_on, stats = run(spec_tokens)
    return {
        'spec_tokens': spec_tokens,
        'requests': n_requests,
        'prompt_len': prompt_len,
        'max_new_tokens': max_new,
        'spec_off': off,
        'spec_on': on,
        'outputs_match': out_off == out_on,
        'spec_ticks': stats['spec_ticks'],
        'spec_proposed_tokens': stats['spec_proposed_tokens'],
        'spec_accepted_tokens': stats['spec_accepted_tokens'],
        'spec_accept_len_mean': stats['spec_accept_len_mean'],
        'itl_p50_speedup': round(
            off['itl_p50_ms'] / max(on['itl_p50_ms'], 1e-9), 3),
        'itl_p99_speedup': round(
            off['itl_p99_ms'] / max(on['itl_p99_ms'], 1e-9), 3),
    }


def _kernel_probe(cfg, params, *, smoke: bool, vocab: int,
                  seed: int) -> Dict[str, Any]:
    """Paged decode-kernel A/B: gather reference vs the Pallas
    paged-attention kernel on the same int8-paged workload.

    The Pallas path runs under the interpreter, so the numbers here
    pin PARITY (greedy outputs byte-identical) and presence —
    interpret-mode wall-clock is not a perf claim."""
    import os

    import numpy as np

    from skypilot_tpu.serve import batching_engine

    n_requests = 2
    max_new = 8 if smoke else 24
    prompt_len = 12 if smoke else 48
    page_size = 8
    max_len = -(-(prompt_len + max_new + 2) // page_size) * page_size
    rng = np.random.default_rng(seed)
    prompts = [[int(x) for x in
                rng.integers(1, vocab - 1, size=prompt_len)]
               for _ in range(n_requests)]

    def run(kernel: str):
        # The kernel choice is resolved ONCE at engine construction
        # from SKYTPU_DECODE_KERNEL; pin it for the build, restore
        # the caller's environment after.
        saved = {k: os.environ.get(k)
                 for k in ('SKYTPU_DECODE_KERNEL',
                           'SKYTPU_PALLAS_INTERPRET')}
        os.environ['SKYTPU_DECODE_KERNEL'] = kernel
        os.environ['SKYTPU_PALLAS_INTERPRET'] = '1'
        try:
            eng = batching_engine.ContinuousBatchingEngine(
                cfg, params, max_len=max_len, slots=n_requests,
                prefill_chunk=16,
                kv_pages=(n_requests + 1) * (max_len // page_size)
                + 4,
                page_size=page_size, quantize_kv=True,
                prefix_caching=False)
        finally:
            for key, value in saved.items():
                if value is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = value
        try:
            if eng.decode_kernel != kernel:
                raise RuntimeError(
                    f'engine resolved kernel {eng.decode_kernel!r}, '
                    f'wanted {kernel!r}')
            eng.generate(prompts[0], 2, timeout=600)  # warm compiles
            t0 = time.perf_counter()
            handles = [eng.submit(p, max_new) for p in prompts]
            outputs = [h.result(timeout=600) for h in handles]
            wall = time.perf_counter() - t0
        finally:
            eng.stop()
        tokens = sum(len(o) for o in outputs)
        return {
            'decode_kernel': kernel,
            'tokens': tokens,
            'wall_s': round(wall, 3),
            'tokens_per_s': round(tokens / max(wall, 1e-9), 2),
        }, outputs

    gather, out_gather = run('gather')
    pallas, out_pallas = run('pallas')
    return {
        'page_size': page_size,
        'quantize_kv': True,
        'prompt_len': prompt_len,
        'max_new_tokens': max_new,
        'interpret_mode': True,
        'kernels': {'gather': gather, 'pallas': pallas},
        'outputs_match': out_gather == out_pallas,
    }


def _run_disagg_config(*, replica_urls, roles, page_size, threshold,
                       long_prompt_len, chat_prompt_len, chat_max_new,
                       n_chat, n_bursts, burst_interval_s, vocab,
                       seed) -> Dict[str, Any]:
    """One routing-LB fleet over two ALREADY-RUNNING replica processes
    under the bursty mixed workload: N steady chat token streams
    decode while long prompts burst in Poisson-spaced.  Roles are an
    LB-side attribute, so the SAME replica processes serve both
    configs — the caller contrasts roles=['mixed','mixed']
    (role-blind) against ['prefill','decode'] (disaggregated + KV
    handoff)."""
    import numpy as np
    import requests

    from skypilot_tpu.observability import metrics as obs_metrics
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import router as router_lib

    def counter_total(name: str, **labels) -> float:
        parsed = obs_metrics.parse_exposition(obs_metrics.expose())
        total = 0.0
        for labelset, value in (parsed.get(name) or {}).items():
            d = dict(labelset)
            if all(d.get(k) == v for k, v in labels.items()):
                total += value
        return total

    handoff_ok_0 = counter_total('skytpu_lb_handoff_total',
                                 outcome='ok')
    handoff_fb_0 = counter_total('skytpu_lb_handoff_total',
                                 outcome='fallback')
    rng = np.random.default_rng(seed)
    lb = lb_lib.SkyServeLoadBalancer(
        'http://127.0.0.1:1',
        router=router_lib.Router(threshold=threshold))
    try:
        lb.set_replicas([
            {'url': url, 'role': role, 'page_size': page_size}
            for url, role in zip(replica_urls, roles)])
        lb_port = lb.start()
        base = f'http://127.0.0.1:{lb_port}'

        def long_prompt():
            return [int(x) for x in rng.integers(
                1, vocab - 1, size=long_prompt_len)]

        # Warm the routed path for THIS fleet config (any cold compile
        # belongs to warmup, not the measured window).
        requests.post(f'{base}/generate',
                      json={'prompt_ids': [long_prompt()],
                            'max_new_tokens': 2}, timeout=300)

        # Steady chat decodes: each client keeps an SSE stream open
        # back-to-back (a finished conversation is immediately
        # replaced), recording every token arrival per session — gaps
        # are only ever measured WITHIN a session, never across the
        # reconnect seam.
        chat_sessions: List[List[float]] = []
        sessions_lock = threading.Lock()
        chat_stop = threading.Event()
        tokens_seen = [0]

        def chat_client(idx: int) -> None:
            session_rng = np.random.default_rng((seed, idx))
            while not chat_stop.is_set():
                prompt = [int(x) for x in session_rng.integers(
                    1, vocab - 1, size=chat_prompt_len)]
                times: List[float] = []
                with sessions_lock:
                    chat_sessions.append(times)
                try:
                    with requests.post(
                            f'{base}/generate_stream',
                            json={'prompt_ids': prompt,
                                  'max_new_tokens': chat_max_new},
                            stream=True, timeout=300) as resp:
                        for line in resp.iter_lines(chunk_size=16):
                            if chat_stop.is_set():
                                return
                            if line.startswith(b'data:') and \
                                    b'[DONE]' not in line:
                                times.append(time.perf_counter())
                                tokens_seen[0] += 1
                except requests.RequestException:
                    if not chat_stop.is_set():
                        time.sleep(0.01)

        chat_threads = [threading.Thread(target=chat_client, args=(i,))
                        for i in range(n_chat)]
        for t in chat_threads:
            t.start()
        deadline = time.time() + 60
        while tokens_seen[0] < 3 * n_chat and time.time() < deadline:
            time.sleep(0.01)

        # Long-prompt bursts, Poisson-spaced, while the chats decode.
        long_latencies: List[float] = []
        lat_lock = threading.Lock()

        def burst_client(prompt) -> None:
            t0 = time.perf_counter()
            try:
                requests.post(f'{base}/generate',
                              json={'prompt_ids': [prompt],
                                    'max_new_tokens': 2}, timeout=300)
            except requests.RequestException:
                return
            with lat_lock:
                long_latencies.append(
                    (time.perf_counter() - t0) * 1e3)

        t_burst0 = time.perf_counter()
        burst_threads = []
        for _ in range(n_bursts):
            thread = threading.Thread(target=burst_client,
                                      args=(long_prompt(),))
            thread.start()
            burst_threads.append(thread)
            time.sleep(float(rng.exponential(burst_interval_s)))
        for thread in burst_threads:
            thread.join()
        t_burst1 = time.perf_counter()
        time.sleep(0.1)
        chat_stop.set()
        for thread in chat_threads:
            thread.join(timeout=30)
    finally:
        lb.stop()
    # Chat ITL during the burst window: the number disaggregation
    # exists to protect.
    itls = []
    for times in chat_sessions:
        window = [x for x in times
                  if t_burst0 - 0.05 <= x <= t_burst1 + 0.1]
        itls.extend(b - a for a, b in zip(window, window[1:]))
    return {
        'roles': list(roles),
        'chat_streams': n_chat,
        'chat_tokens_in_burst_window': len(itls),
        'chat_itl_p50_ms': round(_percentile(itls, 50) * 1e3, 2),
        'chat_itl_p99_ms': round(_percentile(itls, 99) * 1e3, 2),
        'chat_itl_max_ms': round(max(itls, default=0.0) * 1e3, 2),
        'long_requests': len(long_latencies),
        'long_latency_p50_ms': round(
            _percentile(long_latencies, 50), 2),
        'long_latency_p99_ms': round(
            _percentile(long_latencies, 99), 2),
        'handoffs_ok': counter_total(
            'skytpu_lb_handoff_total', outcome='ok') - handoff_ok_0,
        'handoff_fallbacks': counter_total(
            'skytpu_lb_handoff_total',
            outcome='fallback') - handoff_fb_0,
    }


def _sp_prefill_probe(*, smoke: bool, model: str = 'tiny'
                      ) -> Dict[str, Any]:
    """Long-context prefill scaling with host count (ISSUE 9).

    Each host count runs `python -m skypilot_tpu.serve.slice_replica
    --bench-prefill` in its OWN subprocess pinned to `hosts x
    cores_per_host` CPU cores — the local stand-in for "each host
    brings its own chips": the sequence axis splits the quadratic
    attention across the slice, and the extra hosts' cores are what
    turn that split into wall-clock.  The pinned number is
    prefill_speedup_Nx = t(1 host) / t(N hosts); the tier-1 smoke
    floor-asserts the 2-host ratio."""
    import os
    import subprocess
    import sys

    prompt_len = 3072 if smoke else 8192
    host_counts = [1, 2] if smoke else [1, 2, 4]
    try:
        cores = sorted(os.sched_getaffinity(0))
    except AttributeError:
        cores = []
    cores_per_host = max(1, len(cores) // max(host_counts)) \
        if cores else 0
    results: Dict[int, Dict[str, Any]] = {}
    for hosts in host_counts:
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        env['XLA_FLAGS'] = (
            f'--xla_force_host_platform_device_count={hosts}')
        preexec = None
        if cores_per_host and hasattr(os, 'sched_setaffinity'):
            pinned = set(cores[:hosts * cores_per_host])
            preexec = (lambda p=pinned:
                       os.sched_setaffinity(0, p))  # noqa: E731
        proc = subprocess.run(
            [sys.executable, '-m',
             'skypilot_tpu.serve.slice_replica', '--bench-prefill',
             '--num-hosts', str(hosts), '--sequence', str(hosts),
             '--prompt-len', str(prompt_len), '--model', model,
             '--iters', '3' if smoke else '5'],
            env=env, capture_output=True, text=True, timeout=600,
            preexec_fn=preexec, check=True)
        results[hosts] = json.loads(proc.stdout.strip().splitlines()[-1])
    base = results[1]['prefill_s']
    out: Dict[str, Any] = {
        'prompt_len': prompt_len,
        'cores_per_host': cores_per_host,
        'per_hosts': {str(h): r for h, r in results.items()},
    }
    for hosts in host_counts[1:]:
        out[f'prefill_speedup_{hosts}x'] = round(
            base / max(results[hosts]['prefill_s'], 1e-9), 3)
    return out


def _spawn_replica(port: int, *, max_len: int, slots: int,
                   kv_pages: int, page_size: int, prefill_chunk: int,
                   cpus=None):
    """One model-server replica as a REAL subprocess (its own GIL, GC,
    and XLA thread pool — like a real fleet; in-process replicas bleed
    each other's pauses into the ITL measurements).  `cpus` pins the
    replica to a core set: two replicas on disjoint halves of the
    machine are the closest local stand-in for two hosts — without it,
    one replica's wide prefill steals the other's decode cores and the
    A/B measures this box's scheduler, not the routing policy."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    preexec = None
    if cpus and hasattr(os, 'sched_setaffinity'):
        preexec = lambda: os.sched_setaffinity(0, cpus)  # noqa: E731
    return subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.serve.model_server',
         '--model', 'tiny', '--port', str(port),
         '--max-len', str(max_len), '--max-batch', str(slots),
         '--continuous-batching', '--kv-pages', str(kv_pages),
         '--page-size', str(page_size),
         '--prefill-chunk', str(prefill_chunk), '--quantize-kv'],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        preexec_fn=preexec)


def _disagg_probe(*, smoke: bool, vocab: int, seed: int
                  ) -> Dict[str, Any]:
    """Prefill/decode disaggregation A/B: the SAME bursty workload
    (steady chat SSE streams + Poisson long-prompt bursts) against a
    role-blind mixed fleet vs a prefill+decode fleet with KV page
    handoff — over real HTTP, with each replica its own process (int8
    KV: the production paged config, and the compact int8+scales wire
    format).  The claim under test: in-flight decode ITL p99 during
    bursts collapses when long prefills are kept off decode replicas
    (and the handed-off pages make the decode-side prefill a prefix
    hit)."""
    import socket
    import time as time_lib

    import requests

    # long_prompt_len is chosen PAGE-ALIGNED (prompt-1 divisible by
    # page_size): the handed-off pages then cover the whole prefilled
    # region and the decode replica admits the request as a FULL
    # prefix hit — zero prefill compute on the decode pool, the
    # best-case the page-granular wire format was designed for.
    # The prompt is long enough that each prefill chunk's compute
    # (attention is quadratic in context) dwarfs a decode tick AND the
    # decode-side page-adoption scatter; ~4 chunks per admission keeps
    # the stall-event count well above the p99 index so the percentile
    # reads the stalls, not scheduler noise.
    engine = dict(max_len=1024, slots=3, kv_pages=768, page_size=8,
                  prefill_chunk=224)
    knobs: Dict[str, Any] = dict(
        page_size=8, threshold=64, long_prompt_len=897,
        chat_prompt_len=8, chat_max_new=280, n_chat=2, n_bursts=10,
        burst_interval_s=0.15, vocab=vocab, seed=seed)
    if not smoke:
        engine = dict(max_len=2048, slots=3, kv_pages=1024,
                      page_size=8, prefill_chunk=480)
        knobs.update(long_prompt_len=1921, n_bursts=12,
                     chat_max_new=600, burst_interval_s=0.25)

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(('', 0))
            return s.getsockname()[1]

    import os
    ports = [free_port(), free_port()]
    try:
        cores = sorted(os.sched_getaffinity(0))
    except AttributeError:
        cores = []
    halves = [None, None]
    if len(cores) >= 2:
        halves = [set(cores[:len(cores) // 2]),
                  set(cores[len(cores) // 2:])]
    procs = [_spawn_replica(p, cpus=half, **engine)
             for p, half in zip(ports, halves)]
    urls = [f'http://127.0.0.1:{p}' for p in ports]
    try:
        # Readiness + warmup per replica: the long-prompt chunks, the
        # chat shape, and the handoff legs (export on replica 0,
        # import on replica 1) all compile before anything is timed.
        deadline = time_lib.time() + 300
        for url in urls:
            while True:
                try:
                    if requests.get(url + '/', timeout=2) \
                            .status_code == 200:
                        break
                except requests.RequestException:
                    pass
                if time_lib.time() > deadline:
                    raise RuntimeError(
                        f'replica {url} never became ready')
                time_lib.sleep(0.25)
        warm_long = list(range(1, knobs['long_prompt_len'] + 1))
        for url in urls:
            requests.post(f'{url}/generate',
                          json={'prompt_ids': [warm_long],
                                'max_new_tokens': 2}, timeout=300)
            requests.post(f'{url}/generate',
                          json={'prompt_ids':
                                [[1] * knobs['chat_prompt_len']],
                                'max_new_tokens': 2}, timeout=300)
        export = requests.post(
            f'{urls[0]}/prefill_export',
            json={'prompt_ids': warm_long,
                  'page_size': knobs['page_size']}, timeout=300)
        export.raise_for_status()
        requests.post(f'{urls[1]}/kv_import', json=export.json(),
                      timeout=300).raise_for_status()
        # Bytes-on-wire: the SAME export over the binary octet-stream
        # frame vs the JSON/base64 payload (the LB ships binary by
        # default; the ratio is the drop the binary wire buys).
        export_bin = requests.post(
            f'{urls[0]}/prefill_export',
            json={'prompt_ids': warm_long,
                  'page_size': knobs['page_size'],
                  'wire': 'binary'}, timeout=300)
        export_bin.raise_for_status()
        handoff_wire = {
            'json_bytes': len(export.content),
            'binary_bytes': len(export_bin.content),
            'bytes_ratio': round(
                len(export_bin.content) / max(len(export.content), 1),
                4),
        }
        mixed = _run_disagg_config(replica_urls=urls,
                                   roles=('mixed', 'mixed'), **knobs)
        disagg = _run_disagg_config(replica_urls=urls,
                                    roles=('prefill', 'decode'),
                                    **knobs)
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:  # pylint: disable=broad-except
                proc.kill()
    ratio = (disagg['chat_itl_p99_ms'] /
             max(mixed['chat_itl_p99_ms'], 1e-9))
    return {
        'long_prompt_len': knobs['long_prompt_len'],
        'prefill_chunk': engine['prefill_chunk'],
        'page_size': knobs['page_size'],
        'prefill_threshold': knobs['threshold'],
        'replicas_per_fleet': 2,
        'mixed': mixed,
        'disaggregated': disagg,
        'itl_p99_ratio_vs_mixed': round(ratio, 4),
        'handoff_wire': handoff_wire,
    }


def _batch_infer_probe(*, smoke: bool, vocab: int, seed: int
                       ) -> Dict[str, Any]:
    """Offline bulk inference riding the QoS floor (ISSUE 20): a
    saturating batch-infer driver streams a sharded manifest through
    the routing LB as QoS class `batch` while one interactive chat
    stream decodes.  A/B: the interactive stream's ITL on an idle
    fleet vs with the batch driver saturating — the floor the weighted
    QoS admission exists to protect — plus batch row throughput and
    how often the driver was shed-and-retried (the 429/Retry-After
    cooperative backoff contract)."""
    import json as json_lib
    import os
    import tempfile

    import numpy as np
    import requests

    from skypilot_tpu.batch import manifest as manifest_lib
    from skypilot_tpu.batch import runner as runner_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import model_server as model_server_lib
    from skypilot_tpu.serve import router as router_lib

    n_rows = 24 if smoke else 120
    max_new = 6 if smoke else 16
    chat_max_new = 32 if smoke else 300
    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix='skytpu-bench-batch-')
    input_path = os.path.join(tmp, 'input.jsonl')
    with open(input_path, 'w', encoding='utf-8') as f:
        for _ in range(n_rows):
            ids = [int(x) for x in rng.integers(1, vocab - 1, size=6)]
            f.write(json_lib.dumps({'prompt_ids': ids}) + '\n')
    run_dir = os.path.join(tmp, 'run')
    manifest_lib.build_manifest(input_path, run_dir, num_shards=4)

    def make_server():
        return model_server_lib.ModelServer(
            'tiny', max_len=64, max_batch=2, continuous_batching=True,
            kv_pages=48, page_size=8, prefill_chunk=16)

    # Smoke keeps one replica: the floor A/B (driver saturating the
    # engine vs one interactive stream) needs contention, not a fleet,
    # and a second server is mostly tier-1 compile time.
    servers = [make_server()] if smoke else [make_server(), make_server()]
    lb = lb_lib.SkyServeLoadBalancer(
        'http://127.0.0.1:1',
        router=router_lib.Router(threshold=10_000))
    shutdowns: List[Any] = []
    try:
        urls = []
        for server in servers:
            port, stop = model_server_lib.start_background(server)
            shutdowns.append(stop)
            urls.append(f'http://127.0.0.1:{port}')
        lb.set_replicas([{'url': u, 'role': 'mixed'} for u in urls])
        lb_port = lb.start()
        base = f'http://127.0.0.1:{lb_port}'
        # Warm both replicas' shapes before anything is timed.
        for url in urls:
            requests.post(f'{url}/generate',
                          json={'prompt_ids': [[1, 2, 3, 4, 5, 6]],
                                'max_new_tokens': 2}, timeout=300)

        def chat_session(max_new_tokens: int) -> List[float]:
            """One interactive SSE stream; token arrival times."""
            times: List[float] = []
            prompt = [int(x) for x in
                      rng.integers(1, vocab - 1, size=4)]
            with requests.post(f'{base}/generate_stream',
                               json={'prompt_ids': prompt,
                                     'max_new_tokens': max_new_tokens},
                               stream=True, timeout=300) as resp:
                for line in resp.iter_lines(chunk_size=16):
                    if line.startswith(b'data:') and \
                            b'[DONE]' not in line:
                        times.append(time.perf_counter())
            return times

        def itls_ms(times: List[float]) -> List[float]:
            return [(b - a) * 1e3 for a, b in zip(times, times[1:])]

        # A: the interactive stream on an idle fleet.
        idle_itls = itls_ms(chat_session(chat_max_new))

        # B: same stream with the batch driver saturating the pool.
        job = runner_lib.BatchInferJob(run_dir, base,
                                       max_new_tokens=max_new,
                                       inflight=8)
        summary_holder: Dict[str, Any] = {}

        def drive() -> None:
            summary_holder.update(job.run())

        driver = threading.Thread(target=drive, daemon=True)
        t0 = time.perf_counter()
        driver.start()
        loaded_itls: List[float] = []
        while True:  # at least one full interactive session under load
            loaded_itls.extend(itls_ms(chat_session(chat_max_new)))
            if not driver.is_alive():
                break
        driver.join(timeout=600)
        elapsed = time.perf_counter() - t0
    finally:
        lb.stop()
        for stop in shutdowns:
            stop()
        for server in servers:
            server.close()
    rows_done = summary_holder.get('rows') or 0
    return {
        'rows': rows_done,
        'shards': summary_holder.get('shards_total'),
        'duplicates_dropped': summary_holder.get('duplicates_dropped'),
        'driver_retries': summary_holder.get('retries'),
        'elapsed_s': round(elapsed, 3),
        'rows_per_s': round(rows_done / max(elapsed, 1e-9), 3),
        'idle_itl_p50_ms': round(_percentile(idle_itls, 50), 2),
        'idle_itl_p99_ms': round(_percentile(idle_itls, 99), 2),
        'loaded_itl_p50_ms': round(_percentile(loaded_itls, 50), 2),
        'loaded_itl_p99_ms': round(_percentile(loaded_itls, 99), 2),
        'itl_p99_ratio_vs_idle': round(
            _percentile(loaded_itls, 99) /
            max(_percentile(idle_itls, 99), 1e-9), 4),
    }


def _dynamic_roles_probe(cfg, params, *, smoke: bool, vocab: int,
                         seed: int) -> Dict[str, Any]:
    """Dynamic fractional role budgets vs static roles (ISSUE 17)
    under an adversarial shifting mix: an all-prefill burst (long
    prompts, 2 new tokens) flips mid-window into an all-decode burst
    (short prompts, long generations).  One replica must serve the
    whole shift — the per-replica core of the fleet A/B (the chaos
    scenario `workload_flip_morph` covers the fleet/LB layer; here the
    replica is an in-process engine so the measurement is engine
    capacity, not HTTP or GIL artifacts).  Static keeps a launch-time
    pure-role budget through the shift — BOTH pure roles are measured,
    and dynamic is scored against the better one, so the baseline is
    the strongest static choice, not a strawman: whichever pure role
    you pin, the other phase starves at its 1-token liveness floor.
    Dynamic gets what the controller's rebalancer pushes over
    /role_budget: prefill-leaning split while the burst is prefill,
    flipped in place (version-stamped, warm weights, no restart) to
    decode-leaning when the workload flips.  Headline:
    in_window_tokens_ratio (prompt + generated tokens of requests
    COMPLETED inside the fixed window, dynamic / best static).  The
    probe then replays the same prompts through a budget-flipping
    engine non-contended and byte-compares against an unclamped run:
    budgets may only reschedule work, never change tokens."""
    import itertools

    import numpy as np

    from skypilot_tpu.serve import batching_engine
    from skypilot_tpu.serve import scheduler as scheduler_lib

    slots = 8
    chunk = 32
    max_len = 96 if smoke else 224
    long_len = 64 if smoke else 160
    short_len = 4
    long_max_new = 2
    short_max_new = 40 if smoke else 48
    # The prefill burst is a wash by construction (the prefill-pinned
    # static and the prefill-leaning dynamic run the same budget); the
    # decode burst is where budget-matching pays, so it gets the
    # longer half of the window.
    phase_prefill_s = 0.6 if smoke else 2.0
    phase_decode_s = 1.8 if smoke else 5.0
    workers = 2 * slots
    ver = itertools.count(1)

    engine = batching_engine.ContinuousBatchingEngine(
        cfg, params, max_len=max_len, slots=slots,
        prefill_chunk=chunk)
    try:
        budget = scheduler_lib.RoleBudget

        # Warm every compile before any measured window — including
        # the SHRUNK chunk widths a decode-leaning budget clamps
        # prefill to (a 6-token budget buckets pieces at widths 8/6/4,
        # the 1-token pure-decode floor at width 1; cold, each is a
        # fresh XLA compile landing right in the window).  The one
        # engine is reused across configs, so all of them are equally
        # warm.
        engine.generate(list(range(1, long_len + 1)), long_max_new,
                        timeout=600)
        engine.generate(list(range(1, short_len + 1)), 4, timeout=600)
        engine.set_role_budget(budget.from_split(
            0.1, slots=slots, prefill_chunk=chunk, version=next(ver)))
        engine.generate(list(range(1, long_len + 1)), long_max_new,
                        timeout=600)
        engine.set_role_budget(budget.for_role(
            'decode', slots=slots, prefill_chunk=chunk,
            version=next(ver)))
        engine.generate(list(range(1, short_len + 1)), 4, timeout=600)
        engine.set_role_budget(None)

        def run_config(mode: str) -> Dict[str, Any]:
            swaps0 = engine.stats()['budget_swaps']
            if mode == 'dynamic':
                # The rebalancer's clamped prefill-leaning extreme;
                # flipped to decode-leaning mid-window below.
                engine.set_role_budget(budget.from_split(
                    0.9, slots=slots, prefill_chunk=chunk,
                    version=next(ver)))
            else:
                engine.set_role_budget(budget.for_role(
                    mode, slots=slots, prefill_chunk=chunk,
                    version=next(ver)))
            lock = threading.Lock()
            totals = {'in_window_tokens': 0, 'requests': 0,
                      'prefill_phase_tokens': 0,
                      'decode_phase_tokens': 0}
            t0 = time.perf_counter()
            t_flip = t0 + phase_prefill_s
            t_end = t_flip + phase_decode_s

            def client(idx: int) -> None:
                wrng = np.random.default_rng((seed, idx))
                while True:
                    now = time.perf_counter()
                    if now >= t_end:
                        return
                    prefill_phase = now < t_flip
                    if prefill_phase:
                        prompt = [int(x) for x in wrng.integers(
                            1, vocab - 1, size=long_len)]
                        max_new = long_max_new
                    else:
                        prompt = [int(x) for x in wrng.integers(
                            1, vocab - 1, size=short_len)]
                        max_new = short_max_new
                    out = engine.generate(prompt, max_new,
                                          timeout=120)
                    if time.perf_counter() <= t_end:
                        with lock:
                            totals['in_window_tokens'] += \
                                len(prompt) + len(out)
                            totals['requests'] += 1
                            key = ('prefill_phase_tokens'
                                   if prefill_phase
                                   else 'decode_phase_tokens')
                            totals[key] += len(prompt) + len(out)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(workers)]
            for t in threads:
                t.start()
            if mode == 'dynamic':
                # The mid-window rebalance: the workload flipped, so
                # the budget flips with it (in place, version-ordered
                # — running decodes finish, no restart).
                time.sleep(max(0.0, t_flip - time.perf_counter()))
                engine.set_role_budget(budget.from_split(
                    0.1, slots=slots, prefill_chunk=chunk,
                    version=next(ver)))
            for t in threads:
                t.join(timeout=180)
            totals['budget_swaps'] = (
                engine.stats()['budget_swaps'] - swaps0)
            return totals

        # The decode-pinned static is strictly the weaker baseline on
        # this mix (its prefill burst crawls at the 1-token floor); the
        # smoke skips it for tier-1 wall-clock and scores dynamic
        # against the prefill pin — the full run measures all three.
        static_prefill = run_config('prefill')
        static_decode = None if smoke else run_config('decode')
        dynamic = run_config('dynamic')

        # Token-exactness, non-contended: the SAME prompts through an
        # unclamped engine vs one whose budget flips between requests.
        # Budgets reschedule; they must never touch the token stream.
        exact_rng = np.random.default_rng((seed, 104729))
        exact_prompts = [
            [int(x) for x in exact_rng.integers(1, vocab - 1, size=n)]
            for n in (short_len, long_len, short_len + 3, long_len // 2)
        ]
        engine.set_role_budget(None)
        reference = [engine.generate(p, 8, timeout=120)
                     for p in exact_prompts]
        flipped = []
        for i, prompt in enumerate(exact_prompts):
            role = ('prefill', 'decode', 'mixed')[i % 3]
            engine.set_role_budget(budget.for_role(
                role, slots=slots, prefill_chunk=chunk,
                version=next(ver)))
            flipped.append(engine.generate(prompt, 8, timeout=120))
    finally:
        engine.stop()
    statics = [s for s in (static_prefill, static_decode)
               if s is not None]
    best_static = max(s['in_window_tokens'] for s in statics)
    ratio = dynamic['in_window_tokens'] / max(best_static, 1)
    out = {
        'slots': slots,
        'prefill_chunk': chunk,
        'long_prompt_len': long_len,
        'short_prompt_len': short_len,
        'phase_prefill_s': phase_prefill_s,
        'phase_decode_s': phase_decode_s,
        'workers': workers,
        'static_prefill': static_prefill,
        'dynamic': dynamic,
        'best_static_in_window_tokens': best_static,
        'in_window_tokens_ratio': round(ratio, 4),
        'outputs_match': flipped == reference,
    }
    if static_decode is not None:
        out['static_decode'] = static_decode
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--model', default='tiny')
    parser.add_argument('--slots', type=int, default=4)
    parser.add_argument('--max-len', type=int, default=512)
    parser.add_argument('--requests', type=int, default=48)
    parser.add_argument('--rate', type=float, default=150.0,
                        help='Poisson arrival rate (requests/s).  The '
                             'default SATURATES the CPU tiny config so '
                             'tokens/s measures engine capacity, not '
                             'offered load; lower it to probe latency '
                             'at sub-saturation.')
    parser.add_argument('--max-new-tokens', type=int, default=32)
    parser.add_argument('--prompt-lens', default='8,24,64,128',
                        help='Comma-separated prompt-length mix.')
    parser.add_argument('--prefill-chunk', type=int, default=256)
    parser.add_argument('--stall-prompt-len', type=int, default=2048,
                        help='Long-admission prompt for the ITL stall '
                             'probe.')
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--skip-legacy', action='store_true',
                        help='Skip the pre-pipeline A/B run.')
    parser.add_argument('--skip-stall-probe', action='store_true')
    parser.add_argument('--skip-paged-probes', action='store_true',
                        help='Skip the paged-KV capacity and '
                             'prefix-cache TTFT probes.')
    parser.add_argument('--skip-disagg-probe', action='store_true',
                        help='Skip the prefill/decode disaggregation '
                             'A/B (two replicas + routing LB over '
                             'real HTTP).')
    parser.add_argument('--skip-spec-probe', action='store_true',
                        help='Skip the self-speculative decoding A/B '
                             '(repetitive-text ITL + acceptance).')
    parser.add_argument('--skip-kernel-probe', action='store_true',
                        help='Skip the paged decode-kernel A/B '
                             '(gather vs Pallas parity/perf).')
    parser.add_argument('--skip-dynamic-roles', action='store_true',
                        help='Skip the dynamic fractional-role-budget '
                             'A/B (static pure pools vs in-place '
                             'budget rebalancing under a shifting '
                             'prefill/decode mix).')
    parser.add_argument('--skip-sp-probe', action='store_true',
                        help='Skip the multi-host sequence-parallel '
                             'long-context prefill scaling probe '
                             '(subprocess per host count).')
    parser.add_argument('--skip-batch-probe', action='store_true',
                        help='Skip the offline batch-infer QoS-floor '
                             'probe (saturating batch driver vs one '
                             'interactive stream, A/B ITL).')
    parser.add_argument('--page-size', type=int, default=16,
                        help='KV page size for the paged probes.')
    parser.add_argument('--prefix-len', type=int, default=256,
                        help='Shared system-prompt length for the '
                             'prefix-cache TTFT probe.')
    parser.add_argument('--smoke', action='store_true',
                        help='Seconds-scale config for CI '
                             '(tests/unit/test_bench_serve.py).')
    parser.add_argument('--pin', action='store_true',
                        help='With --smoke: write the pinned '
                             'BENCH_serve_smoke.json at the repo root. '
                             'Default smoke output goes to a temp path '
                             'so every tier-1 run does not churn the '
                             'pinned file.')
    parser.add_argument('--out', default=None,
                        help='Output JSON path (default '
                             'BENCH_serve.json; --smoke defaults to a '
                             'temp path unless --pin).')
    args = parser.parse_args()
    if args.smoke:
        # Seconds-scale but still SATURATING (offered load well above
        # the legacy engine's capacity) so speedup_vs_legacy measures
        # the decode loop, not the arrival process.
        args.requests = 32
        args.rate = 400.0
        args.max_new_tokens = 16
        args.prompt_lens = '4,8,16'
        args.max_len = 64
        args.prefill_chunk = 32
        args.stall_prompt_len = 96
        args.page_size = 8
        args.prefix_len = 96
    if args.out:
        out_path = args.out
    elif args.smoke:
        # Smoke runs on every tier-1 pass; writing the pinned file
        # each time was pure VCS churn — temp by default, --pin to
        # refresh the committed sample.
        if args.pin:
            out_path = 'BENCH_serve_smoke.json'
        else:
            import os
            import tempfile
            out_path = os.path.join(
                tempfile.gettempdir(),
                f'bench_serve_smoke-{os.getpid()}.json')
    else:
        out_path = 'BENCH_serve.json'

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.models import configs
    from skypilot_tpu.serve import batching_engine

    if jax.default_backend() != 'cpu':
        raise SystemExit(
            f'bench_serve.py is the CPU harness (see its header); the '
            f'JAX backend is {jax.default_backend()!r}.  Run it with '
            f'JAX_PLATFORMS=cpu.')
    cfg = configs.get_config(args.model)
    from skypilot_tpu.models.transformer import Transformer
    params = nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params'])
    vocab = cfg.vocab_size
    prompt_lens = [int(x) for x in args.prompt_lens.split(',')]

    # --smoke: serve /metrics on loopback and sample it around the
    # pipelined run (the observability signal the smoke asserts on).
    metrics_port = None
    metrics_shutdown = None
    scrape_samples: List[Dict[str, Any]] = []
    if args.smoke:
        from skypilot_tpu.observability import metrics as obs_metrics
        metrics_port, metrics_shutdown = (
            obs_metrics.start_exposition_server())

    results: Dict[str, Any] = {}
    profile_snapshot: Optional[Dict[str, Any]] = None
    for mode, pipelined in (('pipelined', True), ('legacy', False)):
        if mode == 'legacy' and args.skip_legacy:
            continue
        rng = np.random.default_rng(args.seed)
        workload = _workload(rng, args.requests, args.rate, prompt_lens,
                             args.max_new_tokens, vocab)
        eng = batching_engine.ContinuousBatchingEngine(
            cfg, params, max_len=args.max_len, slots=args.slots,
            prefill_chunk=args.prefill_chunk, pipelined=pipelined)
        try:
            # Warm every compile (tick, buckets, chunk) outside the
            # timed region with the REAL shapes — including the top
            # bucket the +25% prompt-length jitter can reach.
            warm_lens = sorted(set(prompt_lens) |
                               {int(max(prompt_lens) * 1.25) + 1})
            for base in warm_lens:
                eng.generate(list(range(1, base + 1)),
                             min(4, args.max_new_tokens), timeout=600)
            scraper = None
            if mode == 'pipelined' and metrics_port is not None:
                scrape_samples.append(_scrape_metrics(metrics_port))

                def _mid_scrape():
                    time.sleep(0.3)  # land inside the ~seconds run
                    scrape_samples.append(_scrape_metrics(metrics_port))

                scraper = threading.Thread(target=_mid_scrape)
                scraper.start()
            result = _run_load(eng, workload)
            if scraper is not None:
                scraper.join()
                scrape_samples.append(_scrape_metrics(metrics_port))
            if mode == 'pipelined':
                # Tick-phase attribution for the history record (the
                # perf-regression observatory keys breakdowns to runs).
                profile_snapshot = eng.profile()
        finally:
            eng.stop()
        results[mode] = result
    if metrics_shutdown is not None:
        metrics_shutdown()

    payload: Dict[str, Any] = {
        'metric': 'serve_decode_tokens_per_sec',
        'value': results['pipelined']['tokens_per_s'],
        'unit': 'tokens/s',
        'config': {
            'model': args.model,
            'slots': args.slots,
            'max_len': args.max_len,
            'requests': args.requests,
            'poisson_rate': args.rate,
            'max_new_tokens': args.max_new_tokens,
            'prompt_lens': prompt_lens,
            'prefill_chunk': args.prefill_chunk,
            'backend': jax.default_backend(),
        },
        'pipelined': results['pipelined'],
    }
    if 'legacy' in results:
        payload['legacy'] = results['legacy']
        legacy_tps = max(results['legacy']['tokens_per_s'], 1e-9)
        payload['speedup_vs_legacy'] = round(
            results['pipelined']['tokens_per_s'] / legacy_tps, 2)

    if scrape_samples:
        # The observability contract of the smoke: key series exist,
        # the latency histograms are exposed, and the counters are
        # monotone (and actually advanced) across the run's scrapes.
        for key in ('ticks', 'decode_tokens'):
            values = [s[key] for s in scrape_samples]
            if any(b < a for a, b in zip(values, values[1:])):
                raise RuntimeError(
                    f'/metrics counter {key} went BACKWARDS across '
                    f'scrapes: {values}')
            if values[-1] <= values[0]:
                raise RuntimeError(
                    f'/metrics counter {key} did not advance over the '
                    f'pipelined run: {values}')
        if not all(s['histograms_present'] for s in scrape_samples):
            raise RuntimeError(
                'queue-wait/ITL/TTFT histograms missing from /metrics')
        payload['metrics_scrape'] = {
            'samples': scrape_samples,
            'series_monotone': True,
        }

    if not args.skip_stall_probe:
        chunk_s = _measure_chunk_compute(
            cfg, params, args.prefill_chunk,
            args.stall_prompt_len + 64, vocab)
        max_new_bg = 80 if args.smoke else 400
        chunked = _stall_probe(
            cfg, params, slots=args.slots,
            prompt_len=args.stall_prompt_len,
            chunk=args.prefill_chunk, max_new_bg=max_new_bg,
            vocab=vocab, pipelined_chunked=True)
        unchunked = _stall_probe(
            cfg, params, slots=args.slots,
            prompt_len=args.stall_prompt_len,
            chunk=args.prefill_chunk, max_new_bg=max_new_bg,
            vocab=vocab, pipelined_chunked=False)
        # The engine runs at most one chunk between ticks, so a running
        # decode's worst gap is one chunk + one tick (+ host noise):
        # bound it by one chunk's compute plus a few baseline ITLs.
        bound_ms = round(chunk_s * 1e3 +
                         max(5 * chunked['baseline_itl_p50_ms'], 50.0),
                         2)
        payload['chunked_prefill_stall'] = {
            'stall_prompt_len': args.stall_prompt_len,
            'prefill_chunk': args.prefill_chunk,
            'chunk_compute_ms': round(chunk_s * 1e3, 2),
            'max_itl_during_admission_ms':
                chunked['max_itl_during_admission_ms'],
            'baseline_itl_p50_ms': chunked['baseline_itl_p50_ms'],
            'bound_ms': bound_ms,
            'stall_bounded_by_chunk':
                chunked['max_itl_during_admission_ms'] <= bound_ms,
            'unchunked_max_itl_ms':
                unchunked['max_itl_during_admission_ms'],
        }

    if not args.skip_paged_probes:
        ps = args.page_size
        payload['paged_capacity'] = _capacity_probe(
            cfg, params, dense_slots=args.slots,
            max_len=args.max_len, page_size=ps,
            prompt_len=8, max_new=8, vocab=vocab, quantize_kv=True,
            # Smoke caps concurrency at 16 (a 4x ratio already proves
            # the mechanism in seconds); the full run lets it ride.
            max_concurrency=16 if args.smoke else 256)
        probe_max_len = -(-(args.prefix_len + 16) // ps) * ps
        payload['prefix_cache'] = _prefix_probe(
            cfg, params, max_len=probe_max_len, page_size=ps,
            chunk=max(ps, 8), prefix_len=args.prefix_len,
            vocab=vocab, quantize_kv=True)

    if not args.skip_spec_probe:
        payload['spec_decode'] = _spec_probe(
            cfg, params, smoke=args.smoke, vocab=vocab,
            seed=args.seed)

    if not args.skip_kernel_probe:
        payload['paged_kernel'] = _kernel_probe(
            cfg, params, smoke=args.smoke, vocab=vocab,
            seed=args.seed)

    if not args.skip_disagg_probe:
        payload['disaggregation'] = _disagg_probe(
            smoke=args.smoke, vocab=vocab, seed=args.seed)

    if not args.skip_dynamic_roles:
        payload['dynamic_roles'] = _dynamic_roles_probe(
            cfg, params, smoke=args.smoke, vocab=vocab,
            seed=args.seed)

    if not args.skip_sp_probe:
        payload['sp_prefill'] = _sp_prefill_probe(smoke=args.smoke,
                                                  model=args.model)

    if not args.skip_batch_probe:
        payload['batch_infer'] = _batch_infer_probe(
            smoke=args.smoke, vocab=vocab, seed=args.seed)

    line = json.dumps(payload)
    print(line)
    with open(out_path, 'w', encoding='utf-8') as f:
        f.write(line + '\n')
    _append_history(args, payload, profile_snapshot)


def _append_history(args, payload: Dict[str, Any],
                    profile_snapshot: Optional[Dict[str, Any]]) -> None:
    """One run record into the perf-regression observatory
    (BENCH_history.jsonl; `sky bench diff` consumes it).  The
    COMMITTED history only grows behind --pin (a blessed run) or an
    explicit SKYTPU_BENCH_HISTORY_PATH — tier-1 runs this script
    (smoke AND full probes) on every pass and must not churn the
    repo; unblessed runs land in a throwaway per-process path."""
    import os
    import tempfile

    from skypilot_tpu.observability import bench_history
    path = None
    if (not args.pin and
            not os.environ.get('SKYTPU_BENCH_HISTORY_PATH')):
        path = os.path.join(
            tempfile.gettempdir(),
            f'bench_serve_history-{os.getpid()}.jsonl')
    pipelined = payload.get('pipelined') or {}
    phases = None
    if profile_snapshot:
        phases = {name: agg.get('total_s')
                  for name, agg in
                  (profile_snapshot.get('phases') or {}).items()}
    record = {
        'source': 'bench_serve',
        'metric': payload['metric'],
        'value': payload['value'],
        'unit': payload['unit'],
        'config': payload['config'],
        'tokens_per_s': pipelined.get('tokens_per_s'),
        'ttft_p50_ms': pipelined.get('ttft_p50_ms'),
        'ttft_p99_ms': pipelined.get('ttft_p99_ms'),
        'itl_p50_ms': pipelined.get('itl_p50_ms'),
        'itl_p99_ms': pipelined.get('itl_p99_ms'),
        'speedup_vs_legacy': payload.get('speedup_vs_legacy'),
        'phases': phases,
        'profiled_ticks': (profile_snapshot or {}).get('ticks'),
        'batch_rows_per_s':
            (payload.get('batch_infer') or {}).get('rows_per_s'),
        'batch_itl_p99_ratio':
            (payload.get('batch_infer') or {}).get(
                'itl_p99_ratio_vs_idle'),
    }
    try:
        where = bench_history.append_record(record, path)
        print(f'# bench history appended: {where}')
    except OSError as e:
        print(f'# bench history append failed: {e}')


if __name__ == '__main__':
    main()
