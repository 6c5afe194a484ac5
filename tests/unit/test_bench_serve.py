"""Serving perf smoke: `bench_serve.py --smoke` runs on every PR
(tier-1, NOT slow-marked — this is the guardrail that keeps the decode
hot loop fast).  Output goes to a TEMP path (the pinned
BENCH_serve_smoke.json at the repo root only refreshes behind
`--pin`, so tier-1 runs stop churning the committed sample)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def test_bench_serve_smoke(tmp_path):
    out_path = os.path.join(str(tmp_path), 'BENCH_serve_smoke.json')
    pinned = os.path.join(_REPO_ROOT, 'BENCH_serve_smoke.json')
    pinned_mtime = (os.path.getmtime(pinned)
                    if os.path.exists(pinned) else None)
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, 'bench_serve.py'),
         '--smoke', '--out', out_path],
        cwd=_REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=600, check=False)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out_path, encoding='utf-8') as f:
        data = json.load(f)
    # The pinned repo-root sample must NOT have been rewritten (that
    # was pure VCS churn; only --pin updates it).
    if pinned_mtime is not None:
        assert os.path.getmtime(pinned) == pinned_mtime
    # Schema the BENCH trajectory depends on.
    assert data['metric'] == 'serve_decode_tokens_per_sec'
    assert data['unit'] == 'tokens/s'
    assert data['value'] > 0
    for mode in ('pipelined', 'legacy'):
        stats = data[mode]
        assert stats['tokens'] > 0
        for key in ('tokens_per_s', 'ttft_p50_ms', 'ttft_p99_ms',
                    'itl_p50_ms', 'itl_p99_ms'):
            assert stats[key] >= 0, (mode, key, stats)
    # The pipelined loop must not regress below the pre-change engine
    # on the saturating smoke workload (the PR's perf claim is >= 1.5x;
    # the smoke asserts a conservative floor so CI noise can't flake).
    assert data['speedup_vs_legacy'] >= 1.2, data
    # Observability signal: the smoke scraped /metrics around the
    # pipelined run; key engine counters must exist, be monotone, and
    # have advanced (bench_serve itself raises when they don't).
    scrape = data['metrics_scrape']
    assert scrape['series_monotone'] is True
    samples = scrape['samples']
    assert len(samples) >= 2
    assert samples[-1]['ticks'] > samples[0]['ticks']
    assert samples[-1]['decode_tokens'] > samples[0]['decode_tokens']
    assert all(s['histograms_present'] for s in samples)
    stall = data['chunked_prefill_stall']
    assert stall['max_itl_during_admission_ms'] > 0
    assert stall['chunk_compute_ms'] > 0
    # Chunked admission must stall running decodes by at most ~one
    # chunk's compute (the bound includes scheduling slack).
    assert stall['stall_bounded_by_chunk'], stall
    # Paged KV: at the dense cache's exact memory budget, the int8
    # page pool must run >= 2x the concurrent slots (the full bench
    # pins >10x; 2x is the flake-proof floor) — and actually ran them
    # concurrently, then drained the pool.
    cap = data['paged_capacity']
    assert cap['max_concurrent_paged'] >= 2 * cap['max_concurrent_dense'], cap
    assert cap['peak_busy_slots'] >= 2 * cap['max_concurrent_dense'], cap
    assert cap['pool_drained'] is True, cap
    # Prefix cache: a shared-prefix hit must collapse TTFT (adopting
    # cached pages instead of re-prefilling; the full bench pins
    # <= 0.25x, the smoke floor is looser for CI noise).
    prefix = data['prefix_cache']
    assert prefix['prefix_hit_pages'] > 0, prefix
    assert prefix['ttft_hit_ratio'] <= 0.5, prefix
    assert prefix['ttft_hit_ms'] < prefix['ttft_cold_ms'], prefix
    # Self-speculative decoding (ISSUE 16): on repetitive text the
    # n-gram drafter must accept more than one token per verify tick
    # on average, the accepted burst must collapse ITL p50 (the full
    # bench sees ~80x; 1.2x is the flake-proof floor), and the token
    # stream must be byte-identical with drafting on vs off — speed
    # is the ONLY thing speculation is allowed to change.
    spec = data['spec_decode']
    assert spec['outputs_match'] is True, spec
    assert spec['spec_ticks'] > 0, spec
    assert spec['spec_accept_len_mean'] > 1.0, spec
    assert spec['itl_p50_speedup'] >= 1.2, spec
    # Pallas paged-attention kernel (ISSUE 16): both decode-kernel
    # paths run the same int8-paged workload and must agree token-for
    # -token.  No wall-clock claim — off-TPU the Pallas path runs
    # under the interpreter, so parity + presence is the contract.
    kern = data['paged_kernel']
    assert kern['outputs_match'] is True, kern
    for kernel in ('gather', 'pallas'):
        assert kern['kernels'][kernel]['tokens'] > 0, kern
    # Disaggregation (ISSUE 8): under the bursty long-prompt +
    # chat-decode workload, routing prefills to a prefill replica and
    # handing the KV pages to the decode replica must beat the
    # role-blind mixed fleet on in-flight decode ITL p99 during
    # bursts.  The full bench pins <= 0.5x; the smoke floor is looser
    # so shared-CI scheduling noise can't flake tier-1.
    disagg = data['disaggregation']
    assert disagg['disaggregated']['handoffs_ok'] >= 1, disagg
    assert disagg['disaggregated']['handoff_fallbacks'] == 0, disagg
    assert disagg['mixed']['chat_tokens_in_burst_window'] > 50, disagg
    assert disagg['disaggregated']['chat_tokens_in_burst_window'] > 50, \
        disagg
    assert disagg['itl_p99_ratio_vs_mixed'] <= 0.75, disagg
    # Binary KV-handoff wire (ISSUE 9 satellite): the octet-stream
    # frame must ship the SAME pages in materially fewer bytes than
    # the JSON/base64 wire (theory ~0.75x from dropping base64; the
    # floor leaves headroom for header overhead on tiny payloads).
    wire = disagg['handoff_wire']
    assert wire['binary_bytes'] > 0 and wire['json_bytes'] > 0, wire
    assert wire['bytes_ratio'] <= 0.85, wire
    # Multi-host slice prefill (ISSUE 9 tentpole): a 2-host emulated
    # slice (sequence-parallel ring attention, each host bringing its
    # own cores) must prefill the long context faster than one host.
    # Observed ~1.3x on the CI box; 1.05x is the flake-proof floor —
    # the claim is "improves with host count", pinned conservatively.
    sp = data['sp_prefill']
    assert sp['per_hosts']['1']['prefill_s'] > 0, sp
    assert sp['prefill_speedup_2x'] >= 1.05, sp
    # Dynamic fractional role budgets (ISSUE 17): one replica serves a
    # prefill burst that flips into a decode burst.  Rebalanced
    # budgets (prefill-leaning, then flipped in place mid-window) must
    # out-produce the BEST static pure-role pin on in-window tokens —
    # whichever pure role you choose, the other phase starves at its
    # 1-token liveness floor.  Observed ~1.4-1.8x on the CI box; 1.2x
    # is the flake-proof floor.  Budgets may reschedule work but never
    # change tokens: the non-contended replay must match exactly.
    # (The smoke pins only the prefill-leaning static — empirically the
    # stronger baseline on this mix; the slow full A/B measures the
    # decode pin too and scores dynamic against the best of both.)
    dyn = data['dynamic_roles']
    assert dyn['outputs_match'] is True, dyn
    assert dyn['dynamic']['budget_swaps'] >= 2, dyn
    for config in ('static_prefill', 'dynamic'):
        assert dyn[config]['in_window_tokens'] > 0, dyn
        assert dyn[config]['requests'] > 0, dyn
    assert dyn['in_window_tokens_ratio'] >= 1.2, dyn
    # Offline batch inference riding the QoS floor (ISSUE 20): the
    # saturating batch-infer driver must complete EVERY manifest row
    # through the LB (exactly-once ledger, no duplicates), and the
    # concurrent interactive stream must keep decoding — its ITL p99
    # under batch saturation may degrade but must stay within a
    # generous flake-proof envelope of the idle fleet (the weighted
    # QoS admission is what holds this floor; the full A/B below
    # measures the real ratio).
    batch = data['batch_infer']
    assert batch['rows'] == 24, batch
    assert batch['duplicates_dropped'] == 0, batch
    assert batch['rows_per_s'] > 0, batch
    for key in ('idle_itl_p50_ms', 'idle_itl_p99_ms',
                'loaded_itl_p50_ms', 'loaded_itl_p99_ms'):
        assert batch[key] > 0, (key, batch)
    assert batch['itl_p99_ratio_vs_idle'] <= 20, batch


@pytest.mark.slow
def test_bench_dynamic_roles_full(tmp_path):
    """The full (non-smoke) dynamic-roles A/B: longer windows, longer
    prompts/generations — the committed BENCH_serve.json section.
    Slow-marked; tier-1 runs the seconds-scale smoke floor above."""
    out_path = os.path.join(str(tmp_path), 'BENCH_dyn_roles.json')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, 'bench_serve.py'),
         '--skip-legacy', '--skip-stall-probe', '--skip-paged-probes',
         '--skip-disagg-probe', '--skip-spec-probe',
         '--skip-kernel-probe', '--skip-sp-probe',
         '--skip-batch-probe', '--out', out_path],
        cwd=_REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=900, check=False)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out_path, encoding='utf-8') as f:
        data = json.load(f)
    dyn = data['dynamic_roles']
    assert dyn['outputs_match'] is True, dyn
    # Full run measures BOTH pure-role pins; the ratio is vs the best.
    assert dyn['static_decode']['in_window_tokens'] > 0, dyn
    assert dyn['best_static_in_window_tokens'] == max(
        dyn['static_prefill']['in_window_tokens'],
        dyn['static_decode']['in_window_tokens']), dyn
    assert dyn['in_window_tokens_ratio'] >= 1.2, dyn
    # The decode burst is where budget-matching pays: the in-place
    # flip must clearly beat the prefill-pinned replica there.
    assert dyn['dynamic']['decode_phase_tokens'] > \
        1.5 * dyn['static_prefill']['decode_phase_tokens'], dyn


@pytest.mark.slow
def test_bench_batch_infer_full(tmp_path):
    """The full (non-smoke) batch-infer QoS-floor A/B: 120 manifest
    rows at driver inflight 8 against a 2-replica mixed fleet while a
    long interactive stream decodes.  Slow-marked; tier-1 runs the
    seconds-scale smoke floor above."""
    out_path = os.path.join(str(tmp_path), 'BENCH_batch_infer.json')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, 'bench_serve.py'),
         '--skip-legacy', '--skip-stall-probe', '--skip-paged-probes',
         '--skip-disagg-probe', '--skip-spec-probe',
         '--skip-kernel-probe', '--skip-dynamic-roles',
         '--skip-sp-probe', '--out', out_path],
        cwd=_REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=900, check=False)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out_path, encoding='utf-8') as f:
        data = json.load(f)
    batch = data['batch_infer']
    # Every row lands exactly once even at full scale.
    assert batch['rows'] == 120, batch
    assert batch['duplicates_dropped'] == 0, batch
    assert batch['rows_per_s'] > 0, batch
    # The QoS floor: an interactive stream sharing the fleet with a
    # saturating batch driver must not collapse.  Observed ~2-4x ITL
    # p99 inflation on the CI box; 10x is the flake-proof ceiling.
    assert batch['itl_p99_ratio_vs_idle'] <= 10, batch
