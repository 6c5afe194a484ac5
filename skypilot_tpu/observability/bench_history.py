"""Perf-regression observatory: an append-only history of bench runs
and noise-aware run-over-run diffing.

`bench.py` appends one JSON line per run — config, git rev,
throughput, latency quantiles, MFU estimate, and (for a serving run)
the profiler's tick-phase breakdown — to a committed
`BENCH_history.jsonl` at the repo root (`SKYTPU_BENCH_HISTORY_PATH`
overrides).  `sky bench diff` compares the newest run of each
(metric, config) group against its predecessors and exits non-zero on
regression.

The threshold is noise-aware: a key regresses when its relative change
in the bad direction exceeds ``max(min_rel, noise_k x cv)`` where
``cv`` is the coefficient of variation (stdev/mean) of the baseline
runs — a naturally jittery series needs a bigger move to count than a
dead-flat one.
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import time
from typing import Any, Dict, List, Optional

HISTORY_BASENAME = 'BENCH_history.jsonl'

# Direction per comparable key: True = larger is better.
HIGHER_IS_BETTER = {
    'value': True,
    'tokens_per_s': True,
    'mfu_estimate': True,
    'ttft_p50_ms': False,
    'ttft_p99_ms': False,
    'itl_p50_ms': False,
    'itl_p99_ms': False,
}

DEFAULT_MIN_REL = 0.10   # ignore moves under 10% regardless of noise
DEFAULT_NOISE_K = 3.0    # 3-sigma-of-relative-noise gate


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def history_path(path: Optional[str] = None) -> str:
    if path:
        return path
    env = os.environ.get('SKYTPU_BENCH_HISTORY_PATH')
    if env:
        return env
    return os.path.join(repo_root(), HISTORY_BASENAME)


def git_rev() -> Optional[str]:
    """Short git rev of the working tree (None outside a checkout —
    history must append fine from an exported tarball)."""
    try:
        out = subprocess.run(
            ['git', 'rev-parse', '--short', 'HEAD'],
            cwd=repo_root(), capture_output=True, text=True,
            timeout=10, check=False)
    except (OSError, subprocess.SubprocessError):
        return None
    rev = out.stdout.strip()
    return rev or None


def append_record(record: Dict[str, Any],
                  path: Optional[str] = None) -> str:
    """Append one run record (stamping ts/git_rev when absent);
    returns the path written."""
    record = dict(record)
    record.setdefault('ts', time.time())
    if 'git_rev' not in record:
        record['git_rev'] = git_rev()
    target = history_path(path)
    parent = os.path.dirname(target)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(target, 'a', encoding='utf-8') as f:
        f.write(json.dumps(record, sort_keys=True) + '\n')
    return target


def load_records(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """Every parseable record, file order (malformed lines skipped —
    a truncated append must not brick the observatory)."""
    target = history_path(path)
    if not os.path.exists(target):
        return []
    records: List[Dict[str, Any]] = []
    with open(target, 'r', encoding='utf-8') as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                records.append(rec)
    return records


def group_key(record: Dict[str, Any]) -> str:
    """Runs are comparable when metric AND config match — a slots=8
    run never baselines a slots=64 one."""
    return json.dumps({'metric': record.get('metric'),
                       'config': record.get('config')}, sort_keys=True)


def diff_records(records: List[Dict[str, Any]],
                 last: Optional[int] = None,
                 min_rel: float = DEFAULT_MIN_REL,
                 noise_k: float = DEFAULT_NOISE_K
                 ) -> List[Dict[str, Any]]:
    """Compare each group's newest run against its baseline (the
    `last` preceding runs; default: all of them).

    Returns one finding per comparable key of each group with >= 2
    runs: baseline mean, latest value, relative change, the noise-aware
    threshold, and whether the move is a regression (bad direction,
    over threshold).  Improvements and in-noise moves carry
    ``regression: False`` so callers can render the whole picture."""
    groups: Dict[str, List[Dict[str, Any]]] = {}
    for rec in records:
        groups.setdefault(group_key(rec), []).append(rec)
    findings: List[Dict[str, Any]] = []
    for key, runs in groups.items():
        runs = sorted(runs, key=lambda r: r.get('ts') or 0.0)
        if len(runs) < 2:
            continue
        latest = runs[-1]
        baseline_runs = runs[:-1]
        if last is not None and last > 0:
            baseline_runs = baseline_runs[-last:]
        meta = json.loads(key)
        for field, higher_better in HIGHER_IS_BETTER.items():
            cur = latest.get(field)
            prior = [r[field] for r in baseline_runs
                     if isinstance(r.get(field), (int, float))]
            if not isinstance(cur, (int, float)) or not prior:
                continue
            base = statistics.fmean(prior)
            if base == 0:
                continue
            cv = (statistics.pstdev(prior) / abs(base)
                  if len(prior) > 1 else 0.0)
            threshold = max(min_rel, noise_k * cv)
            change = (cur - base) / abs(base)
            worse = (change < 0) if higher_better else (change > 0)
            findings.append({
                'metric': meta['metric'],
                'config': meta['config'],
                'field': field,
                'baseline': base,
                'baseline_runs': len(prior),
                'latest': cur,
                'latest_rev': latest.get('git_rev'),
                'change': change,
                'threshold': threshold,
                'regression': bool(worse and abs(change) > threshold),
            })
    return findings


def format_findings(findings: List[Dict[str, Any]]) -> List[str]:
    """Human lines, regressions first."""
    lines: List[str] = []
    ordered = sorted(findings,
                     key=lambda f: (not f['regression'],
                                    str(f['metric']), f['field']))
    for f in ordered:
        flag = 'REGRESSION' if f['regression'] else 'ok'
        lines.append(
            f"[{flag}] {f['metric']} {f['field']}: "
            f"{f['baseline']:.4g} -> {f['latest']:.4g} "
            f"({f['change']:+.1%}, threshold ±{f['threshold']:.0%}, "
            f"baseline n={f['baseline_runs']}"
            + (f", rev {f['latest_rev']}" if f.get('latest_rev')
               else '') + ')')
    return lines
