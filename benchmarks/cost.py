"""Peaks and bytes: what the yardstick knows of the chip and of no
architecture.  What a model needs (parameters, FLOPs, cache bytes) is
its family's to count (`families/<family>.py`).  A later PR may change
the program; it may not change this file.
"""
from __future__ import annotations

from typing import Any, Dict

# Published per-chip peaks, keyed by a substring of `device_kind`, most
# specific first.  Source: Google Cloud TPU documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s), "TPU v5p" (459 TFLOP/s,
# 2765 GB/s), "TPU v6e" (918 TFLOP/s, 1640 GB/s), "TPU v4" (275 TFLOP/s,
# 1200 GB/s).  The FLOP/s column is copied from bench.py `_peak_flops`.
# A device that is not here is an error, never a default.
_PEAKS = (
    ('v6 lite', {'flops_bf16': 918e12, 'hbm_bytes_per_s': 1640e9}),
    ('v6e', {'flops_bf16': 918e12, 'hbm_bytes_per_s': 1640e9}),
    ('v5 lite', {'flops_bf16': 197e12, 'hbm_bytes_per_s': 819e9}),
    ('v5litepod', {'flops_bf16': 197e12, 'hbm_bytes_per_s': 819e9}),
    ('v5e', {'flops_bf16': 197e12, 'hbm_bytes_per_s': 819e9}),
    ('v5p', {'flops_bf16': 459e12, 'hbm_bytes_per_s': 2765e9}),
    ('v4', {'flops_bf16': 275e12, 'hbm_bytes_per_s': 1200e9}),
)

DTYPE_BYTES = {'bfloat16': 2, 'float16': 2, 'float32': 4, 'int8': 1}


def peaks(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    for key, row in _PEAKS:
        if key in kind:
            return dict(row)
    raise ValueError(
        f'no published peaks on record for device_kind {device_kind!r}; '
        f'add a row with its source to benchmarks/cost.py')


def paged_attention_floor_s(cache_bytes: int, flops: int,
                            peak: Dict[str, float]) -> Dict[str, Any]:
    """Least time for decode attention that has to read `cache_bytes`
    from HBM once and compute `flops` over them (a family's
    `decode_cache_bytes` and `decode_attention_flops`, summed over the
    contexts decoded): whichever takes longer at the peaks; says which
    bound it was."""
    by_bytes = cache_bytes / peak['hbm_bytes_per_s']
    by_flops = flops / peak['flops_bf16']
    return {'seconds': max(by_bytes, by_flops),
            'bound': 'hbm' if by_bytes >= by_flops else 'flops'}
