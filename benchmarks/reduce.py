"""From a profiler trace (`.xplane.pb`) to numbers.

The smallest reduction the per-layer metrics need: for every device
plane, the line of executed operations; the union of their intervals
(busy time); time by operation name; the operations that took most
time of their own; the longest idle gaps.  Read with nothing but
`jax.profiler.ProfileData`.

On a TPU the operations of a compiled program sit on the line "XLA
Ops" of a plane "/device:TPU:<n>", each named by its whole HLO text,
and the programs themselves on the line "XLA Modules"
(`jit_paged_engine_step(<hash>)`).  An operation is told by a short
name: the program it ran in, its HLO name and opcode, and a custom
call's target (`paged_engine_step/%closed_call.8 custom-call
tpu_custom_call`).  A `while` (the layer scan) is an event that
contains its body's events, so time by name is the event's whole
duration, and time of its own is that less the events inside it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Tuple

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
_HLO = re.compile(r'^(%[\w.\-]+) = .*?\s([a-z][a-z\-]*)\(')
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_MODULE = re.compile(r'^jit_+(.*?)\(\d+\)$')

Event = Tuple[str, int, int]    # name, start_ns, duration_ns


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not paths:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return paths[-1]


def short_name(hlo: str, module: str = '') -> str:
    """`<program>/<%name> <opcode> [<custom call target>]` from an
    operation's HLO text; other names pass, cut to 120 characters."""
    m = _HLO.match(hlo)
    if not m:
        return f'{module}/{hlo}'[:120] if module else hlo[:120]
    target = _TARGET.search(hlo)
    return (f'{module}/{m.group(1)} {m.group(2)}' +
            (f' {target.group(1)}' if target else ''))[:120]


def device_events(path: str) -> Dict[int, List[Event]]:
    """device ordinal -> its operation events under their short names,
    each put to the program whose interval holds its start."""
    import bisect
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[int, List[Event]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        modules = sorted(
            (int(e.start_ns), int(e.start_ns + e.duration_ns),
             (_MODULE.match(e.name).group(1) if _MODULE.match(e.name)
              else e.name))
            for e in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        starts = [mod[0] for mod in modules]
        names: Dict[Tuple[str, str], str] = {}
        events = []
        for e in lines[OPS_LINE].events:
            start = int(e.start_ns)
            i = bisect.bisect_right(starts, start) - 1
            module = modules[i][2] if i >= 0 and start < modules[i][1] \
                else ''
            key = (e.name, module)
            if key not in names:
                names[key] = short_name(e.name, module)
            events.append((names[key], start, int(e.duration_ns)))
        out[int(m.group(1))] = events
    return out


def reduce_device(events: List[Event]) -> Dict[str, Any]:
    """One device's operations -> window, busy union, time by name, own
    time by name, idle gaps (start offset and length, longest first)."""
    if not events:
        return {'window_s': 0.0, 'busy_s': 0.0, 'by_name': {},
                'own_by_name': {}, 'gaps': []}
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    t_first = events[0][1]
    t_last = max(s + d for _, s, d in events)
    by_name: Dict[str, float] = {}
    own: Dict[str, float] = {}
    busy_ns = 0
    gaps: List[Tuple[float, float]] = []
    cover_end = t_first
    stack: List[List[Any]] = []     # [name, end, own_ns]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own_ns = stack.pop()
            own[name] = own.get(name, 0.0) + own_ns / 1e9

    for name, start, dur in events:
        end = start + dur
        by_name[name] = by_name.get(name, 0.0) + dur / 1e9
        close(start)
        if stack:
            stack[-1][2] -= min(dur, max(0, stack[-1][1] - start))
        stack.append([name, end, dur])
        if start > cover_end:
            gaps.append(((cover_end - t_first) / 1e9,
                         (start - cover_end) / 1e9))
            busy_ns += dur
            cover_end = end
        elif end > cover_end:
            busy_ns += end - cover_end
            cover_end = end
    close(t_last)
    gaps.sort(key=lambda g: -g[1])
    return {'window_s': (t_last - t_first) / 1e9, 'busy_s': busy_ns / 1e9,
            'by_name': by_name, 'own_by_name': own, 'gaps': gaps}


def reduce_trace(path: str, chips: int) -> Dict[str, Any]:
    """The trace as the per-layer metrics read it: busy and window
    seconds averaged over the chips used, time by name summed over
    them, and the two lists of the result line's `breakdown`."""
    per_device = {k: reduce_device(v)
                  for k, v in sorted(device_events(path).items())}
    used = [d for d in per_device.values() if d['busy_s'] > 0][:chips]
    if not used:
        raise RuntimeError(
            f'the trace {path} shows no operation on any device plane '
            f'(planes matching {DEVICE_PLANE.pattern}, line {OPS_LINE!r})')
    by_name: Dict[str, float] = {}
    own: Dict[str, float] = {}
    for d in used:
        for k, v in d['by_name'].items():
            by_name[k] = by_name.get(k, 0.0) + v
        for k, v in d['own_by_name'].items():
            own[k] = own.get(k, 0.0) + v
    top = sorted(own.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted((g for d in used for g in d['gaps']),
                  key=lambda g: -g[1])[:10]
    return {
        'devices': len(used),
        'window_s': sum(d['window_s'] for d in used) / len(used),
        'busy_s': sum(d['busy_s'] for d in used) / len(used),
        'by_name': by_name,
        'own_by_name': own,
        'breakdown': {
            'device_ops': [[k, v] for k, v in top],
            # Gaps are listed by length only: the host spans that would
            # say what the host was doing in each are a later PR's.
            'idle_gaps': [[f'unattributed@{at:.3f}s', length]
                          for at, length in gaps],
        },
    }
