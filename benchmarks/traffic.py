"""Seeded traffic and the loop that offers it.

One general generator reads a traffic mix from `workloads/<name>.json`;
a new mix is a new data file.  The generator and the open-loop
submitter come from `bench_serve.py` (`_workload`, `_run_load`,
`_Tracked`), corrected in two ways: first-token time counts from when a
request was *due*, not from when the submitter got round to it, and
lengths are drawn from a distribution instead of four fixed sizes.

Every seed offers the same work: lengths are the `levels`
mid-quantiles of a clipped log-normal, arrival gaps the mid-quantiles
of an exponential, and a seed only permutes them and draws the token
ids, so set-up can warm exactly the shapes the window will use.  Which
seed permutes them is the mix's choice: without `order_seed` the run's
own, so every run sees another order; with it, that fixed one, so
every run replays one arrival trace and the run's seed draws only the
token ids (and the weights).  On the chip a tail over 42 requests read
17% apart between orders and 1% apart between runs of one order (PR
25), so the cells fix theirs.

Keys of a mix (all but `loop` and the two length blocks optional):
  loop            "open" (needs `rate_per_s`) or "closed" (`clients`)
  judged          "tails" or "throughput": which end-to-end metrics the
                  cell reports (run.py reads it, nothing here does)
  prompt_tokens   {median, sigma, min, max, levels}: the request's own
                  tokens (the question, where a prefix is shared)
  output_tokens   {median, sigma, min, max, levels}
  order_seed      fixes the order of lengths and gaps (see above)
  dither_ms       open loop: each request falls due up to this much
                  later, drawn from the run's seed.  A fixed trace
                  replays one alignment of the arrivals with the
                  engine's loop to a tenth of a percent, until the
                  host stalls once and every later first token moves by
                  a part of a tick (PR 28): with the dither a run
                  samples the alignment, so the spread of a set of
                  seeds is the metric's own resolution on any machine
  shared_prefix   {count, tokens}: each request starts with one of
                  `count` seeded documents, drawn evenly; set-up asks
                  each document once, so the window runs on a filled
                  prefix cache
"""
from __future__ import annotations

import dataclasses
import math
import queue
import statistics
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    """One request and, once offered, the benchmark's own clock readings
    (seconds since the window opened)."""
    index: int
    prompt: List[int]
    max_new: int
    due_s: Optional[float] = None   # open loop: when it is due
    sent_s: Optional[float] = None
    token_s: List[float] = dataclasses.field(default_factory=list)
    done_s: Optional[float] = None
    error: Optional[str] = None
    handle: Any = None

    @property
    def start_s(self) -> Optional[float]:
        """Where first-token time counts from: due (open) or sent."""
        return self.due_s if self.due_s is not None else self.sent_s


def percentile(values: List[float], pct: float) -> float:
    """Nearest rank: the smallest value with `pct` percent of the
    sample at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(pct / 100.0 * len(ordered)) - 1)]


def levels(dist: Dict[str, Any]) -> List[int]:
    """The `levels` mid-quantiles of a log-normal, clipped to
    [min, max], in a fixed order that spreads any partial cycle over
    the whole range (stride by the golden ratio)."""
    n = int(dist['levels'])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        x = dist['median'] * math.exp(dist['sigma'] * z)
        out.append(int(min(dist['max'], max(dist['min'], round(x)))))
    stride = next(s for s in range(max(1, round(n * 0.618)), 2 * n + 2)
                  if math.gcd(s, n) == 1)
    return [out[(i * stride) % n] for i in range(n)]


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def _tokens(seed: int, stream: int, index: int, n: int,
            vocab: int) -> List[int]:
    return _rng(seed, stream, index).integers(1, vocab, size=n).tolist()


class Mix:
    """A traffic mix bound to a seed and a vocabulary: an endless,
    reproducible sequence of requests."""

    def __init__(self, spec: Dict[str, Any], seed: int, vocab: int):
        self.spec = spec
        self.seed = int(seed)
        self.order_seed = int(spec.get('order_seed', seed))
        self.vocab = int(vocab)
        self.prompt_levels = levels(spec['prompt_tokens'])
        self.output_levels = levels(spec['output_tokens'])
        # Work of one cycle: prompt level i with output level (i * 7 +
        # 3) mod m, the same pairs whatever the seed.
        self.cycle = len(self.prompt_levels)
        shared = spec.get('shared_prefix')
        self.prefixes = ([] if not shared else [
            _tokens(self.seed, 2, d, int(shared['tokens']), self.vocab)
            for d in range(int(shared['count']))])

    def pair(self, slot: int):
        m = len(self.output_levels)
        return (self.prompt_levels[slot % self.cycle],
                self.output_levels[(slot * 7 + 3) % m])

    def _order(self, cycle_no: int) -> np.ndarray:
        return _rng(self.order_seed, 1, cycle_no).permutation(self.cycle)

    def request(self, index: int) -> Request:
        slot = int(self._order(index // self.cycle)[index % self.cycle])
        own, max_new = self.pair(slot)
        prompt = _tokens(self.seed, 3, index, own, self.vocab)
        prefix: List[int] = []
        if self.prefixes:
            prefix = self.prefixes[(slot + index // self.cycle) %
                                   len(self.prefixes)]
        return Request(index, prefix + prompt, max_new)

    def open_schedule(self, seconds: float) -> List[Request]:
        """Requests due inside a window of `seconds` at `rate_per_s`:
        the first at half a mean gap, the gaps after it the
        mid-quantiles of an exponential in seeded order, scaled so that
        the last request falls half a mean gap before the close."""
        rate = float(self.spec['rate_per_s'])
        n = max(2, int(round(rate * seconds)))
        gaps = np.array([-math.log(1 - (i + 0.5) / (n - 1))
                         for i in range(n - 1)])
        gaps *= seconds * (n - 1) / n / gaps.sum()
        gaps = gaps[_rng(self.order_seed, 4).permutation(n - 1)]
        due = np.concatenate([[0.0], np.cumsum(gaps)]) + seconds / (2 * n)
        dither = float(self.spec.get('dither_ms', 0.0)) / 1e3
        if dither:
            due = due + _rng(self.seed, 8).uniform(0.0, dither, n)
        out = []
        for i in range(n):
            r = self.request(i)
            r.due_s = float(due[i])
            out.append(r)
        # A dither wider than the smallest gap can swap two neighbours.
        return sorted(out, key=lambda r: r.due_s)

    def warmup(self) -> List[List[Request]]:
        """Set-up's requests, in phases that run one after another.
        Where documents are shared, first each document once, so the
        window finds it cached.  Then one request of each prompt length
        the mix holds, since a length can compile shapes of its own
        (the program scatters a prefill into the pool with one program
        per count of fresh pages), two tokens each so the tick runs."""
        base = 1 << 30
        phases: List[List[Request]] = []
        if self.prefixes:
            phases.append([
                Request(base + 1000 + d,
                        p + _tokens(self.seed, 6, d, 17, self.vocab), 2)
                for d, p in enumerate(self.prefixes)])
        prefix = self.prefixes[0] if self.prefixes else []
        shapes: List[Request] = []
        seen = set()
        for slot in range(self.cycle):
            own, _ = self.pair(slot)
            if own in seen:
                continue
            seen.add(own)
            shapes.append(Request(
                base + len(shapes),
                prefix + _tokens(self.seed, 5, slot, own, self.vocab), 2))
        phases.append(shapes)
        return phases


class Driver:
    """Offers requests to `submit(prompt, max_new) -> handle` (the
    engine's own entry) from one thread and takes each token's arrival
    time on the benchmark's clock through `handle.add_watcher`."""

    def __init__(self, submit, t0: Optional[float] = None):
        self._submit = submit
        self.t0 = time.perf_counter() if t0 is None else t0
        self.sent: List[Request] = []
        self.lateness_s: List[float] = []
        self._finished: 'queue.Queue[Request]' = queue.Queue()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def offer(self, r: Request) -> None:
        r.sent_s = self.now()

        def watcher(token, r=r):
            if token is None:
                r.done_s = self.now()
                self._finished.put(r)
            else:
                r.token_s.append(self.now())

        self.sent.append(r)
        try:
            r.handle = self._submit(r.prompt, r.max_new)
        except Exception as e:  # pylint: disable=broad-except
            # A refusal (queue full, pool exhausted) is a failed request,
            # counted; it must not end the run.
            r.error = f'{type(e).__name__}: {e}'
            r.done_s = self.now()
            self._finished.put(r)
            return
        r.handle.add_watcher(watcher)

    def run_open(self, schedule: List[Request], seconds: float) -> None:
        for r in schedule:
            wait = r.due_s - self.now()
            if wait > 0:
                time.sleep(wait)
            self.lateness_s.append(max(0.0, self.now() - r.due_s))
            self.offer(r)
        rest = seconds - self.now()
        if rest > 0:
            time.sleep(rest)

    def run_closed(self, requests: Iterator[Request], clients: int,
                   seconds: float) -> None:
        """`clients` requests in flight, the next offered as one
        returns, until `seconds` have passed or `requests` runs out and
        the last has returned."""
        in_flight = 0
        for r in requests:
            self.offer(r)
            in_flight += 1
            if in_flight < clients:
                continue
            rest = seconds - self.now()
            if rest <= 0:
                return
            try:
                self._finished.get(timeout=rest)
            except queue.Empty:
                return
            in_flight -= 1
        while in_flight:
            try:
                self._finished.get(timeout=max(0.0, seconds - self.now()))
            except queue.Empty:
                return
            in_flight -= 1

    def wait_first_tokens(self, timeout: float) -> None:
        """After the close: a request of the window whose first token
        is still out is waited for (it is late, not lost)."""
        deadline = time.perf_counter() + timeout
        for r in self.sent:
            while (not r.token_s and r.done_s is None and
                   time.perf_counter() < deadline):
                time.sleep(0.002)
