#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the serving main path once, through the entry points a user
calls, at the full width of Llama-3-8B (`models/configs.LLAMA3_8B`,
depth cut to 4 layers so one 16 GB chip holds it, random bf16 weights
from a seed):

  make-model    write a converted-checkpoint directory
                (`model_config.json` + orbax step 0 of {'params': ...},
                the layout `models/import_weights.save_converted`
                writes and `--model auto` reads)
  serve:bf16    `python -m skypilot_tpu.serve.model_server --model auto
                --checkpoint-dir <dir> --continuous-batching --kv-pages
                ...` on the default async front; prompts of three
                lengths (one longer than two prefill chunks: chunk 0
                takes the flash kernel, later chunks the masked path),
                each sent alone twice (cold, then through the prefix
                cache: the greedy repeat must be identical), then
                fresh prompts all in flight at once (prefill chunks
                interleaved with ticks that run several slots), and
                one `/generate_stream`
  serve:int8kv  the same with `--quantize-kv` (int8 kernel body)
  serve:spec    the same with `--spec-tokens 3` (the S = k+1 verify tick)
  serve:bf16:cached
                serve:bf16 again: every program now comes from the
                compile cache, and greedy tokens must equal the cold run's

`--chips 4` runs the four-chip host instead: the server with
`--tensor 4` on the same requests, then `examples/train_llama.py
--model auto --init-from <dir> --fused-ce --fsdp 2 --tensor 2` for six
steps at seq-len 2048 (depth 2, so weights, gradients and Adam state at
16 bytes a parameter fit 64 GB).

It passes only on a TPU: every request returns the number of tokens
asked with ids inside the vocabulary, greedy repeats are identical, the
decode kernel is the Pallas one and not interpreted, no engine failed,
and every child reported platform `tpu`.  The last line of stdout is
then `{"ok": true, "device": {...}}` with the device as the children's
JAX reported it.  Anything else exits non-zero and prints no result.

One process per chip: this parent imports nothing that imports JAX
(stdlib only); each phase is a child that owns the chip alone and has
exited before the next starts.

`--dry-run` runs the same phases here at `tiny` size with
`JAX_PLATFORMS=cpu` and the kernels interpreted, to debug the script
without a chip.  Its output says `device=cpu, not a pass`.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, '.chip_smoke')
# The contract is 1200 s, compilation included; stop before that with a
# reason rather than be killed without one.
DEADLINE_S = 1150.0
READY_S = 420.0          # a server that has not answered by then is hung
NO_ACCELERATOR = 3       # make-model child's exit code: platform != tpu

FULL = {
    'preset': 'llama3-8b', 'overrides': {},
    'serve_layers': 4, 'train_layers': 2,
    'max_len': 1024, 'slots': 4, 'kv_pages': 512, 'page_size': 16,
    'prefill_chunk': 256, 'new_tokens': 16, 'spec_tokens': 3,
    # (n - 1) is a whole number of pages, so the repeat of a prompt is a
    # full prefix-cache hit and decodes from the very same KV bytes: in
    # the same slot, identical greedy tokens are then owed by
    # construction, not by luck of bf16 rounding.  641 spans three
    # prefill chunks.
    'prompt_lens': (17, 193, 641),
    'train': {'seq_len': 2048, 'batch': 4, 'steps': 6},
}
DRY = {
    'preset': 'tiny', 'overrides': {'n_heads': 8, 'n_kv_heads': 4},
    'serve_layers': 2, 'train_layers': 2,
    'max_len': 128, 'slots': 4, 'kv_pages': 64, 'page_size': 8,
    'prefill_chunk': 32, 'new_tokens': 8, 'spec_tokens': 3,
    'prompt_lens': (9, 25, 73),
    'train': {'seq_len': 64, 'batch': 4, 'steps': 6},
}


class SmokeFailure(Exception):
    """A phase did not meet the contract; the message says which."""


# ------------------------------------------------------------------ children


def _child_make_model(out_dir: str, n_layers: int, seed: int,
                      dry_run: bool) -> None:
    """Child process: random weights from a seed -> converted-checkpoint
    directory.  Prints one JSON line: the device and what was written."""
    import jax

    dev = jax.devices()[0]
    # The same shape the server's GET / reports.
    report = {'device': {'platform': dev.platform, 'kind': dev.device_kind,
                         'count': len(jax.devices())},
              'jax_version': jax.__version__}
    if dev.platform != 'tpu' and not dry_run:
        # Say what was found and stop before any work is done.
        print(json.dumps(report))
        sys.exit(NO_ACCELERATOR)

    import flax.linen as nn
    import jax.numpy as jnp

    from skypilot_tpu import compile_cache
    from skypilot_tpu.models import configs
    from skypilot_tpu.models import import_weights
    from skypilot_tpu.models.transformer import Transformer

    cache_dir = compile_cache.enable()
    size = DRY if dry_run else FULL
    cfg = configs.get_config(size['preset'], n_layers=n_layers,
                             **size['overrides'])
    # Stored in the activation dtype (bf16 at Llama widths) and from
    # host arrays, as `import_weights --dtype bfloat16` stores a served
    # model; model_config.json keeps the config's own param_dtype.
    model = Transformer(cfg.replace(param_dtype=cfg.dtype))
    params = jax.jit(lambda rng: nn.meta.unbox(
        model.init(rng, jnp.zeros((1, 8), jnp.int32))['params']))(
            jax.random.PRNGKey(seed))
    params = jax.device_get(params)
    leaves = jax.tree_util.tree_leaves(params)
    import_weights.save_converted(out_dir, params, cfg)
    print(json.dumps({
        **report, 'cache_dir': cache_dir, 'n_layers': cfg.n_layers,
        'd_model': cfg.d_model, 'vocab_size': cfg.vocab_size,
        'n_params': int(sum(a.size for a in leaves)),
        'bytes': int(sum(a.nbytes for a in leaves)),
    }))


# ------------------------------------------------------------------- helpers


def _http(method: str, url: str, body: Any = None,
          timeout: float = 600.0) -> Tuple[int, Any]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={'Content-Type': 'application/json'})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
            code = resp.status
    except urllib.error.HTTPError as e:
        raw, code = e.read(), e.code
    try:
        return code, json.loads(raw)
    except json.JSONDecodeError:
        return code, raw.decode(errors='replace')


def _http_sse(url: str, body: Any, timeout: float = 600.0) -> List[str]:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method='POST',
        headers={'Content-Type': 'application/json'})
    events = []
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith('data: '):
                events.append(line[len('data: '):])
    return events


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _prompt(length: int, vocab: int, seed: int) -> List[int]:
    """`length` token ids in [1, vocab) from a tiny LCG: the parent
    must not import numpy's cousins, and needs no better randomness."""
    out, x = [], (seed * 2654435761 + 12345) % (1 << 31)
    for _ in range(length):
        x = (1103515245 * x + 12345) % (1 << 31)
        out.append(1 + (x >> 8) % (vocab - 1))
    return out


def _tail(path: str, n: int = 60) -> str:
    try:
        with open(path, errors='replace') as f:
            return ''.join(f.readlines()[-n:])
    except OSError as e:
        return f'(no log: {e})'


# --------------------------------------------------------------------- smoke


class Smoke:

    def __init__(self, args: argparse.Namespace) -> None:
        self.dry_run: bool = args.dry_run
        self.chips: int = args.chips
        self.keep: bool = args.keep
        self.size = DRY if self.dry_run else FULL
        self.log_dir = os.path.abspath(
            args.log_dir or os.path.join(WORK, 'logs'))
        self.t0 = time.monotonic()
        self.procs: List[subprocess.Popen] = []
        self.devices: List[Dict[str, Any]] = []   # one per child
        self.vocab: Optional[int] = None

    # -------------------------------------------------------------- plumbing

    def left(self) -> float:
        left = DEADLINE_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise SmokeFailure(f'over the {DEADLINE_S:.0f} s budget')
        return left

    def env(self) -> Dict[str, str]:
        env = dict(os.environ)
        env['PYTHONPATH'] = os.pathsep.join(
            p for p in (ROOT, env.get('PYTHONPATH')) if p)
        env['PYTHONUNBUFFERED'] = '1'
        # Whatever a child builds at run time lands under the work dir.
        env['SKYTPU_HOME'] = os.path.join(WORK, 'home')
        if self.dry_run:
            env['JAX_PLATFORMS'] = 'cpu'
            env['SKYTPU_PALLAS_INTERPRET'] = '1'
            env['XLA_FLAGS'] = (
                f'--xla_force_host_platform_device_count={self.chips}')
        return env

    def spawn(self, name: str, argv: List[str]) -> Tuple[
            subprocess.Popen, str]:
        log = os.path.join(self.log_dir, name.replace(':', '_') + '.log')
        with open(log, 'w') as f:
            proc = subprocess.Popen(  # pylint: disable=consider-using-with
                [sys.executable] + argv, cwd=ROOT, env=self.env(),
                stdout=f, stderr=subprocess.STDOUT,
                start_new_session=True)
        self.procs.append(proc)
        return proc, log

    def stop(self, proc: subprocess.Popen) -> None:
        """Terminate a child and whatever it started (own session)."""
        if proc.poll() is None:
            for sig, wait in ((signal.SIGTERM, 20), (signal.SIGKILL, 10)):
                try:
                    os.killpg(proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    proc.wait(timeout=wait)
                    break
                except subprocess.TimeoutExpired:
                    continue
        if proc in self.procs:
            self.procs.remove(proc)

    def cleanup(self) -> None:
        for proc in list(self.procs):
            self.stop(proc)
        if not self.keep:
            shutil.rmtree(os.path.join(WORK, 'models'), ignore_errors=True)

    def run_child(self, name: str, argv: List[str],
                  ok_codes: Tuple[int, ...] = (0,)) -> Tuple[str, float]:
        """Run a child to its end; returns (its output, wall seconds)."""
        t = time.monotonic()
        proc, log = self.spawn(name, argv)
        try:
            proc.wait(timeout=self.left())
        except subprocess.TimeoutExpired:
            self.stop(proc)
            raise SmokeFailure(f'{name}: still running at the budget; '
                               f'log tail:\n{_tail(log)}') from None
        self.procs.remove(proc)
        if proc.returncode not in ok_codes:
            raise SmokeFailure(f'{name}: exit code {proc.returncode}; '
                               f'log tail:\n{_tail(log)}')
        with open(log, errors='replace') as f:
            return f.read(), time.monotonic() - t

    def note_device(self, name: str, device: Dict[str, Any],
                    jax_version: str) -> None:
        """Record what a child's JAX reported ({platform, kind, count});
        anything but the TPU asked for fails the run."""
        platform, count = device['platform'], device['count']
        self.devices.append(device)
        print(f'phase={name} platform={platform} device_kind='
              f'{device["kind"]!r} count={count} jax={jax_version}',
              flush=True)
        if platform != 'tpu' and not self.dry_run:
            raise SmokeFailure(
                f'{name}: JAX found no accelerator (device={platform}); '
                f'this smoke passes only on a TPU.  --dry-run debugs '
                f'the script on the CPU.')
        if count != self.chips and not self.dry_run:
            raise SmokeFailure(f'{name}: {count} device(s), expected '
                               f'{self.chips} (--chips)')

    # ---------------------------------------------------------------- phases

    def make_model(self, n_layers: int) -> str:
        name = f'make-model:L{n_layers}'
        out_dir = os.path.join(WORK, 'models', f'L{n_layers}')
        shutil.rmtree(out_dir, ignore_errors=True)
        argv = [os.path.abspath(__file__), '--child-make-model', out_dir,
                '--layers', str(n_layers)]
        if self.dry_run:
            argv.append('--dry-run')
        out, wall = self.run_child(name, argv,
                                   ok_codes=(0, NO_ACCELERATOR))
        info = json.loads(out.strip().splitlines()[-1])
        # Raises when the child stopped at a platform that is not tpu.
        self.note_device(name, info['device'], info['jax_version'])
        self.vocab = info['vocab_size']
        print(f'phase={name} ok wall_s={wall:.1f} n_layers='
              f'{info["n_layers"]} d_model={info["d_model"]} vocab='
              f'{info["vocab_size"]} params={info["n_params"] / 1e9:.3f}B '
              f'bytes={info["bytes"] / 1e9:.2f}GB cache_dir='
              f'{info["cache_dir"]}', flush=True)
        return out_dir

    def serve(self, name: str, model_dir: str, extra: List[str],
              expect: Optional[Dict[int, List[int]]] = None
              ) -> Dict[int, List[int]]:
        """One server child: start, probe, requests, stop.  Returns
        {prompt length: greedy tokens}; `expect` (an earlier phase's
        return, same programs and weights) must be reproduced."""
        s = self.size
        port = _free_port()
        base = f'http://127.0.0.1:{port}'
        t_start = time.monotonic()
        proc, log = self.spawn(name, [
            '-m', 'skypilot_tpu.serve.model_server', '--model', 'auto',
            '--checkpoint-dir', model_dir, '--continuous-batching',
            '--kv-pages', str(s['kv_pages']),
            '--page-size', str(s['page_size']),
            '--max-len', str(s['max_len']),
            '--max-batch', str(s['slots']),
            '--prefill-chunk', str(s['prefill_chunk']),
            '--port', str(port)] + extra)
        try:
            health = self._wait_ready(name, proc, log, base)
            start_s = time.monotonic() - t_start
            self.note_device(name, health['device'],
                             health['jax_version'])
            kernel = health['decode_kernel']
            interpret = health['pallas_interpret']
            if kernel != 'pallas':
                raise SmokeFailure(
                    f'{name}: decode kernel is {kernel!r}, not the '
                    f'Pallas kernel')
            if interpret and not self.dry_run:
                raise SmokeFailure(f'{name}: kernels run interpreted')

            prompts = {n: _prompt(n, self.vocab, seed=n)
                       for n in s['prompt_lens']}
            # One at a time, twice: cold (prefill), then through the
            # prefix cache.  Alone, a request always takes slot 0, and
            # only then is "the greedy repeat is identical" owed: under
            # --tensor N the low-order bits depend on the slot's row in
            # the collectives (measured: same slot identical over 300
            # tokens with or without neighbours, another slot may flip
            # an argmax of these random weights), so requests racing
            # for slots are checked for shape, not for equality.
            t = time.monotonic()
            first = self._round(name, base, prompts, concurrent=False)
            cold_s = time.monotonic() - t
            t = time.monotonic()
            again = self._round(name, base, prompts, concurrent=False)
            repeat_s = time.monotonic() - t
            if again != first:
                raise SmokeFailure(
                    f'{name}: greedy repeat differs: {first} then '
                    f'{again}')
            # Fresh prompts, all at once: prefill chunks interleave
            # with ticks that run several slots.
            t = time.monotonic()
            self._round(name, base,
                        {n: _prompt(n, self.vocab, seed=n + 1000)
                         for n in s['prompt_lens']}, concurrent=True)
            together_s = time.monotonic() - t
            if expect is not None and first != expect:
                raise SmokeFailure(
                    f'{name}: greedy tokens differ from the earlier '
                    f'run of the same programs: {expect} then {first}')

            t = time.monotonic()
            shortest = min(prompts)
            events = _http_sse(
                base + '/generate_stream',
                {'prompt_ids': prompts[shortest],
                 'max_new_tokens': s['new_tokens']}, timeout=self.left())
            stream_s = time.monotonic() - t
            if not events or events[-1] != '[DONE]':
                raise SmokeFailure(f'{name}: stream did not end in '
                                   f'[DONE]: {events[-3:]}')
            streamed = [json.loads(e)['token'] for e in events[:-1]]
            if streamed != first[shortest]:
                raise SmokeFailure(
                    f'{name}: /generate_stream gave {streamed}, '
                    f'/generate gave {first[shortest]}')

            code, health = _http('GET', base + '/', timeout=60)
            engine = health.get('engine', {}) if code == 200 else {}
            if code != 200 or engine.get('failed'):
                raise SmokeFailure(f'{name}: engine failed: {code} '
                                   f'{str(health)[:400]}')
            code, metrics = _http('GET', base + '/metrics', timeout=60)
            gauge = [ln.split()[-1] for ln in str(metrics).splitlines()
                     if ln.startswith('skytpu_engine_decode_kernel_pallas')]
            if code != 200 or not gauge or float(gauge[0]) != 1.0:
                raise SmokeFailure(
                    f'{name}: skytpu_engine_decode_kernel_pallas is '
                    f'{gauge} (want 1)')
            if '--spec-tokens' in extra and not engine.get('spec_ticks'):
                raise SmokeFailure(f'{name}: no speculative tick ran')
            # Counts for the record (kept beside the log): which jitted
            # entries compiled how often, the host-clock tick phases.
            code, profile = _http('GET', base + '/profile', timeout=60)
            compiles = ''
            if code == 200 and profile.get('profile'):
                with open(log[:-len('.log')] + '.profile.json', 'w') as f:
                    json.dump(profile['profile'], f)
                compiles = ','.join(
                    f'{fn}:{st["compiles"]}' for fn, st in sorted(
                        profile['profile']['recompiles']['fns'].items())
                    if st['compiles'])
            print(f'phase={name} ok decode_kernel={kernel} interpret='
                  f'{interpret} start_s={start_s:.1f} cold_s='
                  f'{cold_s:.1f} repeat_s={repeat_s:.2f} together_s='
                  f'{together_s:.2f} stream_s={stream_s:.2f} '
                  f'ticks={engine.get("ticks")} '
                  f'prefill_chunks={engine.get("prefill_chunks")} '
                  f'prefix_hits={engine.get("prefix_cache_hits")} spec_ticks='
                  f'{engine.get("spec_ticks", 0)} tokens='
                  f'{engine.get("tokens_generated")} compiles={compiles}',
                  flush=True)
            return first
        except SmokeFailure:
            raise
        except Exception as e:  # pylint: disable=broad-except
            raise SmokeFailure(f'{name}: {type(e).__name__}: {e}; log '
                               f'tail:\n{_tail(log)}') from e
        finally:
            self.stop(proc)

    def _wait_ready(self, name: str, proc: subprocess.Popen, log: str,
                    base: str) -> Dict[str, Any]:
        t_start = time.monotonic()
        while True:
            if proc.poll() is not None:
                raise SmokeFailure(
                    f'{name}: server exited with code {proc.returncode} '
                    f'before answering; log tail:\n{_tail(log)}')
            try:
                code, health = _http('GET', base + '/', timeout=10)
                if code == 200:
                    return health
                raise SmokeFailure(f'{name}: GET / answered {code}: '
                                   f'{str(health)[:400]}')
            except (urllib.error.URLError, ConnectionError, socket.timeout):
                pass
            self.left()
            if time.monotonic() - t_start > READY_S:
                raise SmokeFailure(
                    f'{name}: no answer on GET / after {READY_S:.0f} s; '
                    f'log tail:\n{_tail(log)}')
            time.sleep(1.0)

    def _round(self, name: str, base: str, prompts: Dict[int, List[int]],
               concurrent: bool) -> Dict[int, List[int]]:
        """POST /generate for every prompt, all at once or one by one;
        returns {prompt length: tokens}, each answer checked."""
        want = self.size['new_tokens']
        results: Dict[int, Any] = {}

        def one(n: int) -> None:
            try:
                results[n] = _http(
                    'POST', base + '/generate',
                    {'prompt_ids': [prompts[n]], 'max_new_tokens': want},
                    timeout=self.left())
            except Exception as e:  # pylint: disable=broad-except
                results[n] = (0, f'{type(e).__name__}: {e}')

        threads = [threading.Thread(target=one, args=(n,))
                   for n in prompts]
        for t in threads:
            t.start()
            if not concurrent:
                t.join()
        for t in threads:
            t.join()
        tokens: Dict[int, List[int]] = {}
        for n, (code, body) in sorted(results.items()):
            if code != 200:
                raise SmokeFailure(f'{name}: /generate (prompt {n}) '
                                   f'answered {code}: {str(body)[:400]}')
            rows = body['tokens']
            if len(rows) != 1 or len(rows[0]) != want:
                raise SmokeFailure(
                    f'{name}: prompt {n}: asked {want} tokens, got '
                    f'{[len(r) for r in rows]}')
            if not all(isinstance(t, int) and 0 <= t < self.vocab
                       for t in rows[0]):
                raise SmokeFailure(f'{name}: prompt {n}: ids outside '
                                   f'the vocabulary: {rows[0]}')
            tokens[n] = rows[0]
        return tokens

    def train(self, model_dir: str) -> None:
        name = 'train:fsdp2xtensor2'
        t = self.size['train']
        out, wall = self.run_child(name, [
            os.path.join(ROOT, 'examples', 'train_llama.py'),
            '--model', 'auto', '--init-from', model_dir, '--fused-ce',
            '--fsdp', '2', '--tensor', '2',
            '--seq-len', str(t['seq_len']),
            '--batch-size', str(t['batch']), '--steps', str(t['steps'])])
        lines = out.splitlines()
        mesh = next((ln for ln in lines if ln.startswith('mesh: ')), '')
        try:
            device = {
                'platform': mesh.split('platform=')[1].split()[0],
                'kind': mesh.split("device_kind='")[1].split("'")[0],
                'count': int(mesh.split(' over ')[1].split()[0])}
            jax_version = mesh.split('jax=')[1].rstrip(')')
        except (IndexError, ValueError):
            raise SmokeFailure(f'{name}: no mesh line in:\n'
                               f'{out[-2000:]}') from None
        self.note_device(name, device, jax_version)
        losses = [float(ln.split('loss=')[1].split()[0])
                  for ln in lines if ln.startswith('step ')]
        if (len(losses) < 2 or
                not all(x == x and abs(x) != float('inf') for x in losses)
                or not losses[-1] < losses[0]):
            raise SmokeFailure(f'{name}: loss not finite and falling '
                               f'over {t["steps"]} steps: {losses}')
        mem_line = next((ln for ln in lines
                         if ln.startswith('device memory: ')), None)
        if mem_line is None:
            raise SmokeFailure(f'{name}: no device memory line')
        in_use = [d['bytes_in_use']
                  for d in json.loads(mem_line[len('device memory: '):])]
        if self.dry_run:
            share = 'not reported on cpu'
        else:
            if (len(in_use) != self.chips or not all(in_use) or
                    min(in_use) < 0.5 * max(in_use)):
                raise SmokeFailure(
                    f'{name}: the state is not spread over the '
                    f'devices: bytes_in_use={in_use}')
            share = '/'.join(f'{b / 1e9:.2f}' for b in in_use) + 'GB'
        print(f'phase={name} ok wall_s={wall:.1f} steps={t["steps"]} '
              f'seq_len={t["seq_len"]} batch={t["batch"]} loss_first='
              f'{losses[0]:.4f} loss_last={losses[-1]:.4f} '
              f'bytes_in_use={share}', flush=True)

    # ------------------------------------------------------------------- run

    def run(self) -> None:
        os.makedirs(self.log_dir, exist_ok=True)
        s = self.size
        spec = ['--spec-tokens', str(s['spec_tokens'])]
        if self.chips == 1:
            model_dir = self.make_model(s['serve_layers'])
            cold = self.serve('serve:bf16', model_dir, [])
            self.serve('serve:int8kv', model_dir, ['--quantize-kv'])
            self.serve('serve:spec', model_dir, spec)
            self.serve('serve:bf16:cached', model_dir, [], expect=cold)
        else:
            model_dir = self.make_model(s['train_layers'])
            tensor = ['--tensor', str(self.chips)]
            self.serve('serve:tensor4', model_dir, tensor)
            self.serve('serve:tensor4:int8kv+spec', model_dir,
                       tensor + ['--quantize-kv'] + spec)
            self.train(model_dir)
        seen = {json.dumps(d, sort_keys=True) for d in self.devices}
        if len(seen) != 1:
            raise SmokeFailure(f'children disagree on the device: '
                               f'{sorted(seen)}')


def main() -> None:
    parser = argparse.ArgumentParser(
        description=__doc__.split('\n\n')[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument('--chips', type=int, default=1, choices=(1, 4),
                        help='1: the serving path on one chip (default). '
                             '4: the four-chip host (--tensor 4 server, '
                             'fsdp x tensor trainer).')
    parser.add_argument('--dry-run', action='store_true',
                        help='tiny size on the CPU, kernels interpreted: '
                             'debugs this script, proves nothing about '
                             'a chip.')
    parser.add_argument('--keep', action='store_true',
                        help='keep the generated model directory')
    parser.add_argument('--log-dir', default=None,
                        help='where the children log '
                             '(default .chip_smoke/logs)')
    parser.add_argument('--child-make-model', default=None,
                        help=argparse.SUPPRESS)
    parser.add_argument('--layers', type=int, default=None,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child_make_model:
        _child_make_model(args.child_make_model, args.layers, seed=0,
                          dry_run=args.dry_run)
        return

    smoke = Smoke(args)
    # A killed parent still stops its children.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        smoke.run()
    except SmokeFailure as e:
        print(f'chip_smoke: FAILED after '
              f'{time.monotonic() - smoke.t0:.0f} s: {e}', file=sys.stderr)
        sys.exit(1)
    finally:
        smoke.cleanup()
    total = time.monotonic() - smoke.t0
    if args.dry_run:
        print(f'dry run finished in {total:.0f} s: device=cpu, not a pass')
        return
    print(f'all phases passed in {total:.0f} s')
    print(json.dumps({'ok': True, 'device': smoke.devices[0]}))


if __name__ == '__main__':
    main()
