"""Asyncio HTTP front end for the model server replica.

Replaces the stdlib ThreadingHTTPServer front (serve/model_server.py)
on the serving path: one event loop owns every socket — N concurrent
SSE streams, health probes, and JSON requests never spawn a thread per
connection in front of the GIL'd engine.  Token delivery rides the
engine's watcher hook (batching_engine._Request.add_watcher →
loop.call_soon_threadsafe → asyncio.Queue), so a streaming response
wakes only when its request produces a token.  Blocking compute that
cannot stream (lock-step decode.generate, engine result() for the
non-stream endpoints) runs in the default executor, bounded by the
engine's own slot count.

Zero dependencies, same endpoint surface as the threaded front
(GET /, GET /metrics, POST /generate, /generate_stream,
/generate_text — all POST routes honor and echo X-SkyTPU-Request-Id);
the hand-rolled HTTP follows serve/load_balancer.py's precedent.

Parity: the reference ships no replica server (SkyPilot serves user
containers); this is the framework-native replica of SURVEY.md's
serve stack.
"""
from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Dict, Optional, Tuple

from skypilot_tpu import sky_logging
from skypilot_tpu.observability import logs as logs_lib
from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.observability import tracing
from skypilot_tpu.serve import batching_engine as batching_engine_lib
from skypilot_tpu.serve import handoff as handoff_lib
from skypilot_tpu.serve import http_protocol
from skypilot_tpu.serve import model_server as model_server_lib
from skypilot_tpu.serve import qos as qos_lib
from skypilot_tpu.serve import router as router_lib

logger = sky_logging.init_logger(__name__)

_REQUEST_ID_KEY = tracing.REQUEST_ID_HEADER.lower()


def _route_meta(headers: Dict[str, str]) -> Optional[Dict[str, Any]]:
    """Routing facts the LB forwarded (lower-cased header map); None
    for direct hits.  Mirrors the threaded front's counting."""
    role = headers.get(router_lib.ROUTED_ROLE_HEADER.lower())
    affinity = headers.get(router_lib.AFFINITY_HEADER.lower())
    handoff_ms = headers.get(router_lib.HANDOFF_MS_HEADER.lower())
    if not (role or affinity or handoff_ms):
        return None
    model_server_lib._M_ROUTED.labels(  # pylint: disable=protected-access
        role=role or 'unknown', affinity=affinity or 'none').inc()
    try:
        ms = float(handoff_ms) if handoff_ms else None
    except ValueError:
        ms = None
    return {'routed_role': role,
            'affinity_hit': affinity == 'hit' if affinity else None,
            'handoff_ms': ms,
            'attempt': model_server_lib._attempt_header(  # pylint: disable=protected-access
                headers.get(router_lib.ATTEMPT_HEADER.lower()))}

_MAX_BODY = 64 * 1024 * 1024
_IDLE_TIMEOUT = 300.0


class _HttpError(Exception):

    def __init__(self, code: int, message: str,
                 headers: Optional[Dict[str, str]] = None) -> None:
        super().__init__(message)
        self.code = code
        self.headers = headers or {}


def _backpressure_error(e: Exception) -> Optional[_HttpError]:
    """Admission-control pushback as honest HTTP: 429 + Retry-After
    when the engine queue is full, 503 + Retry-After when the request
    expired queued, 504 when its own deadline passed — so the
    LB/client backs off instead of timing out."""
    if isinstance(e, batching_engine_lib.QueueFull):
        return _HttpError(429, str(e),
                          {'Retry-After': str(int(e.retry_after))})
    if isinstance(e, batching_engine_lib.QueueExpired):
        return _HttpError(503, str(e),
                          {'Retry-After': str(int(e.retry_after))})
    if isinstance(e, batching_engine_lib.DeadlineExceeded):
        return _HttpError(504, str(e))
    return None


def _deadline_ms(headers: Dict[str, str]) -> Optional[float]:
    """The request's X-SkyTPU-Deadline-Ms (lower-cased header map),
    else the replica's env default."""
    raw = headers.get(router_lib.DEADLINE_HEADER.lower())
    if raw:
        try:
            ms = float(raw)
            return ms if ms > 0 else None
        except ValueError:
            pass
    return model_server_lib.default_deadline_ms()


def _qos_class(headers: Dict[str, str]) -> str:
    """The request's X-SkyTPU-QoS-Class (lower-cased header map),
    clamped to a known class."""
    return qos_lib.normalize(
        headers.get(router_lib.QOS_CLASS_HEADER.lower()))


async def _read_request(reader: asyncio.StreamReader
                        ) -> Optional[Tuple[str, str, Dict[str, str],
                                            bytes]]:
    """(method, path, headers, body) or None on clean EOF."""
    try:
        head = await asyncio.wait_for(reader.readuntil(b'\r\n\r\n'),
                                      timeout=_IDLE_TIMEOUT)
    except (asyncio.IncompleteReadError, ConnectionResetError):
        return None
    except asyncio.TimeoutError:
        return None
    lines = head.decode('latin-1').split('\r\n')
    try:
        method, path, _ = lines[0].split(' ', 2)
    except ValueError as e:
        raise _HttpError(400, f'bad request line: {lines[0]!r}') from e
    headers: Dict[str, str] = {}
    for line in lines[1:]:
        if ':' in line:
            k, v = line.split(':', 1)
            headers[k.strip().lower()] = v.strip()
    try:
        length = int(headers.get('content-length', 0))
    except ValueError as e:
        raise _HttpError(400, 'bad Content-Length') from e
    if length > _MAX_BODY:
        raise _HttpError(413, 'request body too large')
    if length:
        # Same idle bound as the head read: a client that sends headers
        # then stalls must not hold a task + fd forever.
        try:
            body = await asyncio.wait_for(reader.readexactly(length),
                                          timeout=_IDLE_TIMEOUT)
        except asyncio.TimeoutError as e:
            raise _HttpError(408, 'request body timed out') from e
    else:
        body = b''
    return method, path, headers, body


def _json_response(code: int, payload: Dict[str, Any],
                   headers: Optional[Dict[str, str]] = None) -> bytes:
    body = json.dumps(payload).encode()
    reason = {200: 'OK', 400: 'Bad Request', 404: 'Not Found',
              408: 'Request Timeout', 413: 'Payload Too Large',
              429: 'Too Many Requests',
              500: 'Internal Server Error',
              503: 'Service Unavailable',
              504: 'Gateway Timeout'}.get(code, 'Error')
    extra = ''.join(f'{k}: {v}\r\n'
                    for k, v in (headers or {}).items())
    return (f'HTTP/1.1 {code} {reason}\r\n'
            f'Content-Type: application/json\r\n'
            f'Content-Length: {len(body)}\r\n'
            f'{extra}'
            f'\r\n').encode() + body


class AsyncModelServer:
    """Serves a ModelServer's model/engine from one asyncio loop."""

    def __init__(self, server: 'model_server_lib.ModelServer') -> None:
        self.server = server
        self._loop: Optional[asyncio.AbstractEventLoop] = None

    # ------------------------------------------------------------ bridge

    def _watch(self, request) -> 'asyncio.Queue':
        """Bridge an engine request's tokens onto the event loop."""
        assert self._loop is not None
        q: 'asyncio.Queue' = asyncio.Queue()
        loop = self._loop
        request.add_watcher(
            lambda token: loop.call_soon_threadsafe(q.put_nowait, token))
        return q

    # --------------------------------------------------------- endpoints

    def _health(self) -> Tuple[int, Dict[str, Any]]:
        server = self.server
        payload: Dict[str, Any] = {
            'status': 'ok',
            'model': f'{server.cfg.d_model}x{server.cfg.n_layers}',
            'role': server.role,
            'num_hosts': server.num_hosts,
            'draining': server.draining,
            'weight_version': server.weight_version,
            **server.runtime,
        }
        engine = server._engine  # pylint: disable=protected-access
        code = 200
        if engine is not None:
            stats = engine.stats()
            payload['engine'] = stats
            if 'slice' in stats:
                # Gang health top-level: the controller probe retires a
                # degraded slice (dead rank) instead of waiting it out.
                payload['slice'] = stats['slice']
            if stats['failed']:
                payload['status'] = 'engine_failed'
                code = 503
        return code, payload

    def _sampling(self, req: Dict[str, Any]):
        """(temperature, top_k, seed) — request fields, falling back to
        the server's CLI defaults."""
        server = self.server
        return (float(req.get('temperature', server.default_temperature)),
                int(req.get('top_k', server.default_top_k)),
                int(req.get('seed', server.default_seed)))

    async def _generate(self, req: Dict[str, Any], rid: str,
                        route_meta: Optional[Dict[str, Any]] = None,
                        deadline_ms: Optional[float] = None,
                        qos_class: Optional[str] = None,
                        reader: Optional[asyncio.StreamReader] = None,
                        watch_disconnect: bool = False
                        ) -> Dict[str, Any]:
        t0 = time.perf_counter()
        temperature, top_k, seed = self._sampling(req)
        handles: list = []
        loop = asyncio.get_running_loop()

        def _call():
            # Explicit rid bind: the context carries the header's id,
            # but a direct hit may have had rid generated above.
            with logs_lib.bind(request_id=rid):
                return self.server.generate(
                    req['prompt_ids'],
                    int(req.get('max_new_tokens', 16)),
                    temperature, top_k, seed=seed, request_id=rid,
                    route_meta=route_meta, deadline_ms=deadline_ms,
                    qos_class=qos_class, on_submit=handles.extend)
        # wrap_context: run_in_executor runs the callable in a bare
        # pool thread where contextvars reset — without the copied
        # context, records emitted inside generate() would lose (or
        # worse, inherit a sibling's) request id.
        gen = loop.run_in_executor(None, logs_lib.wrap_context(_call))
        if watch_disconnect and reader is not None:
            # Connection: close (the LB's routed path, one-shot
            # clients): no further request bytes are legitimate, so a
            # read completing with EOF IS the client hanging up —
            # cancel the engine slots instead of decoding to a dead
            # socket.  Data would mean a protocol violation; treat it
            # the same and let the write path surface the error.
            watchdog = asyncio.ensure_future(reader.read(1))
            done, _ = await asyncio.wait(
                {gen, watchdog}, return_when=asyncio.FIRST_COMPLETED)
            if gen not in done:
                for handle in handles:
                    handle.cancel()
                # The executor call returns promptly once the worker
                # reaps the cancelled slots; await it so nothing leaks.
                try:
                    await gen
                except Exception:  # pylint: disable=broad-except
                    pass
                raise model_server_lib.ClientDisconnected(
                    'client disconnected mid-generation')
            # Wait the cancellation out: until the watchdog's read has
            # actually left the reader, the connection loop's next
            # readuntil() raises "another coroutine is already waiting"
            # (met on the chip host on every one-shot /generate).
            watchdog.cancel()
            await asyncio.gather(watchdog, return_exceptions=True)
            tokens = gen.result()
        else:
            tokens = await gen
        model_server_lib._maybe_journal_request(  # pylint: disable=protected-access
            'serve_request_done', request_id=rid, status='ok',
            tokens=sum(len(t) for t in tokens))
        if qos_class == qos_lib.BATCH:
            model_server_lib._M_BATCH_ROWS.inc(len(tokens))  # pylint: disable=protected-access
        return {'tokens': tokens,
                'weight_version': self.server.weight_version,
                'latency_ms': round((time.perf_counter() - t0) * 1e3, 1)}

    def _reject_if_draining(self) -> None:
        """503 + Retry-After for new generation work on a draining
        replica — the LB's same-role retry lands it on a sibling."""
        if self.server.draining:
            model_server_lib._M_DRAIN_REJECTED.inc()  # pylint: disable=protected-access
            raise _HttpError(503, 'replica is draining',
                             {'Retry-After': '5'})

    async def _prefix_export(self, req: Dict[str, Any],
                             binary: bool = False) -> Any:
        """Drain-time sibling handoff: export the hottest prefix-cache
        POOL pages (no prefill runs); allowed while draining."""
        engine = self.server._engine  # pylint: disable=protected-access
        if engine is None:
            raise _HttpError(400, 'prefix export requires '
                                  '--continuous-batching')
        try:
            return await asyncio.get_running_loop().run_in_executor(
                None, logs_lib.wrap_context(
                    lambda: engine.export_prefix_pages(
                        max_pages=int(req.get('max_pages', 64)),
                        binary=binary)))
        except handoff_lib.HandoffError as e:
            raise _HttpError(404, str(e)) from e

    async def _prefill_export(self, req: Dict[str, Any],
                              binary: bool = False) -> Any:
        """KV handoff, prefill side (compute runs in the executor so
        token streams on this loop keep flowing).  binary=True returns
        the raw octet-stream frame instead of the JSON payload."""
        engine = self.server._engine  # pylint: disable=protected-access
        if engine is None:
            raise _HttpError(400, 'KV handoff requires '
                                  '--continuous-batching')
        self._reject_if_draining()
        prompt = req['prompt_ids']
        if (isinstance(prompt, list) and prompt and
                isinstance(prompt[0], list)):
            if len(prompt) != 1:
                raise _HttpError(400,
                                 'export serves one prompt per request')
            prompt = prompt[0]
        try:
            return await asyncio.get_running_loop().run_in_executor(
                None, logs_lib.wrap_context(
                    lambda: engine.export_prefill(
                        [int(t) for t in prompt],
                        page_size=req.get('page_size'),
                        binary=binary)))
        except handoff_lib.HandoffError as e:
            raise _HttpError(400, str(e)) from e

    async def _kv_import(self, decoded: Dict[str, Any]
                         ) -> Dict[str, Any]:
        """KV handoff, decode side (waits on the engine worker in the
        executor — the loop never blocks on the import).  `decoded` is
        the wire-agnostic dict from handoff.decode_payload /
        decode_binary."""
        engine = self.server._engine  # pylint: disable=protected-access
        if engine is None:
            raise _HttpError(400, 'KV handoff requires '
                                  '--continuous-batching')
        # Imported pages would die with this replica anyway.
        self._reject_if_draining()
        try:
            imported, cached = (
                await asyncio.get_running_loop().run_in_executor(
                    None, logs_lib.wrap_context(
                        lambda: engine.import_pages(
                            decoded['hashes'], decoded['page_size'],
                            decoded['k'], decoded['v'],
                            k_scale=decoded.get('k_scale'),
                            v_scale=decoded.get('v_scale')))))
        except handoff_lib.HandoffRejected as e:
            raise _HttpError(503, str(e)) from e
        except handoff_lib.HandoffError as e:
            raise _HttpError(400, str(e)) from e
        return {'imported_pages': imported, 'cached_pages': cached}

    async def _generate_text(self, req: Dict[str, Any],
                             writer: asyncio.StreamWriter,
                             rid: str,
                             route_meta: Optional[Dict[str, Any]] = None,
                             deadline_ms: Optional[float] = None,
                             qos_class: Optional[str] = None
                             ) -> None:
        self._reject_if_draining()
        server = self.server
        tok = server.tokenizer
        if server.cfg.vocab_size < tok.vocab_size:
            raise _HttpError(
                400, f'model vocab {server.cfg.vocab_size} < tokenizer '
                     f'vocab {tok.vocab_size}: checkpoint and tokenizer '
                     'do not match')
        text = req.get('prompt')
        if not isinstance(text, str) or not text:
            raise _HttpError(400, 'prompt must be a non-empty string')
        ids = tok.encode(text, add_bos=True)
        if not ids:
            raise _HttpError(400, 'prompt tokenized to nothing')
        if req.get('stream'):
            await self._stream(writer, ids, req, rid, text_mode=True,
                               route_meta=route_meta,
                               deadline_ms=deadline_ms,
                               qos_class=qos_class)
            return
        t0 = time.perf_counter()
        temperature, top_k, seed = self._sampling(req)

        def _call():
            with logs_lib.bind(request_id=rid):
                return server.generate(
                    [ids], int(req.get('max_new_tokens', 64)),
                    temperature, top_k,
                    stop_token=tok.eos_ids or None, seed=seed,
                    request_id=rid, route_meta=route_meta,
                    deadline_ms=deadline_ms, qos_class=qos_class)
        tokens = (await asyncio.get_running_loop().run_in_executor(
            None, logs_lib.wrap_context(_call)))[0]
        stops = [i for i, t in enumerate(tokens) if t in tok.eos_ids]
        if stops:
            tokens = tokens[:stops[0]]
        writer.write(_json_response(200, {
            'completion': tok.decode(tokens),
            'tokens': tokens,
            'latency_ms': round((time.perf_counter() - t0) * 1e3, 1),
        }, {tracing.REQUEST_ID_HEADER: rid}))
        await writer.drain()

    async def _stream(self, writer: asyncio.StreamWriter, ids, req,
                      rid: str, *, text_mode: bool,
                      route_meta: Optional[Dict[str, Any]] = None,
                      deadline_ms: Optional[float] = None,
                      qos_class: Optional[str] = None
                      ) -> None:
        """SSE over chunked transfer; token events or UTF-8-safe text
        deltas.  Purely event-driven: no thread parks waiting."""
        self._reject_if_draining()
        server = self.server
        engine = server._engine  # pylint: disable=protected-access
        if engine is None:
            raise _HttpError(
                400, 'streaming requires --continuous-batching')
        tok = server.tokenizer
        # Text mode stops at the tokenizer's full stop set (model EOS +
        # chat turn-end markers — instruct checkpoints end turns there).
        # Token mode keeps the request's raw stop_token (may be int 0).
        stop_ids = ((tok.eos_ids or None) if text_mode
                    else req.get('stop_token'))
        from skypilot_tpu.models import decode  # pylint: disable=import-outside-toplevel
        temperature, top_k, seed = self._sampling(req)
        try:
            request = engine.submit(
                [int(t) for t in ids],
                int(req.get('max_new_tokens', 64 if text_mode else 16)),
                stop_token=stop_ids,
                sampling=decode.SamplingConfig(
                    temperature=temperature, top_k=top_k, seed=seed),
                request_id=rid, route_meta=route_meta,
                deadline_ms=deadline_ms, qos_class=qos_class)
        except ValueError:
            raise
        except Exception as e:  # pylint: disable=broad-except
            # Full admission queue: 429 + Retry-After.  Stopped/failed
            # engine: the replica is unavailable, not the request
            # wrong — 503 like the threaded front, so LB retry logic
            # classifies it correctly.
            bp = _backpressure_error(e)
            if bp is not None:
                raise bp from e
            raise _HttpError(503, f'{type(e).__name__}: {e}') from e
        q = self._watch(request)
        writer.write(b'HTTP/1.1 200 OK\r\n'
                     b'Content-Type: text/event-stream\r\n'
                     b'Cache-Control: no-cache\r\n' +
                     f'{tracing.REQUEST_ID_HEADER}: {rid}\r\n'.encode() +
                     b'Transfer-Encoding: chunked\r\n\r\n')

        def chunk(data: str) -> bytes:
            payload = f'data: {data}\n\n'.encode()
            return (f'{len(payload):x}\r\n'.encode() + payload + b'\r\n')

        decoder = None
        if text_mode:
            from skypilot_tpu.models.tokenizer import StreamDecoder  # pylint: disable=import-outside-toplevel
            decoder = StreamDecoder(tok)
        try:
            while True:
                token = await asyncio.wait_for(q.get(), timeout=600)
                if token is None:
                    if request.error is not None:
                        raise request.error
                    break
                if text_mode:
                    if token in tok.eos_ids:
                        break
                    delta = decoder.push(token)
                    if delta:
                        writer.write(chunk(json.dumps({'text': delta})))
                else:
                    writer.write(chunk(json.dumps({'token': token})))
                await writer.drain()
            if decoder is not None:
                tail = decoder.finish()
                if tail:
                    writer.write(chunk(json.dumps({'text': tail})))
            writer.write(chunk('[DONE]') + b'0\r\n\r\n')
            await writer.drain()
        except (BrokenPipeError, ConnectionResetError):
            # Client went away: free the slot instead of decoding the
            # rest of max_new_tokens for nobody.
            request.cancel()
        except asyncio.CancelledError:
            # Task cancelled (loop shutdown): same slot-leak logic,
            # then propagate — cancellation must not be swallowed.
            request.cancel()
            raise
        except Exception as e:  # pylint: disable=broad-except
            request.cancel()
            try:
                writer.write(chunk(json.dumps(
                    {'error': f'{type(e).__name__}: {e}'})) +
                    b'0\r\n\r\n')
                await writer.drain()
            except (BrokenPipeError, ConnectionResetError, OSError):
                pass

    # ------------------------------------------------------- connection

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                try:
                    parsed = await _read_request(reader)
                except _HttpError as e:
                    # Malformed request line / Content-Length / too-big
                    # body: answer like the threaded front does, then
                    # drop the connection (framing is unreliable now).
                    writer.write(_json_response(e.code,
                                                {'error': str(e)}))
                    await writer.drain()
                    break
                except (asyncio.LimitOverrunError, ValueError) as e:
                    writer.write(_json_response(
                        400, {'error': f'bad request: {e}'}))
                    await writer.drain()
                    break
                if parsed is None:
                    break
                method, path, headers, body = parsed
                path, _, query = path.partition('?')
                route = (path if path in http_protocol.REPLICA_PATHS
                         else (logs_lib.HEALTH_ROUTE
                               if method == 'GET' else 'unknown'))
                status = 200
                # Request-scoped log context for everything this task
                # awaits while serving the request (contextvars flow
                # through awaits natively; executor hops re-wrap via
                # logs_lib.wrap_context).  Entered without `with` so
                # the existing try/except chain keeps its shape; the
                # finally below closes it.
                _log_ctx = logs_lib.bind(
                    request_id=headers.get(_REQUEST_ID_KEY),
                    attempt=model_server_lib._attempt_header(  # pylint: disable=protected-access
                        headers.get(router_lib.ATTEMPT_HEADER.lower())),
                    process='replica',
                    replica_id=self.server.replica_id,
                    role=self.server.role)
                _log_ctx.__enter__()  # pylint: disable=unnecessary-dunder-call
                try:
                    if method == 'GET':
                        if path == http_protocol.METRICS:
                            engine = self.server._engine  # pylint: disable=protected-access
                            if engine is not None:
                                engine.stats()  # freshen gauges
                            text = metrics_lib.expose().encode()
                            writer.write(
                                (f'HTTP/1.1 200 OK\r\n'
                                 f'Content-Type: '
                                 f'{metrics_lib.CONTENT_TYPE}\r\n'
                                 f'Content-Length: {len(text)}\r\n'
                                 f'\r\n').encode() + text)
                        elif path == http_protocol.SPANS:
                            # Trace-segment export for cross-process
                            # assembly (sky serve trace).
                            writer.write(_json_response(
                                200, self.server.export_spans(
                                    **model_server_lib.parse_span_query(
                                        query))))
                        elif path == http_protocol.PROFILE:
                            # Continuous-profiling export (tick-phase
                            # ring + recompile sentinel).
                            writer.write(_json_response(
                                200, self.server.export_profile()))
                        elif path == http_protocol.LOGS:
                            # Structured log-ring export (sky serve
                            # logs): recent records, seq-paginated.
                            writer.write(_json_response(
                                200, {'records':
                                      logs_lib.get_ring().export(
                                          **logs_lib.parse_log_query(
                                              query))}))
                        else:
                            code, payload = self._health()
                            status = code
                            writer.write(_json_response(code, payload))
                        await writer.drain()
                        continue
                    if method != 'POST':
                        raise _HttpError(404, 'unknown method')
                    ctype = headers.get('content-type') or ''
                    if (path == http_protocol.KV_IMPORT and
                            handoff_lib.CONTENT_TYPE_BINARY in ctype):
                        # Binary handoff frame: raw array bytes, no
                        # JSON parse of a megabyte body.
                        try:
                            decoded = handoff_lib.decode_binary(body)
                        except handoff_lib.HandoffError as e:
                            raise _HttpError(400, str(e)) from e
                        t0, wall0 = time.perf_counter(), time.time()
                        result = await self._kv_import(decoded)
                        self.server.record_handoff_segment(
                            'kv_import',
                            headers.get(_REQUEST_ID_KEY) or
                            tracing.new_request_id(), wall0,
                            (time.perf_counter() - t0) * 1e3,
                            attempt=model_server_lib._attempt_header(  # pylint: disable=protected-access
                                headers.get(
                                    router_lib.ATTEMPT_HEADER.lower())),
                            imported_pages=result.get(
                                'imported_pages'),
                            cached_pages=result.get('cached_pages'))
                        writer.write(_json_response(200, result))
                        await writer.drain()
                        continue
                    try:
                        req = json.loads(body or b'{}')
                    except json.JSONDecodeError as e:
                        raise _HttpError(400, f'bad JSON: {e}') from e
                    # Propagated request id (LB injects one when the
                    # client didn't send it); echoed on every reply.
                    rid = (headers.get(_REQUEST_ID_KEY) or
                           tracing.new_request_id())
                    meta = _route_meta(headers)
                    deadline_ms = _deadline_ms(headers)
                    qos_class = _qos_class(headers)
                    if path == http_protocol.GENERATE:
                        self._reject_if_draining()
                        one_shot = 'close' in (
                            headers.get('connection') or '').lower()
                        try:
                            payload = await self._generate(
                                req, rid, meta,
                                deadline_ms=deadline_ms,
                                qos_class=qos_class,
                                reader=reader,
                                watch_disconnect=one_shot)
                        except model_server_lib.ClientDisconnected:
                            break  # no reply owed; slots already freed
                        writer.write(_json_response(
                            200, payload,
                            {tracing.REQUEST_ID_HEADER: rid}))
                        await writer.drain()
                    elif path == http_protocol.GENERATE_STREAM:
                        prompt = req['prompt_ids']
                        if (isinstance(prompt, list) and prompt and
                                isinstance(prompt[0], list)):
                            if len(prompt) != 1:
                                raise _HttpError(
                                    400,
                                    'streaming serves one prompt '
                                    'per request')
                            prompt = prompt[0]
                        await self._stream(writer, prompt, req, rid,
                                           text_mode=False,
                                           route_meta=meta,
                                           deadline_ms=deadline_ms,
                                           qos_class=qos_class)
                    elif path == http_protocol.GENERATE_TEXT:
                        await self._generate_text(
                            req, writer, rid, meta,
                            deadline_ms=deadline_ms,
                            qos_class=qos_class)
                    elif path == http_protocol.DRAIN:
                        writer.write(_json_response(
                            200, self.server.drain()))
                        await writer.drain()
                    elif path == http_protocol.ROLE_BUDGET:
                        try:
                            result = self.server.apply_role_budget(req)
                        except (KeyError, ValueError, TypeError) as e:
                            raise _HttpError(400, str(e)) from e
                        writer.write(_json_response(200, result))
                        await writer.drain()
                    elif path == http_protocol.WEIGHTS_SWAP:
                        # Checkpoint restore is blocking I/O: run it in
                        # the executor so in-flight streams keep
                        # flowing while the weights load.
                        try:
                            result = await (
                                asyncio.get_running_loop()
                                .run_in_executor(
                                    None, logs_lib.wrap_context(
                                        lambda r=req: (
                                            self.server
                                            .weights_swap(r)))))
                        except (KeyError, ValueError, TypeError) as e:
                            raise _HttpError(400, str(e)) from e
                        writer.write(_json_response(200, result))
                        await writer.drain()
                    elif path == http_protocol.PREFIX_EXPORT:
                        binary = (req.get('wire') == 'binary' or
                                  handoff_lib.CONTENT_TYPE_BINARY in
                                  (headers.get('accept') or ''))
                        result = await self._prefix_export(
                            req, binary=binary)
                        if binary:
                            writer.write(
                                (f'HTTP/1.1 200 OK\r\n'
                                 f'Content-Type: '
                                 f'{handoff_lib.CONTENT_TYPE_BINARY}'
                                 f'\r\nContent-Length: '
                                 f'{len(result)}\r\n\r\n'
                                 ).encode() + result)
                        else:
                            writer.write(_json_response(200, result))
                        await writer.drain()
                    elif path == http_protocol.PREFILL_EXPORT:
                        binary = (req.get('wire') == 'binary' or
                                  handoff_lib.CONTENT_TYPE_BINARY in
                                  (headers.get('accept') or ''))
                        t0, wall0 = time.perf_counter(), time.time()
                        result = await self._prefill_export(
                            req, binary=binary)
                        self.server.record_handoff_segment(
                            'prefill_export', rid, wall0,
                            (time.perf_counter() - t0) * 1e3,
                            attempt=model_server_lib._attempt_header(  # pylint: disable=protected-access
                                headers.get(
                                    router_lib.ATTEMPT_HEADER.lower())))
                        if binary:
                            writer.write(
                                (f'HTTP/1.1 200 OK\r\n'
                                 f'Content-Type: '
                                 f'{handoff_lib.CONTENT_TYPE_BINARY}'
                                 f'\r\nContent-Length: '
                                 f'{len(result)}\r\n\r\n'
                                 ).encode() + result)
                        else:
                            writer.write(_json_response(200, result))
                        await writer.drain()
                    elif path == http_protocol.KV_IMPORT:
                        try:
                            decoded = handoff_lib.decode_payload(req)
                        except handoff_lib.HandoffError as e:
                            raise _HttpError(400, str(e)) from e
                        t0, wall0 = time.perf_counter(), time.time()
                        result = await self._kv_import(decoded)
                        self.server.record_handoff_segment(
                            'kv_import', rid, wall0,
                            (time.perf_counter() - t0) * 1e3,
                            attempt=model_server_lib._attempt_header(  # pylint: disable=protected-access
                                headers.get(
                                    router_lib.ATTEMPT_HEADER.lower())),
                            imported_pages=result.get(
                                'imported_pages'),
                            cached_pages=result.get('cached_pages'))
                        writer.write(_json_response(200, result))
                        await writer.drain()
                    else:
                        raise _HttpError(404, 'unknown path')
                except _HttpError as e:
                    status = e.code
                    writer.write(_json_response(
                        e.code, {'error': str(e)}, e.headers))
                    await writer.drain()
                except (KeyError, ValueError, TypeError) as e:
                    status = 400
                    writer.write(_json_response(400, {'error': str(e)}))
                    await writer.drain()
                except (BrokenPipeError, ConnectionResetError):
                    status = 0  # client gone; nothing went on the wire
                    break
                except Exception as e:  # pylint: disable=broad-except
                    # Engine failures must reach the client as HTTP,
                    # not a dropped connection; admission pushback as
                    # 429/503 + Retry-After.
                    bp = _backpressure_error(e)
                    if bp is not None:
                        status = bp.code
                        writer.write(_json_response(
                            bp.code, {'error': str(bp)}, bp.headers))
                    else:
                        status = 500
                        writer.write(_json_response(
                            500, {'error': f'{type(e).__name__}: {e}'}))
                    await writer.drain()
                finally:
                    # Access log INSIDE the binding so the record
                    # carries the request identity.
                    logs_lib.access_log(logger, method, route, status)
                    _log_ctx.__exit__(None, None, None)
        except (BrokenPipeError, ConnectionResetError,
                asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (BrokenPipeError, ConnectionResetError, OSError,
                    RuntimeError):
                # RuntimeError: loop already closed during shutdown —
                # the transport dies with it either way.
                pass

    # ------------------------------------------------------------ server

    async def run(self, host: str = '0.0.0.0', port: int = 0,
                  ready: Optional['asyncio.Future'] = None) -> None:
        self._loop = asyncio.get_running_loop()
        server = await asyncio.start_server(self._handle, host, port)
        bound = server.sockets[0].getsockname()[1]
        logger.info(f'async model server on :{bound}')
        if ready is not None:
            ready.set_result(bound)
        async with server:
            await server.serve_forever()


def serve_forever(server: 'model_server_lib.ModelServer',
                  port: int = 0) -> None:
    try:
        asyncio.run(AsyncModelServer(server).run(port=port))
    finally:
        server.close()


def start_background(server: 'model_server_lib.ModelServer',
                     port: int = 0):
    """Tests: run the async front on a daemon thread's event loop;
    returns (port, shutdown_fn)."""
    import threading  # pylint: disable=import-outside-toplevel
    front = AsyncModelServer(server)
    loop = asyncio.new_event_loop()
    ready: 'asyncio.Future' = loop.create_future()
    boot_error: list = []

    def _run() -> None:
        asyncio.set_event_loop(loop)
        try:
            loop.run_until_complete(front.run(port=port, ready=ready))
        except asyncio.CancelledError:
            pass
        except Exception as e:  # pylint: disable=broad-except
            boot_error.append(e)  # e.g. EADDRINUSE before ready
        finally:
            loop.close()

    thread = threading.Thread(target=_run, daemon=True)
    thread.start()
    while not ready.done():
        if not thread.is_alive():
            raise RuntimeError(
                f'async server failed to start: '
                f'{boot_error[0] if boot_error else "unknown"}')
        time.sleep(0.01)

    def shutdown() -> None:
        def _stop() -> None:
            for task in asyncio.all_tasks(loop):
                task.cancel()
        loop.call_soon_threadsafe(_stop)
        thread.join(timeout=10)

    return ready.result(), shutdown
