"""Cross-host serving control plane: replicas as real OS processes.

Everything the fleet does in one process — `Router` placement,
`FleetController` heal/scale, KV handoff — keeps working when the
replicas move behind sockets, because this module preserves the exact
engine protocol both sides already speak:

- **worker side** (:func:`serve_engine`) — binds a live
  `ServingEngine`/`DecodeEngine` onto the observe diagnostics HTTP
  server (the one already serving /readyz, /metrics, /statusz) as a
  set of POST endpoints: ``/rpc/submit`` (one-shot inference),
  ``/rpc/generate`` (decode token stream), ``/rpc/drain``,
  ``/rpc/shutdown``, ``/rpc/state`` (placement signals), and
  ``/rpc/kv/export`` + ``/rpc/kv/install`` (the KVPacket handoff on
  sockets, sha1-stamped by default — handoff_verify_enabled('socket')).
  Submit/generate ack **admission early**: the HTTP status line is sent
  the moment the engine accepts (or refuses, typed) the request, and
  the body streams when the result exists — so a remote queue-full is
  a synchronous typed error exactly like the in-process one, and the
  router's shed accounting does not change shape.
- **client side** (:class:`RemoteReplica`) — a proxy implementing the
  engine protocol the `Router`/`PhaseRouter`/`FleetController` drive:
  ``submit`` -> Future/stream, ``ready()``, ``queue_depth()``,
  ``free_pages()``/``free_slots()``/``decode_load()``, ``drain``,
  ``shutdown``, with per-call connection/read timeouts, bounded
  exponential-backoff reconnect, and EVERY transport failure mapped to
  :class:`RemoteReplicaError` — an ``EngineClosedError`` subclass — so
  failover, hedging, and the retry budget work with zero router
  changes. ``ready()`` is a /readyz probe with a **heartbeat timeout**:
  a hung worker (alive but wedged, e.g. SIGSTOP) stops answering
  within ``heartbeat_timeout_s`` and is declared dead by the
  controller's next census tick, same as a corpse.
- **spawner** (:class:`ProcessReplicaFactory`) — a `ReplicaFactory`
  for `FleetController` that spawns real worker processes
  (``tools/replica_worker.py``), waits for the /readyz flip, and — when a
  replica's shutdown path finds the process still alive — SIGKILLs
  and reaps the corpse, so the controller's lineage/backoff/quarantine
  machinery governs real PIDs.

**Fleet observability** rides the same wires: a request carrying a
``reqtrace.RequestContext`` ships its wire form (``ctx.to_wire()``) in
the submit/generate envelope and the KV-export request, the worker
reconstitutes it at admission (``reqtrace.from_wire``) so both
processes span under ONE trace_id linked by Chrome-trace flow events;
``ready()`` piggybacks an NTP-style /clockz exchange (EWMA offset,
``rpc.clock_offset_seconds`` gauge, ``clock_offset()``) so merged
traces can shift replica timestamps onto the controller clock; the
factory wires each worker a controller-known flight-dump path
(``postmortem()`` reads it back, SIGKILL included) and registers the
replica with ``observe.fleet`` for /varz scraping + federated /tracez.

Env knobs are read per call (this file is in tools/repo_lint.py's
ENV_SCOPED_FILES). Typed errors cross the wire as a JSON envelope
``{"error": {"type", "message"}}`` and are re-raised as the same class
on the client (QueueFullError, SLOShedError, ValueError, Handoff
errors, ...); an unknown worker-side type becomes
:class:`RemoteCallError` — a plain RuntimeError, NEVER an
EngineClosedError, so a bad request cannot masquerade as a dead
replica and trigger failover. See docs/serving.md "Cross-host fleet".
"""

import http.client
import json
import os
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time

from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np

from .. import observe as _obs
from ..observe import diagnostics as _diag
from ..observe import reqtrace as _reqtrace
from .engine import EngineClosedError, QueueFullError

__all__ = ['RemoteReplica', 'RemoteReplicaError', 'RemoteCallError',
           'ProcessReplicaFactory', 'serve_engine', 'EngineBinding',
           'pack_arrays', 'unpack_arrays']

_WIRE_MAGIC = b'PTRP'          # paddle-tpu rpc payload (arrays frame)


class RemoteReplicaError(EngineClosedError):
    """Transport-level failure talking to a replica worker — connect
    refused/timeout, read timeout, connection reset (the SIGKILL
    shape), or a worker that answered garbage. Subclasses
    EngineClosedError ON PURPOSE: to the router this replica is gone,
    and gone replicas mean failover/heal, never a failed request."""


class RemoteCallError(RuntimeError):
    """The worker raised an exception type this client cannot map. A
    plain RuntimeError — NOT an EngineClosedError — because an
    application error (bad feed, internal bug) must fail the request,
    not trigger failover onto the next replica."""


# ------------------------------------------------------------- wire
def pack_arrays(meta, arrays):
    """MAGIC + u32 header length + header JSON + raw array bytes. The
    header carries ``meta`` (JSON-safe dict) plus per-array
    name/dtype/shape in a fixed order; bf16 ships as its raw 2-byte
    payload via io._to_numpy, same as the KVPacket wire."""
    from .. import io as _io
    blobs, ents = [], []
    for name in sorted(arrays):
        raw, dtype_name = _io._to_numpy(np.asarray(arrays[name]))
        raw = np.ascontiguousarray(raw)
        ents.append({'name': name, 'dtype': dtype_name,
                     'shape': list(raw.shape)})
        blobs.append(raw.tobytes())
    header = json.dumps({'meta': meta or {}, 'arrays': ents},
                        sort_keys=True).encode()
    return b''.join([_WIRE_MAGIC, struct.pack('<I', len(header)),
                     header] + blobs)


def unpack_arrays(data):
    """Inverse of :func:`pack_arrays` -> (meta, {name: ndarray})."""
    from .. import io as _io
    if data[:4] != _WIRE_MAGIC:
        raise RemoteReplicaError('bad RPC payload (magic %r)'
                                 % data[:4])
    (hlen,) = struct.unpack('<I', data[4:8])
    doc = json.loads(data[8:8 + hlen].decode())
    off = 8 + hlen
    arrays = {}
    for ent in doc['arrays']:
        dtype_name = ent['dtype']
        shape = tuple(ent['shape'])
        base = 'uint16' if dtype_name == 'bfloat16' else dtype_name
        n = int(np.prod(shape)) * np.dtype(base).itemsize
        if off + n > len(data):
            raise RemoteReplicaError(
                'truncated RPC payload (worker died mid-write?)')
        raw = np.frombuffer(data[off:off + n], dtype=base).reshape(shape)
        arrays[ent['name']] = _io._from_numpy(raw, dtype_name)
        off += n
    return doc.get('meta') or {}, arrays


def _frame(doc):
    """u32-length-prefixed JSON frame (the generate token stream)."""
    payload = json.dumps(doc, sort_keys=True).encode()
    return struct.pack('<I', len(payload)) + payload


def _error_doc(exc):
    return {'error': {'type': type(exc).__name__, 'message': str(exc)}}


def _error_classes():
    """Wire-name -> exception class, built per call (lazy imports keep
    this module cycle-free with router/handoff)."""
    from .handoff import (HandoffError, KVDtypeMismatchError,
                          KVGeometryError)
    from .router import NoReplicaAvailableError, SLOShedError
    from .tenancy import QuotaExceededError
    return {
        'QueueFullError': QueueFullError,
        'SLOShedError': SLOShedError,
        'QuotaExceededError': QuotaExceededError,
        'EngineClosedError': EngineClosedError,
        'RemoteReplicaError': RemoteReplicaError,
        'NoReplicaAvailableError': NoReplicaAvailableError,
        'HandoffError': HandoffError,
        'KVDtypeMismatchError': KVDtypeMismatchError,
        'KVGeometryError': KVGeometryError,
        'ValueError': ValueError,
        'KeyError': KeyError,
        'TypeError': TypeError,
        'TimeoutError': TimeoutError,
    }


def _raise_remote(payload, status=None):
    """Re-raise a worker error envelope as its typed class."""
    try:
        doc = json.loads(payload.decode('utf-8', 'replace'))
        err = doc.get('error') or {}
        name = err.get('type', '')
        message = err.get('message', '')
    except Exception:
        name, message = '', payload[:200].decode('utf-8', 'replace')
    cls = _error_classes().get(name)
    if cls is not None:
        raise cls(message)
    raise RemoteCallError('%s%s(HTTP %s) %s'
                          % (name, ': ' if name else '', status,
                             message))


_ERR_STATUS = {'QueueFullError': 429, 'SLOShedError': 429,
               'QuotaExceededError': 429,
               'EngineClosedError': 503, 'ValueError': 400,
               'TypeError': 400, 'KeyError': 400,
               'HandoffError': 409, 'KVDtypeMismatchError': 409,
               'KVGeometryError': 409}


# ------------------------------------------------------------ worker side
class EngineBinding(object):
    """Handle on one engine's registered RPC endpoints (unregister on
    close). ``on_shutdown`` (when given) runs after a remote shutdown
    request has been acked — the worker main loop exits on it."""

    PATHS = ('submit', 'generate', 'drain', 'shutdown', 'state',
             'kv/export', 'kv/install')

    def __init__(self, engine, prefix, on_shutdown):
        self.engine = engine
        self.prefix = prefix.rstrip('/')
        self._on_shutdown = on_shutdown

    def paths(self):
        return ['%s/%s' % (self.prefix, p) for p in self.PATHS]

    def close(self):
        for p in self.paths():
            _diag.unregister_post_handler(p)


def _send_json(handler, code, doc):
    handler._send(code, json.dumps(doc, sort_keys=True, default=str))


def _send_error(handler, exc):
    _obs.inc('rpc.errors_total', type=type(exc).__name__)
    _send_json(handler, _ERR_STATUS.get(type(exc).__name__, 500),
               _error_doc(exc))


def _ack_stream(handler):
    """Send the early 200 admission ack: status + headers now, body
    when the result exists. Connection: close (no Content-Length) is
    the framing — the client reads to EOF."""
    handler.close_connection = True
    handler.send_response(200)
    handler.send_header('Content-Type', 'application/octet-stream')
    handler.send_header('Connection', 'close')
    handler.end_headers()
    handler.wfile.flush()


def serve_engine(engine, prefix='/rpc', on_shutdown=None):
    """Expose ``engine`` over the diagnostics HTTP server (start it
    separately via observe.serve). Returns an :class:`EngineBinding`.
    The engine's own ready() check (registered by its start()) drives
    /readyz — the same flip a local balancer watches."""
    binding = EngineBinding(engine, prefix, on_shutdown)
    pre = binding.prefix

    def timed(method, fn):
        def handler(h, body):
            t0 = time.perf_counter()
            _obs.inc('rpc.requests_total', method=method)
            try:
                fn(h, body)
            except Exception as e:   # admission-path error: typed wire
                _send_error(h, e)
            finally:
                _obs.record('rpc.request_seconds',
                            time.perf_counter() - t0, method=method)
        return handler

    def h_submit(h, body):
        meta, feed = unpack_arrays(body)
        # reconstitute the caller's trace context from the envelope
        # (None when the hop carried none): the replica-side spans land
        # under the SAME trace_id, and the pre-armed flow handle links
        # them back to the controller's flow_begin
        ctx = _reqtrace.from_wire(meta.get('trace'))
        t_in = time.perf_counter()
        # admission runs HERE, synchronously: QueueFullError /
        # EngineClosedError / ValueError travel back as the HTTP
        # status before any compute happens
        if ctx is not None:
            ctx.flow_step()
            ctx.event('rpc_admitted', replica=str(engine.name))
            fut = engine.submit(feed, ctx=ctx)
        else:
            fut = engine.submit(feed, deadline_s=meta.get('deadline_s'))
        _ack_stream(h)
        try:
            outs = fut.result()
            payload = pack_arrays(
                {'ok': True, 'n': len(outs)},
                {'f%06d' % i: np.asarray(a)
                 for i, a in enumerate(outs)})
        except Exception as e:
            _obs.inc('rpc.errors_total', type=type(e).__name__)
            payload = pack_arrays(_error_doc(e), {})
        if ctx is not None:
            ctx.stage('rpc_execute', t_in, time.perf_counter(),
                      replica=str(engine.name))
            ctx.flow_end()
        h.wfile.write(payload)
        h.wfile.flush()

    def h_generate(h, body):
        req = json.loads(body.decode()) if body else {}
        ctx = _reqtrace.from_wire(req.get('trace'))
        t_in = time.perf_counter()
        if ctx is not None:
            ctx.flow_step()
            ctx.event('rpc_admitted', replica=str(engine.name))
        stream = engine.submit(
            [int(t) for t in req.get('prompt', [])],
            max_new_tokens=int(req.get('max_new_tokens', 16)),
            temperature=float(req.get('temperature', 0.0)),
            seed=int(req.get('seed', 0)),
            eos_id=req.get('eos_id'),
            tenant=req.get('tenant'),
            priority=req.get('priority'),
            ctx=ctx)
        _ack_stream(h)
        try:
            for tok in stream:
                h.wfile.write(_frame({'token': int(tok)}))
                h.wfile.flush()
            tokens = stream.result()
            if ctx is not None:
                ctx.stage('rpc_execute', t_in, time.perf_counter(),
                          replica=str(engine.name), tokens=len(tokens))
                ctx.flow_end()
            h.wfile.write(_frame({'done': True,
                                  'finish_reason': stream.finish_reason,
                                  'tokens': [int(t) for t in tokens]}))
        except Exception as e:
            _obs.inc('rpc.errors_total', type=type(e).__name__)
            h.wfile.write(_frame(_error_doc(e)))
        h.wfile.flush()

    def h_drain(h, body):
        req = json.loads(body.decode()) if body else {}
        ok = engine.drain(timeout=req.get('timeout'))
        _send_json(h, 200, {'drained': bool(ok)})

    def h_shutdown(h, body):
        req = json.loads(body.decode()) if body else {}
        drain = bool(req.get('drain', True))
        _obs.flight_event('rpc_shutdown', replica=str(engine.name),
                          drain=drain)
        # synchronous: with drain=True every accepted request has
        # resolved BEFORE this ack goes out — the drain-before-ack
        # contract the client tests assert
        engine.shutdown(drain=drain)
        _send_json(h, 200, {'ok': True, 'drained': drain})
        if binding._on_shutdown is not None:
            binding._on_shutdown()

    def h_state(h, body):
        doc = {'name': str(engine.name), 'pid': os.getpid(),
               'ready': bool(engine.ready()),
               'queue_depth': int(engine.queue_depth())}
        for attr in ('free_pages', 'free_slots', 'decode_load'):
            fn = getattr(engine, attr, None)
            if callable(fn):
                doc[attr] = fn()
        nb = getattr(engine, 'num_blocks', None)
        if nb is not None:
            doc['num_blocks'] = int(nb)
        geo = getattr(engine, 'kv_geometry', None)
        if callable(geo):
            doc['kv_geometry'] = geo()
        _send_json(h, 200, doc)

    def h_kv_export(h, body):
        from .handoff import export_packet
        req = json.loads(body.decode()) if body else {}
        pkt = export_packet(engine, [int(t) for t in
                                     req.get('tokens', [])])
        if pkt is not None and req.get('trace'):
            # the trace context rides the packet header so the
            # INSTALLING side (another process entirely) can span its
            # kv_install under the originating trace_id
            pkt.header['trace'] = req['trace']
        data = b'' if pkt is None else pkt.to_bytes(transport='socket')
        h.close_connection = True
        h.send_response(200)
        h.send_header('Content-Type', 'application/octet-stream')
        h.send_header('Content-Length', str(len(data)))
        h.end_headers()
        if data:
            h.wfile.write(data)
        h.wfile.flush()
        _obs.inc('rpc.kv_export_bytes_total', len(data))

    def h_kv_install(h, body):
        from .handoff import KVPacket, install_packet
        covered, installed, dedup = install_packet(
            engine, KVPacket.from_bytes(body))
        _obs.inc('rpc.kv_install_bytes_total', len(body))
        _send_json(h, 200, {'covered': covered, 'installed': installed,
                            'dedup': dedup})

    for path, fn in (('submit', h_submit), ('generate', h_generate),
                     ('drain', h_drain), ('shutdown', h_shutdown),
                     ('state', h_state), ('kv/export', h_kv_export),
                     ('kv/install', h_kv_install)):
        _diag.register_post_handler('%s/%s' % (pre, path),
                                    timed(path, fn))
    return binding


# ------------------------------------------------------------ client side
class RemoteReplica(object):
    """Client proxy for one replica worker — the exact engine protocol
    the Router/PhaseRouter/FleetController already speak, over HTTP.

    ::

        rep = RemoteReplica('http://127.0.0.1:8471', name='r0')
        fut = rep.submit({'x': batch})          # Future, typed errors
        rep.ready()                             # /readyz w/ heartbeat
        rep.shutdown(drain=True)                # + SIGKILL/reap corpse

    ``proc`` (a subprocess.Popen, when this client owns the worker)
    lets ready() short-circuit on a dead PID and shutdown() reap the
    corpse. ``clock``/``sleep`` are injectable for the synthetic-clock
    unit tests; every reconnect is bounded exponential backoff
    (``backoff_base_s * 2^i`` capped at ``backoff_max_s``,
    ``reconnect_tries`` attempts), and every transport failure raises
    :class:`RemoteReplicaError` (an EngineClosedError)."""

    def __init__(self, url, name=None, kind='serving', proc=None,
                 prefix='/rpc', connect_timeout_s=1.0,
                 admission_timeout_s=5.0, read_timeout_s=60.0,
                 heartbeat_timeout_s=2.0, ready_ttl_s=0.2,
                 state_ttl_s=0.05, reconnect_tries=3,
                 backoff_base_s=0.05, backoff_max_s=1.0,
                 max_inflight=8, clock=None, sleep=None,
                 clock_sync_every_s=1.0, postmortem_path=None):
        url = url.rstrip('/')
        hostport = url.split('://', 1)[-1]
        host, _, port = hostport.rpartition(':')
        self._host, self._port = host or '127.0.0.1', int(port)
        self.url = url
        self.name = str(name) if name else 'remote@%s' % hostport
        self.kind = kind
        self.proc = proc
        self._prefix = prefix.rstrip('/')
        self.connect_timeout_s = float(connect_timeout_s)
        self.admission_timeout_s = float(admission_timeout_s)
        self.read_timeout_s = float(read_timeout_s)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.ready_ttl_s = float(ready_ttl_s)
        self.state_ttl_s = float(state_ttl_s)
        self.reconnect_tries = max(1, int(reconnect_tries))
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self._clock = clock or time.monotonic
        self._sleep = sleep or time.sleep
        self._mu = threading.Lock()
        self._pool = ThreadPoolExecutor(
            max_workers=int(max_inflight),
            thread_name_prefix='paddle_tpu_rpc_%s' % self.name)
        self._closed = False
        self._ready_cache = (None, False)     # (asof, ok)
        self._state_cache = (None, {})        # (asof, doc)
        self._geometry = None
        self.clock_sync_every_s = float(clock_sync_every_s)
        self.postmortem_path = postmortem_path
        self._clock_est = None                # lazy ClockOffsetEstimator
        self._clock_sync_at = None

    # --------------------------------------------------------- transport
    def _connect(self, timeout=None, force=False):
        """One TCP connect with bounded exponential-backoff retries.
        Raises RemoteReplicaError after ``reconnect_tries`` failures —
        the typed 'this replica is gone' the router failovers on.
        ``force`` connects even after close — the /shutdown RPC itself
        must go out AFTER ``_closed`` flips (which fences new work)."""
        last = None
        for i in range(self.reconnect_tries):
            if self._closed and not force:
                raise RemoteReplicaError(
                    'RemoteReplica %r is shut down' % self.name)
            conn = http.client.HTTPConnection(
                self._host, self._port,
                timeout=timeout if timeout is not None
                else self.connect_timeout_s)
            try:
                conn.connect()
                return conn
            except (OSError, socket.timeout) as e:
                last = e
                conn.close()
                if i + 1 < self.reconnect_tries:
                    self._sleep(min(self.backoff_max_s,
                                    self.backoff_base_s * (2.0 ** i)))
        _obs.inc('rpc.connect_failures_total', replica=self.name)
        raise RemoteReplicaError(
            'replica %r unreachable at %s:%d after %d attempts '
            '(%s: %s)' % (self.name, self._host, self._port,
                          self.reconnect_tries, type(last).__name__,
                          last))

    def _start_request(self, path, body, read_timeout,
                       ctype='application/octet-stream', force=False):
        """POST and read status+headers (the admission phase). Returns
        (conn, resp) with the socket timeout already widened to
        ``read_timeout`` for the body. Non-200 responses are consumed
        and re-raised typed."""
        conn = self._connect(force=force)
        # Connection: close responses hand the socket over to the
        # response object (conn.sock goes None inside getresponse), so
        # keep our own reference to retime reads for the body phase
        sock = conn.sock
        try:
            conn.request('POST', '%s%s' % (self._prefix, path),
                         body=body,
                         headers={'Content-Type': ctype,
                                  'Content-Length': str(len(body))})
            sock.settimeout(self.admission_timeout_s)
            resp = conn.getresponse()
        except (OSError, socket.timeout,
                http.client.HTTPException) as e:
            conn.close()
            _obs.inc('rpc.transport_errors_total', replica=self.name)
            raise RemoteReplicaError(
                'replica %r: %s during %s (%s)'
                % (self.name, type(e).__name__, path, e))
        if resp.status != 200:
            try:
                payload = resp.read()
            finally:
                resp.close()
                conn.close()
            _raise_remote(payload, resp.status)
        try:
            sock.settimeout(read_timeout)
        except OSError:
            pass                     # socket raced closed: reads will raise
        return conn, resp

    def _call(self, path, body=b'', read_timeout=None,
              ctype='application/json', force=False):
        """One-shot JSON RPC: POST, read the whole body, parse."""
        conn, resp = self._start_request(
            path, body,
            read_timeout if read_timeout is not None
            else self.read_timeout_s, ctype=ctype, force=force)
        try:
            data = resp.read()
        except (OSError, socket.timeout,
                http.client.HTTPException) as e:
            _obs.inc('rpc.transport_errors_total', replica=self.name)
            raise RemoteReplicaError(
                'replica %r: %s reading %s response'
                % (self.name, type(e).__name__, path))
        finally:
            resp.close()
            conn.close()
        return data

    def _call_json(self, path, doc=None, read_timeout=None,
                   force=False):
        data = self._call(
            path, json.dumps(doc or {}).encode(),
            read_timeout=read_timeout, force=force)
        try:
            return json.loads(data.decode())
        except ValueError:
            raise RemoteReplicaError(
                'replica %r: unparseable %s response' % (self.name,
                                                         path))

    # ----------------------------------------------------------- intake
    def submit(self, feed, ctx=None, deadline_s=None, **gen_kw):
        """Serving kind: ``feed`` is {name: array}; returns a Future of
        the fetch list. Decode kind: ``feed`` is the prompt token ids
        (``max_new_tokens``/``temperature``/``seed``/``eos_id`` ride in
        ``gen_kw``); returns a RemoteStream. Admission errors
        (QueueFullError, ValueError, ...) raise synchronously — the
        worker acks admission before computing — and transport
        failures raise/settle RemoteReplicaError."""
        if self.kind == 'decode':
            return self._generate(feed, ctx=ctx, **gen_kw)
        if deadline_s is None and ctx is not None:
            deadline_s = ctx.remaining()
        meta = {'deadline_s': deadline_s}
        if ctx is not None:
            # trace context crosses the process boundary in the
            # envelope; the flow arrow starts HERE so the worker's
            # flow_step draws controller→replica in the merged view
            meta['trace'] = ctx.to_wire()
            ctx.flow_begin('rpc_hop')
        t0 = time.perf_counter()
        body = pack_arrays(meta, dict(feed))
        conn, resp = self._start_request('/submit', body,
                                         self.read_timeout_s)
        if ctx is not None:
            ctx.stage('rpc_admission', t0, time.perf_counter(),
                      replica=self.name)
        fut = Future()
        fut.set_running_or_notify_cancel()
        self._pool.submit(self._read_submit_result, conn, resp, fut)
        return fut

    def _read_submit_result(self, conn, resp, fut):
        try:
            data = resp.read()       # to EOF (Connection: close)
            if not data:
                raise RemoteReplicaError(
                    'replica %r closed the connection before the '
                    'result (killed mid-request?)' % self.name)
            meta, arrays = unpack_arrays(data)
            if 'error' in meta:
                cls = _error_classes().get(meta['error'].get('type'))
                raise (cls or RemoteCallError)(
                    meta['error'].get('message', ''))
            fut.set_result([arrays['f%06d' % i]
                            for i in range(int(meta.get('n', 0)))])
        except (OSError, socket.timeout,
                http.client.HTTPException) as e:
            _obs.inc('rpc.transport_errors_total', replica=self.name)
            fut.set_exception(RemoteReplicaError(
                'replica %r: %s mid-request (worker died?)'
                % (self.name, type(e).__name__)))
        except BaseException as e:
            fut.set_exception(e)
        finally:
            resp.close()
            conn.close()

    def predict(self, feed, timeout=None):
        return self.submit(feed).result(timeout)

    def _generate(self, prompt, ctx=None, max_new_tokens=16,
                  temperature=0.0, seed=0, eos_id=None, tenant=None,
                  priority=None):
        doc = {
            'prompt': [int(t) for t in prompt],
            'max_new_tokens': int(max_new_tokens),
            'temperature': float(temperature), 'seed': int(seed),
            'eos_id': eos_id, 'tenant': tenant,
            'priority': priority}
        if ctx is not None:
            doc['trace'] = ctx.to_wire()
            ctx.flow_begin('rpc_hop')
        body = json.dumps(doc).encode()
        t0 = time.perf_counter()
        conn, resp = self._start_request('/generate', body,
                                         self.read_timeout_s,
                                         ctype='application/json')
        if ctx is not None:
            ctx.stage('rpc_admission', t0, time.perf_counter(),
                      replica=self.name)
        stream = RemoteStream(self.name, len(prompt))
        self._pool.submit(self._read_stream, conn, resp, stream)
        return stream

    def _read_stream(self, conn, resp, stream):
        try:
            while True:
                head = self._read_exact(resp, 4)
                (n,) = struct.unpack('<I', head)
                doc = json.loads(self._read_exact(resp, n).decode())
                if 'error' in doc:
                    cls = _error_classes().get(doc['error'].get('type'))
                    raise (cls or RemoteCallError)(
                        doc['error'].get('message', ''))
                if doc.get('done'):
                    stream._finish(doc.get('finish_reason'),
                                   doc.get('tokens') or [])
                    return
                stream._put(doc['token'])
        except (OSError, socket.timeout,
                http.client.HTTPException) as e:
            _obs.inc('rpc.transport_errors_total', replica=self.name)
            stream._fail(RemoteReplicaError(
                'replica %r: %s mid-stream (worker died?)'
                % (self.name, type(e).__name__)))
        except BaseException as e:
            stream._fail(e)
        finally:
            resp.close()
            conn.close()

    @staticmethod
    def _read_exact(resp, n):
        chunks = []
        got = 0
        while got < n:
            c = resp.read(n - got)
            if not c:
                raise RemoteReplicaError(
                    'stream truncated (%d of %d bytes)' % (got, n))
            chunks.append(c)
            got += len(c)
        return b''.join(chunks)

    # -------------------------------------------------------- lifecycle
    def ready(self):
        """/readyz probe with the heartbeat timeout: a worker that is
        dead (PID reaped), unreachable, degraded, OR simply not
        answering within ``heartbeat_timeout_s`` (SIGSTOP, GIL wedge)
        reads as not ready — which is exactly the signal the
        FleetController's census turns into DEAD + heal. Cached for
        ``ready_ttl_s`` so placement loops don't probe per request."""
        if self._closed:
            return False
        if self.proc is not None and self.proc.poll() is not None:
            return False
        now = self._clock()
        asof, ok = self._ready_cache
        if asof is not None and now - asof < self.ready_ttl_s:
            return ok
        ok = self._probe_readyz()
        with self._mu:
            self._ready_cache = (now, ok)
        if ok:
            # piggyback clock alignment on the heartbeat: only after a
            # SUCCESSFUL probe (a half-dead worker must not eat extra
            # connections), throttled to one exchange per
            # clock_sync_every_s
            self._maybe_sync_clock(now)
        return ok

    def _probe_readyz(self):
        conn = http.client.HTTPConnection(
            self._host, self._port, timeout=self.heartbeat_timeout_s)
        try:
            conn.request('GET', '/readyz')
            resp = conn.getresponse()
            resp.read()
            return resp.status == 200
        except (OSError, socket.timeout,
                http.client.HTTPException):
            _obs.inc('rpc.heartbeat_misses_total', replica=self.name)
            return False
        finally:
            conn.close()

    def _maybe_sync_clock(self, now):
        """One NTP-style four-timestamp exchange against the worker's
        /clockz (t0 send / t1 recv / t2 send / t3 recv), folded into
        the EWMA estimator and published as the
        ``rpc.clock_offset_seconds{replica=}`` gauge. Any failure is
        silent — clock alignment is advisory, never on the request
        path."""
        with self._mu:
            if self._clock_sync_at is not None and \
                    now - self._clock_sync_at < self.clock_sync_every_s:
                return
            self._clock_sync_at = now
        conn = http.client.HTTPConnection(
            self._host, self._port, timeout=self.heartbeat_timeout_s)
        try:
            t0 = time.time()
            conn.request('GET', '/clockz')
            resp = conn.getresponse()
            data = resp.read()
            t3 = time.time()
            if resp.status != 200:
                return
            doc = json.loads(data.decode())
            t1, t2 = float(doc['t_recv']), float(doc['t_send'])
        except (OSError, socket.timeout, ValueError, KeyError,
                TypeError, http.client.HTTPException):
            return                   # pre-/clockz server or torn reply
        finally:
            conn.close()
        from ..observe.fleet import ClockOffsetEstimator
        with self._mu:
            if self._clock_est is None:
                self._clock_est = ClockOffsetEstimator()
            off = self._clock_est.update(t0, t1, t2, t3)
        _obs.set_gauge('rpc.clock_offset_seconds', off,
                       replica=self.name)

    def clock_offset(self):
        """EWMA-smoothed wall-clock offset of the worker relative to
        this process (worker − local, seconds) — None before the first
        successful /clockz exchange. tools/fleet_trace.py and the
        federated /tracez shift replica span timestamps by this."""
        est = self._clock_est
        return est.offset() if est is not None else None

    def postmortem(self):
        """The worker's last flight-recorder dump (SIGTERM dump or
        periodic heartbeat snapshot) parsed from ``postmortem_path`` —
        None when no path was configured or no dump exists yet. This
        survives SIGKILL: the worker re-dumps on a heartbeat cadence,
        so the controller can read a dead replica's final seconds."""
        if not self.postmortem_path:
            return None
        from ..observe.flight import load_postmortem
        return load_postmortem(self.postmortem_path)

    def _state(self):
        now = self._clock()
        asof, doc = self._state_cache
        if asof is not None and now - asof < self.state_ttl_s:
            return doc
        try:
            doc = self._call_json('/state',
                                  read_timeout=self.heartbeat_timeout_s)
        except (RemoteReplicaError, RemoteCallError):
            doc = {}
        with self._mu:
            self._state_cache = (now, doc)
        return doc

    def queue_depth(self):
        """Placement signal; an unreachable worker reports a huge depth
        so the ranked candidate list deprioritizes it until ready()
        flips it out entirely."""
        doc = self._state()
        return int(doc.get('queue_depth', 1 << 20))

    def free_pages(self):
        return int(self._state().get('free_pages', 0))

    def free_slots(self):
        return int(self._state().get('free_slots', 0))

    def decode_load(self):
        return float(self._state().get('decode_load', float('inf')))

    @property
    def num_blocks(self):
        nb = self._state().get('num_blocks')
        return int(nb) if nb is not None else 0

    def kv_geometry(self):
        if self._geometry is None:
            geo = self._state().get('kv_geometry')
            if geo is None:
                raise RemoteReplicaError(
                    'replica %r reported no kv_geometry' % self.name)
            self._geometry = geo
        return self._geometry

    @property
    def pid(self):
        return self.proc.pid if self.proc is not None else None

    # ------------------------------------------------------- KV handoff
    def export_packet_bytes(self, tokens, ctx=None):
        """serving.handoff duck-type: the worker exports + serializes
        (sha1-stamped, socket default) and this returns the raw packet
        bytes — b'' when nothing was cached to ship. ``ctx`` (when
        given) rides the request so the exported packet's header
        carries the trace context to the installing side."""
        doc = {'tokens': [int(t) for t in tokens]}
        if ctx is not None:
            doc['trace'] = ctx.to_wire()
        return self._call('/kv/export', json.dumps(doc).encode())

    def install_packet_bytes(self, data):
        """serving.handoff duck-type: install on the WORKER, against
        its own prefix cache (dedup preserved). Returns (covered,
        installed, dedup)."""
        doc = self._call_json_raw('/kv/install', data)
        return (int(doc.get('covered', 0)), int(doc.get('installed', 0)),
                int(doc.get('dedup', 0)))

    def _call_json_raw(self, path, body):
        data = self._call(path, body,
                          ctype='application/octet-stream')
        try:
            return json.loads(data.decode())
        except ValueError:
            raise RemoteReplicaError(
                'replica %r: unparseable %s response' % (self.name,
                                                         path))

    # ---------------------------------------------------------- teardown
    def drain(self, timeout=None):
        """Remote drain: blocks until every accepted request resolved
        worker-side (or timeout). False on timeout OR transport
        failure — a dead worker cannot promise a drain."""
        wait = self.read_timeout_s if timeout is None else timeout + 5.0
        try:
            doc = self._call_json('/drain', {'timeout': timeout},
                                  read_timeout=wait)
            return bool(doc.get('drained'))
        except (RemoteReplicaError, RemoteCallError):
            return False

    def shutdown(self, drain=True, timeout=None):
        """Remote shutdown, then — when this client owns the worker
        process — make death REAL: wait briefly for a clean exit,
        SIGKILL anything still alive (a hung/stopped corpse), and
        reap it so no zombie outlives the fleet."""
        self._closed = True
        from ..observe.fleet import fleet as _fleet
        _fleet().unregister(self.name)
        try:
            # force: _closed is already set (fencing new submits), but
            # THIS call must still reach the worker — otherwise every
            # shutdown degrades to the SIGKILL path and the worker
            # never exports its trace/flight files
            self._call_json('/shutdown', {'drain': bool(drain)},
                            read_timeout=(self.read_timeout_s
                                          if timeout is None
                                          else timeout), force=True)
        except (RemoteReplicaError, RemoteCallError):
            pass                     # already dead/unreachable: fall through
        if self.proc is not None:
            grace = 5.0 if timeout is None else max(0.1, timeout)
            try:
                self.proc.wait(timeout=grace if drain else 0.5)
            except subprocess.TimeoutExpired:
                self.proc.kill()     # SIGKILL: corpses don't negotiate
                try:
                    self.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            _obs.flight_event('rpc_worker_reaped', replica=self.name,
                              pid=self.proc.pid,
                              returncode=self.proc.returncode)
        self._pool.shutdown(wait=False)

    def close(self):
        self.shutdown(drain=True)


class RemoteStream(object):
    """Client half of a decode generation stream — the
    GenerationStream surface (iterate for tokens, ``result()`` for the
    list, ``finish_reason``) fed by the RPC frame reader."""

    _END = object()

    def __init__(self, replica, prompt_len):
        self.replica = replica
        self.prompt_len = prompt_len
        self.finish_reason = None
        self._q = __import__('queue').Queue()
        self._future = Future()
        self._future.set_running_or_notify_cancel()

    def _put(self, token):
        self._q.put(int(token))

    def _finish(self, reason, tokens):
        self.finish_reason = reason
        self._q.put(self._END)
        if not self._future.done():
            self._future.set_result(list(tokens))

    def _fail(self, exc):
        self.finish_reason = 'error'
        self._q.put(self._END)
        if not self._future.done():
            self._future.set_exception(exc)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._END:
                return
            yield item

    def result(self, timeout=None):
        return self._future.result(timeout)

    def done(self):
        return self._future.done()


# ------------------------------------------------------------- spawner
class ProcessReplicaFactory(object):
    """ReplicaFactory for FleetController: ``create(name)`` spawns a
    REAL worker process (tools/replica_worker.py), waits for its port
    file and /readyz flip, and returns the RemoteReplica driving it.

    ``config`` is the worker's engine description (see
    tools/replica_worker.py): ``kind`` ('serving'|'decode') plus the
    engine kwargs/model paths. Every spawn inherits the parent
    environment. Worker JSONL metrics land
    beside the parent's sink (``<parent-stem>-<name>.jsonl``) with the
    replica name as the record ``host``, so
    ``tools/metrics_report.py --fleet`` merges the run."""

    def __init__(self, config, workdir=None, python=None,
                 worker_path=None, env=None, spawn_timeout_s=120.0,
                 heartbeat_timeout_s=2.0, connect_timeout_s=1.0,
                 admission_timeout_s=5.0, read_timeout_s=60.0,
                 max_inflight=8):
        self.config = dict(config)
        self.kind = self.config.get('kind', 'serving')
        self.workdir = workdir or tempfile.mkdtemp(
            prefix='paddle_tpu_fleet_')
        os.makedirs(self.workdir, exist_ok=True)
        self.python = python or sys.executable
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        self.worker_path = worker_path or os.path.join(
            root, 'tools', 'replica_worker.py')
        self.env = dict(env or {})
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.connect_timeout_s = float(connect_timeout_s)
        self.admission_timeout_s = float(admission_timeout_s)
        self.read_timeout_s = float(read_timeout_s)
        self.max_inflight = int(max_inflight)
        self._mu = threading.Lock()
        self._replicas = {}

    def _worker_jsonl(self, name):
        parent = _obs.jsonl_path()
        if parent:
            stem, ext = os.path.splitext(os.path.basename(parent))
            return os.path.join(os.path.dirname(os.path.abspath(parent))
                                or '.', '%s-%s%s' % (stem, name,
                                                     ext or '.jsonl'))
        return os.path.join(self.workdir, 'metrics-%s.jsonl' % name)

    def create(self, name):
        """Spawn + wait ready; raises on spawn/readiness failure (the
        controller counts it as spawn_failures_total and backs the
        lineage off — a broken worker config crash-loops into
        quarantine instead of spinning)."""
        cfg = dict(self.config)
        cfg['name'] = name
        port_file = os.path.join(self.workdir, '%s.port' % name)
        try:
            os.remove(port_file)
        except OSError:
            pass
        cfg['port_file'] = port_file
        cfg.setdefault('metrics_jsonl', self._worker_jsonl(name))
        cfg.setdefault('host_label', name)
        # controller-known postmortem + trace paths: the worker dumps
        # its flight ring here on SIGTERM and on a heartbeat cadence
        # (so SIGKILL still leaves a recent snapshot), and exports its
        # span recorder here on exit — tools/fleet_trace.py merges the
        # per-process trace files into one Perfetto view
        cfg.setdefault('flight_dump',
                       os.path.join(self.workdir,
                                    '%s.flight.json' % name))
        cfg.setdefault('trace_json',
                       os.path.join(self.workdir,
                                    '%s.trace.json' % name))
        cfg_path = os.path.join(self.workdir, '%s.json' % name)
        with open(cfg_path, 'w') as f:
            json.dump(cfg, f, sort_keys=True)
        log_path = os.path.join(self.workdir, '%s.log' % name)
        env = dict(os.environ)
        env.update(self.env)
        # the worker script lives in tools/: put the repo root (where
        # the paddle_tpu package is importable from) on its path
        root = os.path.dirname(os.path.dirname(self.worker_path))
        env['PYTHONPATH'] = (root + os.pathsep + env['PYTHONPATH']
                             if env.get('PYTHONPATH') else root)
        t0 = time.perf_counter()
        log_f = open(log_path, 'ab')
        try:
            proc = subprocess.Popen(
                [self.python, self.worker_path, '--config', cfg_path],
                stdout=log_f, stderr=subprocess.STDOUT, env=env,
                cwd=os.path.dirname(self.worker_path) and
                os.path.dirname(os.path.dirname(self.worker_path)))
        finally:
            log_f.close()
        deadline = t0 + self.spawn_timeout_s
        doc = None
        while time.perf_counter() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    'replica worker %r exited rc=%s before serving '
                    '(log: %s%s)' % (name, proc.returncode, log_path,
                                     _log_tail(log_path)))
            if os.path.exists(port_file):
                try:
                    with open(port_file) as f:
                        doc = json.load(f)
                    break
                except ValueError:
                    pass             # torn read of the atomic rename
            time.sleep(0.02)
        if doc is None:
            proc.kill()
            proc.wait(timeout=10)
            raise RuntimeError('replica worker %r never published its '
                               'port within %.0fs (log: %s%s)'
                               % (name, self.spawn_timeout_s, log_path,
                                  _log_tail(log_path)))
        rep = RemoteReplica(
            doc['url'], name=name, kind=self.kind, proc=proc,
            heartbeat_timeout_s=self.heartbeat_timeout_s,
            connect_timeout_s=self.connect_timeout_s,
            admission_timeout_s=self.admission_timeout_s,
            read_timeout_s=self.read_timeout_s,
            max_inflight=self.max_inflight,
            postmortem_path=cfg['flight_dump'])
        while time.perf_counter() < deadline:
            if rep.ready():
                break
            if proc.poll() is not None:
                raise RuntimeError(
                    'replica worker %r died rc=%s before ready '
                    '(log: %s%s)' % (name, proc.returncode, log_path,
                                     _log_tail(log_path)))
            time.sleep(0.05)
        else:
            rep.shutdown(drain=False, timeout=1.0)
            raise RuntimeError('replica worker %r never became ready '
                               'within %.0fs (log: %s%s)'
                               % (name, self.spawn_timeout_s, log_path,
                                  _log_tail(log_path)))
        spawn_s = time.perf_counter() - t0
        _obs.record('rpc.spawn_seconds', spawn_s)
        _obs.flight_event('rpc_worker_spawned', replica=name,
                          pid=proc.pid, url=doc['url'],
                          seconds=round(spawn_s, 3))
        # every live worker joins the metrics federation: the fleet
        # poller scrapes its /varz and the controller's /fleetz +
        # federated /tracez see it (shutdown unregisters)
        from ..observe.fleet import fleet as _fleet
        _fleet().register(rep, name=name)
        with self._mu:
            self._replicas[name] = rep
        return rep

    def replicas(self):
        with self._mu:
            return dict(self._replicas)

    def close(self):
        """Kill + reap every worker this factory spawned (teardown —
        a chaos run must not leak PIDs)."""
        with self._mu:
            reps = list(self._replicas.values())
            self._replicas.clear()
        for rep in reps:
            try:
                rep.shutdown(drain=False, timeout=1.0)
            except Exception:
                if rep.proc is not None and rep.proc.poll() is None:
                    rep.proc.kill()
                    try:
                        rep.proc.wait(timeout=10)
                    except Exception:
                        pass


def _log_tail(path, n=6):
    try:
        with open(path, 'rb') as f:
            lines = f.read().decode('utf-8', 'replace').splitlines()
        return ('\n  | ' + '\n  | '.join(lines[-n:])) if lines else ''
    except OSError:
        return ''
