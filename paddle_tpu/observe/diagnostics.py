"""Live diagnostics HTTP server: scrape a running process instead of
waiting for its JSONL.

Stdlib-only (``http.server`` in a daemon thread), off by default, and
started via ``observe.serve(port=...)`` or ``PADDLE_TPU_STATUSZ_PORT``
(picked up by ``observe.enable_from_env()``). Routes:

    /metrics   Prometheus text exposition of the whole registry
               (counters, gauges, histogram count/sum + quantiles)
    /varz      the observe.snapshot() dict as JSON (exact values,
               host/pid tagged — the JSONL line shape, live)
    /statusz   run headline JSON: uptime, process_index, executor
               compile-cache per-key hit/miss/compile-seconds, the
               autotuner panel (tuning-table size, decision
               counts), trainer in-flight pipeline depth, MFU/goodput,
               the decode-engine panel (running/waiting sequences,
               KV-page occupancy, preemption/token counters), the
               static-verifier panel (programs verified, diagnostics
               by severity/pass — paddle_tpu.analysis), anomaly
               state, the SLO panel (per-route objective, burn rate,
               goodput, predicted p99, slowest sampled trace ids) and
               fleet-router panel (replica readiness/queue depths,
               dispatch/retry/shed counters), flight-recorder
               occupancy, health results
    /tracez    last N completed spans as JSON (?n=200), or ONE sampled
               request's cross-thread timeline (?trace_id=<id>) — with
               fleet replicas registered (observe.fleet) the trace_id
               query federates to every replica and returns the merged
               cross-PROCESS timeline, remote timestamps shifted onto
               this clock by the estimated offset (&local=1 pins the
               query to this process; that is how replicas are queried,
               so federation cannot recurse)
    /fleetz    the federated fleet view: per-replica scrape health,
               the merged re-labeled registry snapshot, and derived
               panels (queue-depth skew, cross-replica p99 spread,
               handoff wire rate); /metrics?scope=fleet renders the
               same merge as Prometheus text
    /clockz    four-timestamp clock-exchange endpoint: answers with
               its receive/send wall-clock stamps so the controller's
               NTP-style estimator (observe.fleet.ClockOffsetEstimator)
               can track this process's clock offset
    /healthz   200 ok / 503 degraded from the liveness health checks
               plus the anomaly monitor (degraded while any detector
               is tripped)
    /readyz    same, but ALL checks including readiness-only ones
               (ServingEngine registers its ready() here on start())

Health checks are pluggable: ``observe.register_health_check(name, fn)``
where ``fn()`` returns truthy/falsy or ``(ok, detail)``. Checks
registered with ``readiness_only=True`` gate /readyz but not /healthz
(an engine that has not warmed up yet is unready, not unhealthy).

POST handlers are pluggable the same way: ``register_post_handler(
path, fn)`` where ``fn(handler, body_bytes)`` owns the whole response
(it may send status + headers early and stream the body — the serving
RPC control plane's submit/stream endpoints in serving/rpc.py do
exactly that, acking admission before the result exists). An
unhandled exception inside ``fn`` becomes a 500 JSON envelope
``{"error": {"type", "message"}}`` when the response has not started
yet; the GET routes are unaffected.

The server only reads shared state under the registry's own locks; it
adds zero work to instrumented call sites — the hot-path contract
stays one ``enabled()`` boolean read, server or no server.
"""

import http.server
import json
import os
import threading
import time

from .registry import parse_rendered, prometheus_exposition

__all__ = ['DiagnosticsServer', 'start', 'stop', 'active',
           'register_health_check', 'unregister_health_check',
           'run_health_checks', 'register_post_handler',
           'unregister_post_handler']

_lock = threading.Lock()
_server = None          # the active DiagnosticsServer, if any

_checks_lock = threading.Lock()
_checks = {}            # name -> (fn, readiness_only)

_post_lock = threading.Lock()
_post_handlers = {}     # path -> fn(handler, body_bytes)


# ------------------------------------------------------- health checks
def register_health_check(name, fn, readiness_only=False):
    """Register ``fn`` under ``name``. ``fn()`` returns truthy/falsy or
    ``(ok, detail)``; raising counts as failing. ``readiness_only``
    checks gate /readyz but not /healthz. Re-registering a name
    replaces it."""
    if not callable(fn):
        raise TypeError('health check %r is not callable' % name)
    with _checks_lock:
        _checks[str(name)] = (fn, bool(readiness_only))


def unregister_health_check(name):
    with _checks_lock:
        _checks.pop(str(name), None)


def run_health_checks(include_readiness=False):
    """(all_ok, {name: {'ok', 'detail'}}) — always includes the built-in
    ``anomaly`` pseudo-check (degraded while any detector is tripped)."""
    from . import anomaly_tripped
    with _checks_lock:
        items = sorted(_checks.items())
    results = {}
    all_ok = True
    for name, (fn, readiness_only) in items:
        if readiness_only and not include_readiness:
            continue
        try:
            r = fn()
            if isinstance(r, tuple):
                ok, detail = bool(r[0]), r[1]
            else:
                ok, detail = bool(r), None
        except Exception as e:
            ok, detail = False, '%s: %s' % (type(e).__name__, e)
        results[name] = {'ok': ok, 'detail': detail}
        all_ok = all_ok and ok
    tripped = anomaly_tripped()
    results['anomaly'] = {
        'ok': not tripped,
        'detail': ('tripped: %s' % ', '.join(tripped)) if tripped
        else None}
    return all_ok and not tripped, results


# -------------------------------------------------------- POST handlers
def register_post_handler(path, fn):
    """Route POST ``path`` to ``fn(handler, body_bytes)``. ``handler``
    is the live BaseHTTPRequestHandler: the fn owns the response (use
    ``handler._send`` for one-shot bodies, or send status + headers
    itself and stream). Re-registering a path replaces the handler —
    the serving RPC layer (serving/rpc.py) binds engines here."""
    if not callable(fn):
        raise TypeError('POST handler for %r is not callable' % path)
    with _post_lock:
        _post_handlers[str(path)] = fn


def unregister_post_handler(path):
    with _post_lock:
        _post_handlers.pop(str(path), None)


def _post_handler(path):
    with _post_lock:
        return _post_handlers.get(path)


# ------------------------------------------------------------- payloads
def _executor_cache_table(snap):
    """Per-compile-cache-key hit/miss/seconds table from the registry's
    executor.* metrics (key = observe.key_id of the full cache key)."""
    table = {}

    def ent(key):
        return table.setdefault(key or '', {
            'kind': None, 'hits': 0, 'misses': 0,
            'trace_seconds': None, 'compile_seconds': None,
            'first_dispatch_seconds': None})

    for rendered, v in snap.get('counters', {}).items():
        name, labels = parse_rendered(rendered)
        if name == 'executor.cache_hit_total':
            e = ent(labels.get('key'))
            e['hits'] += v
            e['kind'] = labels.get('kind', e['kind'])
        elif name == 'executor.cache_miss_total':
            e = ent(labels.get('key'))
            e['misses'] += v
            e['kind'] = labels.get('kind', e['kind'])
    for rendered, st in snap.get('histograms', {}).items():
        name, labels = parse_rendered(rendered)
        if name in ('executor.trace_seconds', 'executor.compile_seconds',
                    'executor.first_dispatch_seconds'):
            key = labels.get('key')
            if key in table:
                table[key][name.split('.', 1)[1]] = st.get('sum')
    return table


def _tuning_status(snap):
    """Autotuner panel (None when no tuning.* metric exists): table
    size plus decision counts by (op, source) — 'table' = replayed from
    the persisted table, 'measured' = microbenchmarked this process."""
    gauges = snap.get('gauges', {})
    counters = snap.get('counters', {})
    if not any(k.startswith('tuning.')
               for k in list(gauges) + list(counters)):
        return None
    decisions = {}
    for rendered, v in counters.items():
        name, labels = parse_rendered(rendered)
        if name == 'tuning.decisions_total':
            k = '%s/%s/%s' % (labels.get('op', '?'),
                              labels.get('source', '?'),
                              labels.get('impl', '?'))
            decisions[k] = v
    return {
        'table_size': gauges.get('tuning.table_size'),
        'tables_ignored':
            counters.get('tuning.table_ignored_total'),
        'decisions': decisions,
    }


def _decode_status(snap):
    """Decode-engine panel (None when no decode.* metric exists):
    running/waiting sequences, KV-page occupancy, preemption and token
    counters — the live view of serving/decode's scheduler + pool."""
    gauges = snap.get('gauges', {})
    counters = snap.get('counters', {})
    if not any(k.startswith('decode.')
               for k in list(gauges) + list(counters)):
        return None
    finished = {}
    lookups = {}
    for rendered, v in counters.items():
        name, labels = parse_rendered(rendered)
        if name == 'decode.finished_total':
            finished[labels.get('reason', '?')] = v
        elif name == 'decode.prefix_cache_lookups_total':
            lookups[labels.get('outcome', '?')] = v
    looked = sum(lookups.values())
    spec_steps = counters.get('decode.spec_steps_total', 0)
    accepted = counters.get('decode.spec_accepted_tokens_total', 0)
    stall = snap.get('histograms', {}).get(
        'decode.alloc_stall_seconds', {})
    handoffs = sum(v for k, v in counters.items()
                   if parse_rendered(k)[0] == 'handoff.count_total')
    return {
        'running_seqs': gauges.get('decode.running_seqs'),
        'waiting_seqs': gauges.get('decode.waiting_seqs'),
        'kv_blocks_free': gauges.get('decode.kv_blocks_free'),
        'kv_blocks_total': gauges.get('decode.kv_blocks_total'),
        'kv_block_occupancy': gauges.get('decode.kv_block_occupancy'),
        'tokens_total': counters.get('decode.tokens_total'),
        'steps_total': counters.get('decode.steps_total'),
        'prefills_total': counters.get('decode.prefills_total'),
        'preemptions_total': counters.get('decode.preemptions_total'),
        'pool_exhausted_total':
            counters.get('decode.pool_exhausted_total'),
        'finished_total': finished,
        # prefix cache: hit rate over lookups, tokens whose prefill
        # was skipped, resident cached pages, LRU evictions
        'prefix_cache_hit_rate':
            (lookups.get('hit', 0) / float(looked)) if looked else None,
        'prefix_tokens_reused_total':
            counters.get('decode.prefix_tokens_reused_total'),
        'prefix_cache_pages': gauges.get('decode.prefix_cache_pages'),
        'prefix_evictions_total':
            counters.get('decode.prefix_evictions_total'),
        # speculative decoding: mean accepted draft length per step
        'spec_steps_total': spec_steps or None,
        'spec_accepted_len_mean':
            (accepted / float(spec_steps)) if spec_steps else None,
        # allocator pressure: page handoff lands whole page groups at
        # once, so fragmentation and alloc stalls are cross-replica
        # signals — free count vs largest contiguous run, plus time
        # requests spent waiting on the allocator
        'kv_largest_free_run':
            gauges.get('decode.kv_largest_free_run'),
        'kv_fragmentation': gauges.get('decode.kv_fragmentation'),
        'alloc_stalls': stall.get('count'),
        'alloc_stall_seconds_p99': stall.get('p99'),
        # KV handoff (disaggregated prefill/decode): hops, pages moved
        # vs deduplicated at the receiving cache, wire bytes
        'handoff_total': handoffs or None,
        'handoff_pages_installed_total':
            counters.get('handoff.pages_installed_total'),
        'handoff_pages_deduped_total':
            counters.get('handoff.pages_deduped_total'),
        'handoff_bytes_total': counters.get('handoff.bytes_total'),
        'handoff_seconds_p99': snap.get('histograms', {}).get(
            'handoff.seconds', {}).get('p99'),
    }


def _analysis_status(snap):
    """Static-verifier panel (None when no analysis.* metric exists):
    programs verified by label, diagnostics by (severity, pass), and
    total verify seconds — the live answer to 'did the verifier see
    this program, and what did it say'."""
    counters = snap.get('counters', {})
    histograms = snap.get('histograms', {})
    if not any(k.startswith('analysis.')
               for k in list(counters) + list(histograms)):
        return None
    verified = {}
    diagnostics = {}
    for rendered, v in counters.items():
        name, labels = parse_rendered(rendered)
        if name == 'analysis.programs_verified_total':
            verified[labels.get('label', '?')] = v
        elif name == 'analysis.diagnostics_total':
            k = '%s/%s' % (labels.get('severity', '?'),
                           labels.get('pass', '?'))
            diagnostics[k] = diagnostics.get(k, 0) + v
    seconds = 0.0
    for rendered, st in histograms.items():
        name, _ = parse_rendered(rendered)
        if name == 'analysis.verify_seconds':
            seconds += st.get('sum') or 0.0
    return {'programs_verified': verified,
            'diagnostics': diagnostics,
            'verify_seconds': round(seconds, 6)}


def _slo_status(snap):
    """SLO panel (None when no slo.* metric exists): per-route
    objective, burn rate, goodput, predicted p99, and the slowest
    sampled trace ids — rendered from the registry's slo.* metrics so
    the panel works against a live tracker OR a replayed snapshot."""
    gauges = snap.get('gauges', {})
    counters = snap.get('counters', {})
    if not any(k.startswith('slo.') for k in list(gauges)
               + list(counters)):
        return None
    routes = {}

    def ent(route):
        return routes.setdefault(route or '?', {
            'latency_budget_s': None, 'availability_target': None,
            'burn_rate': None, 'goodput_rps': None,
            'predicted_p99_s': None, 'requests_total': 0,
            'in_slo_total': 0, 'violations_total': 0, 'slowest': []})

    gmap = {'slo.latency_budget_seconds': 'latency_budget_s',
            'slo.availability_target': 'availability_target',
            'slo.burn_rate': 'burn_rate',
            'slo.goodput_rps': 'goodput_rps',
            'slo.predicted_p99_seconds': 'predicted_p99_s'}
    for rendered, v in gauges.items():
        name, labels = parse_rendered(rendered)
        if name in gmap:
            ent(labels.get('route'))[gmap[name]] = v
        elif name == 'slo.slowest_seconds':
            ent(labels.get('route'))['slowest'].append(
                {'seconds': v, 'trace_id': labels.get('trace_id')})
    cmap = {'slo.requests_total': 'requests_total',
            'slo.in_slo_total': 'in_slo_total',
            'slo.violations_total': 'violations_total'}
    for rendered, v in counters.items():
        name, labels = parse_rendered(rendered)
        if name in cmap:
            ent(labels.get('route'))[cmap[name]] = v
    for r in routes.values():
        r['slowest'].sort(key=lambda s: -(s['seconds'] or 0.0))
        del r['slowest'][5:]
    return routes


def _router_status(snap):
    """Fleet-router panel (None when no router.* metric exists):
    replica readiness + queue depths, dispatch/retry/shed counters."""
    gauges = snap.get('gauges', {})
    counters = snap.get('counters', {})
    if not any(k.startswith('router.') for k in list(gauges)
               + list(counters)):
        return None
    depths, dispatched, retries, shed = {}, {}, 0, {}
    for rendered, v in gauges.items():
        name, labels = parse_rendered(rendered)
        if name == 'router.replica_queue_depth':
            depths[labels.get('replica', '?')] = v
    for rendered, v in counters.items():
        name, labels = parse_rendered(rendered)
        if name == 'router.dispatch_total':
            dispatched[labels.get('replica', '?')] = v
        elif name == 'router.retries_total':
            retries += v
        elif name == 'router.shed_total':
            shed[labels.get('reason', '?')] = v
    hedges = sum(v for k, v in counters.items()
                 if parse_rendered(k)[0] == 'router.hedge_total')
    requests = sum(v for k, v in counters.items()
                   if parse_rendered(k)[0] == 'router.requests_total')
    phases = {}
    for rendered, v in gauges.items():
        name, labels = parse_rendered(rendered)
        if name in ('router.phase_replicas',
                    'router.phase_replicas_ready'):
            ph = phases.setdefault(labels.get('phase', '?'), {})
            ph['ready' if name.endswith('_ready') else 'total'] = v
    for rendered, v in counters.items():
        name, labels = parse_rendered(rendered)
        if name == 'router.phase_dispatch_total':
            ph = phases.setdefault(labels.get('phase', '?'), {})
            ph['dispatched'] = ph.get('dispatched', 0) + v
    return {
        'replicas_ready': gauges.get('router.replicas_ready'),
        'replicas_total': gauges.get('router.replicas_total'),
        'replica_queue_depth': depths,
        'dispatch_total': dispatched,
        'retries_total': retries,
        'shed_total': shed,
        'no_replica_total': counters.get('router.no_replica_total'),
        'hedge_total': hedges,
        'hedge_fraction': round(hedges / requests, 6) if requests
        else None,
        'retry_budget_tokens':
            gauges.get('router.retry_budget_tokens'),
        # disaggregated fleets: per-phase replica census + dispatches
        'phases': phases or None,
    }


_FLEET_STATE_NAMES = {0: 'UP', 1: 'DRAINING', 2: 'QUARANTINED',
                      3: 'DEAD'}


def _fleet_status(snap):
    """Fleet-controller panel (None when no controller.* metric
    exists): per-replica state machine (UP/DRAINING/QUARANTINED/DEAD
    from the controller.replica_state gauge codes), the census by
    state, and the scale/heal/quarantine counters — works against a
    live controller OR a replayed snapshot."""
    gauges = snap.get('gauges', {})
    counters = snap.get('counters', {})
    if not any(k.startswith('controller.') for k in list(gauges)
               + list(counters)):
        return None
    replicas, census = {}, {}
    ready = None
    for rendered, v in gauges.items():
        name, labels = parse_rendered(rendered)
        if name == 'controller.replica_state':
            replicas[labels.get('replica', '?')] = \
                _FLEET_STATE_NAMES.get(int(v), '?')
        elif name == 'controller.replicas':
            census[labels.get('state', '?')] = v
        elif name == 'controller.replicas_ready':
            ready = v

    def total(counter):
        return sum(v for k, v in counters.items()
                   if parse_rendered(k)[0] == counter)

    return {
        'replicas': replicas,
        'census': census,
        'replicas_ready': ready,
        'scale_out_total': total('controller.scale_out_total'),
        'scale_in_total': total('controller.scale_in_total'),
        'heals_total': total('controller.heals_total'),
        'deaths_total': total('controller.deaths_total'),
        'quarantines_total': total('controller.quarantines_total'),
        'spawn_failures_total':
            total('controller.spawn_failures_total'),
    }


def _statusz_doc():
    from . import (anomaly_state, enabled, flight_dump_path,
                   flight_recorder, goodput, snapshot)
    snap = snapshot()
    gauges = snap.get('gauges', {})
    fr = flight_recorder()
    total, evicted = fr.counts()
    with _lock:
        srv = _server
    ok, checks = run_health_checks(include_readiness=True)
    return {
        'uptime_seconds': round(time.time() - fr.started_at, 3),
        'pid': snap.get('pid'),
        'process_index': snap.get('host'),
        'telemetry_enabled': enabled(),
        'server': ({'host': srv.host, 'port': srv.port}
                   if srv is not None else None),
        'goodput': goodput(),
        'mfu': gauges.get('trainer.mfu'),
        'steps_per_sec_ema': gauges.get('trainer.steps_per_sec_ema'),
        'steps_total': snap.get('counters', {}).get('trainer.steps_total'),
        'inflight_depth': gauges.get('trainer.inflight_depth'),
        'prefetch_queue_depth':
            gauges.get('trainer.prefetch_queue_depth'),
        'executor_cache': _executor_cache_table(snap),
        'tuning': _tuning_status(snap),
        'decode': _decode_status(snap),
        'analysis': _analysis_status(snap),
        'slo': _slo_status(snap),
        'router': _router_status(snap),
        'fleet': _fleet_status(snap),
        'anomalies': anomaly_state(),
        'flight': {'events': total, 'evicted': evicted,
                   'capacity': fr.capacity,
                   'dump_path': flight_dump_path()},
        'healthy': ok,
        'health': checks,
    }


def _tracez_doc(query):
    from . import spans
    params = dict(p.split('=', 1) for p in query.split('&') if '=' in p)
    try:
        n = int(params.get('n', 200))
    except Exception:
        n = 200
    rec = spans()
    evs = rec.events()
    trace_id = params.get('trace_id')
    if trace_id:
        # one sampled request's full cross-thread timeline: every span,
        # instant, and flow event whose args carry this trace id
        # (reqtrace.RequestContext tags them all)
        evs = [e for e in evs
               if (e.get('args') or {}).get('trace_id') == trace_id]
        doc = {'trace_id': trace_id, 'spans': evs,
               'threads': sorted({e.get('tid') for e in evs}),
               'recorded': len(evs)}
        # federation: unless the caller pinned the query to this
        # process (&local=1 — how WE query replicas, so a federating
        # replica cannot recurse), fan out to every registered fleet
        # replica and append its matching spans, timestamps shifted
        # onto this process's clock by the estimated offset
        if 'local' not in params:
            from .fleet import fleet
            fed = fleet()
            if fed.replicas():
                remote = fed.federated_trace(trace_id)
                doc['spans'] = sorted(evs + remote['spans'],
                                      key=lambda e: e.get('ts', 0.0))
                doc['recorded'] = len(doc['spans'])
                doc['sources'] = remote['sources']
        return doc
    return {'spans': evs[-max(1, n):], 'recorded': len(evs),
            'dropped': getattr(rec, '_dropped', 0)}


_INDEX = """paddle_tpu diagnostics server
/metrics   Prometheus exposition of the metrics registry
           (?scope=fleet: the federated fleet-wide merge)
/varz      observe.snapshot() as JSON
/statusz   run headline: uptime, cache keys, pipeline depth, MFU/goodput
/tracez    last completed spans (?n=200); ?trace_id= federates to
           registered fleet replicas unless &local=1
/fleetz    federated fleet view: per-replica scrape health, merged
           registry snapshot, derived panels (queue skew, p99 spread)
/clockz    four-timestamp clock exchange endpoint (NTP-style offset
           estimation by the controller)
/healthz   liveness (503 while degraded / anomaly tripped)
/readyz    readiness (all checks incl. readiness-only)
"""


class _Handler(http.server.BaseHTTPRequestHandler):
    server_version = 'paddle-tpu-diagnostics'
    protocol_version = 'HTTP/1.1'

    def log_message(self, fmt, *args):   # stay silent on stderr
        pass

    def _send(self, code, body, ctype='application/json'):
        data = body.encode('utf-8')
        self.send_response(code)
        self.send_header('Content-Type', ctype + '; charset=utf-8')
        self.send_header('Content-Length', str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        from . import snapshot
        path, _, query = self.path.partition('?')
        try:
            if path in ('/', '/help'):
                self._send(200, _INDEX, ctype='text/plain')
            elif path == '/metrics':
                if 'scope=fleet' in query:
                    from .fleet import fleet
                    body = prometheus_exposition(
                        fleet().merged_snapshot())
                else:
                    body = prometheus_exposition(snapshot())
                self._send(200, body,
                           ctype='text/plain; version=0.0.4')
            elif path == '/clockz':
                # NTP-style exchange: the caller stamps t0 before the
                # request and t3 after the reply; we answer with our
                # receive/send wall-clock stamps (t1, t2)
                t_recv = time.time()
                self._send(200, json.dumps({'t_recv': t_recv,
                                            't_send': time.time(),
                                            'pid': os.getpid()}))
            elif path == '/fleetz':
                from .fleet import fleet
                self._send(200, json.dumps(fleet().fleet_doc(),
                                           sort_keys=True, default=str))
            elif path == '/varz':
                self._send(200, json.dumps(snapshot(), sort_keys=True,
                                           default=str))
            elif path == '/statusz':
                self._send(200, json.dumps(_statusz_doc(),
                                           sort_keys=True, default=str))
            elif path == '/tracez':
                self._send(200, json.dumps(_tracez_doc(query),
                                           default=str))
            elif path in ('/healthz', '/readyz'):
                ok, checks = run_health_checks(
                    include_readiness=(path == '/readyz'))
                self._send(200 if ok else 503, json.dumps(
                    {'status': 'ok' if ok else 'degraded',
                     'checks': checks}, sort_keys=True, default=str))
            else:
                self._send(404, json.dumps({'error': 'no route %s' % path,
                                            'routes': ['/metrics', '/varz',
                                                       '/statusz',
                                                       '/tracez',
                                                       '/fleetz',
                                                       '/clockz',
                                                       '/healthz',
                                                       '/readyz']}))
        except Exception as e:   # never kill the serving thread
            try:
                self._send(500, json.dumps(
                    {'error': '%s: %s' % (type(e).__name__, e)}))
            except Exception:
                pass

    def do_POST(self):
        path, _, _query = self.path.partition('?')
        fn = _post_handler(path)
        if fn is None:
            with _post_lock:
                routes = sorted(_post_handlers)
            self._send(404, json.dumps({'error': 'no POST route %s'
                                        % path, 'routes': routes}))
            return
        try:
            length = int(self.headers.get('Content-Length', 0) or 0)
            body = self.rfile.read(length) if length > 0 else b''
            fn(self, body)
        except Exception as e:   # handler died before/while responding
            try:
                self._send(500, json.dumps(
                    {'error': {'type': type(e).__name__,
                               'message': str(e)}}))
            except Exception:
                pass             # response already started: drop the wire


class _ThreadingServer(http.server.ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True


class DiagnosticsServer(object):
    """Handle on the running server: .host/.port/.url, close()."""

    def __init__(self, httpd, thread):
        self._httpd = httpd
        self._thread = thread
        self.host, self.port = httpd.server_address[:2]
        self.url = 'http://%s:%d' % (self.host, self.port)

    def close(self):
        stop()


def start(host='127.0.0.1', port=0):
    """Start the server (idempotent: a second call returns the running
    instance). port=0 binds an ephemeral port — read it back from the
    returned object's .port."""
    global _server
    with _lock:
        if _server is not None:
            return _server
        httpd = _ThreadingServer((host, int(port)), _Handler)
        t = threading.Thread(target=httpd.serve_forever,
                             kwargs={'poll_interval': 0.2},
                             daemon=True,
                             name='paddle_tpu_diagnostics')
        t.start()
        _server = DiagnosticsServer(httpd, t)
        return _server


def stop():
    """Shut the server down and release the port (no-op when stopped)."""
    global _server
    with _lock:
        srv, _server = _server, None
    if srv is not None:
        srv._httpd.shutdown()
        srv._httpd.server_close()
        srv._thread.join(timeout=5)


def active():
    with _lock:
        return _server
