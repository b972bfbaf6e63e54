"""paddle_tpu.observe — the telemetry subsystem.

Six pieces, one switch:

- a dependency-free metrics registry (labeled counters / gauges /
  histograms) with a periodic JSONL sink, an end-of-run summary table,
  and a Prometheus text-exposition renderer (`registry.py`),
- host-side span tracing exported as Chrome-trace/Perfetto JSON,
  bridged to ``jax.profiler.TraceAnnotation`` so host spans line up
  with XLA device traces (`spans.py`),
- MFU/goodput accounting: XLA ``cost_analysis()`` FLOPs vs the chip's
  peak, and productive-steps-over-total-wall goodput that charges
  restart/recompile/checkpoint time against the run (`mfu.py`),
- a live diagnostics HTTP server — ``serve(port=...)`` or
  ``PADDLE_TPU_STATUSZ_PORT`` — with /metrics /varz /statusz /tracez
  /healthz /readyz and a pluggable health-check registry
  (`diagnostics.py`),
- a flight recorder: bounded ring of structured events dumped as a
  postmortem JSON on trainer exceptions, guard raises, SIGTERM, and
  injected kills — armed by ``PADDLE_TPU_FLIGHT_DUMP`` even with
  metrics off (`flight.py`, rendered by tools/flight_report.py),
- streaming anomaly detection: EWMA z-score detectors over loss /
  step-time / anything fed to ``anomaly()``, flipping /healthz to
  degraded while tripped (`anomaly.py`),
- per-request distributed tracing: ``RequestContext`` correlates one
  request's spans across threads via trace ids + Chrome-trace flow
  events, sampled by ``PADDLE_TPU_TRACE_SAMPLE``, with histogram
  exemplars linking /metrics p99 spikes to /tracez traces
  (`reqtrace.py`),
- SLO tracking: declared per-route objectives, rolling error-budget
  burn rate, goodput, and the predicted p99 that drives the serving
  router's SLO-aware admission (`slo.py`).

Instrumented call sites across the executor, trainer, reader, fault,
and parallel layers all funnel through the module-level helpers here
(``inc`` / ``set_gauge`` / ``record`` / ``span``), every one of which
checks ``enabled()`` first — a module-global read — so with
observability off a hot loop pays one boolean test per call site and
nothing else. Turn it on with::

    from paddle_tpu import observe
    observe.enable(jsonl='run_metrics.jsonl', trace='run_trace.json')
    ...train...
    observe.disable()          # final snapshot + trace export

or ``PADDLE_TPU_METRICS_JSONL=... PADDLE_TPU_TRACE_JSON=...`` with
``observe.enable_from_env()``. See docs/observability.md for the
metric catalog.
"""

import atexit
import contextlib
import gc
import json
import os
import sys
import threading
import time
import zlib

from .anomaly import AnomalyMonitor
from .flight import FlightRecorder
from .mfu import (GoodputTracker, cost_analysis_flops,  # noqa: F401
                  device_peak_flops, overlap_fraction)
from .registry import Registry
from .spans import SpanRecorder, StateClock

__all__ = ['enabled', 'enable', 'enable_from_env', 'disable', 'reset',
           'registry', 'spans', 'counter', 'gauge', 'histogram', 'inc',
           'set_gauge', 'add_gauge', 'record', 'get_gauge', 'get_counter',
           'span', 'StateClock', 'key_id', 'flush', 'maybe_flush',
           'jsonl_path',
           'export_trace',
           'run_begin', 'step_done', 'overhead', 'goodput',
           'step_telemetry', 'summary_table', 'snapshot',
           'device_peak_flops', 'cost_analysis_flops', 'overlap_fraction',
           # live diagnostics / crash forensics / anomaly surface
           'serve', 'stop_serving', 'register_health_check',
           'unregister_health_check', 'flight_recorder', 'flight_event',
           'flight_dump', 'flight_dump_path', 'arm_flight',
           'arm_flight_from_env', 'anomaly', 'anomaly_state',
           'anomaly_tripped']

_enabled = False          # THE gate: helpers read this module global
_REG = Registry()
_SPANS = SpanRecorder()
_GOODPUT = GoodputTracker()
_FLIGHT = FlightRecorder()
_ANOMALY = AnomalyMonitor()
_SINK = {'path': None, 'every_secs': 30.0, 'last': 0.0,
         'trace_path': None}
_atexit_armed = []

# flight recording has its own single-read gate so a crash-forensics-
# only run (PADDLE_TPU_FLIGHT_DUMP set, metrics off) still records the
# ring. _flight_on == (_enabled or _flight_armed), maintained at every
# state change, so the disabled hot path stays ONE boolean read.
_flight_on = False
_flight_armed = False
_FLIGHT_DUMP = {'path': None, 'last_exc': None, 'last_path': None}

# span drops become a registry counter (satellite: a truncated trace is
# detectable from /metrics alone). Name-based lookup so registry.clear()
# cannot orphan the counter object.
_SPANS.on_drop = lambda n=1: (
    _REG.counter('spans_dropped_total').inc(n) if _enabled else None)


# ------------------------------------------------------------- lifecycle
def enabled():
    """True when telemetry is on. The disabled fast path everywhere is
    this one global read."""
    return _enabled


def enable(jsonl=None, trace=None, every_secs=30.0):
    """Turn telemetry on. `jsonl` appends periodic metric snapshots
    (one JSON object per line) plus a final ``kind: "summary"`` line on
    disable()/exit; `trace` writes a Chrome-trace JSON of all recorded
    spans at the same points. `every_secs` throttles maybe_flush()."""
    global _enabled, _flight_on
    _enabled = True
    _flight_on = True
    if jsonl is not None:
        _SINK['path'] = jsonl
    if trace is not None:
        _SINK['trace_path'] = trace
    _SINK['every_secs'] = every_secs
    _SINK['last'] = time.monotonic()
    if not _atexit_armed:
        _atexit_armed.append(True)
        atexit.register(_atexit_flush)
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def jsonl_path():
    """Path of the JSONL metrics sink, or None when no sink is set.
    The cross-process fleet uses this to place each replica worker's
    sink beside the parent's (``<stem>-<replica>.jsonl``), so one
    ``tools/metrics_report.py --fleet <dir>`` merges the whole run."""
    return _SINK['path']


def enable_from_env(environ=None):
    """enable() iff PADDLE_TPU_METRICS_JSONL and/or PADDLE_TPU_TRACE_JSON
    (or PADDLE_TPU_OBSERVE=1) is set; additionally arms the flight
    recorder from PADDLE_TPU_FLIGHT_DUMP and starts the diagnostics
    server on PADDLE_TPU_STATUSZ_PORT. Returns whether telemetry is
    on."""
    env = os.environ if environ is None else environ
    jsonl = env.get('PADDLE_TPU_METRICS_JSONL')
    trace = env.get('PADDLE_TPU_TRACE_JSON')
    if jsonl or trace or env.get('PADDLE_TPU_OBSERVE') == '1':
        enable(jsonl=jsonl, trace=trace)
    arm_flight_from_env(env)
    port = env.get('PADDLE_TPU_STATUSZ_PORT')
    if port:
        try:
            serve(port=int(port))
        except Exception as e:
            import warnings
            warnings.warn('observe: diagnostics server on port %s failed '
                          'to start (%s: %s)' % (port, type(e).__name__, e))
    return _enabled


def disable():
    """Final snapshot (kind 'summary') + trace export, then gate off.
    Flight recording stays on when separately armed (arm_flight)."""
    global _enabled, _flight_on
    if _enabled:
        flush(kind='summary')
        export_trace()
    _enabled = False
    _flight_on = _flight_armed
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def reset():
    """Clear every metric, span, flight event, anomaly baseline, and the
    goodput ledger (sink config and the enabled flag survive, and with
    the flag the collector's callback). profiler.reset_profiler() calls
    this."""
    _REG.clear()
    _SPANS.clear()
    _GOODPUT.reset()
    _FLIGHT.clear()
    _ANOMALY.reset()


def _atexit_flush():
    if _enabled and _SINK['path']:
        try:
            flush(kind='summary')
        except Exception:
            pass
    if _enabled and _SINK['trace_path']:
        try:
            export_trace()
        except Exception:
            pass


# ------------------------------------------------ the interpreter's pauses
_gc_began = None


def _on_gc(phase, info):
    """In ``gc.callbacks`` while telemetry is on: a collection stops
    every Python thread wherever it strikes, so each is a ``host.gc``
    span on the ring's clock, on the thread it struck (the latest to
    start, hence the innermost over whatever span was open there), a
    record of ``host.gc_seconds{generation}`` and one more in
    ``host.gc_total{generation}``; ``host.gc_seconds_total`` sums the
    three generations. It runs inside the collector: explicit bounds, no
    profiler annotation, nothing but the ring's and the registry's own
    appends (both locks are re-entrant: the collection may have struck
    this thread inside either)."""
    global _gc_began
    if phase == 'start':
        _gc_began = time.perf_counter()
        return
    t1 = time.perf_counter()
    t0, _gc_began = _gc_began, None
    if t0 is None or not _enabled:
        return
    generation = info['generation']
    _SPANS.add_span('host.gc', t0, t1, info)
    _REG.histogram('host.gc_seconds').observe(t1 - t0,
                                              generation=generation)
    _REG.counter('host.gc_total').inc(generation=generation)
    _REG.counter('host.gc_seconds_total').inc(t1 - t0)


# --------------------------------------------------------------- access
def registry():
    return _REG


def spans():
    return _SPANS


def counter(name, help=''):
    return _REG.counter(name, help)


def gauge(name, help=''):
    return _REG.gauge(name, help)


def histogram(name, help=''):
    return _REG.histogram(name, help)


# ------------------------------------------------- gated helper facade
# Call sites in hot loops use these: when disabled each is one global
# read + return.
def inc(name, n=1, **labels):
    if _enabled:
        _REG.counter(name).inc(n, **labels)


def set_gauge(name, value, **labels):
    if _enabled:
        _REG.gauge(name).set(value, **labels)


def add_gauge(name, n, **labels):
    if _enabled:
        _REG.gauge(name).add(n, **labels)


def record(name, value, exemplar=None, **labels):
    """Histogram observation; ``exemplar`` (a trace id) rides along to
    the worst-bucket exemplar slot so /metrics p99 spikes link to
    /tracez?trace_id= (see reqtrace.py)."""
    if _enabled:
        _REG.histogram(name).observe(value, exemplar=exemplar, **labels)


def get_gauge(name, default=None, **labels):
    return _REG.gauge(name).value(default=default, **labels)


def get_counter(name, **labels):
    return _REG.counter(name).value(**labels)


class _NullCtx(object):
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


class _SpanCtx(object):
    __slots__ = ('name', 'attrs', 'hist', 'labels', 'clock', '_sp')

    def __init__(self, name, attrs, hist, labels, clock):
        self.name = name
        self.attrs = attrs
        self.hist = hist
        self.labels = labels
        self.clock = clock

    def __enter__(self):
        sp = self._sp = _SPANS.begin(self.name, self.attrs or None)
        if self.clock is not None:
            self.clock.enter(self.labels['state'], sp.t0)
        return sp

    def __exit__(self, *exc):
        seconds = _SPANS.end(self._sp)
        if seconds is not None:
            if self.hist is not None:
                _REG.histogram(self.hist).observe(seconds,
                                                  **(self.labels or {}))
            if self.clock is not None:
                self.clock.exit(self.labels['state'],
                                self._sp.t0 + seconds)
        return False


def span(name, record=None, labels=None, clock=None, **attrs):
    """Context manager recording one nested host span (and, when jax is
    loaded, a jax.profiler.TraceAnnotation of the same name). ``record``
    names a histogram that takes the span's duration on exit, under
    ``labels``: one pair of clock readings gives both the span (the
    profiler's clock, a traced window) and the histogram (the whole
    run). ``clock`` is a ``StateClock`` of which the span is the state
    ``labels['state']``: the same pair of readings moves it, so its
    totals are the histogram's sums (and the loop's time between two
    spans). ``attrs`` carry identifiers (step, bucket, request id); they
    never go into the name. No-op singleton when disabled."""
    if not _enabled:
        return _NULL
    return _SpanCtx(name, attrs, record, labels, clock)


def key_id(key):
    """Stable 8-hex-digit id for an unwieldy cache key, used as a metric
    label (full keys embed object ids and shape tuples)."""
    return '%08x' % (zlib.crc32(repr(key).encode()) & 0xffffffff)


# ---------------------------------------------------------------- sink
def flush(kind='snapshot'):
    """Write one JSONL snapshot line now (if a sink path is set)."""
    _SINK['last'] = time.monotonic()
    path = _SINK['path']
    if not path:
        return
    _GOODPUT.publish(_REG)
    line = _REG.to_json_line(ts=round(time.time(), 3), kind=kind,
                             pid=os.getpid(), host=_host())
    with open(path, 'a') as f:
        f.write(line + '\n')


def maybe_flush():
    """Time-throttled flush — call freely from step loops."""
    if not _enabled or not _SINK['path']:
        return
    if time.monotonic() - _SINK['last'] >= _SINK['every_secs']:
        flush()


def export_trace(path=None):
    """Write the Chrome trace JSON (default: the enable(trace=...) path).
    Returns the path written, or None when there is nowhere to write."""
    path = path or _SINK['trace_path']
    if not path:
        return None
    return _SPANS.export(path)


def summary_table():
    _GOODPUT.publish(_REG)
    return _REG.summary_table()


def _host():
    """The `host` tag on flushed/snapshot records that makes merged
    multihost JSONLs attributable. ``PADDLE_TPU_OBSERVE_HOST`` (read
    per call) overrides — replica worker subprocesses stamp their
    replica name here so a fleet's side-by-side JSONLs stay
    disambiguated even though every worker is jax process 0; otherwise
    jax.process_index() when jax is loaded and initialized, else 0
    (never imports jax itself)."""
    label = os.environ.get('PADDLE_TPU_OBSERVE_HOST')
    if label:
        return label
    jax = sys.modules.get('jax')
    if jax is not None:
        try:
            return int(jax.process_index())
        except Exception:
            pass
    return 0


def snapshot():
    _GOODPUT.publish(_REG)
    snap = _REG.snapshot()
    snap['host'] = _host()
    snap['pid'] = os.getpid()
    return snap


# ---------------------------------------------------------- mfu/goodput
def run_begin():
    if _enabled:
        _GOODPUT.begin()


def step_done(seconds, steps=1):
    if _enabled:
        _GOODPUT.step(seconds, steps)


def overhead(kind, seconds):
    if _enabled:
        _GOODPUT.overhead(kind, seconds)


def goodput():
    return _GOODPUT.goodput()


def step_telemetry():
    """Small per-step dict attached to EndStepEvent (cheap reads only):
    step wall time EMA / throughput / MFU / goodput, where known."""
    return {
        'steps_per_sec_ema': get_gauge('trainer.steps_per_sec_ema'),
        'step_seconds_last': get_gauge('trainer.step_seconds_last'),
        'mfu': get_gauge('trainer.mfu'),
        'goodput': _GOODPUT.goodput(),
    }


# ----------------------------------------------------- diagnostics server
def serve(port=None, host='127.0.0.1'):
    """Start the live diagnostics HTTP server (/metrics /varz /statusz
    /tracez /healthz /readyz — see observe/diagnostics.py). Stdlib-only,
    daemon thread, idempotent. port=None reads PADDLE_TPU_STATUSZ_PORT
    (default 0 = ephemeral; read the bound port off the returned
    object). Implies enable(): a scrape endpoint over an empty registry
    would be pointless."""
    from . import diagnostics
    if port is None:
        port = int(os.environ.get('PADDLE_TPU_STATUSZ_PORT', '0') or 0)
    if not _enabled:
        enable()
    return diagnostics.start(host=host, port=int(port))


def stop_serving():
    """Shut the diagnostics server down (no-op when not running)."""
    from . import diagnostics
    diagnostics.stop()


def register_health_check(name, fn, readiness_only=False):
    """Plug a health check into /healthz (and /readyz); fn() returns
    truthy/falsy or (ok, detail). readiness_only=True gates only
    /readyz (e.g. ServingEngine.ready before warmup)."""
    from . import diagnostics
    diagnostics.register_health_check(name, fn,
                                      readiness_only=readiness_only)


def unregister_health_check(name):
    from . import diagnostics
    diagnostics.unregister_health_check(name)


# --------------------------------------------------------- flight recorder
def flight_recorder():
    return _FLIGHT


def flight_event(kind, /, **data):
    """Append one structured event to the flight ring. One module-global
    boolean read + return when neither telemetry nor the flight
    recorder is armed (the hot-path contract)."""
    if _flight_on:
        _FLIGHT.record(kind, **data)


def arm_flight(path=None, capacity=None):
    """Turn flight recording on independently of the metrics gate and
    (optionally) set the postmortem dump path. With a path set, a
    SIGTERM — the preemption signal — dumps before the default handler
    runs."""
    global _flight_armed, _flight_on
    _flight_armed = True
    _flight_on = True
    if capacity:
        _FLIGHT.capacity = int(capacity)
    if path:
        _FLIGHT_DUMP['path'] = path
        _install_sigterm_handler()
    return _FLIGHT


def arm_flight_from_env(environ=None):
    """arm_flight() iff PADDLE_TPU_FLIGHT_DUMP names a dump path (the
    Trainer calls this at train start, so a preempted run leaves a
    postmortem without any code change)."""
    env = os.environ if environ is None else environ
    path = env.get('PADDLE_TPU_FLIGHT_DUMP')
    if path:
        arm_flight(path=path)
    return _flight_on


def flight_dump_path():
    return _FLIGHT_DUMP['path']


def flight_dump(reason, exc=None, path=None, extra=None):
    """Write the postmortem JSON now (ring + final metrics snapshot +
    last spans + anomaly state + exception). No-op unless flight
    recording is on AND a path is known (arm_flight/env/explicit).
    Re-dumping for the SAME exception object is a no-op, so the guard's
    dump and the trainer's outer except don't overwrite each other's
    reason. Never raises — forensics must not mask the original
    failure. Returns the path written, or None."""
    if not _flight_on:
        return None
    path = path or _FLIGHT_DUMP['path']
    if not path:
        return None
    if exc is not None and exc is _FLIGHT_DUMP['last_exc']:
        return _FLIGHT_DUMP['last_path']
    try:
        _GOODPUT.publish(_REG)
        p = _FLIGHT.dump(path, reason, exc=exc,
                         metrics=_REG.snapshot(),
                         spans=_SPANS.events()[-100:],
                         anomalies=_ANOMALY.state(),
                         host=_host(), extra=extra)
    except Exception:
        return None
    if exc is not None:
        _FLIGHT_DUMP['last_exc'] = exc
        _FLIGHT_DUMP['last_path'] = p
    return p


_sigterm_state = {'installed': False}


def _install_sigterm_handler():
    """Dump a postmortem on SIGTERM (the preemption notice), then chain
    to the previously installed handler / default behavior. Main-thread
    only (signal.signal's requirement); never fails the caller."""
    if _sigterm_state['installed']:
        return
    import signal
    if threading.current_thread() is not threading.main_thread():
        return
    try:
        prev = signal.getsignal(signal.SIGTERM)

        def _on_sigterm(signum, frame):
            flight_dump('sigterm')
            if callable(prev):
                prev(signum, frame)
            else:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _on_sigterm)
        _sigterm_state['installed'] = True
    except (ValueError, OSError):
        pass


# ------------------------------------------------------ anomaly detection
def anomaly(signal, value):
    """Feed one sample to the streaming anomaly monitor (EWMA z-score
    per signal — see observe/anomaly.py). Publishes
    anomaly_score{signal=}/anomaly_tripped{signal=} gauges, counts
    trips, records trip/clear flight events, and flips /healthz to
    degraded while tripped. One boolean read + return when disabled.
    Returns the sample's z-score (None when disabled)."""
    if not _enabled:
        return None
    score, transitioned, tripped = _ANOMALY.observe(signal, value)
    _REG.gauge('anomaly_score').set(score, signal=signal)
    _REG.gauge('anomaly_tripped').set(1 if tripped else 0, signal=signal)
    if transitioned:
        if tripped:
            _REG.counter('anomaly_trips_total').inc(signal=signal)
            flight_event('anomaly_trip', signal=signal, score=score,
                         value=value)
        else:
            flight_event('anomaly_clear', signal=signal)
    return score


def anomaly_state():
    """{signal: detector state} — /statusz and postmortems."""
    return _ANOMALY.state()


def anomaly_tripped():
    """Sorted names of currently-tripped anomaly signals."""
    return _ANOMALY.tripped()
