"""Live diagnostics surface (paddle_tpu.observe): the /metrics
Prometheus exposition (round-trip parsed mid-train), /varz /statusz
/tracez payloads, /healthz-/readyz health-check plumbing (including the
anomaly-driven degradation and ServingEngine.ready), the flight
recorder ring + postmortem dump + tools/flight_report.py, the
spans_dropped_total satellite, metrics_report --prom/--per-host, and
the disabled-path overhead contract for the new call sites."""

import gc
import importlib
import json
import os
import re
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _diag_clean():
    """Leave the diagnostics/telemetry globals as other tests expect:
    server stopped, health checks gone, flight disarmed, gate off."""
    from paddle_tpu import observe
    from paddle_tpu.observe import diagnostics
    yield
    diagnostics.stop()
    with diagnostics._checks_lock:
        diagnostics._checks.clear()
    observe._SINK['path'] = None
    observe._SINK['trace_path'] = None
    observe._flight_armed = False
    observe._FLIGHT_DUMP.update(path=None, last_exc=None, last_path=None)
    observe.disable()
    observe.reset()


def _get(url, timeout=10):
    """(status, body) — 4xx/5xx come back as values, not raises."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode('utf-8')
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode('utf-8')


# one value line of the text exposition format
_PROM_LINE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{([^}]*)\})? '
    r'(-?(?:[0-9.]+(?:[eE][+-]?[0-9]+)?)|NaN|[+-]Inf)$')
_PROM_LABEL = re.compile(r'^([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"$')


def parse_prom(text):
    """Strict exposition parse -> (series, types): every non-comment
    line must be a well-formed sample, every label well-quoted."""
    series, types = {}, {}
    for ln in text.splitlines():
        if not ln.strip():
            continue
        if ln.startswith('#'):
            parts = ln.split()
            if len(parts) >= 4 and parts[1] == 'TYPE':
                types[parts[2]] = parts[3]
            continue
        m = _PROM_LINE.match(ln)
        assert m, 'unparseable exposition line: %r' % ln
        name, labelstr, val = m.groups()
        labels = {}
        if labelstr:
            for item in re.split(r',(?=[a-zA-Z_])', labelstr):
                lm = _PROM_LABEL.match(item)
                assert lm, 'bad label %r in %r' % (item, ln)
                labels[lm.group(1)] = lm.group(2)
        series[(name, tuple(sorted(labels.items())))] = float(val)
    return series, types


# ----------------------------------------------------------- exposition
def test_prometheus_exposition_round_trip():
    from paddle_tpu.observe.registry import (Registry,
                                             prometheus_exposition)

    reg = Registry()
    reg.counter('requests_total').inc(3, shard='a')
    reg.counter('requests_total').inc(4)
    reg.gauge('queue.depth').set(7.5, ring='x')
    h = reg.histogram('step.seconds')
    for v in range(100):
        h.observe(v / 100.0, phase='feed')
    text = prometheus_exposition(reg.snapshot())
    series, types = parse_prom(text)

    assert types['requests_total'] == 'counter'
    assert types['queue_depth'] == 'gauge'
    assert types['step_seconds'] == 'summary'     # dots mangled
    assert series[('requests_total', (('shard', 'a'),))] == 3
    assert series[('requests_total', ())] == 4
    assert series[('queue_depth', (('ring', 'x'),))] == 7.5
    # summary consistency: count/sum exact, quantiles within the data
    lk = (('phase', 'feed'),)
    assert series[('step_seconds_count', lk)] == 100
    assert series[('step_seconds_sum', lk)] == pytest.approx(49.5)
    for q in ('0.5', '0.9', '0.95', '0.99'):
        v = series[('step_seconds', tuple(sorted(
            (('phase', 'feed'), ('quantile', q))))) ]
        assert 0.0 <= v <= 0.99
        assert v >= 0.4 * float(q)                # roughly ordered


# ------------------------------------------------ live server + trainer
def _tiny_trainer(fluid):
    def train_func():
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1)
        return fluid.layers.mean(fluid.layers.square_error_cost(pred, y))

    return fluid.Trainer(train_func,
                         lambda: fluid.optimizer.SGD(learning_rate=0.01),
                         place=fluid.CPUPlace())


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [{'x': rng.rand(8, 4).astype('float32'),
             'y': rng.rand(8, 1).astype('float32')} for _ in range(n)]


def test_serve_scrapes_during_training():
    """The acceptance e2e: with observe.serve() active during
    Trainer.train, /metrics is valid Prometheus exposition containing
    step counters and phase histograms — scraped mid-loop AND verified
    exactly after; /varz, /statusz, /tracez all answer."""
    import paddle_tpu as fluid
    from paddle_tpu import observe

    srv = observe.serve(port=0)
    assert srv.port > 0
    trainer = _tiny_trainer(fluid)
    batches = _batches(3)

    live = {}

    def handler(e):
        if isinstance(e, fluid.trainer.EndStepEvent) and e.step == 2:
            live['code'], live['body'] = _get(srv.url + '/metrics')

    trainer.train(1, reader=lambda: iter(batches),
                  event_handler=handler)

    # mid-train scrape: valid exposition with the step counter and the
    # phase histogram series already present
    assert live['code'] == 200
    series, types = parse_prom(live['body'])
    assert types['trainer_steps_total'] == 'counter'
    assert series[('trainer_steps_total', ())] >= 2
    assert types['trainer_phase_seconds'] == 'summary'
    assert any(n == 'executor_cache_miss_total' for n, _ in series)

    # post-train: exposition and /varz agree exactly
    code, body = _get(srv.url + '/metrics')
    assert code == 200
    series, _ = parse_prom(body)
    code, varz = _get(srv.url + '/varz')
    assert code == 200
    snap = json.loads(varz)
    assert snap['host'] == 0 and snap['pid'] == os.getpid()
    st = snap['histograms']['trainer.step_seconds']
    assert series[('trainer_step_seconds_count', ())] == st['count'] == 3
    assert series[('trainer_step_seconds_sum', ())] == \
        pytest.approx(st['sum'])
    for phase in ('feed', 'compute', 'fetch'):
        assert series[('trainer_phase_seconds_count',
                       (('phase', phase),))] == 3

    # /statusz: uptime, cache keys with hit/miss/compile time, pipeline
    # depth, goodput headline
    code, body = _get(srv.url + '/statusz')
    assert code == 200
    doc = json.loads(body)
    assert doc['uptime_seconds'] > 0
    assert doc['process_index'] == 0
    assert doc['steps_total'] == 3
    assert doc['inflight_depth'] == 0
    assert doc['goodput'] is not None
    cache = doc['executor_cache']
    assert cache, 'no executor cache keys in statusz'
    step_keys = [k for k, e in cache.items()
                 if e['misses'] == 1 and e['hits'] == 2]
    assert step_keys, cache      # the step program: 1 miss then 2 hits
    assert cache[step_keys[0]]['trace_seconds'] > 0
    assert doc['healthy'] is True and 'anomaly' in doc['health']

    # /tracez: completed spans with the chrome-trace fields
    code, body = _get(srv.url + '/tracez')
    assert code == 200
    tz = json.loads(body)
    names = {s['name'] for s in tz['spans']}
    assert 'trainer.step' in names and tz['dropped'] == 0
    assert all({'name', 'ts', 'dur'} <= set(s) for s in tz['spans'])

    # unknown route: typed 404, server stays up
    code, body = _get(srv.url + '/nope')
    assert code == 404 and '/metrics' in body
    observe.stop_serving()


def test_statusz_executor_table_columns(monkeypatch):
    """A key that has missed and hit: its /statusz row counts both,
    carries the trace / compile / first-dispatch seconds of the miss,
    and has no column of a disk cache."""
    # the compile seconds come from the cost probe, which benchmark/run.py
    # turns off for its process: an in-process rehearsal earlier on this
    # worker leaves it off
    monkeypatch.delenv('PADDLE_TPU_OBSERVE_COST', raising=False)
    import paddle_tpu as fluid
    from paddle_tpu import observe
    from paddle_tpu.observe.diagnostics import _executor_cache_table

    observe.reset()
    observe.enable()
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    out = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for _ in range(3):
        exe.run(feed={'x': np.ones((2, 4), 'float32')}, fetch_list=[out])
    table = _executor_cache_table(observe.snapshot())
    row, = [e for e in table.values() if e['hits'] == 2]
    assert set(row) == {'kind', 'hits', 'misses', 'trace_seconds',
                        'compile_seconds', 'first_dispatch_seconds'}
    assert row['kind'] == 'single' and row['misses'] == 1
    assert row['trace_seconds'] > 0
    assert row['compile_seconds'] > 0
    assert row['first_dispatch_seconds'] > 0


def test_healthz_degraded_while_anomaly_tripped():
    """NaN loss trips the streaming detector immediately; /healthz
    flips to 503 degraded until enough in-band samples clear it."""
    from paddle_tpu import observe

    srv = observe.serve(port=0)
    assert _get(srv.url + '/healthz')[0] == 200
    for _ in range(5):
        observe.anomaly('loss', 1.0)
    observe.anomaly('loss', float('nan'))     # no baseline needed
    code, body = _get(srv.url + '/healthz')
    assert code == 503
    doc = json.loads(body)
    assert doc['status'] == 'degraded'
    assert 'loss' in doc['checks']['anomaly']['detail']
    assert observe.anomaly_tripped() == ['loss']
    assert observe.get_counter('anomaly_trips_total', signal='loss') == 1
    assert observe.get_gauge('anomaly_tripped', signal='loss') == 1
    # trip + clear land in the flight ring (the leading indicator a
    # postmortem wants)
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'anomaly_trip' in kinds
    # hysteresis: clear_after in-band samples recover health
    det = observe._ANOMALY.detector('loss')
    for _ in range(det.clear_after):
        observe.anomaly('loss', 1.0)
    assert observe.anomaly_tripped() == []
    assert _get(srv.url + '/healthz')[0] == 200


def test_health_check_registry_and_readyz():
    from paddle_tpu import observe

    srv = observe.serve(port=0)
    observe.register_health_check('disk', lambda: True)
    observe.register_health_check('warm', lambda: (False, 'cold cache'),
                                  readiness_only=True)
    # liveness ignores readiness-only checks; readiness honors them
    code, body = _get(srv.url + '/healthz')
    assert code == 200 and 'warm' not in json.loads(body)['checks']
    code, body = _get(srv.url + '/readyz')
    assert code == 503
    assert json.loads(body)['checks']['warm']['detail'] == 'cold cache'
    # a raising check fails closed
    observe.register_health_check('db', lambda: 1 / 0)
    code, body = _get(srv.url + '/healthz')
    assert code == 503
    assert 'ZeroDivisionError' in \
        json.loads(body)['checks']['db']['detail']
    observe.unregister_health_check('db')
    observe.unregister_health_check('warm')
    assert _get(srv.url + '/readyz')[0] == 200


# ----------------------------------------------- serving engine readiness
class _StubPredictor(object):
    feed_names = ['x']

    def feed_specs(self):
        return {'x': ((4, 3), 'float32')}

    def predict(self, feed):
        x = np.asarray(feed['x'])
        return [x.sum(axis=1, keepdims=True)]


def test_serving_engine_ready_gates_readyz():
    from paddle_tpu import observe
    from paddle_tpu.serving import ServingEngine

    srv = observe.serve(port=0)
    eng = ServingEngine(_StubPredictor(), max_batch_size=4)
    assert not eng.ready()                 # not started, not warmed
    eng.start()
    assert not eng.ready()                 # started but would compile
    code, body = _get(srv.url + '/readyz')
    assert code == 503
    checks = json.loads(body)['checks']
    name = [n for n in checks if n.startswith('serving.engine')][0]
    assert checks[name]['detail'] == 'not warmed up'
    assert _get(srv.url + '/healthz')[0] == 200   # unready != unhealthy

    nsig = eng.warmup()
    assert nsig > 0 and eng.ready()
    assert _get(srv.url + '/readyz')[0] == 200
    # and it still actually serves
    out = eng.predict({'x': np.ones((2, 3), 'float32')})
    np.testing.assert_allclose(out[0], np.full((2, 1), 3.0))

    eng.shutdown()
    assert not eng.ready()
    # the check unregisters on shutdown: readyz no longer lists it
    code, body = _get(srv.url + '/readyz')
    assert code == 200 and name not in json.loads(body)['checks']


# ------------------------------------------------------- flight recorder
def test_flight_ring_bounds_and_postmortem_schema(tmp_path):
    from paddle_tpu.observe.flight import FlightRecorder

    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record('step_end', step=i, loss=float(i))
    evs = fr.events()
    assert len(evs) == 8
    assert [e['data']['step'] for e in evs] == list(range(12, 20))
    total, evicted = fr.counts()
    assert total == 20 and evicted == 12

    boom = ValueError('boom')
    path = str(tmp_path / 'pm.json')
    fr.record('nan_sample', value=float('nan'))   # must stay valid JSON
    fr.dump(path, 'unit_test', exc=boom,
            metrics={'counters': {'c': 1}, 'gauges': {}},
            anomalies={'loss': {'tripped': True, 'score': 9.0}})
    doc = json.loads(open(path).read())
    assert doc['kind'] == 'paddle_tpu_postmortem' and doc['schema'] == 1
    assert doc['reason'] == 'unit_test'
    assert doc['pid'] == os.getpid()
    assert doc['exception']['type'] == 'ValueError'
    assert doc['exception']['message'] == 'boom'
    assert doc['events_total'] == 21 and doc['evicted_events'] == 13
    assert doc['events'][-1]['data']['value'] == 'nan'
    assert doc['metrics']['counters']['c'] == 1
    assert doc['anomalies']['loss']['tripped'] is True


def test_guard_raise_dumps_postmortem_once(tmp_path):
    import paddle_tpu as fluid  # noqa: F401  (platform boot)
    from paddle_tpu import observe
    from paddle_tpu.fault.guards import BadStepError, BadStepGuard

    pm = str(tmp_path / 'pm.json')
    observe.arm_flight(path=pm)
    assert observe.flight_dump_path() == pm
    g = BadStepGuard('raise')
    g.handle(np.float32(1.0), 1)
    with pytest.raises(BadStepError) as ei:
        g.handle(np.float32(np.nan), 2)
    doc = json.loads(open(pm).read())
    assert doc['reason'] == 'bad_step'
    assert doc['exception']['type'] == 'BadStepError'
    trips = [e for e in doc['events'] if e['kind'] == 'guard_trip']
    assert trips and trips[-1]['data']['policy'] == 'raise'
    # the trainer's outer handler re-dumps the SAME exception: deduped,
    # the richer reason from the raise site wins
    assert observe.flight_dump('trainer_exception', exc=ei.value) == pm
    assert json.loads(open(pm).read())['reason'] == 'bad_step'


def test_trainer_exception_path_dumps(tmp_path):
    import paddle_tpu as fluid
    from paddle_tpu import observe

    pm = str(tmp_path / 'pm.json')
    observe.arm_flight(path=pm)
    trainer = _tiny_trainer(fluid)
    batches = _batches(2)

    def bad_reader():
        yield batches[0]
        raise RuntimeError('reader died mid-epoch')

    with pytest.raises(RuntimeError, match='reader died'):
        trainer.train(1, reader=bad_reader)
    doc = json.loads(open(pm).read())
    assert doc['reason'] == 'trainer_exception'
    assert doc['exception']['type'] == 'RuntimeError'
    kinds = [e['kind'] for e in doc['events']]
    assert 'step_end' in kinds           # the ring saw the last steps
    assert kinds[-1] == 'train_exception'


def test_flight_report_cli(tmp_path):
    from paddle_tpu import observe

    pm = str(tmp_path / 'pm.json')
    observe.enable()
    observe.arm_flight(path=pm)
    for i in range(5):
        observe.flight_event('step_end', step=i, loss=1.0 - 0.1 * i)
    observe.anomaly('loss', float('nan'))
    observe.flight_dump('unit_test')
    observe.disable()

    tool = os.path.join(REPO, 'tools', 'flight_report.py')
    r = subprocess.run([sys.executable, tool, pm],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert 'reason: unit_test' in r.stdout
    assert 'TRIPPED' in r.stdout          # anomaly state at death
    assert 'step_end' in r.stdout and 'Δloss' in r.stdout

    r = subprocess.run([sys.executable, tool, pm, '--json'],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc['reason'] == 'unit_test' and doc['last_step'] == 4
    assert doc['tripped'] == ['loss']

    # not a postmortem: clean failure
    bad = str(tmp_path / 'bad.json')
    open(bad, 'w').write('{"kind": "something_else"}')
    r = subprocess.run([sys.executable, tool, bad],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1 and 'not a paddle_tpu postmortem' in r.stderr


# ------------------------------------------------------ span drop counter
def test_spans_dropped_total_counter(monkeypatch):
    from paddle_tpu import observe
    spans_mod = importlib.import_module('paddle_tpu.observe.spans')

    monkeypatch.setattr(spans_mod, 'MAX_EVENTS', 3)
    observe.enable()
    gc.disable()          # a pause of the collector is a span of the ring
    try:
        for i in range(5):
            with observe.span('s%d' % i):
                pass
    finally:
        gc.enable()
    # a ring: the newest survive, so a long-lived server exports its
    # last minutes and not its start-up
    assert [e['name'] for e in observe.spans().events()] == \
        ['s2', 's3', 's4']
    assert observe.get_counter('spans_dropped_total') == 2
    # visible from the exposition alone (the satellite's point)
    from paddle_tpu.observe.registry import prometheus_exposition
    series, _ = parse_prom(prometheus_exposition(observe.snapshot()))
    assert series[('spans_dropped_total', ())] == 2


# ------------------------------------------------- metrics_report updates
def test_metrics_report_per_host_and_prom(tmp_path):
    from paddle_tpu import observe

    jsonl = str(tmp_path / 'm.jsonl')
    observe.enable(jsonl=jsonl)
    observe.inc('trainer.steps_total', 5)
    observe.record('trainer.step_seconds', 0.25)
    observe.set_gauge('run.goodput', 0.5)
    observe.flush(kind='summary')
    observe._SINK['path'] = None
    observe.disable()
    # a flushed record carries the host tag (satellite)
    rec = json.loads(open(jsonl).readline())
    assert rec['host'] == 0 and rec['pid'] == os.getpid()
    # fake a second host's summary alongside (merged multihost file)
    rec2 = dict(rec)
    rec2['host'], rec2['pid'] = 1, rec['pid'] + 1
    rec2['counters'] = {'trainer.steps_total': 7}
    with open(jsonl, 'a') as f:
        f.write(json.dumps(rec2) + '\n')

    tool = os.path.join(REPO, 'tools', 'metrics_report.py')
    r = subprocess.run([sys.executable, tool, jsonl, '--per-host'],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert 'host 0' in r.stdout and 'host 1' in r.stdout

    r = subprocess.run([sys.executable, tool, jsonl, '--prom'],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    series, types = parse_prom(r.stdout)
    assert types['trainer_steps_total'] == 'counter'
    assert series[('trainer_steps_total', ())] == 7    # newest summary
    r = subprocess.run([sys.executable, tool, jsonl, '--prom', '--json'],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 2                            # mutually exclusive


# -------------------------------------------------- disabled-path contract
def test_disabled_path_one_boolean_read():
    """With the server unstarted, telemetry off, and the flight
    recorder disarmed, the NEW call sites (flight_event / anomaly) cost
    one module-global read + return and record nothing — same contract
    as inc/record/set_gauge."""
    from paddle_tpu import observe

    observe.disable()
    assert not observe.enabled()
    n = 50000
    for _ in range(1000):     # warm up
        observe.flight_event('step_end', step=1)
        observe.anomaly('loss', 1.0)
    t0 = time.perf_counter()
    for _ in range(n):
        observe.flight_event('step_end', step=1, wall=0.1)
        observe.anomaly('loss', 1.0)
    dt = (time.perf_counter() - t0) / (2 * n)
    assert dt < 2e-6, 'disabled diagnostics call costs %.3gs' % dt
    assert observe.flight_recorder().events() == []
    assert observe.anomaly_state() == {}
    assert observe.snapshot()['counters'] == {}
    from paddle_tpu.observe import diagnostics
    assert diagnostics.active() is None
