"""Fleet serving observability (ISSUE 10): per-request distributed
tracing across threads (flow events, trace ids, histogram exemplars,
/tracez?trace_id=), the SLO layer (objectives, burn rate, goodput,
predicted p99), the multi-replica router (least-loaded + affinity
placement, failover on replica kill, SLO-aware admission), the
loadgen's time-varying QPS schedules, metrics_report --slo, and the
fleet chaos scenario's acceptance contract (tests/chaos.py)."""

import json
import os
import subprocess
import sys
import threading
import time

from concurrent.futures import Future

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.observe import reqtrace
from paddle_tpu.observe.slo import Objective, SloTracker
from paddle_tpu.serving import (EngineClosedError,
                                NoReplicaAvailableError, QueueFullError,
                                Router, ServingEngine, SLOShedError)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _observe_clean():
    from paddle_tpu.observe import diagnostics
    yield
    observe._SINK['path'] = None
    observe._SINK['trace_path'] = None
    observe.disable()
    observe.reset()
    with diagnostics._checks_lock:
        diagnostics._checks.clear()
    os.environ.pop('PADDLE_TPU_TRACE_SAMPLE', None)


def _save_mlp(dirname, in_dim=6):
    x = fluid.layers.data(name='x', shape=[in_dim], dtype='float32')
    h = fluid.layers.fc(input=x, size=8, act='relu')
    out = fluid.layers.fc(input=h, size=3, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(dirname, ['x'], [out], exe)
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    return dirname


def _engine(model_dir, name, **kw):
    from paddle_tpu.inference import create_predictor
    pred = create_predictor(model_dir, place=fluid.CPUPlace())
    kw.setdefault('max_batch_size', 4)
    kw.setdefault('batch_timeout_ms', 1.0)
    eng = ServingEngine(pred, name=name, **kw)
    eng.warmup()
    eng.start()
    return eng


# ------------------------------------------------- cross-thread spans
def test_flow_events_link_threads():
    """spans satellite: flow_begin on the producer thread +
    flow_step/flow_end on a consumer thread emit linked s/t/f events
    with one shared id across two tids."""
    observe.enable()
    rec = observe.spans()
    handle = rec.flow_begin('handoff', attrs={'k': 'v'})
    done = threading.Event()

    def consumer():
        rec.flow_step(handle)
        rec.flow_end(handle)
        done.set()

    t = threading.Thread(target=consumer)
    t.start()
    t.join()
    assert done.is_set()
    flows = [e for e in rec.events() if e.get('cat') == 'flow']
    assert [e['ph'] for e in flows] == ['s', 't', 'f']
    assert len({e['id'] for e in flows}) == 1
    assert len({e['tid'] for e in flows}) == 2   # producer + consumer
    assert flows[-1]['bp'] == 'e'                # arrowhead binding


def test_add_span_explicit_interval_and_instant():
    observe.enable()
    rec = observe.spans()
    t0 = time.perf_counter()
    rec.add_span('stage', t0, t0 + 0.25, attrs={'trace_id': 'abc'})
    rec.add_instant('mark', attrs={'trace_id': 'abc'})
    evs = rec.events()
    span = next(e for e in evs if e['name'] == 'stage')
    assert span['ph'] == 'X'
    assert abs(span['dur'] - 250000.0) < 1000.0     # microseconds
    mark = next(e for e in evs if e['name'] == 'mark')
    assert mark['ph'] == 'i' and mark['s'] == 't'
    # the thread-local begin/end stack API is unchanged alongside
    with observe.span('nested'):
        pass
    assert any(e['name'] == 'nested' for e in rec.events())


# ------------------------------------------------------ request context
def test_sample_rate_reads_env_per_call():
    assert reqtrace.sample_rate({}) == 0.0
    assert reqtrace.sample_rate({'PADDLE_TPU_TRACE_SAMPLE': '1'}) == 1.0
    assert reqtrace.sample_rate({'PADDLE_TPU_TRACE_SAMPLE': '0.5'}) == 0.5
    assert reqtrace.sample_rate({'PADDLE_TPU_TRACE_SAMPLE': '7'}) == 1.0
    assert reqtrace.sample_rate({'PADDLE_TPU_TRACE_SAMPLE': 'zzz'}) == 0.0
    # per-call: flipping the env var flips fresh contexts, no reimport
    observe.enable()
    os.environ['PADDLE_TPU_TRACE_SAMPLE'] = '1'
    assert reqtrace.new_context('r').sampled
    os.environ['PADDLE_TPU_TRACE_SAMPLE'] = '0'
    assert not reqtrace.new_context('r').sampled


def test_context_deadline_and_unsampled_noops():
    observe.enable()
    ctx = reqtrace.new_context('r', deadline_s=30.0, sample=0.0)
    assert not ctx.sampled and ctx.trace_id is None
    assert 29.0 < ctx.remaining() <= 30.0
    assert not ctx.expired()
    assert ctx.exemplar() is None
    ctx.stage('s', 0.0, 1.0)       # all no-ops, nothing recorded
    ctx.event('e')
    ctx.flow_begin('f')
    ctx.flow_end()
    assert [e for e in observe.spans().events()
            if e['name'] != 'host.gc'] == []     # but the collector's own
    expired = reqtrace.new_context('r', deadline_s=-0.001, sample=0.0)
    assert expired.expired()
    # sampling requires telemetry: disabled observe never samples
    observe.disable()
    assert not reqtrace.new_context('r', sample=1.0).sampled


# ----------------------------------------- engine tracing (acceptance)
def test_request_trace_spans_three_threads_with_exemplar(tmp_path):
    """Acceptance: a sampled request exports X-phase spans from >= 3
    distinct threads linked under one trace id in the Perfetto JSON,
    flow events stitch the handoffs, and the Prometheus exposition
    carries the trace id as an exemplar on the request-latency
    histogram."""
    from paddle_tpu.observe.registry import prometheus_exposition

    observe.enable()
    os.environ['PADDLE_TPU_TRACE_SAMPLE'] = '1'
    d = _save_mlp(str(tmp_path / 'm'))
    eng = _engine(d, 'traced')
    rng = np.random.RandomState(0)
    for _ in range(4):
        eng.predict({'x': rng.rand(2, 6).astype('float32')},
                    timeout=60)
    eng.shutdown(drain=True)

    doc = observe.spans().chrome_trace()        # the Perfetto export
    by_trace = {}
    for ev in doc['traceEvents']:
        tid = (ev.get('args') or {}).get('trace_id')
        if tid and ev.get('ph') == 'X':
            by_trace.setdefault(tid, []).append(ev)
    assert by_trace, 'no sampled spans recorded'
    best = max(by_trace.values(), key=lambda evs: len({e['tid']
                                                      for e in evs}))
    names = {e['name'] for e in best}
    assert {'submit', 'queue_wait', 'batch_assemble', 'dispatch',
            'compute', 'unpad'} <= names
    assert len({e['tid'] for e in best}) >= 3   # client+batcher+dispatch
    # flow events share the trace's id and stitch >= 2 threads
    trace_id = (best[0].get('args') or {})['trace_id']
    flows = [e for e in doc['traceEvents'] if e.get('cat') == 'flow'
             and e.get('id') == int(trace_id, 16)]
    assert {'s', 'f'} <= {e['ph'] for e in flows}
    assert len({e['tid'] for e in flows}) >= 2

    expo = prometheus_exposition(observe.snapshot())
    ex_lines = [ln for ln in expo.splitlines()
                if ln.startswith('serving_request_seconds')
                and '# {trace_id="' in ln]
    assert ex_lines, 'no exemplar on the request-latency histogram'
    assert 'quantile="0.99"' in ex_lines[0]


def test_tracez_filters_by_trace_id(tmp_path):
    from paddle_tpu.observe import diagnostics

    observe.enable()
    d = _save_mlp(str(tmp_path / 'm'))
    eng = _engine(d, 'tz')
    ctx = reqtrace.new_context('tz', sample=1.0)
    eng.submit({'x': np.ones((1, 6), 'float32')}, ctx=ctx).result(60)
    eng.predict({'x': np.ones((1, 6), 'float32')})   # unsampled noise
    eng.shutdown(drain=True)

    doc = diagnostics._tracez_doc('trace_id=%s' % ctx.trace_id)
    assert doc['trace_id'] == ctx.trace_id
    assert doc['spans'], 'filter returned nothing'
    assert all((e.get('args') or {}).get('trace_id') == ctx.trace_id
               for e in doc['spans'])
    assert len(doc['threads']) >= 3
    # no filter: plain recent-spans payload
    plain = diagnostics._tracez_doc('n=5')
    assert 'dropped' in plain and len(plain['spans']) <= 5


# ---------------------------------------------------------------- SLO
def test_slo_objective_validation():
    with pytest.raises(ValueError):
        Objective('r', latency_budget_s=0.0)
    with pytest.raises(ValueError):
        Objective('r', 0.1, availability_target=1.0)
    with pytest.raises(ValueError):
        SloTracker([])
    with pytest.raises(ValueError):
        SloTracker([Objective('r', 0.1), Objective('r', 0.2)])
    t = SloTracker([Objective('r', 0.1)])
    with pytest.raises(KeyError):
        t.record('unknown', 0.05)


def test_slo_burn_rate_goodput_p99():
    """Synthetic clock: 100 requests, 5 bad (1 error + 4 over-budget)
    against a 99% availability target -> burn rate 5x; goodput counts
    only in-SLO completions; predicted p99 tracks the window; old
    events evict."""
    obj = Objective('r', latency_budget_s=0.1,
                    availability_target=0.99, window_s=10.0)
    t = SloTracker([obj], registry=None)
    now = 1000.0
    for i in range(95):
        t.record('r', 0.01, ok=True, now=now + i * 0.01)
    t.record('r', 0.05, ok=False, now=now + 1.0)          # 1 error
    for i in range(4):
        t.record('r', 0.5, ok=True, now=now + 1.1 + i * 0.01)  # late
    q = now + 2.0
    assert t.window_counts('r', now=q) == (100, 5)
    assert t.burn_rate('r', now=q) == pytest.approx(5.0)
    # goodput: 95 good over the window's observed span
    span = (now + 1.13) - now
    assert t.goodput('r', now=q) == pytest.approx(95.0 / min(10.0, q - now))
    del span
    p99 = t.predicted_p99('r', now=q + 1.0)
    assert p99 == pytest.approx(0.5)          # the late tail dominates
    # eviction: everything ages out of the 10s window
    assert t.window_counts('r', now=now + 100.0) == (0, 0)
    assert t.burn_rate('r', now=now + 100.0) == 0.0


def test_slo_p99_visible_right_after_idle_read():
    """Regression: reading an idle route (publish/statusz) primes the
    latency cache EMPTY; records landing within the 0.25s re-sort
    throttle must still produce a predicted p99 — SLO admission is
    blind exactly at flash-crowd onset otherwise."""
    t = SloTracker([Objective('r', 0.1, window_s=10.0)], registry=None)
    now = 1000.0
    assert t.predicted_p99('r', now=now) is None   # idle: cache = ()
    for i in range(20):
        t.record('r', 0.02, ok=True, now=now + 0.001 * i)
    assert t.predicted_p99('r', now=now + 0.05) == pytest.approx(0.02)


def test_slo_publishes_metrics_and_slowest():
    observe.enable()
    t = SloTracker([Objective('serve', 0.1, 0.99, window_s=60.0)])
    for i in range(10):
        t.record('serve', 0.01 * (i + 1), ok=True,
                 trace_id='t%02d' % i)
    snap = observe.snapshot()
    assert 'slo.burn_rate{route=serve}' in snap['gauges']
    assert 'slo.latency_budget_seconds{route=serve}' in snap['gauges']
    assert snap['counters']['slo.requests_total{route=serve}'] == 10
    slowest = t.slowest('serve')
    assert len(slowest) == 5
    assert slowest[0][0] == pytest.approx(0.1)   # worst first
    assert slowest[0][1] == 't09'
    assert [s for s, _ in slowest] == sorted(
        [s for s, _ in slowest], reverse=True)
    # the statusz panel renders from the same snapshot
    from paddle_tpu.observe.diagnostics import _slo_status
    panel = _slo_status(observe.snapshot())
    assert panel['serve']['latency_budget_s'] == pytest.approx(0.1)
    assert len(panel['serve']['slowest']) == 5


# ------------------------------------------------------------- loadgen
def test_qps_schedules():
    from paddle_tpu.serving.loadgen import (diurnal, flash_crowd,
                                            heavy_tailed_rows, qps_at)
    assert qps_at(50.0, 3.0) == 50.0
    bp = [(0.0, 10.0), (2.0, 100.0), (4.0, 20.0)]
    assert qps_at(bp, 0.0) == 10.0
    assert qps_at(bp, 1.99) == 10.0
    assert qps_at(bp, 2.0) == 100.0
    assert qps_at(bp, 10.0) == 20.0
    assert qps_at([(1.0, 5.0)], 0.5) == 0.0      # before first breakpoint
    d = diurnal(10.0, 50.0, period_s=10.0)
    assert qps_at(d, 0.0) == pytest.approx(10.0)
    assert qps_at(d, 5.0) == pytest.approx(50.0)
    f = flash_crowd(d, 400.0, t_start=2.0, duration_s=1.0)
    assert qps_at(f, 2.5) == 400.0
    assert qps_at(f, 3.5) == pytest.approx(qps_at(d, 3.5))
    rng = np.random.RandomState(0)
    rows = [heavy_tailed_rows(rng, 1, 8) for _ in range(500)]
    assert min(rows) >= 1 and max(rows) <= 8
    assert np.median(rows) <= 3                  # most requests small


def test_open_loop_schedule_and_stats_timestamps():
    """loadgen satellite: open_loop follows a (t, qps) schedule — the
    quiet and burst phases differ in submission rate — and the Stats
    ledger timestamps rejects/errors so shed windows are plottable."""
    from paddle_tpu.serving.loadgen import Stats, open_loop

    stats = Stats()
    times = []
    state = {'n': 0}

    def submit_request(rng):
        times.append(time.perf_counter())
        state['n'] += 1
        if state['n'] % 5 == 0:
            return None                    # every 5th: a reject
        f = Future()
        if state['n'] % 7 == 0:
            f.set_exception(RuntimeError('boom'))   # typed error
        else:
            f.set_result(None)
        return f, 1

    t0 = time.perf_counter()
    open_loop(submit_request, stats,
              deadline=t0 + 1.0, qps=[(0.0, 30.0), (0.5, 300.0)])
    lo = sum(1 for t in times if t - t0 < 0.5)
    hi = sum(1 for t in times if t - t0 >= 0.5)
    assert hi > 2 * lo, (lo, hi)          # the burst phase is denser
    assert stats.rejected >= 1 and stats.errors >= 1
    assert len(stats.reject_times) == stats.rejected
    assert len(stats.error_times) == stats.errors
    assert all(0.0 <= t <= 1.5 for t in stats.reject_times)
    win = stats.counts_between(0.0, 2.0)
    assert win['ok'] == stats.ok
    assert win['rejected'] == stats.rejected


# -------------------------------------------------------------- router
class FakeReplica(object):
    """Duck-typed replica: resolves futures synchronously."""

    def __init__(self, name, depth=0, ready=True, exc=None):
        self.name = name
        self._depth = depth
        self._ready = ready
        self.exc = exc
        self.submitted = 0

    def ready(self):
        return self._ready

    def queue_depth(self):
        return self._depth

    def submit(self, feed, ctx=None):
        self.submitted += 1
        if isinstance(self.exc, QueueFullError):
            raise self.exc
        f = Future()
        if self.exc is not None:
            f.set_exception(self.exc)
        else:
            f.set_result([self.name])
        return f


def test_router_least_loaded_and_affinity():
    observe.enable()
    a = FakeReplica('a', depth=5)
    b = FakeReplica('b', depth=0)
    c = FakeReplica('c', depth=9)
    r = Router([a, b, c], session_affinity=True)
    # least-loaded without a session: everything lands on b
    for _ in range(3):
        assert r.predict({'x': 1}) == ['b']
    assert (a.submitted, b.submitted, c.submitted) == (0, 3, 0)
    # session affinity beats depth and is sticky
    first = r.predict({'x': 1}, session='user-1')[0]
    for _ in range(3):
        assert r.predict({'x': 1}, session='user-1') == [first]
    # a dead pinned replica falls back to least-loaded, not an error
    pinned = {'a': a, 'b': b, 'c': c}[first]
    pinned._ready = False
    alive = r.predict({'x': 1}, session='user-1')[0]
    assert alive != first
    # no replica ready -> typed availability error
    for rep in (a, b, c):
        rep._ready = False
    with pytest.raises(NoReplicaAvailableError):
        r.submit({'x': 1})
    # queue-full everywhere -> the QueueFullError propagates
    for rep in (a, b, c):
        rep._ready = True
        rep.exc = QueueFullError('full')
    with pytest.raises(QueueFullError):
        r.submit({'x': 1})
    r.close()


def test_router_failover_retries_on_dead_replica():
    observe.enable()
    dead = FakeReplica('dead', depth=0,
                       exc=EngineClosedError('replica gone'))
    live = FakeReplica('live', depth=3)
    r = Router([dead, live], session_affinity=False, retries=2)
    assert r.predict({'x': 1}) == ['live']   # retried transparently
    assert dead.submitted == 1 and live.submitted == 1
    assert observe.get_counter('router.failover_total', replica='dead',
                               route='serve') == 1
    # retries exhausted -> the typed error surfaces, nothing hangs
    lone = FakeReplica('lone', exc=EngineClosedError('gone'))
    r2 = Router([lone], session_affinity=False, retries=1)
    with pytest.raises(EngineClosedError):
        r2.predict({'x': 1})
    r.close()
    r2.close()


def test_router_slo_admission_shed_and_degrade():
    observe.enable()
    tracker = SloTracker([Objective('serve', latency_budget_s=0.05,
                                    window_s=60.0)])
    rep = FakeReplica('r0')
    router = Router([rep], slo=tracker, retries=0)
    assert router.admission == 'slo'
    # healthy window: predicted p99 under budget, admitted
    for _ in range(20):
        tracker.record('serve', 0.01)
    assert router.predict({'x': 1}) == ['r0']
    # poisoned window: predicted p99 blows the budget -> shed, typed
    # as a QueueFullError subclass so reject handling applies
    for i in range(50):
        tracker.record('serve', 0.5, now=time.perf_counter() + 1.0)
    # force past the 0.25s sorted-latency cache so admission sees the
    # poisoned window immediately
    assert tracker.predicted_p99(
        'serve', now=time.perf_counter() + 10.0) == pytest.approx(0.5)
    with pytest.raises(SLOShedError):
        router.submit({'x': 1})
    with pytest.raises(QueueFullError):
        router.submit({'x': 1})
    assert observe.get_counter('router.shed_total',
                               reason='predicted_p99',
                               route='serve') >= 2
    # a long per-request deadline overrides the route budget: admitted
    assert router.predict({'x': 1}, deadline_s=30.0) == ['r0']
    # degrade mode admits past the breach and counts it
    router2 = Router([rep], slo=tracker, on_breach='degrade', retries=0)
    assert router2.predict({'x': 1}) == ['r0']
    assert observe.get_counter('router.degraded_total',
                               route='serve') == 1
    router.close()
    router2.close()


def test_router_failover_kill_replica_midload(tmp_path):
    """Failover satellite: kill one replica mid-load via
    fault.inject.kill_replica — every accepted request completes or
    fails typed (none lost or hung), the dead replica's readiness
    check flips, and traffic rebalances onto the survivors."""
    from paddle_tpu.fault import inject
    from paddle_tpu.observe.diagnostics import run_health_checks

    observe.enable()
    d = _save_mlp(str(tmp_path / 'm'))
    engines = [_engine(d, 'r%d' % i, max_queue_depth=64)
               for i in range(3)]
    tracker = SloTracker([Objective('serve', 1.0, window_s=30.0)])
    router = Router(engines, slo=tracker, retries=3)
    victim = engines[0]
    ok, checks = run_health_checks(include_readiness=True)
    assert checks['serving.r0']['ok']

    rng = np.random.RandomState(0)
    futures = []
    accepted = rejected = 0
    kill_after = 60
    for i in range(180):
        try:
            fut = router.submit(
                {'x': rng.rand(2, 6).astype('float32')}, session=i % 8)
            futures.append(fut)
            accepted += 1
        except QueueFullError:
            rejected += 1
        if i == kill_after:
            before = {n: observe.get_counter('router.dispatch_total',
                                             replica=n, route='serve')
                      for n, _ in router.replicas()}
            inject.kill_replica(victim, drain=False)
            assert victim.ready() is False
        time.sleep(0.002)
    for eng in engines[1:]:
        eng.shutdown(drain=True)

    # zero lost/hung: every accepted future resolves, errors are typed
    resolved, typed_errors = 0, 0
    for fut in futures:
        try:
            fut.result(timeout=30)
            resolved += 1
        except (QueueFullError, EngineClosedError,
                NoReplicaAvailableError):
            typed_errors += 1
    assert resolved + typed_errors == accepted
    assert resolved > 0

    # the dead replica's /readyz check flips (kill_replica keeps the
    # corpse's check registered, unlike a graceful shutdown)
    ok, checks = run_health_checks(include_readiness=True)
    assert checks['serving.r0']['ok'] is False
    # traffic rebalanced: survivors took dispatches after the kill
    after = {n: observe.get_counter('router.dispatch_total',
                                    replica=n, route='serve')
             for n, _ in router.replicas()}
    assert after['r1'] + after['r2'] > before['r1'] + before['r2']
    assert after['r0'] == before['r0']        # corpse takes nothing
    # the kill is a flight event (chaos forensics)
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'replica_kill' in kinds
    router.close()


# ------------------------------------------------- metrics_report --slo
def test_metrics_report_slo_json(tmp_path):
    """CLI satellite: --slo renders objectives/burn/goodput/slowest
    from a JSONL, stdlib-only (no jax import), --json schema stable."""
    observe.enable(jsonl=str(tmp_path / 'm.jsonl'))
    t = SloTracker([Objective('fleet', 0.2, 0.95, window_s=30.0)])
    for i in range(20):
        t.record('fleet', 0.01 * (i + 1), ok=(i % 7 != 0),
                 trace_id='%012x' % i)
    t.publish()
    observe.flush(kind='summary')

    tool = os.path.join(REPO, 'tools', 'metrics_report.py')
    r = subprocess.run(
        [sys.executable, tool, str(tmp_path / 'm.jsonl'), '--slo',
         '--json'],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    route = doc['routes']['fleet']
    assert route['latency_budget_s'] == pytest.approx(0.2)
    assert route['availability_target'] == pytest.approx(0.95)
    assert route['burn_rate'] is not None and route['burn_rate'] > 0
    assert route['goodput_rps'] is not None
    assert route['predicted_p99_s'] is not None
    assert len(route['slowest']) == 5
    lats = [s['seconds'] for s in route['slowest']]
    assert lats == sorted(lats, reverse=True)
    assert all(s['trace_id'] for s in route['slowest'])
    # human rendering mentions the objective and trace ids
    r2 = subprocess.run(
        [sys.executable, tool, str(tmp_path / 'm.jsonl'), '--slo'],
        capture_output=True, text=True, timeout=60)
    assert r2.returncode == 0, r2.stderr
    assert 'objective' in r2.stdout and 'trace_id=' in r2.stdout
    # no jax import on the --slo path
    probe = subprocess.run(
        [sys.executable, '-c',
         'import importlib.util, sys\n'
         'spec = importlib.util.spec_from_file_location("mr", %r)\n'
         'm = importlib.util.module_from_spec(spec)\n'
         'spec.loader.exec_module(m)\n'
         'assert m.main([%r, "--slo"]) == 0\n'
         'assert "jax" not in sys.modules\n'
         % (tool, str(tmp_path / 'm.jsonl'))],
        capture_output=True, text=True, timeout=60)
    assert probe.returncode == 0, probe.stderr


# ------------------------------------------------ fleet chaos scenario
def test_bench_fleet_chaos_scenario(tmp_path):
    """Acceptance: ``chaos.fleet_chaos`` runs flash-crowd +
    replica-kill against a 3-replica router and the ledger proves:
    zero accepted-request losses, the victim not ready from the kill
    on, the survivors dispatched to and serving after it, and slo.*
    metrics in the metrics JSONL. (That a dead replica's queued
    requests fail over is ``test_router_failover_retries_on_dead_replica``'s
    to hold: whether the victim's queue holds any at the kill depends
    on how fast this CPU ran.)"""
    from chaos import fleet_chaos
    jsonl = str(tmp_path / 'fleet.jsonl')
    observe.enable(jsonl=jsonl)
    r = fleet_chaos()
    observe.flush(kind='summary')

    assert r['replicas'] == 3
    assert r['accepted'] > 0
    assert r['lost'] == 0, r                      # zero accepted losses
    assert r['kill']['kills'] == 1
    assert r['kill']['ready_before'] is True
    assert r['kill']['ready_after'] is False
    assert r['kill']['survivors_dispatched_after'] > 0, r   # rebalanced
    assert r['kill']['ok_after'] > 0, r           # and the fleet served on
    assert r['max_trace_threads'] >= 3            # cross-thread traces
    assert r['sampled_traces'] > 0

    # slo.* metrics landed in the metrics JSONL
    with open(jsonl) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    summary = [x for x in recs if x.get('kind') == 'summary'][-1]
    gauges = summary['gauges']
    assert 'slo.burn_rate{route=fleet}' in gauges
    assert 'slo.goodput_rps{route=fleet}' in gauges
    assert 'slo.latency_budget_seconds{route=fleet}' in gauges
    assert any(k.startswith('router.dispatch_total')
               for k in summary['counters'])
