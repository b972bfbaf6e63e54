"""Self-healing autoscaling fleet (ISSUE 11): dynamic router
membership, hedged requests under a retry budget, the expired-deadline
admission fast path, the FleetController state machine (scale out/in,
heal with exponential backoff, crash-loop quarantine) driven on a
synthetic clock, fault.inject crash_loop / kill_replica(drain=True),
the /statusz fleet panel, metrics_report --fleet, and the tests/chaos.py
autoscale chaos acceptance contract."""

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
from paddle_tpu.fault import inject
from paddle_tpu.observe.slo import Objective, SloTracker
from paddle_tpu.serving import (EngineClosedError, FleetController,
                                QueueFullError, Router, ServingEngine,
                                SLOShedError)
from paddle_tpu.serving.controller import (DEAD, DRAINING, QUARANTINED,
                                           UP)

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


class FakeReplica(object):
    """Duck-typed replica. ``manual=True`` returns pending futures the
    test resolves by hand — deterministic hedge-race choreography."""

    def __init__(self, name, depth=0, ready=True, exc=None,
                 manual=False):
        self.name = name
        self._depth = depth
        self._ready = ready
        self.exc = exc
        self.manual = manual
        self.submitted = 0
        self.pending = []
        self.log = []

    def ready(self):
        return self._ready

    def queue_depth(self):
        return self._depth

    def submit(self, feed, ctx=None):
        self.submitted += 1
        if isinstance(self.exc, QueueFullError):
            raise self.exc
        f = Future()
        if self.manual:
            self.pending.append(f)
        elif self.exc is not None:
            f.set_exception(self.exc)
        else:
            f.set_result([self.name])
        return f

    def drain(self, timeout=None):
        self.log.append('drain')
        return True

    def shutdown(self, drain=True):
        self.log.append(('shutdown', drain))
        self._ready = False


# ---------------------------------------------------------- membership
def test_router_dynamic_membership():
    observe.enable()
    a, b = FakeReplica('a'), FakeReplica('b', depth=5)
    r = Router([a, b], session_affinity=False)
    c = FakeReplica('c')
    r.add_replica(c)
    assert [n for n, _ in r.replicas()] == ['a', 'b', 'c']
    with pytest.raises(ValueError):
        r.add_replica(FakeReplica('c'))          # names stay unique
    # removed replica takes no new work from this instant
    got = r.remove_replica('a')
    assert got is a
    for _ in range(4):
        assert r.predict({'x': 1})[0] in ('b', 'c')
    assert a.submitted == 0
    with pytest.raises(KeyError):
        r.remove_replica('nope')
    assert observe.get_counter('router.membership_changes_total',
                               change='add', route='serve') == 1
    assert observe.get_counter('router.membership_changes_total',
                               change='remove', route='serve') == 1
    r.close()


def test_router_excludes_draining_replica(tmp_path):
    """Drain-routing regression (ISSUE 11 satellite): a replica whose
    drain/shutdown has BEGUN — ready() False, queue empty, not full —
    must never appear in _candidates; scale-in retires it with zero
    new work routed on."""
    observe.enable()
    d = _save_mlp(str(tmp_path / 'm'))
    eng = _engine(d, 'retiree')
    healthy = FakeReplica('healthy')
    r = Router([eng, healthy], session_affinity=False)
    assert {n for n, _ in r._candidates()} == {'retiree', 'healthy'}
    # the moment drain/shutdown begins ready() flips; the replica is
    # not FULL (queue empty) — exclusion must key on readiness
    eng._draining = True
    assert eng.queue_depth() == 0
    assert eng.ready() is False
    assert [n for n, _ in r._candidates()] == ['healthy']
    assert r.predict({'x': 1}) == ['healthy']
    eng._draining = False
    eng.shutdown(drain=True)
    r.close()


# ---------------------------------------------------- deadline fast path
def test_router_expired_deadline_fast_path():
    """ISSUE 11 satellite: an already-exhausted deadline sheds
    synchronously in _admission_check — no dispatch, no retry-budget
    deposit or hedge token spent."""
    observe.enable()
    rep = FakeReplica('r0')
    r = Router([rep], hedge=True, hedge_delay_s=0.001,
               retry_budget=0.5, retry_budget_burst=4.0)
    tokens0 = r._budget.tokens
    with pytest.raises(SLOShedError):
        r.submit({'x': 1}, deadline_s=-0.5)
    with pytest.raises(QueueFullError):       # subclass contract holds
        r.submit({'x': 1}, deadline_s=-0.5)
    assert rep.submitted == 0                 # no dispatch consumed
    assert r._budget.tokens == tokens0        # no token moved
    assert observe.get_counter('router.shed_total',
                               reason='deadline_expired',
                               route='serve') == 2
    # a live deadline still admits
    assert r.predict({'x': 1}, deadline_s=30.0) == ['r0']
    r.close()


# ------------------------------------------------------------- hedging
def test_router_hedge_first_completion_wins():
    observe.enable()
    slow = FakeReplica('slow', manual=True)
    fast = FakeReplica('fast', depth=9)
    r = Router([slow, fast], hedge=True, hedge_delay_s=0.01,
               session_affinity=False, retries=1)
    fut = r.submit({'x': 1})
    assert slow.submitted == 1 and fast.submitted == 0
    deadline = time.perf_counter() + 5.0
    while fast.submitted == 0 and time.perf_counter() < deadline:
        time.sleep(0.005)                     # hedge timer fires
    assert fast.submitted == 1
    fast.pending = []                         # fast resolved instantly
    assert fut.result(5.0) == ['fast']        # first completion wins
    assert observe.get_counter('router.hedge_total',
                               route='serve') == 1
    assert observe.get_counter('router.hedge_wins_total',
                               winner='hedge', route='serve') == 1
    # the loser completing with the SAME payload is not a mismatch
    slow.pending[0].set_result(['fast'])
    assert observe.get_counter('router.hedge_mismatch_total',
                               route='serve') in (None, 0)
    r.close()


def test_router_hedge_mismatch_detected():
    observe.enable()
    a = FakeReplica('a', manual=True)
    b = FakeReplica('b', depth=9, manual=True)
    r = Router([a, b], hedge=True, hedge_delay_s=0.01,
               session_affinity=False)
    fut = r.submit({'x': 1})
    deadline = time.perf_counter() + 5.0
    while b.submitted == 0 and time.perf_counter() < deadline:
        time.sleep(0.005)
    a.pending[0].set_result([np.arange(3)])
    assert np.array_equal(fut.result(5.0)[0], np.arange(3))
    # the hedge completes with DIFFERENT bits: a determinism alarm
    b.pending[0].set_result([np.arange(3) + 1])
    assert observe.get_counter('router.hedge_mismatch_total',
                               route='serve') == 1
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'router_hedge_mismatch' in kinds
    r.close()


def test_router_retry_budget_bounds_hedges():
    """An empty token bucket suppresses hedging — retries can never
    amplify an overload."""
    observe.enable()
    slow1 = FakeReplica('s1', manual=True)
    slow2 = FakeReplica('s2', depth=9, manual=True)
    r = Router([slow1, slow2], hedge=True, hedge_delay_s=0.005,
               session_affinity=False, retry_budget=0.0,
               retry_budget_burst=1.0)
    futs = [r.submit({'x': i}) for i in range(3)]
    time.sleep(0.2)                # all three hedge timers fired
    # burst bought exactly ONE hedge; deposits are 0/request
    assert slow2.submitted == 1
    assert observe.get_counter('router.hedge_suppressed_total',
                               reason='budget', route='serve') == 2
    for f in slow1.pending + slow2.pending:
        f.set_result(['done'])
    for f in futs:
        assert f.result(5.0) == ['done']
    r.close()


def test_router_failover_chain_deaths_resolve_future():
    """Regression (review): _attempt_died must retire the dead
    attempt's outstanding slot even when its redispatch succeeds.
    With two replicas that BOTH die mid-request, the leaked slot used
    to make the final failure stash its exception instead of settling
    — predict() without a timeout blocked forever. The future must
    resolve with EngineClosedError."""
    observe.enable()
    a = FakeReplica('a', manual=True)
    b = FakeReplica('b', depth=5, manual=True)
    r = Router([a, b], session_affinity=False, retries=2)
    fut = r.submit({'x': 1})
    assert a.submitted == 1
    a.pending[0].set_exception(EngineClosedError('a died'))
    assert b.submitted == 1            # failover redispatch landed
    b.pending[0].set_exception(EngineClosedError('b died'))
    assert fut.done()                  # the pre-fix repro: stays False
    assert isinstance(fut.exception(timeout=5.0), EngineClosedError)
    assert observe.get_counter('router.failover_total', replica='a',
                               route='serve') == 1
    assert observe.get_counter('router.failover_total', replica='b',
                               route='serve') == 1
    r.close()


def test_router_failover_no_retry_paths_resolve_future():
    """The no-redispatch death paths settle too: retries exhausted,
    and an empty retry budget."""
    observe.enable()
    a = FakeReplica('a', manual=True)
    r = Router([a], session_affinity=False, retries=0)
    fut = r.submit({'x': 1})
    a.pending[0].set_exception(EngineClosedError('gone'))
    assert isinstance(fut.exception(timeout=5.0), EngineClosedError)
    r.close()
    c = FakeReplica('c', manual=True)
    d = FakeReplica('d', depth=5)
    r2 = Router([c, d], session_affinity=False, retries=2,
                retry_budget=0.0, retry_budget_burst=0.0)
    fut2 = r2.submit({'x': 1})
    c.pending[0].set_exception(EngineClosedError('gone'))
    assert isinstance(fut2.exception(timeout=5.0), EngineClosedError)
    assert d.submitted == 0            # no budget, no redispatch
    assert observe.get_counter('router.retry_budget_exhausted_total',
                               kind='failover', route='serve') == 1
    r2.close()


def test_router_hedge_nan_payloads_not_a_mismatch():
    """Bit-identical NaN-bearing outputs (a model that legitimately
    emits NaNs, the poison_nans chaos action) must not fire the
    hedge determinism alarm."""
    from paddle_tpu.serving.router import _results_equal
    nan_arr = np.array([1.0, np.nan, 3.0])
    assert _results_equal([nan_arr.copy()], [nan_arr.copy()])
    assert not _results_equal([nan_arr], [np.array([1.0, 2.0, 3.0])])
    # non-float dtypes take the equal_nan-free path (equal_nan raises
    # on them) and still compare correctly
    assert _results_equal([np.array(['x'])], [np.array(['x'])])
    assert not _results_equal([np.array([1, 2])], [np.array([1, 3])])
    observe.enable()
    a = FakeReplica('a', manual=True)
    b = FakeReplica('b', depth=9, manual=True)
    r = Router([a, b], hedge=True, hedge_delay_s=0.01,
               session_affinity=False)
    fut = r.submit({'x': 1})
    deadline = time.perf_counter() + 5.0
    while b.submitted == 0 and time.perf_counter() < deadline:
        time.sleep(0.005)
    a.pending[0].set_result([nan_arr.copy()])
    b.pending[0].set_result([nan_arr.copy()])
    fut.result(5.0)
    assert observe.get_counter('router.hedge_mismatch_total',
                               route='serve') in (None, 0)
    r.close()


def test_router_session_pins_stable_across_membership():
    """Rendezvous session pinning: a scale event only reassigns the
    sessions that hash onto the changed replica — everyone else keeps
    their pin (the old modulus scheme churned the whole keyspace)."""
    observe.enable()
    reps = {n: FakeReplica(n) for n in ('a', 'b', 'c')}
    r = Router(list(reps.values()))
    sessions = ['s%d' % i for i in range(40)]
    pin0 = {s: r._candidates(session=s)[0][0] for s in sessions}
    assert len(set(pin0.values())) > 1       # spread across the fleet
    victim = pin0[sessions[0]]
    removed = r.remove_replica(victim)
    for s in sessions:
        if pin0[s] != victim:                # untouched by the change
            assert r._candidates(session=s)[0][0] == pin0[s]
    r.add_replica(removed)                   # and adding it back
    assert {s: r._candidates(session=s)[0][0]
            for s in sessions} == pin0       # restores every pin
    r.close()


def test_slo_predicted_quantile():
    t = SloTracker([Objective('q', 1.0, window_s=60.0)])
    now = time.perf_counter()
    for i in range(100):
        t.record('q', (i + 1) / 100.0, now=now)
    assert t.predicted_quantile('q', 0.95, now=now) == \
        pytest.approx(0.96)
    assert t.predicted_p99('q', now=now) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        t.predicted_quantile('q', 1.5)


# ------------------------------------------------------ fleet controller
def _fleet(n=2, slo=None, **ctl_kw):
    reps = [FakeReplica('r%d' % i) for i in range(n)]
    router = Router(reps, slo=slo, admission='none',
                    session_affinity=False)
    spawned = []

    def factory(name):
        rep = FakeReplica(name)
        spawned.append(rep)
        return rep

    ctl = FleetController(router, factory, slo=slo, **ctl_kw)
    return router, ctl, reps, spawned


def test_controller_scale_out_on_pressure_and_cooldown():
    observe.enable()
    tracker = SloTracker([Objective('serve', 0.05, window_s=5.0)])
    router, ctl, reps, spawned = _fleet(
        2, slo=tracker, min_replicas=2, max_replicas=4,
        burn_high=1.0, scale_out_cooldown_s=1.0, trough_s=1e9)
    now = time.perf_counter()
    for _ in range(50):
        tracker.record('serve', 0.5, ok=False, now=now)
    ctl.step(now=now + 0.3)
    assert len(spawned) == 1                   # pressure -> one spawn
    assert len(router.replicas()) == 3         # registered after ready
    ctl.step(now=now + 0.5)                    # inside cooldown
    assert len(spawned) == 1
    ctl.step(now=now + 1.5)                    # cooldown over
    assert len(spawned) == 2
    ctl.step(now=now + 3.0)
    assert len(spawned) == 2                   # max_replicas=4 caps it
    assert ctl.census()[UP] == 4
    assert observe.get_counter('controller.scale_out_total',
                               route='serve', reason='burn_rate') == 2
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'controller_scale_out' in kinds
    ctl.close()
    router.close()


def test_controller_scale_in_drains_before_shutdown():
    observe.enable()
    router, ctl, reps, spawned = _fleet(
        3, min_replicas=1, trough_s=1.0, scale_in_cooldown_s=0.1,
        queue_low=2.0)
    reps[0]._depth = 3                         # least-loaded is r1/r2
    now = time.perf_counter()
    ctl.step(now=now)                          # trough starts
    assert ctl.census()[UP] == 3
    ctl.step(now=now + 1.2)                    # sustained -> scale in
    assert ctl.census()[UP] == 2
    victim = next(rep for rep in reps if rep.log)
    assert victim is not reps[0]               # least-loaded picked
    # zero-loss ordering: deregistered, DRAINED, then shut down
    assert victim.log == ['drain', ('shutdown', True)]
    assert victim.name not in [n for n, _ in router.replicas()]
    assert observe.get_counter('controller.scale_in_total',
                               route='serve') == 1
    # min_replicas floor: another sustained trough cannot go below 1
    ctl.step(now=now + 2.5)
    ctl.step(now=now + 4.0)
    assert ctl.census()[UP] >= 1
    ctl.close()
    router.close()


def test_controller_phase_pool_custom_pressure():
    """Per-phase scaling (ISSUE 14): a FleetController driving ONE
    phase of a PhaseRouter through its pool() adapter, scaling on a
    pluggable pressure_fn/calm_fn pair (the page-pressure policy's
    shape) instead of the SLO/queue-depth default."""
    from paddle_tpu.serving import PhaseRouter
    observe.enable()
    d0 = FakeReplica('d0')
    pr = PhaseRouter([], [d0], colocated=True, route='px')
    pool = pr.pool('decode')
    assert pool.route == 'px/decode'
    spawned = []

    def factory(name):
        rep = FakeReplica(name)
        spawned.append(rep)
        return rep

    box = {'frac': 0.9}

    def press(now):
        hot = box['frac'] < 0.15
        return hot, 'page_pressure' if hot else None, \
            {'free_page_frac': box['frac'], 'mean_queue_depth': 0.0,
             'burn_rate': None}

    def calm(signals):
        return signals['free_page_frac'] > 0.5

    ctl = FleetController(pool, factory, min_replicas=1,
                          max_replicas=3, scale_out_cooldown_s=0.0,
                          trough_s=0.5, scale_in_cooldown_s=0.0,
                          pressure_fn=press, calm_fn=calm)
    now = time.perf_counter()
    ctl.step(now=now)
    assert spawned == []                       # calm: no spawn
    box['frac'] = 0.05                         # page pressure
    ctl.step(now=now + 1.0)
    assert len(spawned) == 1                   # scaled the decode pool
    assert len(pr.members('decode')) == 2
    assert pr.members('prefill') == []         # other phase untouched
    assert observe.get_counter('controller.scale_out_total',
                               route='px/decode',
                               reason='page_pressure') == 1
    box['frac'] = 0.9                          # sustained calm
    ctl.step(now=now + 2.0)                    # trough starts
    ctl.step(now=now + 3.0)                    # sustained -> scale in
    assert len(pr.members('decode')) == 1
    ctl.close()
    pr.close()


def test_controller_heal_backoff_quarantine_cycle():
    observe.enable()
    router, ctl, reps, spawned = _fleet(
        2, min_replicas=1, max_replicas=3, backoff_base_s=0.5,
        crash_loop_threshold=2, crash_window_s=30.0, quarantine_s=60.0,
        trough_s=1e9)
    now = time.perf_counter()
    # death detected, replacement held until the backoff expires
    reps[0]._ready = False
    ctl.step(now=now)
    assert ctl.states()['r0'] == DEAD
    assert 'r0' not in [n for n, _ in router.replicas()]
    ctl.step(now=now + 0.3)                    # inside 0.5s backoff
    assert not spawned
    ctl.step(now=now + 0.6)
    assert len(spawned) == 1                   # healed
    assert spawned[0].name == 'r0-r1'
    assert ctl.states()['r0-r1'] == UP
    assert observe.get_counter('controller.heals_total',
                               route='serve', lineage='r0') == 1
    # the replacement dies too: 2 deaths in window -> quarantine, no
    # more restarts, census marker visible
    spawned[0]._ready = False
    ctl.step(now=now + 1.0)
    ctl.step(now=now + 5.0)
    states = ctl.states()
    assert states.get('r0[quarantined]') == QUARANTINED
    assert len(spawned) == 1                   # breaker stopped spawns
    assert ctl.current('r0') is None
    assert observe.get_counter('controller.quarantines_total',
                               route='serve', lineage='r0') == 1
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'controller_quarantine' in kinds
    # quarantine served: one fresh chance with a clean ledger
    ctl.step(now=now + 70.0)
    assert len(spawned) == 2
    assert ctl.current('r0') is spawned[1]
    assert 'r0[quarantined]' not in ctl.states()
    ctl.close()
    router.close()


# -------------------------------------------------------- fault helpers
def test_kill_replica_drain_true_completes_accepted(tmp_path):
    """ISSUE 11 satellite: kill_replica(drain=True) — the graceful
    half of the chaos helper — completes every accepted request, flips
    the corpse's /readyz, and leaves the drain flag on the flight
    event."""
    from paddle_tpu.observe.diagnostics import run_health_checks

    observe.enable()
    d = _save_mlp(str(tmp_path / 'm'))
    eng = _engine(d, 'g0', max_queue_depth=32)
    rng = np.random.RandomState(0)
    futs = [eng.submit({'x': rng.rand(2, 6).astype('float32')})
            for _ in range(8)]
    inject.kill_replica(eng, drain=True)
    for f in futs:                         # drained, never abandoned
        assert len(f.result(10.0)) == 1
    assert eng.ready() is False
    ok, checks = run_health_checks(include_readiness=True)
    assert checks['serving.g0']['ok'] is False
    ev = [e for e in observe.flight_recorder().events()
          if e['kind'] == 'replica_kill'][-1]
    assert ev['data']['drain'] is True


def test_crash_loop_aims_at_lineage():
    observe.enable()
    victims = [FakeReplica('v0'), FakeReplica('v0-r1')]
    feed = iter(victims + [None, None])
    killed = inject.crash_loop(lambda: next(feed), kills=4,
                               interval_s=0.01)
    assert killed == 2                     # benched slot stops yielding
    assert all(not v.ready() for v in victims)
    evs = [e for e in observe.flight_recorder().events()
           if e['kind'] == 'crash_loop_kill']
    assert len(evs) == 2
    assert [e['data']['replica'] for e in evs] == ['v0', 'v0-r1']
    assert observe.get_counter('fault.replica_kills_total',
                               replica='v0') == 1


# ------------------------------------------------------- /statusz panel
def test_statusz_fleet_panel():
    from paddle_tpu.observe import diagnostics

    observe.enable()
    router, ctl, reps, spawned = _fleet(
        2, min_replicas=1, backoff_base_s=0.01,
        crash_loop_threshold=1, quarantine_s=60.0, trough_s=1e9)
    now = time.perf_counter()
    reps[0]._ready = False
    ctl.step(now=now)
    ctl.step(now=now + 1.0)                # threshold 1 -> quarantine
    doc = diagnostics._statusz_doc()
    fleet = doc['fleet']
    assert fleet['replicas']['r1'] == UP
    assert fleet['replicas']['r0[quarantined]'] == QUARANTINED
    assert fleet['census']['up'] == 1
    assert fleet['census']['quarantined'] == 1
    assert fleet['quarantines_total'] == 1
    assert fleet['deaths_total'] == 1
    assert fleet['replicas_ready'] == 1
    ctl.close()
    router.close()


# -------------------------------------------------- metrics_report --fleet
def test_metrics_report_fleet_json(tmp_path):
    """CLI satellite: --fleet reconstructs the scale timeline from a
    metrics JSONL, stdlib-only (no jax import), --json schema stable."""
    observe.enable(jsonl=str(tmp_path / 'm.jsonl'))
    observe.set_gauge('controller.replicas', 2, state='up',
                      route='serve')
    observe.set_gauge('controller.replicas', 0, state='quarantined',
                      route='serve')
    observe.set_gauge('controller.replica_state', 0, replica='r0',
                      route='serve')
    observe.inc('router.requests_total', 40, route='serve')
    observe.inc('router.hedge_total', 2, route='serve')
    observe.inc('router.dispatch_total', 42, replica='r0',
                route='serve')
    observe.flush(kind='snapshot')
    observe.inc('controller.scale_out_total', route='serve',
                reason='burn_rate')
    observe.set_gauge('controller.replicas', 3, state='up',
                      route='serve')
    observe.set_gauge('controller.replica_state', 2,
                      replica='r1[quarantined]', route='serve')
    observe.inc('controller.quarantines_total', route='serve',
                lineage='r1')
    observe.flush(kind='summary')

    tool = os.path.join(REPO, 'tools', 'metrics_report.py')
    r = subprocess.run(
        [sys.executable, tool, str(tmp_path / 'm.jsonl'), '--fleet',
         '--json'],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert len(doc['census_timeline']) == 2
    assert doc['census_timeline'][0]['census']['serve']['up'] == 2
    assert doc['census_timeline'][1]['census']['serve']['up'] == 3
    assert doc['scale_events'] == [
        {'t': doc['scale_events'][0]['t'], 'scale_out': 1,
         'quarantines': 1}]
    assert doc['replicas']['r0'] == 'UP'
    assert doc['replicas']['r1[quarantined]'] == 'QUARANTINED'
    assert doc['totals']['scale_out_total'] == 1
    assert doc['hedge']['hedges'] == 2
    assert doc['hedge']['hedge_fraction'] == pytest.approx(0.05)
    # human rendering names the timeline sections
    r2 = subprocess.run(
        [sys.executable, tool, str(tmp_path / 'm.jsonl'), '--fleet'],
        capture_output=True, text=True, timeout=60)
    assert r2.returncode == 0, r2.stderr
    assert 'fleet controller timeline' in r2.stdout
    assert 'scale_out +1' in r2.stdout
    # no jax import on the --fleet path
    probe = subprocess.run(
        [sys.executable, '-c',
         'import importlib.util, sys\n'
         'spec = importlib.util.spec_from_file_location("mr", %r)\n'
         'm = importlib.util.module_from_spec(spec)\n'
         'spec.loader.exec_module(m)\n'
         'assert m.main([%r, "--fleet"]) == 0\n'
         'assert "jax" not in sys.modules\n'
         % (tool, str(tmp_path / 'm.jsonl'))],
        capture_output=True, text=True, timeout=60)
    assert probe.returncode == 0, probe.stderr


# ---------------------------------------------- autoscale chaos scenarios
def _flash(r):
    assert r['scale_outs'] >= 1          # the controller reacted
    assert r['census_peak'][UP] > 2      # capacity actually landed
    return 'scale_out'


def _crash(r):
    assert r['kills_performed'] >= 2
    assert r['quarantines'] >= 1         # the breaker engaged
    assert r['heals'] >= 1               # after healing at least once
    assert r['census_peak'][QUARANTINED] >= 1
    # quarantine forensics: the flight event fired and survived
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'controller_quarantine' in kinds
    return 'quarantines'


def _trough(r):
    assert r['scale_ins'] >= 1
    assert r['requests_errored'] == 0    # drain lost nothing
    assert r['drain_timeouts'] == 0
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'controller_scale_in' in kinds
    return 'scale_in'


@pytest.mark.parametrize('scenario,check', [
    ('flash', _flash), ('crash', _crash), ('trough', _trough)],
    ids=['flash', 'crash', 'trough'])
def test_bench_autoscale_chaos_acceptance(tmp_path, monkeypatch,
                                          scenario, check):
    """Acceptance: each of ``chaos.autoscale_chaos``'s three scenarios
    (flash-crowd scale-up, crash-loop quarantine after a heal, trough
    scale-in) loses no accepted request, shows its event by the
    controller's own counters, and keeps the hedging contract for its
    own traffic: retry dispatches inside the token budget, zero
    hedge/primary mismatches. The JSONL reconstructs the timeline via
    metrics_report --fleet."""
    from chaos import autoscale_chaos
    # The router's alarm is bit-exact and stays so. On this XLA:CPU the
    # chaos MLP's batch rung 1 rounds one ulp away from rungs 2 and 4
    # (1.5e-8 at 0.28), so a hedge batched at another rung than its
    # primary trips it 3-5 times a run (ROADMAP D8). For THIS test only,
    # floats compare within rounding; anything larger still counts.
    from paddle_tpu.serving import router as router_mod
    exact = router_mod._arrays_equal

    def within_rounding(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype.kind == 'f' and y.dtype.kind == 'f':
            return x.shape == y.shape and bool(np.allclose(
                x, y, rtol=1e-6, atol=0.0, equal_nan=True))
        return exact(x, y)
    monkeypatch.setattr(router_mod, '_arrays_equal', within_rounding)
    jsonl = str(tmp_path / 'autoscale.jsonl')
    observe.enable(jsonl=jsonl)
    r = autoscale_chaos(scenario)
    observe.flush(kind='summary')

    assert r['accepted'] > 0
    assert r['lost'] == 0, r             # zero accepted-request loss
    event = check(r)
    hedge = r['hedge']
    assert hedge['retry_dispatches'] <= hedge['bound']   # by construction
    assert hedge['mismatches'] == 0      # none beyond rounding

    # the scale timeline reconstructs offline from the JSONL
    tool = os.path.join(REPO, 'tools', 'metrics_report.py')
    rep = subprocess.run(
        [sys.executable, tool, jsonl, '--fleet', '--json'],
        capture_output=True, text=True, timeout=60)
    assert rep.returncode == 0, rep.stderr
    doc = json.loads(rep.stdout)
    assert len(doc['census_timeline']) >= 3
    assert any(event in ev for ev in doc['scale_events'])
    assert doc['hedge']['mismatches'] == 0
