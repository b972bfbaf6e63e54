"""Multi-tenant fleet (ISSUE 18): priority classes, token-bucket
quotas charged at router admission (typed QuotaExceededError over the
QueueFullError hierarchy and the RPC wire), tenant-prefixed rendezvous
session pinning, priority-aware decode preemption / prefix-cache
eviction, the training/serving co-location yield (bit-identical
params), metrics_report --tenants, and the tests/chaos.py multitenant
acceptance scenario."""

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
from paddle_tpu import io as _io
from paddle_tpu import observe
from paddle_tpu.observe.slo import Objective, SloTracker
from paddle_tpu.serving import (PRIORITIES, QueueFullError,
                                QuotaExceededError, Router,
                                TenantRegistry, colocation_yield,
                                slo_burn_pressure, tenant_of_session)
from paddle_tpu.serving.decode.kv_pool import BlockTable, KVPool
from paddle_tpu.serving.decode.prefix_cache import PrefixCache
from paddle_tpu.serving.decode.scheduler import (RUNNING, WAITING,
                                                 Scheduler, Sequence)
from paddle_tpu.serving.rpc import _ERR_STATUS, _error_classes
from paddle_tpu.serving.tenancy import TokenBucket, priority_rank

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


class FakeReplica(object):
    """Duck-typed replica: resolves immediately with its own name."""

    def __init__(self, name, ready=True):
        self.name = name
        self._ready = ready
        self.submitted = 0

    def ready(self):
        return self._ready

    def queue_depth(self):
        return 0

    def submit(self, feed, ctx=None):
        self.submitted += 1
        f = Future()
        f.set_result([self.name])
        return f

    def drain(self, timeout=None):
        return True

    def shutdown(self, drain=True):
        self._ready = False


# --------------------------------------------------------- token bucket
def test_token_bucket_refill_and_refund_deterministic():
    b = TokenBucket(rate=2.0, burst=2.0)
    assert b.try_charge(1.0, now=0.0)
    assert b.try_charge(1.0, now=0.0)
    assert not b.try_charge(1.0, now=0.0)       # burst spent
    assert not b.try_charge(1.0, now=0.25)      # refilled only 0.5
    assert b.try_charge(1.0, now=0.5)           # 0.5 + 0.5 = 1.0
    # a full second refills to burst, never beyond it
    assert b.try_charge(2.0, now=10.0)
    assert not b.try_charge(0.5, now=10.0)
    b.refund(1.0)
    assert b.try_charge(1.0, now=10.0)
    # refund caps at burst
    b.refund(100.0)
    assert b.tokens == 2.0
    # the clock never runs backwards (stale now <= last is a no-op refill)
    assert b.try_charge(2.0, now=20.0)
    assert not b.try_charge(1.0, now=5.0)


def test_session_parsing_and_priority_rank():
    assert tenant_of_session('acme/user-42') == 'acme'
    assert tenant_of_session('acme/a/b') == 'acme'
    assert tenant_of_session('user-42') == 'default'
    assert tenant_of_session(None) == 'default'
    assert tenant_of_session('/oops') == 'default'
    assert tenant_of_session(1234) == 'default'
    assert [priority_rank(p) for p in PRIORITIES] == [0, 1, 2]
    # None and unknown classes land on 'standard': untenanted traffic
    # keeps today's scheduling behavior exactly
    assert priority_rank(None) == 1
    assert priority_rank('no-such-class') == 1


# ------------------------------------------------------------ admission
def test_registry_admit_sheds_typed_and_recovers():
    observe.enable()
    reg = TenantRegistry()
    reg.add('acme', priority='interactive', request_rate=2.0)
    reg.admit('acme/u1', now=0.0)
    reg.admit('acme/u2', now=0.0)
    with pytest.raises(QuotaExceededError) as ei:
        reg.admit('acme/u1', now=0.0)
    assert isinstance(ei.value, QueueFullError)  # existing paths apply
    assert 'requests' in str(ei.value)
    # continuous refill on the caller's clock: admitted again later
    reg.admit('acme/u1', now=1.0)
    assert observe.get_counter('tenant.admitted', tenant='acme',
                               priority='interactive',
                               route='serve') == 3
    assert observe.get_counter('tenant.shed', tenant='acme',
                               priority='interactive',
                               reason='requests', route='serve') == 1
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'tenant_quota_shed' in kinds


def test_registry_token_reject_refunds_request_charge():
    reg = TenantRegistry()
    reg.add('t', request_rate=10.0, token_rate=5.0)
    with pytest.raises(QuotaExceededError) as ei:
        reg.admit('t/s1', tokens=100, now=0.0)
    assert 'tokens' in str(ei.value)
    # the request charge came back, so the oversized request did not
    # also burn request quota
    assert reg.get('t').requests.tokens == 10.0
    reg.admit('t/s1', tokens=5, now=0.0)
    assert reg.get('t').requests.tokens == 9.0


def test_registry_env_knobs_read_per_call(monkeypatch):
    reg = TenantRegistry()
    monkeypatch.setenv('PADDLE_TPU_TENANT_DEFAULT_PRIORITY', 'batch')
    monkeypatch.setenv('PADDLE_TPU_TENANT_DEFAULT_RPS', '1')
    t = reg.resolve('lazy/s0')              # lazily created from env
    assert t.name == 'lazy' and t.priority == 'batch'
    assert t.requests is not None and t.requests.rate == 1.0
    # knobs are read per call, never at import: a tenant first seen
    # under different env gets the new defaults
    monkeypatch.setenv('PADDLE_TPU_TENANT_DEFAULT_PRIORITY', 'bogus')
    monkeypatch.delenv('PADDLE_TPU_TENANT_DEFAULT_RPS')
    t2 = reg.resolve('other/s0')
    assert t2.priority == 'standard' and t2.requests is None
    # unprefixed sessions account under the implicit 'default' tenant
    assert reg.resolve(None).name == 'default'
    assert reg.names() == ['default', 'lazy', 'other']


def test_router_quota_shed_never_touches_a_replica():
    rep = FakeReplica('r0')
    reg = TenantRegistry()
    reg.add('acme', priority='interactive', request_rate=1.0)
    router = Router([rep], tenants=reg)
    try:
        fut = router.submit({'x': np.zeros((1, 4), np.float32)},
                            session='acme/u1')
        assert fut.result(timeout=10) == ['r0']
        with pytest.raises(QuotaExceededError):
            router.submit({'x': np.zeros((1, 4), np.float32)},
                          session='acme/u1')
        assert rep.submitted == 1           # shed before any dispatch
    finally:
        router.close()


# ---------------------------------------- rendezvous pinning (tenants)
def test_rendezvous_pinning_with_tenant_prefixed_sessions():
    """Tenant-prefixed session ids feed the rendezvous hash whole: the
    pin is stable, a membership change only moves sessions that touch
    the added/removed replica, and two tenants' identical suffixes pin
    independently (the prefix is an accounting key, not a placement
    override that would herd one tenant onto one replica)."""
    router = Router([FakeReplica(n) for n in ('r0', 'r1', 'r2')])
    try:
        sessions = ['%s/u%d' % (t, i) for t in ('acme', 'bob')
                    for i in range(12)]

        def pins():
            return {s: router._candidates(s)[0][0] for s in sessions}

        first = pins()
        assert first == pins()              # stable across calls
        router.add_replica(FakeReplica('r3'), name='r3')
        after_add = pins()
        moved = [s for s in sessions if after_add[s] != first[s]]
        assert moved                        # some keyspace shifts...
        assert all(after_add[s] == 'r3' for s in moved)   # ...only to r3
        router.remove_replica('r3')
        assert pins() == first              # and shifts back exactly
        # same suffix, different tenant prefix: independent pins
        acme = {s.split('/', 1)[1]: first[s] for s in sessions
                if s.startswith('acme/')}
        bob = {s.split('/', 1)[1]: first[s] for s in sessions
               if s.startswith('bob/')}
        assert acme != bob
        # every tenant still spreads over the fleet (no herding)
        assert len(set(acme.values())) > 1
        assert len(set(bob.values())) > 1
    finally:
        router.close()


# ------------------------------------------------- decode scheduling
def _seq(rid, priority=None, prompt_len=3, max_new=4):
    return Sequence(rid, list(range(1, prompt_len + 1)), max_new, 0.0,
                    1, None, priority=priority)


def test_scheduler_admits_highest_class_first_batch_backfills():
    pool = KVPool(num_blocks=8, block_size=4)
    sched = Scheduler(pool, max_batch=2)
    b, s, i = _seq('b', 'batch'), _seq('s', None), _seq('i', 'interactive')
    for seq in (b, s, i):
        sched.add(seq)
    assert sched.pop_admittable().request_id == 'i'
    assert sched.pop_admittable().request_id == 's'
    # batch only backfills a slot no latency-class request wants
    assert sched.pop_admittable() is None
    sched.finish(s, 'max_tokens')
    assert sched.pop_admittable().request_id == 'b'


def test_scheduler_preempts_lowest_class_first():
    observe.enable()
    pool = KVPool(num_blocks=3, block_size=4)
    sched = Scheduler(pool, max_batch=3)
    i, s, b = _seq('i', 'interactive'), _seq('s', None), _seq('b', 'batch')
    for seq in (i, s, b):
        sched.add(seq)
    while sched.pop_admittable() is not None:
        pass
    assert [x.request_id for x in sched.running] == ['i', 's', 'b']
    assert pool.free_blocks() == 0
    # growth under exhaustion evicts the batch-class victim, never the
    # latency classes, and requeues it at the front for continuation
    assert sched.ensure_growth(i, need_tokens=5)
    assert i.state == RUNNING and s.state == RUNNING
    assert b.state == WAITING and b.preemptions == 1
    assert sched.waiting[0] is b
    assert observe.get_counter('tenant.preempted', tenant='default',
                               priority='batch') == 1
    assert observe.get_counter('tenant.preempted', tenant='default',
                               priority='standard') == 0


def test_scheduler_equal_classes_keep_youngest_victim_rule():
    pool = KVPool(num_blocks=2, block_size=4)
    sched = Scheduler(pool, max_batch=2)
    x, y = _seq('x'), _seq('y')
    sched.add(x)
    sched.add(y)
    while sched.pop_admittable() is not None:
        pass
    assert sched.ensure_growth(x, need_tokens=5)
    assert y.state == WAITING and x.state == RUNNING


def test_prefix_cache_evicts_batch_pages_before_interactive():
    observe.enable()
    pool = KVPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    ti, tb = BlockTable(), BlockTable()
    assert pool.grow(ti, 4) and pool.grow(tb, 4)
    cache.publish([1, 2, 3, 4], ti, 4, tenant='fg',
                  priority='interactive')
    cache.publish([9, 9, 9, 9], tb, 4, tenant='bulk', priority='batch')
    pool.release(ti)
    pool.release(tb)
    # touch the batch page LAST: plain LRU would evict the interactive
    # page first; the priority order still takes the batch page
    t = BlockTable()
    assert cache.match([9, 9, 9, 9, 0], t) == 4
    pool.release(t)
    assert cache.reclaim(1) == 1
    t2, t3 = BlockTable(), BlockTable()
    assert cache.match([9, 9, 9, 9, 0], t2) == 0     # batch page gone
    assert cache.match([1, 2, 3, 4, 0], t3) == 4     # interactive kept
    pool.release(t3)
    assert observe.get_counter('tenant.evicted_pages', tenant='bulk',
                               priority='batch') == 1
    cache.clear()


def test_prefix_cache_shared_page_keeps_most_protected_class():
    pool = KVPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    ti, tb = BlockTable(), BlockTable()
    assert pool.grow(ti, 4) and pool.grow(tb, 4)
    cache.publish([1, 2, 3, 4], ti, 4, tenant='fg',
                  priority='interactive')
    # a batch publish of the SAME chain must not demote the page
    cache.publish([1, 2, 3, 4], ti, 4, tenant='bulk', priority='batch')
    cache.publish([7, 7, 7, 7], tb, 4, tenant='bulk', priority='batch')
    pool.release(ti)
    pool.release(tb)
    assert cache.reclaim(1) == 1
    t = BlockTable()
    assert cache.match([1, 2, 3, 4, 0], t) == 4      # survived as
    pool.release(t)                                  # interactive
    cache.clear()


# ----------------------------------------------------------- RPC wire
def test_quota_error_is_typed_over_rpc():
    assert _error_classes()['QuotaExceededError'] is QuotaExceededError
    assert issubclass(QuotaExceededError, QueueFullError)
    # backpressure status: same 429 the other admission sheds use
    assert _ERR_STATUS['QuotaExceededError'] == 429


# ------------------------------------------------------- co-location
class _FakeTrainer(object):
    def __init__(self):
        self.calls = []

    def request_yield(self):
        self.calls.append('yield')

    def resume_from_yield(self):
        self.calls.append('resume')


def test_colocation_yield_edge_triggered_with_hysteresis():
    observe.enable()
    ft = _FakeTrainer()
    flag = {'pressured': False, 'burn': 0.0}

    def pf(now):
        return (flag['pressured'], 'test',
                {'burn_rate': flag['burn'], 'mean_queue_depth': 0.0})

    def cf(signals):
        return signals['burn_rate'] < 0.5

    wp, wc = colocation_yield(ft, pf, cf, route='colo')
    assert wp(0.0)[0] is False and ft.calls == []
    flag.update(pressured=True, burn=2.0)
    assert wp(1.0)[0] is True
    wp(2.0)                                  # edge: yields only once
    assert ft.calls == ['yield']
    assert observe.get_counter('tenant.trainer_yields_total',
                               route='colo') == 1
    assert observe.get_gauge('tenant.trainer_yielded', route='colo') == 1
    # pressure gone but burn above the calm floor: hysteresis holds
    flag.update(pressured=False, burn=1.0)
    wp(3.0)
    assert ft.calls == ['yield']
    flag.update(burn=0.3)
    wp(4.0)
    assert ft.calls == ['yield', 'resume']
    assert observe.get_gauge('tenant.trainer_yielded', route='colo') == 0
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'tenant_yield' in kinds and 'tenant_resume' in kinds
    # the inner calm verdict passes through for fleet scaling
    assert wc({'burn_rate': 0.3}) and not wc({'burn_rate': 0.9})


def test_slo_burn_pressure_tracks_tracker_burn():
    tracker = SloTracker([Objective('colo', 0.01, 0.5, window_s=100.0)])
    pf, cf = slo_burn_pressure(tracker, 'colo')
    pressured, reason, signals = pf(0.5)
    assert pressured is False and signals['burn_rate'] == 0.0
    for _ in range(4):
        tracker.record('colo', 0.1, ok=True, now=1.0)   # violations
    pressured, reason, signals = pf(1.5)
    assert pressured is True and reason == 'burn_rate'
    assert signals['burn_rate'] == pytest.approx(2.0)
    assert not cf(signals)
    for _ in range(20):
        tracker.record('colo', 0.001, ok=True, now=2.0)  # in SLO
    pressured, _, signals = pf(2.5)
    assert pressured is False
    assert signals['burn_rate'] < 0.5 and cf(signals)


def _linreg_train_func():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1)
    return [fluid.layers.mean(fluid.layers.square_error_cost(pred, y))]


def _make_batches(n, batch=8, seed=4):
    rng = np.random.RandomState(seed)
    w = np.random.RandomState(3).randn(4, 1).astype('float32')
    out = []
    for _ in range(n):
        x = rng.randn(batch, 4).astype('float32')
        out.append({'x': x, 'y': (x @ w).astype('float32')})
    return out


def _train(batches, yield_at=None):
    """One fresh run; with ``yield_at`` the event handler requests a
    yield after that step and a sidecar thread resumes once the loop
    has actually parked (drained + blocked)."""
    with fluid.scope_guard(fluid.Scope()):
        fluid.reset_default_programs()
        trainer = fluid.Trainer(
            train_func=_linreg_train_func,
            optimizer_func=lambda: fluid.optimizer.SGD(
                learning_rate=0.1),
            place=fluid.CPUPlace())
        losses, parked_seen = [], []

        def resumer():
            deadline = time.time() + 30
            while not trainer.yielded() and time.time() < deadline:
                time.sleep(0.005)
            parked_seen.append(trainer.yielded())
            trainer.resume_from_yield()

        def handler(e):
            if isinstance(e, fluid.trainer.EndStepEvent):
                losses.append(float(np.asarray(
                    e.metrics[0]).reshape(())))
                if yield_at is not None and e.step == yield_at \
                        and not parked_seen:
                    threading.Thread(target=resumer).start()
                    trainer.request_yield()

        trainer.train(num_epochs=1, event_handler=handler,
                      reader=lambda: iter(batches))
        arrays, _ = _io._snapshot_vars(trainer.program,
                                       predicate=_io._is_persistable)
        return losses, {k: np.array(v) for k, v in arrays.items()}, \
            parked_seen


def test_trainer_yield_resume_is_bit_identical():
    """A mid-run yield/resume parks the drained loop and changes
    nothing about the trajectory: same per-step losses, bitwise-equal
    final params."""
    batches = _make_batches(6)
    base_losses, base_params, _ = _train(batches)
    y_losses, y_params, parked_seen = _train(batches, yield_at=2)
    assert parked_seen == [True]            # it really parked
    assert y_losses == base_losses
    assert set(y_params) == set(base_params)
    for k in base_params:
        np.testing.assert_array_equal(y_params[k], base_params[k])


# ------------------------------------------- metrics_report --tenants
def test_metrics_report_tenants_json(tmp_path):
    """CLI satellite: --tenants renders the per-tenant isolation panel
    from a JSONL, stdlib-only (no jax import), --json schema stable."""
    observe.enable(jsonl=str(tmp_path / 'm.jsonl'))
    observe.inc('tenant.admitted', 5, tenant='acme',
                priority='interactive', route='serve')
    observe.inc('tenant.shed', 3, tenant='bulk', priority='batch',
                reason='requests', route='serve')
    observe.inc('tenant.shed', 2, tenant='bulk', priority='batch',
                reason='tokens', route='serve')
    observe.inc('tenant.preempted', 2, tenant='bulk', priority='batch')
    observe.inc('tenant.evicted_pages', 4, tenant='bulk',
                priority='batch')
    observe.inc('tenant.trainer_yields_total', route='serve')
    observe.set_gauge('tenant.trainer_yielded', 1, route='serve')
    observe.flush(kind='summary')

    tool = os.path.join(REPO, 'tools', 'metrics_report.py')
    r = subprocess.run(
        [sys.executable, tool, str(tmp_path / 'm.jsonl'), '--tenants',
         '--json'],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    acme, bulk = doc['tenants']['acme'], doc['tenants']['bulk']
    assert acme['priority'] == 'interactive' and acme['admitted'] == 5
    assert bulk['shed'] == 5
    assert bulk['shed_reasons'] == {'requests': 3, 'tokens': 2}
    assert bulk['preempted'] == 2 and bulk['evicted_pages'] == 4
    assert doc['trainer']['yields'] == 1
    assert doc['trainer']['yielded'] == 1
    # human rendering: most protected class first, shed-reason split
    r2 = subprocess.run(
        [sys.executable, tool, str(tmp_path / 'm.jsonl'), '--tenants'],
        capture_output=True, text=True, timeout=60)
    assert r2.returncode == 0, r2.stderr
    assert r2.stdout.index('acme') < r2.stdout.index('bulk')
    assert 'shed by' in r2.stdout and 'trainer' in r2.stdout
    # no jax import on the --tenants path
    probe = subprocess.run(
        [sys.executable, '-c',
         'import importlib.util, sys\n'
         'spec = importlib.util.spec_from_file_location("mr", %r)\n'
         'm = importlib.util.module_from_spec(spec)\n'
         'spec.loader.exec_module(m)\n'
         'assert m.main([%r, "--tenants"]) == 0\n'
         'assert "jax" not in sys.modules\n'
         % (tool, str(tmp_path / 'm.jsonl'))],
        capture_output=True, text=True, timeout=60)
    assert probe.returncode == 0, probe.stderr


# ------------------------------------------ multitenant chaos acceptance
@pytest.mark.slow
def test_bench_multitenant_acceptance(tmp_path):
    """Acceptance: ``chaos.multitenant_chaos`` proves, in counts, that
    a batch flood is shed by its quota while the interactive tenant
    loses nothing, typed quota sheds with zero losses, zero priority
    inversions, and a bit-identical co-location yield — and the
    tenant.* ledger lands in the metrics JSONL for --tenants."""
    from chaos import multitenant_chaos
    jsonl = str(tmp_path / 'mt.jsonl')
    observe.enable(jsonl=jsonl)
    r = multitenant_chaos()
    observe.flush(kind='summary')

    mixed = r['noisy_neighbor']['mixed']['tenants']
    assert mixed['bg']['quota_sheds'] > 0     # the flood was shed
    assert mixed['bg']['shed_counter'] == mixed['bg']['quota_sheds']
    for fg in (mixed['fg'], r['noisy_neighbor']['solo']['tenants']['fg']):
        assert fg['admitted'] > 0 and fg['quota_sheds'] == 0
        assert fg['lost'] == 0 and fg['errors'] == 0
    q = r['quota_exhaustion']['tenants']['acme']
    assert q['quota_sheds'] > 0 and q['untyped_rejects'] == 0
    assert q['lost'] == 0 and q['errors'] == 0
    inv = r['priority_inversion']
    assert inv['preempted_interactive'] == 0
    assert inv['preempted_batch'] > 0
    assert all(n == inv['interactive_tokens_asked']
               for n in inv['interactive_tokens'])
    colo = r['colocation']
    assert colo['yielded'] and colo['parked'] and colo['resumed']
    assert colo['bit_identical']

    tool = os.path.join(REPO, 'tools', 'metrics_report.py')
    rep = subprocess.run(
        [sys.executable, tool, jsonl, '--tenants', '--json'],
        capture_output=True, text=True, timeout=60)
    assert rep.returncode == 0, rep.stderr
    doc = json.loads(rep.stdout)
    assert doc['tenants']                    # isolation panel populated
