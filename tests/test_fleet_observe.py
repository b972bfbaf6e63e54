"""Fleet-wide observability (ISSUE 20): reqtrace wire-form propagation
across process hops, NTP-style clock-offset estimation (/clockz +
ClockOffsetEstimator), metrics federation (relabel_snapshot,
FleetFederation scrape/merge, /fleetz, /metrics?scope=fleet, federated
/tracez), the offline Perfetto merger (tools/fleet_trace.py), SLO
fleet-derived panels, and postmortem aggregation (heartbeat-snapshot
dumps surviving SIGKILL, FleetController attaching the dead replica's
final seconds to its heal event)."""

import json
import os
import signal
import sys
import time
import types
import urllib.request

import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.observe import fleet as fleet_mod
from paddle_tpu.observe import reqtrace
from paddle_tpu.observe import slo as slo_mod
from paddle_tpu.observe.fleet import (ClockOffsetEstimator,
                                      FleetFederation, fleet,
                                      http_get_json)
from paddle_tpu.observe.registry import relabel_snapshot
from paddle_tpu.serving import FleetController, Router
from paddle_tpu.serving.handoff import _VERSION, KVPacket
from paddle_tpu.serving.rpc import ProcessReplicaFactory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _observe_clean():
    yield
    fleet().clear()
    observe._SINK['path'] = None
    observe._SINK['trace_path'] = None
    observe.stop_serving()
    observe.disable()
    observe.reset()


def _fleet_trace_mod():
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import fleet_trace
    finally:
        sys.path.pop(0)
    return fleet_trace


# ------------------------------------------------------- wire propagation
def test_wire_roundtrip_reconstitutes_trace():
    observe.enable()
    ctx = reqtrace.new_context('rpc', deadline_s=5.0, sample=1.0,
                               baggage={'tenant': 't0'})
    assert ctx.sampled and ctx.trace_id
    wire = json.loads(json.dumps(ctx.to_wire()))   # the hop is JSON
    assert wire['trace_id'] == ctx.trace_id
    assert wire['sampled'] is True
    assert 0.0 < wire['deadline_s'] <= 5.0         # RELATIVE budget
    assert wire['route'] == 'rpc'
    assert wire['baggage'] == {'tenant': 't0'}

    back = reqtrace.from_wire(wire)
    assert back.trace_id == ctx.trace_id
    assert back.sampled and back.route == 'rpc'
    assert back.baggage == {'tenant': 't0'}
    assert 0.0 < back.remaining() <= 5.0           # re-anchored locally
    # pre-armed flow handle: flow id = the trace id, so the receiving
    # side's flow_step links back to the sender's flow_begin
    assert back._flow is not None
    assert back._flow.flow_id == int(ctx.trace_id, 16)
    # a hop with no trace reconstitutes to None, not a dummy context
    assert reqtrace.from_wire(None) is None
    assert reqtrace.from_wire({}) is None


def test_from_wire_honors_local_telemetry_gate():
    # receiving process has telemetry off: the sampled bit is dropped
    # (spans would land on the floor) but identity/deadline survive
    assert not observe.enabled()
    wire = {'trace_id': 'abc123abc123', 'sampled': True,
            'deadline_s': 1.0, 'route': 'rpc', 'baggage': None}
    ctx = reqtrace.from_wire(wire)
    assert ctx is not None and not ctx.sampled
    assert ctx.trace_id == 'abc123abc123'
    assert ctx._flow is None
    assert 0.0 < ctx.remaining() <= 1.0


def test_kv_packet_header_carries_trace_over_wire():
    observe.enable()
    ctx = reqtrace.new_context('decode', sample=1.0)
    pkt = KVPacket({'version': _VERSION, 'trace': ctx.to_wire()},
                   {'k': np.arange(8, dtype=np.float32).reshape(2, 4)})
    back = KVPacket.from_bytes(pkt.to_bytes(transport='socket'))
    assert back.header['trace']['trace_id'] == ctx.trace_id
    assert back.header['trace']['sampled'] is True
    np.testing.assert_array_equal(np.asarray(back.arrays['k']),
                                  np.asarray(pkt.arrays['k']))


# ---------------------------------------------------------- clock offset
def test_clock_offset_estimator_converges_under_skew():
    est = ClockOffsetEstimator()
    skew = 0.25                       # remote clock runs 250ms ahead
    t = 100.0
    for _ in range(20):
        d = 0.002                     # symmetric one-way delay
        t0 = t
        t1 = t0 + d + skew
        t2 = t1 + 0.0005
        t3 = t0 + 2 * d + 0.0005
        est.update(t0, t1, t2, t3)
        t += 1.0
    assert est.offset() == pytest.approx(skew, abs=1e-9)
    assert est.samples == 20
    # a grossly asymmetric outlier (rtt 150x the best) barely moves it
    est.update(t, t + 0.5 + skew, t + 0.5 + skew, t + 0.6)
    assert est.offset() == pytest.approx(skew, abs=0.002)
    assert est.rtt() == pytest.approx(0.6)


def test_clock_offset_reseeds_after_a_slow_first_exchange():
    # a fresh server: 24 ms for the first exchange, all of it on the way
    # out (the estimate is off by 12 ms), then four of 0.7 ms. Averaged
    # in at alpha 0.25 the seed's error would still be 3.8 ms.
    skew = 0.05
    est = ClockOffsetEstimator()
    est.update(100.0, 100.024 + skew, 100.024 + skew, 100.024)
    assert est.offset() == pytest.approx(skew + 0.012)
    for i in range(1, 5):
        t = 100.0 + i
        est.update(t, t + 0.00035 + skew, t + 0.00035 + skew, t + 0.0007)
        assert abs(est.offset() - skew) <= est.rtt()
    assert est.offset() == pytest.approx(skew, abs=1e-6)
    # a slow exchange later is still only averaged in, down-weighted
    est.update(200.0, 200.024 + skew, 200.024 + skew, 200.024)
    assert est.offset() == pytest.approx(skew, abs=0.0002)


def test_clockz_endpoint_feeds_estimator():
    observe.enable()
    srv = observe.serve(port=0)
    est = ClockOffsetEstimator()
    for _ in range(5):
        t0 = time.time()
        doc = http_get_json(srv.url + '/clockz')
        t3 = time.time()
        est.update(t0, doc['t_recv'], doc['t_send'], t3)
        assert doc['t_recv'] <= doc['t_send']
        assert doc['pid'] == os.getpid()
    # same process, same clock: offset must be ~zero (bounded by rtt)
    assert abs(est.offset()) <= est.rtt() + 1e-6


# ----------------------------------------------------- metrics federation
def test_relabel_snapshot_merges_labels():
    snap = {'counters': {'a_total{route=x}': 3},
            'gauges': {'g': 1.5},
            'histograms': {'h{q=z}': {'count': 1}},
            'pid': 7, 'host': 0, 'ts': 1.0}
    out = relabel_snapshot(snap, replica='r0', host='h0')
    assert out['counters'] == {'a_total{host=h0,replica=r0,route=x}': 3}
    assert out['gauges'] == {'g{host=h0,replica=r0}': 1.5}
    assert out['histograms'] == {'h{host=h0,q=z,replica=r0}':
                                 {'count': 1}}
    # injected labels win on conflict; non-metric keys pass through
    assert out['pid'] == 7 and out['host'] == 0 and out['ts'] == 1.0
    snap2 = {'gauges': {'g{replica=old}': 2}}
    assert relabel_snapshot(snap2, replica='new')['gauges'] == \
        {'g{replica=new}': 2}


def test_poll_interval_env_knob_read_per_call():
    assert fleet_mod.poll_interval({}) == fleet_mod.DEFAULT_POLL_S
    assert fleet_mod.poll_interval(
        {fleet_mod.FLEET_POLL_ENV: '0.5'}) == 0.5
    # zero/malformed must not spin the poll thread
    assert fleet_mod.poll_interval({fleet_mod.FLEET_POLL_ENV: '0'}) \
        == 0.05
    assert fleet_mod.poll_interval({fleet_mod.FLEET_POLL_ENV: 'nan?x'}) \
        == fleet_mod.DEFAULT_POLL_S


def test_slo_fleet_derived_panels():
    r0 = {'gauges': {'worker.queue_depth{replica=r0}': 4},
          'histograms': {'serving.request_seconds{replica=r0}':
                         {'p99': 0.2}},
          'counters': {'handoff.bytes_total{transport=socket}': 1000}}
    r1 = {'gauges': {'worker.queue_depth{replica=r1}': 1},
          'histograms': {'decode.request_seconds': {'p99': 0.1}},
          'counters': {'handoff.bytes_total{transport=socket}': 500}}
    d = slo_mod.fleet_derived({'r0': r0, 'r1': r1})
    assert d['queue_depth']['per_replica'] == {'r0': 4, 'r1': 1}
    assert d['queue_depth']['skew'] == 3
    assert d['queue_depth']['mean'] == 2.5
    assert d['p99_spread_s']['per_replica'] == {'r0': 0.2, 'r1': 0.1}
    assert d['p99_spread_s']['spread'] == pytest.approx(0.1)
    assert d['handoff_bytes_total'] == 1500
    assert d['handoff_bytes_per_s'] is None     # no previous snapshot
    # wire rate from counter deltas against a previous poll
    r0b = dict(r0, counters={'handoff.bytes_total{transport=socket}':
                             3000})
    d2 = slo_mod.fleet_derived({'r0': r0b, 'r1': r1},
                               prev={'r0': r0, 'r1': r1}, dt_s=2.0)
    assert d2['handoff_bytes_per_s'] == pytest.approx(1000.0)
    # empty fleet: everything None/empty, nothing raises
    d3 = slo_mod.fleet_derived({})
    assert d3['queue_depth']['skew'] is None
    assert d3['p99_spread_s']['spread'] is None


def test_fleet_federation_scrape_merge_and_endpoints():
    observe.enable()
    observe.set_gauge('worker.queue_depth', 4, replica='self')
    observe.inc('handoff.bytes_total', 123, transport='socket')
    srv = observe.serve(port=0)
    fed = fleet()
    # a replica handle is duck-typed: .url + optional .clock_offset();
    # point one at our OWN diagnostics server (one process plays both
    # roles — the scrape path is identical)
    fed.register(types.SimpleNamespace(
        url=srv.url, name='self', clock_offset=lambda: 0.5))
    assert fed.poll_once() == 1
    sc = fed.scrapes()['self']
    assert sc['clock_offset_s'] == 0.5
    assert observe.get_gauge('rpc.clock_offset_seconds',
                             replica='self') == 0.5
    merged = fed.merged_snapshot()
    assert any('replica=self' in k for k in merged['gauges'])
    assert any('replica=controller' in k for k in merged['gauges'])
    # /fleetz: scrape health + derived panels + the merged snapshot
    doc = http_get_json(srv.url + '/fleetz')
    assert doc['replicas']['self']['scraped'] is True
    assert doc['replicas']['self']['clock_offset_s'] == 0.5
    assert doc['replicas']['self']['consecutive_errors'] == 0
    assert doc['derived']['queue_depth']['per_replica']['self'] == 4
    assert doc['derived']['handoff_bytes_total'] == 123
    # /metrics?scope=fleet: the merge as Prometheus text
    with urllib.request.urlopen(srv.url + '/metrics?scope=fleet',
                                timeout=5) as resp:
        text = resp.read().decode()
    assert 'replica="self"' in text
    assert 'worker_queue_depth' in text
    # an unreachable replica: error counted, last snapshot retained
    fed.register(types.SimpleNamespace(url='http://127.0.0.1:9',
                                       name='gone'))
    assert fed.poll_once(timeout_s=0.5) == 1
    doc2 = http_get_json(srv.url + '/fleetz')
    assert doc2['replicas']['gone']['consecutive_errors'] >= 1
    assert doc2['replicas']['self']['scraped'] is True
    assert observe.get_counter('fleet.scrape_errors_total',
                               replica='gone') >= 1


def test_fleet_polling_thread_scrapes_on_interval():
    observe.enable()
    observe.set_gauge('worker.queue_depth', 1, replica='self')
    srv = observe.serve(port=0)
    fed = FleetFederation()
    fed.register(types.SimpleNamespace(url=srv.url, name='self'))
    fed.start_polling(interval_s=0.05)
    try:
        deadline = time.time() + 10
        while not fed.scrapes() and time.time() < deadline:
            time.sleep(0.02)
        assert 'self' in fed.scrapes()
    finally:
        fed.stop_polling()


def test_federated_tracez_merges_replica_spans():
    observe.enable()
    ctx = reqtrace.new_context('rpc', sample=1.0)
    t0 = time.perf_counter()
    ctx.stage('stage_a', t0, t0 + 0.001)
    srv = observe.serve(port=0)
    fed = fleet()
    fed.register(types.SimpleNamespace(url=srv.url, name='self'))
    # &local=1 pins the query to this process (how replicas are
    # queried, so federation cannot recurse)
    local = http_get_json('%s/tracez?trace_id=%s&local=1'
                          % (srv.url, ctx.trace_id))
    assert local['recorded'] == 1
    assert 'sources' not in local
    # the federated query appends the replica's spans (here: ourselves
    # again), each tagged with the replica name
    fdoc = http_get_json('%s/tracez?trace_id=%s'
                         % (srv.url, ctx.trace_id))
    assert fdoc['recorded'] == 2
    assert fdoc['sources']['self']['ok'] is True
    assert any((e.get('args') or {}).get('replica') == 'self'
               for e in fdoc['spans'])


# ------------------------------------------------- offline trace merging
def test_fleet_trace_merge_shifts_and_remaps():
    fleet_trace = _fleet_trace_mod()
    ev_ctl = [{'name': 'rpc_admission', 'ph': 'X', 'pid': 10, 'tid': 1,
               'ts': 1000.0, 'dur': 50.0, 'args': {'trace_id': 'abc'}}]
    ev_rep = [{'name': 'rpc_execute', 'ph': 'X', 'pid': 10, 'tid': 7,
               'ts': 2000.0, 'dur': 30.0, 'args': {'trace_id': 'abc'}}]
    doc = fleet_trace.merge_traces([('controller', ev_ctl, 0.0),
                                    ('r0', ev_rep, 0.0005)])
    events = doc['traceEvents']
    xs = [e for e in events if e['ph'] == 'X']
    # pid collision across hosts: remapped to distinct tracks
    assert len({e['pid'] for e in xs}) == 2
    # replica clock 500us ahead: its span shifts back onto the
    # controller timebase
    execs = [e for e in xs if e['name'] == 'rpc_execute']
    assert execs[0]['ts'] == pytest.approx(2000.0 - 500.0)
    assert execs[0]['args']['replica'] == 'r0'
    # each labeled input got a process_name metadata track label
    names = {e['args']['name'] for e in events if e['ph'] == 'M'}
    assert names == {'controller', 'r0'}
    # originals untouched
    assert ev_rep[0]['ts'] == 2000.0 and 'replica' not in ev_rep[0]['args']


def test_fleet_trace_input_spec_and_cli(tmp_path):
    fleet_trace = _fleet_trace_mod()
    assert fleet_trace.parse_input_spec('r0=f.json:0.25') == \
        ('r0', 'f.json', 0.25)
    assert fleet_trace.parse_input_spec('f.json') == (None, 'f.json', 0.0)
    assert fleet_trace.parse_input_spec('a=b.json') == ('a', 'b.json', 0.0)
    # all three accepted file shapes
    assert fleet_trace.load_trace_events([{'ph': 'X'}]) == [{'ph': 'X'}]
    assert fleet_trace.load_trace_events(
        {'traceEvents': [1], 'displayTimeUnit': 'ms'}) == [1]
    assert fleet_trace.load_trace_events({'spans': [2]}) == [2]
    with pytest.raises(ValueError):
        fleet_trace.load_trace_events({'nope': 1})
    a = tmp_path / 'a.trace.json'
    b = tmp_path / 'b.trace.json'
    a.write_text(json.dumps({'traceEvents': [
        {'name': 's', 'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 10.0,
         'dur': 1.0}]}))
    b.write_text(json.dumps({'spans': [
        {'name': 't', 'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 20.0,
         'dur': 1.0}]}))
    out = tmp_path / 'merged.json'
    rc = fleet_trace.main(['--input', 'ctl=%s' % a,
                           '--input', 'r0=%s:0.000005' % b,
                           '--output', str(out)])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert len([e for e in merged['traceEvents']
                if e['ph'] == 'X']) == 2
    assert {e['args']['name'] for e in merged['traceEvents']
            if e['ph'] == 'M'} == {'ctl', 'r0'}


# -------------------------------------------- postmortem aggregation
def _postmortem_doc(reason='heartbeat_snapshot'):
    return {'kind': 'paddle_tpu_postmortem', 'schema': 1,
            'reason': reason, 'pid': 4242,
            'events': [{'seq': 0, 'ts': 1.0, 'kind': 'serving_batch'},
                       {'seq': 1, 'ts': 2.0, 'kind': 'rpc_request'}]}


class _StubReplica(object):
    """Duck-typed replica for the controller: flips dead on command and
    serves a canned postmortem, like a RemoteReplica whose worker left
    a heartbeat snapshot before a SIGKILL."""

    def __init__(self, name, postmortem=None):
        self.name = name
        self._ready = True
        self._postmortem = postmortem

    def ready(self):
        return self._ready

    def queue_depth(self):
        return 0

    def postmortem(self):
        return self._postmortem

    def drain(self, timeout=None):
        return True

    def shutdown(self, drain=True):
        self._ready = False


def test_controller_heal_attaches_postmortem():
    observe.enable()
    pm = _postmortem_doc()
    reps = [_StubReplica('r0', postmortem=pm), _StubReplica('r1')]
    router = Router(reps, admission='none', session_affinity=False)
    ctl = FleetController(router, lambda name: _StubReplica(name),
                          min_replicas=1, max_replicas=3,
                          backoff_base_s=0.01, trough_s=1e9)
    now = time.perf_counter()
    reps[0]._ready = False
    ctl.step(now=now)                 # death: postmortem pulled NOW
    assert observe.get_counter('controller.postmortems_total',
                               route='serve', lineage='r0') == 1
    ctl.step(now=now + 1.0)           # backoff expired: heal
    assert observe.get_counter('controller.heals_total',
                               route='serve', lineage='r0') == 1
    evs = observe.flight_recorder().events()
    dead = [e for e in evs if e['kind'] == 'controller_replica_dead'][-1]
    assert dead['data']['postmortem_reason'] == 'heartbeat_snapshot'
    assert dead['data']['postmortem_events'] == 2
    heal = [e for e in evs if e['kind'] == 'controller_heal'][-1]
    assert heal['data']['postmortem_reason'] == 'heartbeat_snapshot'
    assert heal['data']['postmortem_pid'] == 4242
    assert heal['data']['postmortem_events'] == 2
    assert 'rpc_request' in heal['data']['postmortem_last_kinds']
    ctl.close()
    router.close()


def test_controller_heal_without_postmortem_still_works():
    observe.enable()
    reps = [_StubReplica('r0')]       # postmortem() returns None
    router = Router(reps, admission='none', session_affinity=False)
    ctl = FleetController(router, lambda name: _StubReplica(name),
                          min_replicas=1, max_replicas=2,
                          backoff_base_s=0.01, trough_s=1e9)
    now = time.perf_counter()
    reps[0]._ready = False
    ctl.step(now=now)
    ctl.step(now=now + 1.0)
    assert observe.get_counter('controller.postmortems_total',
                               route='serve', lineage='r0') == 0
    heal = [e for e in observe.flight_recorder().events()
            if e['kind'] == 'controller_heal'][-1]
    assert heal['data']['postmortem_reason'] is None
    assert heal['data']['postmortem_events'] == 0
    ctl.close()
    router.close()


def test_load_postmortem_rejects_non_postmortems(tmp_path):
    from paddle_tpu.observe.flight import load_postmortem
    assert load_postmortem(str(tmp_path / 'missing.json')) is None
    bad = tmp_path / 'bad.json'
    bad.write_text('{not json')
    assert load_postmortem(str(bad)) is None
    wrong = tmp_path / 'wrong.json'
    wrong.write_text(json.dumps({'kind': 'something_else'}))
    assert load_postmortem(str(wrong)) is None
    good = tmp_path / 'good.json'
    good.write_text(json.dumps(_postmortem_doc()))
    assert load_postmortem(str(good))['reason'] == 'heartbeat_snapshot'


def test_flight_postmortem_string_host_survives():
    # fleet workers stamp PADDLE_TPU_OBSERVE_HOST with a replica-name
    # STRING; the postmortem doc must not die in int(host)
    from paddle_tpu.observe.flight import FlightRecorder
    fr = FlightRecorder(capacity=4)
    fr.record('x')
    doc = fr.postmortem('test', host='r0')
    assert doc['host'] == 'r0'
    assert fr.postmortem('test', host=3)['host'] == 3
    assert fr.postmortem('test')['host'] == 0


# --------------------------------------------- real worker process tests
def _chaos_model():
    from chaos import save_chaos_model
    return save_chaos_model(4)


def test_worker_cross_process_trace_and_clock(tmp_path):
    """ONE spawn, the whole tentpole: a sampled request's trace context
    crosses the RPC hop (controller rpc_admission + worker rpc_execute
    under ONE trace_id, flow-linked), ready() piggybacks the /clockz
    exchange, the federated /tracez returns the merged cross-process
    timeline, and tools/fleet_trace.py merges the two span exports into
    one Perfetto doc with offsets applied."""
    observe.enable()
    fac = ProcessReplicaFactory(
        {'kind': 'serving', 'model_dir': _chaos_model(),
         'backend': 'cpu',
         'engine': {'max_batch_size': 2, 'max_queue_depth': 4}},
        workdir=str(tmp_path), spawn_timeout_s=120.0,
        heartbeat_timeout_s=1.0)
    rep = fac.create('w0')
    try:
        assert rep.ready()
        assert rep.clock_offset() is not None   # synced on the probe
        assert abs(rep.clock_offset()) < 5.0    # same machine
        ctx = reqtrace.new_context('rpc', sample=1.0)
        out = rep.submit({'x': np.ones((1, 4), np.float32)},
                         ctx=ctx).result(30)
        assert np.asarray(out[0]).shape[0] == 1
        # controller-side spans landed under the trace id
        local = [e for e in observe.spans().events()
                 if (e.get('args') or {}).get('trace_id')
                 == ctx.trace_id]
        assert any(e['name'] == 'rpc_admission' for e in local)
        # the flow arrow starts on our side with flow id = trace id
        fid = int(ctx.trace_id, 16)
        assert any(e.get('id') == fid and e.get('ph') == 's'
                   for e in observe.spans().events())
        # federated /tracez (factory registered w0 with the fleet):
        # the worker's rpc_execute arrives tagged with its name
        srv = observe.serve(port=0)
        deadline = time.time() + 15
        wspans = []
        while time.time() < deadline:
            doc = http_get_json('%s/tracez?trace_id=%s'
                                % (srv.url, ctx.trace_id))
            wspans = [e for e in doc['spans']
                      if (e.get('args') or {}).get('replica') == 'w0']
            if any(e.get('name') == 'rpc_execute' for e in wspans):
                break
            time.sleep(0.2)
        assert any(e.get('name') == 'rpc_execute' for e in wspans)
        assert doc['sources']['w0']['ok'] is True
        clock_off = rep.clock_offset()
    finally:
        rep.shutdown(drain=True)
        fac.close()
    assert rep.proc.poll() is not None
    # the worker exported its span recorder on exit (trace_json wired
    # by the factory); merge both processes into one Perfetto doc
    worker_trace = tmp_path / 'w0.trace.json'
    deadline = time.time() + 15
    while not worker_trace.exists() and time.time() < deadline:
        time.sleep(0.1)
    assert worker_trace.exists()
    wdoc = json.loads(worker_trace.read_text())
    fleet_trace = _fleet_trace_mod()
    merged = fleet_trace.merge_traces([
        ('controller', observe.spans().events(), 0.0),
        ('w0', fleet_trace.load_trace_events(wdoc), clock_off or 0.0)])
    traced = [e for e in merged['traceEvents']
              if (e.get('args') or {}).get('trace_id') == ctx.trace_id]
    # spans from BOTH processes share the one trace id...
    assert len({e['pid'] for e in traced}) == 2
    # ...linked by flow events sharing the trace-id-derived flow id
    flow_phs = {e['ph'] for e in merged['traceEvents']
                if e.get('id') == fid}
    assert 's' in flow_phs and flow_phs & {'t', 'f'}
    # and the worker labeled its own track at boot
    assert any(e.get('ph') == 'M'
               and (e.get('args') or {}).get('name') == 'w0'
               for e in wdoc['traceEvents'])


def test_worker_sigkill_leaves_postmortem(tmp_path):
    """Chaos kill: SIGKILL runs no handler, but the worker's periodic
    heartbeat snapshot already left a controller-known postmortem;
    RemoteReplica.postmortem() reads the dead worker's final seconds."""
    observe.enable()
    fac = ProcessReplicaFactory(
        {'kind': 'serving', 'model_dir': _chaos_model(),
         'backend': 'cpu', 'postmortem_snapshot_s': 0.2,
         'engine': {'max_batch_size': 2, 'max_queue_depth': 4}},
        workdir=str(tmp_path), spawn_timeout_s=120.0,
        heartbeat_timeout_s=1.0)
    rep = fac.create('v0')
    try:
        assert rep.ready()
        pm_path = tmp_path / 'v0.flight.json'
        assert str(pm_path) == rep.postmortem_path
        deadline = time.time() + 30
        while not pm_path.exists() and time.time() < deadline:
            time.sleep(0.05)
        assert pm_path.exists()      # first heartbeat snapshot landed
        os.kill(rep.pid, signal.SIGKILL)
        rep.proc.wait(timeout=10)
        pm = rep.postmortem()
        assert pm is not None
        assert pm['kind'] == 'paddle_tpu_postmortem'
        assert pm['reason'] == 'heartbeat_snapshot'
        assert pm['host'] == 'v0'    # string host survived the dump
        assert pm['pid'] == rep.pid
    finally:
        rep.shutdown(drain=False)
        fac.close()
