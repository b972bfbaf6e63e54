"""Cross-host control plane (ISSUE 16): RPC wire framing, the
RemoteReplica engine proxy (typed sync admission errors, bounded
backoff reconnect, heartbeat ready()), mid-stream death settling
futures typed (never hanging), drain-before-shutdown-ack, networked
KV handoff (sha1 ON by default on sockets, wire corruption refused
with zero leaked pages, dedup preserved), fault.inject.kill_process,
worker-process spawn via ProcessReplicaFactory, and the merged
multi-process metrics report."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.serving import (EngineClosedError, HandoffError,
                                KVPacket, QueueFullError,
                                RemoteCallError, RemoteReplica,
                                RemoteReplicaError, ServingEngine,
                                serve_engine)
from paddle_tpu.serving import handoff as handoff_mod
from paddle_tpu.serving.rpc import (ProcessReplicaFactory, pack_arrays,
                                    unpack_arrays)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _observe_clean():
    yield
    observe._SINK['path'] = None
    observe._SINK['trace_path'] = None
    observe.stop_serving()
    observe.disable()
    observe.reset()


class _Pred(object):
    """Duck predictor: doubles its input; optional compute delay."""

    feed_names = ['x']

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s

    def feed_specs(self):
        return {'x': ((-1, 3), 'float32')}

    def predict(self, feed):
        if self.delay_s:
            time.sleep(self.delay_s)
        return [np.asarray(feed['x']) * 2.0]


def _engine(name='eng0', delay_s=0.0, **kw):
    kw.setdefault('max_batch_size', 4)
    kw.setdefault('batch_timeout_ms', 1.0)
    kw.setdefault('max_queue_depth', 8)
    eng = ServingEngine(_Pred(delay_s), name=name, **kw)
    eng.warmup()
    eng.start()
    return eng


def _served(eng):
    """Bind ``eng`` onto a live diagnostics server; returns
    (url, binding)."""
    srv = observe.serve(port=0)
    binding = serve_engine(eng)
    return srv.url, binding


# ---------------------------------------------------------- wire frame
def test_pack_arrays_roundtrip_with_bf16():
    import jax.numpy as jnp
    arrays = {'a': np.arange(6, dtype=np.float32).reshape(2, 3),
              'b': np.asarray([1, 2, 3], dtype=np.int64),
              'c': np.asarray([0.5, -1.25], dtype=jnp.bfloat16)}
    meta, back = unpack_arrays(pack_arrays({'k': 'v', 'n': 3}, arrays))
    assert meta == {'k': 'v', 'n': 3}
    assert set(back) == set(arrays)
    for name in arrays:
        a, b = np.asarray(arrays[name]), np.asarray(back[name])
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def test_unpack_rejects_garbage_and_truncation():
    with pytest.raises(RemoteReplicaError):
        unpack_arrays(b'NOPE' + b'\x00' * 16)
    wire = pack_arrays({}, {'a': np.ones((4, 4), np.float32)})
    with pytest.raises(RemoteReplicaError):
        unpack_arrays(wire[:-7])    # worker died mid-write


# ------------------------------------------------- loopback RPC engine
def test_remote_submit_parity_and_state():
    eng = _engine('par0')
    url, binding = _served(eng)
    rep = RemoteReplica(url, name='par0')
    try:
        assert rep.ready()
        x = np.random.RandomState(0).rand(2, 3).astype('float32')
        remote = rep.submit({'x': x}).result(15)
        local = eng.predict({'x': x}, timeout=15)
        assert np.asarray(remote[0]).tobytes() == \
            np.asarray(local[0]).tobytes()
        assert rep.queue_depth() == 0
        # name travels over /rpc/state
        assert rep._state().get('name') == 'par0'
    finally:
        binding.close()
        eng.shutdown()


def test_remote_admission_errors_raise_sync_and_typed():
    """The Router sync-error contract survives the wire: bad feeds and
    queue-full raise the SAME class, synchronously, from submit() —
    and neither is an EngineClosedError (no bogus failover)."""
    eng = _engine('adm0', delay_s=0.2, max_queue_depth=1,
                  dispatch_depth=1)
    url, binding = _served(eng)
    rep = RemoteReplica(url, name='adm0')
    try:
        with pytest.raises(ValueError) as ei:
            rep.submit({'bogus': np.ones((1, 3), np.float32)})
        assert not isinstance(ei.value, EngineClosedError)
        # saturate: 1 computing + 1 queued, then typed backpressure
        futs = [rep.submit({'x': np.ones((1, 3), np.float32)})
                for _ in range(2)]
        with pytest.raises(QueueFullError):
            for _ in range(8):
                futs.append(
                    rep.submit({'x': np.ones((1, 3), np.float32)}))
        for f in futs:
            f.result(15)
    finally:
        binding.close()
        eng.shutdown()


def test_unknown_remote_error_is_not_engine_closed():
    """A worker-side exception type the client can't map must become
    RemoteCallError (plain RuntimeError) — an application bug must
    fail the request, never masquerade as a dead replica."""
    from paddle_tpu.serving.rpc import _raise_remote
    payload = json.dumps({'error': {'type': 'SomeWeirdError',
                                    'message': 'boom'}}).encode()
    with pytest.raises(RemoteCallError) as ei:
        _raise_remote(payload, 500)
    assert not isinstance(ei.value, EngineClosedError)
    with pytest.raises(QueueFullError):
        _raise_remote(json.dumps(
            {'error': {'type': 'QueueFullError',
                       'message': 'full'}}).encode(), 429)


def test_connect_refused_backoff_then_typed():
    """Satellite: connect timeout -> bounded exponential backoff ->
    EngineClosedError subclass. The injectable sleep records the
    schedule; nothing real is slept."""
    sock = socket.socket()
    sock.bind(('127.0.0.1', 0))
    port = sock.getsockname()[1]
    sock.close()                     # nobody listening here
    sleeps = []
    rep = RemoteReplica('http://127.0.0.1:%d' % port, name='ghost',
                        reconnect_tries=4, backoff_base_s=0.05,
                        backoff_max_s=0.15, sleep=sleeps.append)
    with pytest.raises(EngineClosedError) as ei:
        rep.submit({'x': np.ones((1, 3), np.float32)})
    assert isinstance(ei.value, RemoteReplicaError)
    # 4 attempts -> 3 backoffs: base * 2^i capped at max
    assert sleeps == [0.05, 0.1, 0.15]
    assert rep.ready() is False      # heartbeat shares the verdict


def test_midstream_death_settles_future_typed_never_hangs():
    """Satellite: the SIGKILL wire shape — the worker acks admission
    then the connection dies before the body. The future must settle
    with an EngineClosedError subclass, not hang."""
    srv = socket.socket()
    srv.bind(('127.0.0.1', 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def half_server():
        conn, _ = srv.accept()
        conn.recv(65536)             # the POST (enough of it)
        conn.sendall(b'HTTP/1.1 200 OK\r\n'
                     b'Content-Type: application/octet-stream\r\n'
                     b'Connection: close\r\n\r\n')
        time.sleep(0.05)
        conn.close()                 # death before any result bytes

    t = threading.Thread(target=half_server, daemon=True)
    t.start()
    rep = RemoteReplica('http://127.0.0.1:%d' % port, name='victim')
    fut = rep.submit({'x': np.ones((1, 3), np.float32)})
    with pytest.raises(EngineClosedError):
        fut.result(10)
    t.join(timeout=5)
    srv.close()


def test_midstream_death_settles_generate_stream_typed():
    srv = socket.socket()
    srv.bind(('127.0.0.1', 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def half_server():
        import struct as _struct
        conn, _ = srv.accept()
        conn.recv(65536)
        frame = json.dumps({'token': 7}).encode()
        conn.sendall(b'HTTP/1.1 200 OK\r\n'
                     b'Content-Type: application/octet-stream\r\n'
                     b'Connection: close\r\n\r\n'
                     + _struct.pack('<I', len(frame)) + frame)
        time.sleep(0.05)
        conn.close()                 # dies mid-stream, one token in

    t = threading.Thread(target=half_server, daemon=True)
    t.start()
    rep = RemoteReplica('http://127.0.0.1:%d' % port, name='victim',
                        kind='decode')
    stream = rep.submit([1, 2, 3], max_new_tokens=4)
    toks = [tok for tok in stream]   # terminates — never hangs
    assert toks == [7]
    with pytest.raises(EngineClosedError):
        stream.result(10)
    assert stream.finish_reason == 'error'
    t.join(timeout=5)
    srv.close()


def test_drain_completes_accepted_work_before_shutdown_ack():
    """Satellite: every request accepted before shutdown(drain=True)
    must resolve OK before the ack comes back."""
    eng = _engine('drain0', delay_s=0.05, max_queue_depth=16)
    url, binding = _served(eng)
    rep = RemoteReplica(url, name='drain0')
    try:
        futs = [rep.submit({'x': np.ones((1, 3), np.float32)})
                for _ in range(4)]
        rep.shutdown(drain=True)     # blocks until the worker drained
        for f in futs:
            out = f.result(5)        # already computed: no new work
            assert np.asarray(out[0]).shape == (1, 3)
        assert rep.ready() is False
    finally:
        binding.close()
        eng.shutdown()


# ----------------------------------------------------- KV over the wire
SPEC = None
WEIGHTS = None


def _decode_engine(name, **kw):
    global SPEC, WEIGHTS
    from paddle_tpu.serving.decode import (DecodeEngine, LMSpec,
                                           random_weights)
    if SPEC is None:
        SPEC = LMSpec(vocab_size=60, n_layer=2, n_head=2, d_key=8,
                      d_value=8, d_model=16, d_inner=32)
        WEIGHTS = random_weights(SPEC, seed=3)
    kw.setdefault('max_batch', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('num_blocks', 64)
    kw.setdefault('pages_per_seq', 8)
    kw.setdefault('weights', WEIGHTS)
    kw.setdefault('place', fluid.CPUPlace())
    kw.setdefault('prefix_cache', True)
    eng = DecodeEngine(SPEC, name=name, **kw)
    eng.warmup()
    eng.start()
    return eng


def test_handoff_verify_default_is_transport_dependent(monkeypatch):
    """Satellite: sha1 ON by default over sockets, opt-in in-process;
    the env knob still overrides both ways."""
    monkeypatch.delenv('PADDLE_TPU_HANDOFF_VERIFY', raising=False)
    assert handoff_mod.handoff_verify_enabled('socket') is True
    assert handoff_mod.handoff_verify_enabled('inproc') is False
    monkeypatch.setenv('PADDLE_TPU_HANDOFF_VERIFY', '0')
    assert handoff_mod.handoff_verify_enabled('socket') is False
    monkeypatch.setenv('PADDLE_TPU_HANDOFF_VERIFY', '1')
    assert handoff_mod.handoff_verify_enabled('inproc') is True


def test_networked_handoff_bit_identical_with_dedup(monkeypatch):
    """KVPacket over the RPC socket: same generated tokens as the
    in-process handoff, dedup-against-destination-cache preserved."""
    monkeypatch.delenv('PADDLE_TPU_HANDOFF_VERIFY', raising=False)
    src = _decode_engine('src0')
    dst = _decode_engine('dst0')
    ref = _decode_engine('ref0')
    url, binding = _served(dst)
    rep = RemoteReplica(url, name='dst0', kind='decode')
    prompt = [int(t) for t in
              np.random.RandomState(5).randint(0, 60, 12)]
    try:
        src.submit(prompt, max_new_tokens=1).result(30)
        covered = handoff_mod.handoff(src, rep, prompt)
        assert covered > 0
        stream = rep.submit(prompt, max_new_tokens=5, temperature=0.0,
                            seed=2)
        remote_toks = stream.result(30)
        # reference: plain in-process handoff to a third engine
        handoff_mod.handoff(src, ref, prompt)
        ref_toks = ref.submit(prompt, max_new_tokens=5,
                              temperature=0.0, seed=2).result(30)
        assert remote_toks == ref_toks
        # second shipment of the same prefix: destination cache dedups
        _, installed, dedup = rep.install_packet_bytes(
            handoff_mod.export_packet(src, prompt).to_bytes(
                transport='socket'))
        assert installed == 0 and dedup > 0
    finally:
        binding.close()
        for e in (src, dst, ref):
            e.shutdown()


def test_wire_corruption_refused_typed_no_leaked_pages(monkeypatch):
    """Satellite regression: flip ONE byte of the socket wire framing
    — the install must be a typed refusal (sha1 is ON by default for
    socket transport) and the decode pool must not leak a page."""
    monkeypatch.delenv('PADDLE_TPU_HANDOFF_VERIFY', raising=False)
    src = _decode_engine('csrc0')
    dst = _decode_engine('cdst0')
    url, binding = _served(dst)
    rep = RemoteReplica(url, name='cdst0', kind='decode')
    prompt = [int(t) for t in
              np.random.RandomState(9).randint(0, 60, 10)]
    try:
        src.submit(prompt, max_new_tokens=1).result(30)
        wire = bytearray(handoff_mod.export_packet(src, prompt)
                         .to_bytes(transport='socket'))
        assert b'sha1' in bytes(wire)   # stamped by DEFAULT on socket
        wire[-3] ^= 0x40                # one arena byte, bit-flipped
        free_before = dst.free_pages()
        with pytest.raises(HandoffError):
            rep.install_packet_bytes(bytes(wire))
        assert dst.free_pages() == free_before   # nothing leaked
        # and the sender-side wire is still installable untouched
        covered, installed, _ = rep.install_packet_bytes(
            handoff_mod.export_packet(src, prompt).to_bytes(
                transport='socket'))
        assert covered > 0 and installed > 0
    finally:
        binding.close()
        src.shutdown()
        dst.shutdown()


# --------------------------------------------------------- kill_process
def test_kill_process_signals_and_resolver_forms():
    from paddle_tpu.fault import inject
    proc = subprocess.Popen([sys.executable, '-c',
                             'import time; time.sleep(60)'])
    try:
        assert inject.kill_process(proc) == proc.pid
        assert proc.wait(timeout=10) == -signal.SIGKILL
        # a reaped corpse is no victim
        assert inject.kill_process(proc) is None
        # resolver form: None target means no kill (breaker engaged)
        assert inject.kill_process(lambda: None) is None
    finally:
        if proc.poll() is None:
            proc.kill()


# ------------------------------------------------- real worker process
def test_worker_subprocess_end_to_end(tmp_path):
    """ONE real spawn: ProcessReplicaFactory boots
    tools/replica_worker.py, /readyz flips over plain HTTP, submit
    round-trips, shutdown reaps the PID, and the worker's metrics
    JSONL landed beside the parent's with the replica name as host."""
    from chaos import save_chaos_model
    parent_jsonl = tmp_path / 'run.jsonl'
    observe.enable(jsonl=str(parent_jsonl))
    fac = ProcessReplicaFactory(
        {'kind': 'serving', 'model_dir': save_chaos_model(4),
         'backend': 'cpu',
         'engine': {'max_batch_size': 2, 'max_queue_depth': 4}},
        workdir=str(tmp_path), spawn_timeout_s=120.0,
        heartbeat_timeout_s=1.0)
    rep = fac.create('w0')
    try:
        pid = rep.pid
        assert pid is not None and rep.ready()
        out = rep.submit({'x': np.ones((1, 4), np.float32)}).result(30)
        assert np.asarray(out[0]).shape[0] == 1
        # the worker's sink landed beside the parent's
        worker_jsonl = tmp_path / 'run-w0.jsonl'
        deadline = time.time() + 10
        while not worker_jsonl.exists() and time.time() < deadline:
            time.sleep(0.1)
        assert worker_jsonl.exists()
    finally:
        rep.shutdown(drain=True)
        fac.close()
    assert rep.proc.poll() is not None      # reaped, no zombie
    recs = [json.loads(ln) for ln in
            worker_jsonl.read_text().splitlines() if ln.strip()]
    assert any(r.get('host') == 'w0' for r in recs)


def _http_readyz(url):
    """GET /readyz over plain HTTP: the status, or None when the TCP
    layer already says dead — the flip a real balancer sees."""
    import http.client
    host, _, port = url.split('://', 1)[-1].rpartition(':')
    try:
        conn = http.client.HTTPConnection(host, int(port), timeout=1.0)
        conn.request('GET', '/readyz')
        resp = conn.getresponse()
        resp.read()
        conn.close()
        return resp.status
    except Exception:
        return None


def test_worker_identical_bits_then_wedged_then_killed(tmp_path):
    """One real worker process through the cross-host faults, in
    counts and events: the same requests through the worker and
    through an in-process engine give byte-identical results; SIGSTOP
    (alive but wedged) is declared not ready by the heartbeat while the
    PID lives; SIGKILL to the live PID flips /readyz over plain HTTP
    and every later submit settles with the typed EngineClosedError,
    never hanging."""
    from chaos import save_chaos_model
    from paddle_tpu.fault import inject
    from paddle_tpu.inference import create_predictor
    model_dir = save_chaos_model(4)
    engine_kw = {'max_batch_size': 2, 'max_queue_depth': 4}
    fac = ProcessReplicaFactory(
        {'kind': 'serving', 'model_dir': model_dir, 'backend': 'cpu',
         'engine': engine_kw},
        workdir=str(tmp_path), spawn_timeout_s=120.0,
        heartbeat_timeout_s=0.5)
    rep = fac.create('v0')
    local = ServingEngine(create_predictor(model_dir), name='v-local',
                          **engine_kw)
    local.warmup()
    local.start()
    try:
        rng = np.random.RandomState(1234)
        for i in range(6):
            feed = {'x': rng.rand(i % 2 + 1, 4).astype('float32')}
            r_out = rep.submit(dict(feed)).result(30)
            l_out = local.submit(dict(feed)).result(30)
            for a, b in zip(r_out, l_out):
                a, b = np.asarray(a), np.asarray(b)
                assert a.dtype == b.dtype and a.shape == b.shape
                assert a.tobytes() == b.tobytes(), i
        assert _http_readyz(rep.url) == 200 and rep.ready()

        pid = inject.kill_process(rep, sig=signal.SIGSTOP)
        assert pid == rep.pid
        deadline = time.time() + 20
        while rep.ready() and time.time() < deadline:
            time.sleep(0.05)
        assert not rep.ready()              # by the heartbeat alone:
        assert rep.proc.poll() is None      # the PID is still alive

        assert inject.kill_process(rep) == pid      # a live PID died
        assert rep.proc.wait(timeout=10) == -signal.SIGKILL
        assert _http_readyz(rep.url) != 200
        assert not rep.ready()
        with pytest.raises(EngineClosedError):
            rep.submit({'x': np.ones((1, 4), np.float32)}).result(30)
        assert inject.kill_process(rep) is None     # no second victim
    finally:
        local.shutdown(drain=True)
        if rep.proc.poll() is None:
            rep.proc.kill()
        fac.close()


# ------------------------------------------ merged multi-process report
def _jsonl(path, records):
    with open(path, 'w') as f:
        for r in records:
            f.write(json.dumps(r) + '\n')


def test_metrics_report_fleet_merges_worker_processes(tmp_path, capsys):
    """Satellite: tools/metrics_report.py --fleet over a DIRECTORY of
    JSONLs (parent + per-worker sinks) renders one merged run with the
    per-replica census from child-emitted worker.* gauges."""
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    try:
        import metrics_report
    finally:
        sys.path.pop(0)
    _jsonl(tmp_path / 'run.jsonl', [
        {'ts': 10.0, 'kind': 'snapshot', 'pid': 1, 'host': 0,
         'counters': {'controller.heals_total{route=x}': 0},
         'gauges': {'controller.replicas{route=x,state=UP}': 2}},
        {'ts': 12.0, 'kind': 'summary', 'pid': 1, 'host': 0,
         'counters': {'controller.heals_total{route=x}': 1,
                      'controller.deaths_total{route=x}': 1},
         'gauges': {'controller.replicas{route=x,state=UP}': 2,
                    'controller.replica_state{replica=r0}': 0}},
    ])
    _jsonl(tmp_path / 'run-r0.jsonl', [
        {'ts': 10.5, 'kind': 'snapshot', 'pid': 101, 'host': 'r0',
         'counters': {},
         'gauges': {'worker.up{replica=r0}': 1,
                    'worker.ready{replica=r0}': 1,
                    'worker.queue_depth{replica=r0}': 3}},
    ])
    _jsonl(tmp_path / 'run-r1.jsonl', [
        {'ts': 11.0, 'kind': 'snapshot', 'pid': 102, 'host': 'r1',
         'counters': {},
         'gauges': {'worker.up{replica=r1}': 1,
                    'worker.ready{replica=r1}': 0,
                    'worker.queue_depth{replica=r1}': 0}},
    ])
    records = metrics_report.load_records(str(tmp_path))
    assert len(records) == 4
    assert [r['ts'] for r in records] == sorted(r['ts']
                                                for r in records)
    doc = metrics_report.derive_fleet(records)
    assert doc['workers'] == {
        'r0': {'pid': 101, 'up': 1, 'ready': 1, 'queue_depth': 3},
        'r1': {'pid': 102, 'up': 1, 'ready': 0, 'queue_depth': 0}}
    text = metrics_report.render_fleet(records)
    assert 'worker processes' in text
    assert 'r0' in text and 'r1' in text
    # the CLI path: --fleet over the directory
    rc = metrics_report.main([str(tmp_path), '--fleet'])
    assert rc == 0
    assert 'worker processes' in capsys.readouterr().out
