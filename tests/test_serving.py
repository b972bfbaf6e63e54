"""paddle_tpu.serving: shape-bucket ladder math, the micro-batching
engine under concurrency (bit-identical to sequential Predictor.predict,
zero executor cache misses after warmup), QueueFullError backpressure,
drain/shutdown semantics, and the thread-safe executor cache."""

import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.serving import (BucketLadder, EngineClosedError,
                                QueueFullError, ServingEngine,
                                pow2_ladder)


@pytest.fixture(autouse=True)
def _observe_clean():
    from paddle_tpu import observe
    yield
    observe._SINK['path'] = None
    observe._SINK['trace_path'] = None
    observe.disable()
    observe.reset()


def _total(counters, prefix):
    return sum(v for k, v in counters.items() if k.startswith(prefix))


def _save_mlp(dirname):
    x = fluid.layers.data(name='x', shape=[6], dtype='float32')
    h = fluid.layers.fc(input=x, size=8, act='relu')
    out = fluid.layers.fc(input=h, size=3, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(dirname, ['x'], [out], exe)
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    return dirname


# ------------------------------------------------------------- buckets
def test_pow2_ladder_and_rung_lookup():
    assert pow2_ladder(8) == [1, 2, 4, 8]
    assert pow2_ladder(6) == [1, 2, 4, 6]   # non-pow2 cap is the top rung
    assert pow2_ladder(1) == [1]
    with pytest.raises(ValueError):
        pow2_ladder(0)

    lad = BucketLadder(8)
    assert lad.bucket_batch(1) == 1
    assert lad.bucket_batch(3) == 4
    assert lad.bucket_batch(8) == 8
    with pytest.raises(ValueError):
        lad.bucket_batch(9)
    assert lad.signatures() == [(1, None), (2, None), (4, None),
                                (8, None)]

    seq = BucketLadder(4, seq_axes={'ids': 1}, seq_lens=[16, 64])
    assert seq.bucket_seq(5) == 16
    assert seq.bucket_seq(64) == 64
    with pytest.raises(ValueError):
        seq.bucket_seq(65)
    assert len(seq.signatures()) == 3 * 2   # batch rungs x seq rungs


def test_assemble_pads_and_disassemble_unpads():
    lad = BucketLadder(8)
    feeds = [{'x': np.arange(6, dtype='float32').reshape(2, 3)},
             {'x': 10 + np.arange(9, dtype='float32').reshape(3, 3)}]
    padded, info = lad.assemble(feeds)
    assert padded['x'].shape == (8, 3)     # 5 rows -> rung 8
    assert info.sizes == [2, 3] and info.total == 5
    # edge padding replicates the last real row
    np.testing.assert_array_equal(padded['x'][5], padded['x'][4])
    assert abs(info.waste() - 3.0 / 8.0) < 1e-9
    np.testing.assert_array_equal(info.batch_mask(),
                                  [1, 1, 1, 1, 1, 0, 0, 0])

    fetch = padded['x'] * 2.0               # row-aligned fake result
    outs = lad.disassemble([fetch], info)
    assert len(outs) == 2
    np.testing.assert_array_equal(outs[0][0], feeds[0]['x'] * 2.0)
    np.testing.assert_array_equal(outs[1][0], feeds[1]['x'] * 2.0)


def test_assemble_seq_buckets_and_token_mask():
    lad = BucketLadder(4, seq_axes={'x': 1}, seq_lens=[4, 8], pad='zero')
    feeds = [{'x': np.ones((1, 3, 2), 'float32')},
             {'x': np.ones((2, 6, 2), 'float32')}]
    padded, info = lad.assemble(feeds)
    assert padded['x'].shape == (4, 8, 2)   # 3 rows -> 4, seq 6 -> 8
    assert info.seq_sizes == [3, 6] and info.seq_bucket == 8
    mask = info.token_mask()
    assert mask.shape == (4, 8)
    assert mask[0, :3].all() and not mask[0, 3:].any()   # req 0: len 3
    assert mask[1, :6].all() and not mask[2, 6:].any()   # req 1: len 6
    assert not mask[3].any()                             # padding row
    # element-level waste: real = 1*3*1 + 2*6*1 of 4*8
    assert abs(info.waste() - (1.0 - 15.0 / 32.0)) < 1e-9
    # per-request seq un-padding
    outs = lad.disassemble([padded['x']], info, fetch_seq_axes={0: 1})
    assert outs[0][0].shape == (1, 3, 2)
    assert outs[1][0].shape == (2, 6, 2)


def test_assemble_validation():
    lad = BucketLadder(4)
    with pytest.raises(ValueError):
        lad.assemble([])
    with pytest.raises(ValueError):    # inconsistent rows in one request
        lad.rows_of({'a': np.zeros((2, 3)), 'b': np.zeros((3, 3))})
    with pytest.raises(ValueError):    # feed-name mismatch across reqs
        lad.assemble([{'a': np.zeros((1, 2))}, {'b': np.zeros((1, 2))}])
    with pytest.raises(ValueError):
        BucketLadder(4, seq_axes={'a': 1})   # seq_axes without seq_lens


# -------------------------------------------------------------- engine
def test_engine_concurrent_matches_sequential(tmp_path):
    """Acceptance: N threads x mixed batch sizes through the engine ==
    sequential Predictor.predict within float rounding; with warmup, live
    traffic causes ZERO executor cache misses; compiles == warmup
    signatures."""
    from paddle_tpu import observe
    from paddle_tpu.inference import create_predictor

    d = _save_mlp(str(tmp_path / 'm'))
    rng = np.random.RandomState(0)
    sizes = [1, 3, 2, 4, 1, 2, 3, 4, 1, 2, 2, 1]
    reqs = [{'x': rng.rand(n, 6).astype('float32')} for n in sizes]

    seq_pred = create_predictor(d, place=fluid.CPUPlace())
    expected = [seq_pred.predict(r) for r in reqs]

    eng_pred = create_predictor(d, place=fluid.CPUPlace())
    observe.enable()
    observe.reset()
    eng = ServingEngine(eng_pred, max_batch_size=4, batch_timeout_ms=5,
                        max_queue_depth=64)
    nsig = eng.warmup()
    assert nsig == 3               # rungs [1, 2, 4]
    miss_warm = _total(observe.snapshot()['counters'],
                       'executor.cache_miss_total')
    assert miss_warm == nsig       # warmup compiled exactly the ladder

    eng.start()
    results = [None] * len(reqs)

    def client(i):
        results[i] = eng.predict(reqs[i], timeout=60)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.shutdown()

    snap = observe.snapshot()
    assert _total(snap['counters'], 'executor.cache_miss_total') == \
        miss_warm, 'live traffic recompiled despite warmup'
    assert _total(snap['counters'], 'executor.cache_hit_total') >= 1
    assert snap['counters'].get('serving.requests_total') == len(reqs)
    assert snap['histograms']['serving.batch_size']['count'] >= 1
    assert snap['histograms']['serving.padding_waste']['count'] >= 1
    for h in ('serving.queue_seconds', 'serving.compute_seconds',
              'serving.request_seconds'):
        assert any(k.startswith(h) for k in snap['histograms']), h
    assert 'serving.queue_depth' in snap['gauges']

    # a request padded up the batch ladder runs a different compiled
    # shape than the sequential predict, so XLA:CPU may reduce in
    # another order: equal within float rounding, not bit for bit
    for i in range(len(reqs)):
        np.testing.assert_allclose(
            np.asarray(results[i][0]), np.asarray(expected[i][0]),
            rtol=1e-6,
            err_msg='request %d (batch %d) diverged from sequential '
                    'predict' % (i, sizes[i]))


def test_engine_seq_buckets_mask_feed(tmp_path):
    """Sequence bucketing end-to-end: variable-length requests pad up
    the (batch, seq) ladder, the engine-generated token mask keeps the
    masked reduction exact, and per-position fetches un-pad to each
    request's real length."""
    from paddle_tpu import observe
    from paddle_tpu.inference import create_predictor

    x = fluid.layers.data(name='x', shape=[-1, 2], dtype='float32')
    m = fluid.layers.data(name='m', shape=[-1], dtype='float32')
    y = fluid.layers.scale(x, scale=2.0, bias=1.0)          # [B, T, 2]
    mm = fluid.layers.unsqueeze(m, axes=[2])
    s = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(x, mm),
                                dim=1)                      # [B, 2]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / 'seq')
    fluid.io.save_inference_model(d, ['x', 'm'], [y, s], exe)
    fluid.reset_default_programs()
    fluid.global_scope().clear()

    rng = np.random.RandomState(1)
    shapes = [(1, 3), (2, 5), (3, 8), (1, 6), (4, 2), (2, 7)]
    reqs = [{'x': rng.rand(n, t, 2).astype('float32')}
            for n, t in shapes]

    seq_pred = create_predictor(d, place=fluid.CPUPlace())
    expected = []
    for (n, t), r in zip(shapes, reqs):
        expected.append(seq_pred.predict(
            dict(r, m=np.ones((n, t), 'float32'))))

    observe.enable()
    observe.reset()
    eng_pred = create_predictor(d, place=fluid.CPUPlace())
    eng = ServingEngine(eng_pred, max_batch_size=4, batch_timeout_ms=5,
                        seq_axes={'x': 1}, seq_lens=[4, 8],
                        mask_feed='m', fetch_seq_axes={0: 1})
    nsig = eng.warmup()
    assert nsig == 3 * 2
    miss_warm = _total(observe.snapshot()['counters'],
                       'executor.cache_miss_total')
    eng.start()

    results = [None] * len(reqs)

    def client(i):
        results[i] = eng.predict(reqs[i], timeout=60)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.shutdown()

    assert _total(observe.snapshot()['counters'],
                  'executor.cache_miss_total') == miss_warm
    for i, (n, t) in enumerate(shapes):
        assert np.asarray(results[i][0]).shape == (n, t, 2)
        for j in range(2):
            np.testing.assert_array_equal(np.asarray(results[i][j]),
                                          np.asarray(expected[i][j]))
    # the engine owns the mask: supplying it is an error
    with pytest.raises(ValueError):
        eng_pred2 = create_predictor(d, place=fluid.CPUPlace())
        eng2 = ServingEngine(eng_pred2, max_batch_size=4,
                             seq_axes={'x': 1}, seq_lens=[4, 8],
                             mask_feed='m')
        eng2.submit({'x': np.zeros((1, 4, 2), 'float32'),
                     'm': np.ones((1, 4), 'float32')})


def test_engine_queue_full_fast_fail(tmp_path):
    """Over-capacity submits fail fast with QueueFullError instead of
    blocking; once the workers start, everything queued completes."""
    from paddle_tpu.inference import create_predictor

    d = _save_mlp(str(tmp_path / 'm'))
    pred = create_predictor(d, place=fluid.CPUPlace())
    eng = ServingEngine(pred, max_batch_size=2, batch_timeout_ms=1,
                        max_queue_depth=3)
    feeds = [{'x': np.full((1, 6), float(i), 'float32')}
             for i in range(4)]
    futs = [eng.submit(feeds[i]) for i in range(3)]   # engine not started
    t0 = time.perf_counter()
    with pytest.raises(QueueFullError):
        eng.submit(feeds[3])
    assert time.perf_counter() - t0 < 1.0   # fail-fast, not a block
    eng.warmup()
    eng.start()
    outs = [f.result(timeout=60) for f in futs]
    assert all(np.asarray(o[0]).shape == (1, 3) for o in outs)
    eng.shutdown()


def test_engine_shutdown_and_drain(tmp_path):
    from paddle_tpu.inference import create_predictor

    d = _save_mlp(str(tmp_path / 'm'))
    pred = create_predictor(d, place=fluid.CPUPlace())
    eng = ServingEngine(pred, max_batch_size=4, batch_timeout_ms=1)
    eng.warmup()
    eng.start()
    futs = [eng.submit({'x': np.zeros((2, 6), 'float32')})
            for _ in range(5)]
    eng.shutdown(drain=True, timeout=60)     # completes accepted work
    assert all(f.done() and f.exception() is None for f in futs)
    with pytest.raises(EngineClosedError):
        eng.submit({'x': np.zeros((1, 6), 'float32')})

    # non-draining shutdown on a never-started engine fails its queue
    pred2 = create_predictor(d, place=fluid.CPUPlace())
    eng2 = ServingEngine(pred2, max_batch_size=4)
    f2 = eng2.submit({'x': np.zeros((1, 6), 'float32')})
    eng2.shutdown(drain=False)
    assert isinstance(f2.exception(timeout=5), EngineClosedError)


def test_engine_rejects_malformed_submits(tmp_path):
    from paddle_tpu.inference import create_predictor

    d = _save_mlp(str(tmp_path / 'm'))
    pred = create_predictor(d, place=fluid.CPUPlace())
    eng = ServingEngine(pred, max_batch_size=4)
    with pytest.raises(ValueError):          # missing feed
        eng.submit({})
    with pytest.raises(ValueError):          # unknown feed name
        eng.submit({'x': np.zeros((1, 6), 'float32'),
                    'bogus': np.zeros((1,), 'float32')})
    with pytest.raises(ValueError):          # oversize request
        eng.submit({'x': np.zeros((5, 6), 'float32')})
    eng.shutdown(drain=False)


# ---------------------------------------------------- executor threading
def test_executor_concurrent_same_key_compiles_once():
    """Satellite: racing threads on one (program, shapes) key must
    produce exactly ONE compile (per-key lock), and last_cache_miss is
    per-thread."""
    from paddle_tpu import observe

    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    out = fluid.layers.fc(input=x, size=2, act=None)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    observe.enable()
    observe.reset()
    feed = {'x': np.ones((3, 4), 'float32')}
    n_threads, outs, errs = 8, [None] * 8, []

    def worker(i):
        try:
            outs[i] = exe.run(feed=feed, fetch_list=[out])[0]
        except BaseException as e:   # pragma: no cover - diagnostic
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    misses = _total(observe.snapshot()['counters'],
                    'executor.cache_miss_total')
    assert misses == 1, 'duplicate compile under a same-key race'
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


# ------------------------------------------------------------ satellites
def test_predictor_rejects_unknown_feeds(tmp_path):
    from paddle_tpu.inference import create_predictor

    d = _save_mlp(str(tmp_path / 'm'))
    pred = create_predictor(d, place=fluid.CPUPlace())
    with pytest.raises(ValueError, match='unexpected feed'):
        pred.predict({'x': np.zeros((1, 6), 'float32'),
                      'typo': np.zeros((1, 6), 'float32')})
    specs = pred.feed_specs()
    assert set(specs) == {'x'}
    shape, dtype = specs['x']
    assert shape == (-1, 6) and dtype == 'float32'


def test_save_inference_model_atomic(tmp_path, monkeypatch):
    """A failed model dump must not clobber the existing __model__.json
    (unique tmp + os.replace, like checkpoints)."""
    import paddle_tpu.io as pio

    d = _save_mlp(str(tmp_path / 'm'))
    before = open(os.path.join(d, '__model__.json')).read()
    json.loads(before)

    x = fluid.layers.data(name='x', shape=[6], dtype='float32')
    out = fluid.layers.fc(input=x, size=3, act=None)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    class _Boom(Exception):
        pass

    real_dumps = pio.json.dumps

    def boom(*a, **k):
        raise _Boom()

    monkeypatch.setattr(pio.json, 'dumps', boom)
    with pytest.raises(_Boom):
        pio.save_inference_model(d, ['x'], [out], exe)
    monkeypatch.setattr(pio.json, 'dumps', real_dumps)

    assert open(os.path.join(d, '__model__.json')).read() == before
    leftovers = [f for f in os.listdir(d)
                 if f.startswith('__model__.json.')]
    assert leftovers == [], 'torn tmp files left behind: %s' % leftovers


# ------------------------------------------------------------------ soak
@pytest.mark.slow
def test_engine_soak_mixed_sizes(tmp_path):
    """Soak: sustained mixed-size traffic from many threads stays
    bit-identical and never recompiles."""
    from paddle_tpu import observe
    from paddle_tpu.inference import create_predictor

    d = _save_mlp(str(tmp_path / 'm'))
    seq_pred = create_predictor(d, place=fluid.CPUPlace())
    # pre-warm the sequential oracle over every size it will see, so
    # the zero-miss assertion below measures ONLY the engine's compiles
    for n in range(1, 9):
        seq_pred.predict({'x': np.zeros((n, 6), 'float32')})
    eng_pred = create_predictor(d, place=fluid.CPUPlace())
    observe.enable()
    observe.reset()
    eng = ServingEngine(eng_pred, max_batch_size=8, batch_timeout_ms=2,
                        max_queue_depth=256)
    nsig = eng.warmup()
    miss_warm = _total(observe.snapshot()['counters'],
                       'executor.cache_miss_total')
    assert miss_warm == nsig
    eng.start()

    n_threads, per_thread = 8, 40
    errs = []

    def client(tid):
        rng = np.random.RandomState(tid)
        try:
            for k in range(per_thread):
                n = int(rng.randint(1, 9))
                feed = {'x': rng.rand(n, 6).astype('float32')}
                got = eng.predict(feed, timeout=60)
                want = seq_pred.predict(feed)
                np.testing.assert_array_equal(np.asarray(got[0]),
                                              np.asarray(want[0]))
                if k % 7 == 0:
                    time.sleep(0.001)
        except BaseException as e:
            errs.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.shutdown()
    assert not errs, errs[:1]
    snap = observe.snapshot()
    assert _total(snap['counters'], 'executor.cache_miss_total') == \
        miss_warm
    assert snap['counters'].get('serving.requests_total') == \
        n_threads * per_thread
