"""Decode serving: KV pool alloc/free/refcount + exhaustion, ragged
paged attention vs a dense masked reference across mixed lengths, and
the continuous-batching e2e — concurrent mixed-length generation
bit-identical to sequential single-request decode, zero executor cache
misses after warmup, KV pages fully reclaimed after drain, preemption
(evict-and-requeue) preserving streams."""

import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.serving import QueueFullError, EngineClosedError
from paddle_tpu.serving.decode import (BlockTable, DecodeEngine, KVPool,
                                       LMSpec, random_weights)


SPEC = LMSpec(vocab_size=60, n_layer=2, n_head=2, d_key=8, d_value=8,
              d_model=16, d_inner=32)
WEIGHTS = random_weights(SPEC, seed=3)


@pytest.fixture(autouse=True)
def _observe_clean():
    from paddle_tpu import observe
    yield
    observe._SINK['path'] = None
    observe._SINK['trace_path'] = None
    observe.disable()
    observe.reset()


def _engine(**kw):
    kw.setdefault('max_batch', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('num_blocks', 64)
    kw.setdefault('pages_per_seq', 4)
    kw.setdefault('weights', WEIGHTS)
    kw.setdefault('place', fluid.CPUPlace())
    return DecodeEngine(SPEC, **kw)


def _mixed_requests(n=6, seed=0, vocab=60):
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.randint(1, 10))
        reqs.append(dict(prompt_ids=rng.randint(0, vocab, plen).tolist(),
                         max_new_tokens=int(rng.randint(3, 7)),
                         temperature=0.0 if i % 2 == 0 else 0.7,
                         seed=100 + i))
    return reqs


def _misses(snap):
    return sum(v for k, v in snap['counters'].items()
               if k.startswith('executor.cache_miss_total'))


@pytest.fixture(scope='module')
def plain():
    """One engine of the default arguments, built once and started, for
    the two tests of a stream's own properties, which serve a few
    requests through it one at a time and leave it drained."""
    with _engine() as eng:
        yield eng


_SEQ_REF = {}


def _sequential_reference(seed):
    """Per-request sequential decode outputs (one fresh engine per
    request), cached per request-set — the bit-identity baseline shared
    by the continuous-batching and preemption e2es."""
    if seed not in _SEQ_REF:
        out = []
        for r in _mixed_requests(seed=seed):
            e = _engine()
            e.start()
            out.append(e.generate(timeout=120, **r))
            e.shutdown()
        _SEQ_REF[seed] = out
    return _SEQ_REF[seed]


# ------------------------------------------------------------- KV pool
def test_kv_pool_alloc_free_refcount():
    pool = KVPool(num_blocks=8, block_size=4)
    assert pool.free_blocks() == 8
    assert pool.blocks_for(0) == 0
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(4) == 1
    assert pool.blocks_for(5) == 2

    a = pool.alloc(3)
    assert len(a) == 3 and pool.free_blocks() == 5
    assert pool.alloc(6) is None          # exhaustion is None, not raise
    assert pool.free_blocks() == 5        # failed alloc takes nothing

    pool.incref(a)                        # shared prefix: two owners
    pool.free(a)
    assert pool.free_blocks() == 5        # still one owner
    pool.free(a)
    assert pool.free_blocks() == 8        # last owner returns the pages
    with pytest.raises(ValueError):
        pool.free(a)                      # double free detected


def test_kv_pool_grow_and_release():
    pool = KVPool(num_blocks=4, block_size=4)
    t = BlockTable()
    assert pool.grow(t, 1) and len(t) == 1
    assert pool.grow(t, 4) and len(t) == 1     # still fits page 0
    assert pool.grow(t, 5) and len(t) == 2
    assert pool.grow(t, 16) and len(t) == 4
    t2 = BlockTable()
    assert not pool.grow(t2, 1)                # exhausted
    pool.release(t)
    assert pool.free_blocks() == 4 and len(t) == 0
    assert pool.grow(t2, 16)


def test_kv_pool_fork_shares_pages():
    pool = KVPool(num_blocks=4, block_size=4)
    t = BlockTable()
    pool.grow(t, 8)
    f = pool.fork(t)
    assert f.block_ids == t.block_ids
    pool.release(t)
    assert pool.free_blocks() == 2             # fork still owns them
    pool.release(f)
    assert pool.free_blocks() == 4


# -------------------------------------------- ragged paged attention
def test_paged_attention_matches_dense_masked_reference():
    """XLA gather path vs reference_attention (dense keys + key_length
    mask) across mixed lengths: gathering pages in block-table order
    must reconstruct exactly the dense sequence."""
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import reference_attention
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_attention_blocked

    rng = np.random.RandomState(7)
    b, h, nb, bs, p, d = 4, 2, 32, 4, 4, 8
    lens = np.asarray([1, 4, 7, 15], np.int32)     # mixed, page-crossing
    dense_k = rng.randn(b, h, p * bs, d).astype('f')
    dense_v = rng.randn(b, h, p * bs, d).astype('f')
    q = rng.randn(b, h, d).astype('f')

    # scatter the dense sequences into shuffled physical pages of a
    # two-layer arena [L, NB, bs, H*D]; layer 0 stays garbage
    k_pages = rng.randn(2, nb, bs, h * d).astype('f')  # garbage elsewhere
    v_pages = rng.randn(2, nb, bs, h * d).astype('f')
    perm = rng.permutation(nb)[:b * p].reshape(b, p)
    for i in range(b):
        for j in range(p):
            for pages, dense in ((k_pages, dense_k), (v_pages, dense_v)):
                pages[1, perm[i, j]] = dense[i, :, j * bs:(j + 1) * bs] \
                    .transpose(1, 0, 2).reshape(bs, h * d)

    got = paged_attention_blocked(jnp.asarray(q), jnp.asarray(k_pages),
                                  jnp.asarray(v_pages),
                                  jnp.asarray(perm, jnp.int32),
                                  jnp.asarray(lens), layer=1)
    want = reference_attention(jnp.asarray(q)[:, :, None, :],
                               jnp.asarray(dense_k),
                               jnp.asarray(dense_v),
                               key_length=jnp.asarray(lens))[:, :, 0, :]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------- continuous batching
def test_continuous_batching_bit_identical_and_zero_misses():
    """THE acceptance e2e: concurrent mixed-length generation through
    the decode engine yields per-sequence token streams bit-identical
    to sequential single-request decode, with zero executor cache
    misses after warmup and the pool fully reclaimed after drain."""
    from paddle_tpu import observe
    observe.enable()
    reqs = _mixed_requests()

    eng = _engine()
    assert eng.warmup() == len(eng.prompt_buckets) + 1
    m0 = _misses(observe.snapshot())
    eng.start()
    assert eng.ready()
    streams = [eng.submit(**r) for r in reqs]
    conc = [s.result(timeout=120) for s in streams]
    eng.shutdown()
    assert _misses(observe.snapshot()) == m0, \
        'live decode traffic must be 100% executor cache hits'
    assert eng.pool.free_blocks() == eng.pool.num_blocks, \
        'KV pages must be fully reclaimed after drain'

    assert conc == _sequential_reference(0), \
        'continuous batching changed token streams'
    for s, r in zip(streams, reqs):
        assert len(s.result()) <= r['max_new_tokens']
        assert s.finish_reason in ('eos', 'max_tokens')


def test_preemption_requeue_preserves_streams():
    """A pool too small for the offered load must preempt-and-requeue
    (never fail requests), reclaim every page, still produce the exact
    sequential token streams (recompute-style preemption), and leave a
    flight-recorder trail explaining the latency spikes."""
    from paddle_tpu import observe
    observe.enable()
    observe.arm_flight()
    reqs = _mixed_requests(seed=0)
    want = _sequential_reference(0)

    eng = _engine(num_blocks=7)    # max seq needs 4 pages; force evicts
    eng.start()
    streams = [eng.submit(**r) for r in reqs]
    got = [s.result(timeout=120) for s in streams]
    eng.shutdown()
    snap = observe.snapshot()
    assert snap['counters'].get('decode.preemptions_total', 0) > 0, \
        'test must actually exercise eviction'
    assert snap['counters'].get('decode.pool_exhausted_total', 0) > 0
    assert got == want
    assert eng.pool.free_blocks() == eng.pool.num_blocks
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    assert 'decode_pool_exhausted' in kinds
    assert 'decode_preempt' in kinds


def test_streaming_tokens_arrive_incrementally(plain):
    stream = plain.submit([5, 9, 2], max_new_tokens=8)
    got = []
    for tok in stream:
        got.append(tok)
        assert isinstance(tok, int)
    assert got == stream.result()
    assert stream.done()


def test_sampled_streams_deterministic_per_seed(plain):
    kw = dict(max_new_tokens=8, temperature=0.9)
    a = plain.generate([4, 4, 4], seed=11, **kw)
    b = plain.generate([4, 4, 4], seed=11, **kw)
    c = plain.generate([4, 4, 4], seed=12, **kw)
    assert a == b
    assert a != c   # astronomically unlikely to collide over 8 tokens


def test_submit_validation_and_backpressure():
    eng = _engine(max_queue_depth=2)
    # never started: requests queue but nothing drains
    with pytest.raises(ValueError):
        eng.submit([])
    with pytest.raises(ValueError):
        eng.submit([1], max_new_tokens=0)
    with pytest.raises(ValueError):
        eng.submit(list(range(40)))            # > max_prompt_len
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=100)  # > per-seq capacity
    eng.submit([1], max_new_tokens=2)
    eng.submit([1], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        eng.submit([1], max_new_tokens=2)
    eng.shutdown(drain=False)
    with pytest.raises(EngineClosedError):
        eng.submit([1], max_new_tokens=2)


def test_shutdown_without_drain_fails_pending():
    eng = _engine()
    stream = eng.submit([1, 2], max_new_tokens=4)   # never started
    eng.shutdown(drain=False)
    with pytest.raises(EngineClosedError):
        stream.result(timeout=5)
    assert stream.finish_reason == 'error'
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def test_statusz_decode_panel():
    from paddle_tpu import observe
    from paddle_tpu.observe.diagnostics import _decode_status
    observe.enable()
    assert _decode_status(observe.snapshot()) is None
    eng = _engine()
    eng.start()
    eng.generate([3, 1, 4], max_new_tokens=4)
    doc = _decode_status(observe.snapshot())
    assert doc['tokens_total'] >= 4
    assert doc['kv_blocks_total'] == eng.pool.num_blocks
    assert doc['kv_blocks_free'] == eng.pool.num_blocks  # drained
    assert doc['finished_total'].get('max_tokens', 0) + \
        doc['finished_total'].get('eos', 0) >= 1
    eng.shutdown()
    assert doc['running_seqs'] is not None


@pytest.mark.slow
def test_decode_soak_concurrent_submitters():
    """Sustained mixed traffic from concurrent submit threads: every
    stream resolves, pages reclaim, worker survives."""
    eng = _engine(num_blocks=24, max_queue_depth=256)
    eng.start()
    results, errs = [], []
    mu = threading.Lock()

    def client(seed):
        rng = np.random.RandomState(seed)
        for _ in range(12):
            plen = int(rng.randint(1, 10))
            try:
                toks = eng.generate(
                    rng.randint(0, 60, plen).tolist(),
                    max_new_tokens=int(rng.randint(1, 7)),
                    temperature=float(rng.choice([0.0, 0.8])),
                    seed=int(rng.randint(1 << 30)), timeout=120)
                with mu:
                    results.append(toks)
            except Exception as e:   # pragma: no cover - diagnostic
                with mu:
                    errs.append(e)

    threads = [threading.Thread(target=client, args=(50 + i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.shutdown()
    assert not errs
    assert len(results) == 72
    assert all(len(r) >= 1 for r in results)
    assert eng.pool.free_blocks() == eng.pool.num_blocks
