"""Global radix prefix cache + speculative decoding (ISSUE 12).

Unit level: trie match/publish/evict semantics over the KV pool
(full-page-boundary rule, LRU eviction through the pool's reclaimer,
pinning, rollback), fork()'s partial-last-page contract, the n-gram
draft, and the longest-accepted-prefix rule. E2E level: with the
prefix cache on, and separately with speculative decoding on,
concurrent mixed-length streams are token-for-token identical to the
sequential no-cache baseline (extending the PR 6 invariants), the
pool drains to its initial free count through cache-hit + preempt +
requeue interleavings, and warmup covers every signature so live
traffic stays at zero executor cache misses with both features
enabled."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.serving.decode import (BlockTable, DecodeEngine, KVPool,
                                       LMSpec, NgramDraft, PrefixCache,
                                       random_weights)
from paddle_tpu.serving.decode.spec import accept_drafts

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
    kw.setdefault('pages_per_seq', 8)
    kw.setdefault('weights', WEIGHTS)
    kw.setdefault('place', fluid.CPUPlace())
    return DecodeEngine(SPEC, **kw)


def _shared_prefix_requests(n=6, seed=0, vocab=60):
    """Mixed-length requests where most share a 9-token system prompt
    (crosses two full pages at block_size=4) — the traffic shape the
    cache exists for."""
    rng = np.random.RandomState(seed)
    shared = [7, 3, 7, 1, 7, 4, 7, 2, 7]
    reqs = []
    for i in range(n):
        if i % 3 == 2:      # a minority of cold prompts
            prompt = rng.randint(0, vocab, rng.randint(2, 8)).tolist()
        else:
            prompt = shared + rng.randint(
                0, vocab, rng.randint(1, 5)).tolist()
        reqs.append(dict(prompt_ids=prompt,
                         max_new_tokens=int(rng.randint(3, 8)),
                         temperature=0.0 if i % 2 == 0 else 0.7,
                         seed=100 + i))
    return reqs


@pytest.fixture(scope='module')
def baseline():
    """``baseline(seed)``: sequential single-request decode on a plain
    engine (no cache, no speculation), the bit-identity reference. One
    engine, built once, serves every request of every seed one at a
    time, each in the pages its predecessor gave back: a stream depends
    on its request alone (an engine a request against a batch is
    test_decode_serving.py's baseline)."""
    eng = _engine()
    eng.start()
    served = {}

    def of(seed):
        if seed not in served:
            served[seed] = [eng.generate(timeout=120, **r)
                            for r in _shared_prefix_requests(seed=seed)]
        return served[seed]
    yield of
    eng.shutdown()


def _misses(snap):
    return sum(v for k, v in snap['counters'].items()
               if k.startswith('executor.cache_miss_total'))


# ------------------------------------------------------ trie semantics
def test_prefix_cache_match_stops_at_full_page_boundary():
    pool = KVPool(num_blocks=16, block_size=4)
    cache = PrefixCache(pool)
    t = BlockTable()
    tokens = list(range(11))            # 2 full pages + 3-token tail
    assert pool.grow(t, len(tokens))
    cache.publish(tokens, t, upto_tokens=11)
    assert cache.cached_pages() == 2    # the partial page never enters

    # identical prompt: both full pages hit; the tail must prefill
    t2 = BlockTable()
    assert cache.match(tokens, t2) == 8
    assert t2.block_ids == t.block_ids[:2]

    # prompt that IS exactly the cached span: match must stop strictly
    # below the prompt end (>= 1 token must prefill for the sample)
    t3 = BlockTable()
    assert cache.match(tokens[:8], t3) == 4
    assert t3.block_ids == t.block_ids[:1]

    # diverging second page: only the first page hits
    t4 = BlockTable()
    other = tokens[:4] + [55, 56, 57, 58, 9]
    assert cache.match(other, t4) == 4
    assert t4.block_ids == t.block_ids[:1]
    for tb in (t2, t3, t4):
        pool.release(tb)
    pool.release(t)
    cache.clear()
    assert pool.free_blocks() == pool.num_blocks


def test_prefix_cache_eviction_integrates_with_free_list():
    pool = KVPool(num_blocks=4, block_size=4)
    cache = PrefixCache(pool)
    t = BlockTable()
    tokens = list(range(16))
    assert pool.grow(t, 16)
    cache.publish(tokens, t, upto_tokens=16)
    pool.release(t)                     # cache is now the sole owner
    assert pool.free_blocks() == 0
    assert cache.cached_pages() == 4

    # allocation pressure LRU-evicts through the reclaimer: alloc
    # succeeds even though the free list was empty
    got = pool.alloc(2)
    assert got is not None and len(got) == 2
    assert cache.cached_pages() == 2
    assert cache.evictions == 2
    pool.free(got)

    # matched (pinned) pages survive pressure: refcount 2 > 1
    t2 = BlockTable()
    matched = cache.match(list(range(9)), t2)
    assert matched == 8                 # both surviving pages hit
    assert pool.alloc(3) is None        # pinned pages are NOT evictable
    assert cache.cached_pages() == 2
    pool.release(t2)
    assert pool.alloc(3) is not None    # demoted back to evictable
    cache.clear()


def test_prefix_cache_unmatch_rolls_back_admission_failure():
    pool = KVPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    t = BlockTable()
    tokens = list(range(8))
    pool.grow(t, 8)
    cache.publish(tokens, t, upto_tokens=8)
    pool.release(t)

    t2 = BlockTable()
    n = cache.match(list(range(9)), t2)
    assert n == 8 and len(t2.block_ids) == 2
    cache.unmatch(t2, n)
    assert t2.block_ids == []
    assert cache.cached_pages() == 2    # cache refs intact
    cache.clear()
    assert pool.free_blocks() == pool.num_blocks


def test_prefix_cache_lru_evicts_oldest_chain_first():
    pool = KVPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    a, b = BlockTable(), BlockTable()
    pool.grow(a, 4)
    pool.grow(b, 4)
    cache.publish([1, 2, 3, 4], a, upto_tokens=4)
    cache.publish([5, 6, 7, 8], b, upto_tokens=4)
    page_a, page_b = a.block_ids[0], b.block_ids[0]
    pool.release(a)
    pool.release(b)
    # touch chain A: B becomes the LRU victim
    t = BlockTable()
    assert cache.match([1, 2, 3, 4, 9], t) == 4
    pool.release(t)
    assert cache.reclaim(1) == 1
    assert pool.refcount(page_b) == 0   # B evicted
    assert pool.refcount(page_a) == 1   # A still cached
    cache.clear()


def test_fork_partial_last_page_not_shared():
    """Satellite: a fork at a non-boundary point must stop at the last
    FULL page — the donor keeps appending into its partial page, and a
    shared partial page would leak those writes into the child."""
    pool = KVPool(num_blocks=8, block_size=4)
    t = BlockTable()
    pool.grow(t, 11)                    # pages 0,1 full; page 2 partial
    assert len(t.block_ids) == 3
    f = pool.fork(t, frozen_tokens=11)
    assert f.block_ids == t.block_ids[:2]
    assert pool.refcount(t.block_ids[2]) == 1   # partial page private
    # boundary fork shares everything below the boundary
    f2 = pool.fork(t, frozen_tokens=8)
    assert f2.block_ids == t.block_ids[:2]
    # legacy no-arg fork still shares the whole (frozen) table
    f3 = pool.fork(t)
    assert f3.block_ids == t.block_ids
    for tb in (f, f2, f3, t):
        pool.release(tb)
    assert pool.free_blocks() == pool.num_blocks


# ------------------------------------------------------- draft + rule
def test_ngram_draft_learns_and_falls_back():
    d = NgramDraft(max_ngram=3, context=2)
    assert d.propose([1], 3) == []
    # prompt-lookup fallback: suffix [1, 2] seen earlier -> continue 3, 4
    assert d.propose([1, 2, 3, 4, 1, 2], 2) == [3, 4]
    # online learning: teach 7,8 -> 9 -> 10 and chain proposals
    d.observe([7, 8, 9])
    d.observe([8, 9, 10])
    assert d.propose([5, 7, 8], 2) == [9, 10]
    # majority wins over a single conflicting observation
    d.observe([7, 8, 9])
    d.observe([7, 8, 11])
    assert d.propose([0, 7, 8], 1) == [9]


def test_accept_drafts_longest_prefix_rule():
    # out[j] is the target's token after consuming tokens[0..j]
    assert accept_drafts([5, 6, 7], [5, 6, 7, 8]) == [5, 6, 7, 8]
    assert accept_drafts([5, 6, 7], [5, 6, 9, 8]) == [5, 6, 9]
    assert accept_drafts([4, 6, 7], [5, 6, 7, 8]) == [5]
    assert accept_drafts([], [3]) == [3]


# --------------------------------------------------------------- e2es
def test_prefix_cache_bit_identical_and_pool_drains(baseline):
    """THE cache acceptance e2e: concurrent shared-prefix traffic with
    the cache on yields streams bit-identical to the sequential
    no-cache baseline, actually hits (prefill tokens skipped > 0), and
    the pool drains to its initial free count after shutdown."""
    from paddle_tpu import observe
    observe.enable()
    want = baseline(0)
    eng = _engine(prefix_cache=True)
    eng.warmup()
    m0 = _misses(observe.snapshot())
    eng.start()
    streams = [eng.submit(**r) for r in _shared_prefix_requests(seed=0)]
    got = [s.result(timeout=120) for s in streams]
    eng.shutdown()
    snap = observe.snapshot()
    assert got == want, 'prefix cache changed token streams'
    assert _misses(snap) == m0, \
        'cache-hit prefills must reuse warmed suffix buckets'
    assert snap['counters'].get(
        'decode.prefix_tokens_reused_total', 0) > 0
    assert snap['counters'].get(
        'decode.prefix_cache_lookups_total{outcome=hit}', 0) > 0
    assert eng.pool.free_blocks() == eng.pool.num_blocks, \
        'cache.clear() at shutdown must drain the pool to initial'


def test_spec_decode_bit_identical_zero_misses(baseline):
    """THE speculation acceptance e2e: draft-and-verify decode (greedy
    and sampled rows mixed) emits streams bit-identical to plain
    decode, with the verify signature warmed (zero live misses) and
    accepted drafts actually flowing."""
    from paddle_tpu import observe
    observe.enable()
    want = baseline(0)
    eng = _engine(spec_k=3)
    sigs = eng.warmup()
    assert sigs == len(eng.prompt_buckets) + 2   # decode + verify keys
    m0 = _misses(observe.snapshot())
    eng.start()
    streams = [eng.submit(**r) for r in _shared_prefix_requests(seed=0)]
    got = [s.result(timeout=120) for s in streams]
    eng.shutdown()
    snap = observe.snapshot()
    assert got == want, 'speculative decoding changed token streams'
    assert _misses(snap) == m0, \
        'verify dispatches must be 100% executor cache hits'
    assert snap['counters'].get('decode.spec_steps_total', 0) > 0
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def test_cache_hit_preempt_requeue_drain_invariant(baseline):
    """Satellite: the pool-free-count-returns-to-initial drain
    invariant extended with cache-hit + preempt + requeue
    interleavings — a pool small enough that admission, growth, cache
    eviction, and preemption all fight over the same pages, with both
    features enabled."""
    from paddle_tpu import observe
    observe.enable()
    observe.arm_flight()
    want = baseline(0)
    eng = _engine(num_blocks=9, prefix_cache=True, spec_k=2)
    eng.start()
    streams = [eng.submit(**r) for r in _shared_prefix_requests(seed=0)]
    got = [s.result(timeout=120) for s in streams]
    eng.shutdown()
    snap = observe.snapshot()
    assert got == want, \
        'preemption under cache pressure changed token streams'
    assert snap['counters'].get('decode.pool_exhausted_total', 0) > 0, \
        'test must actually exercise pool pressure'
    assert snap['counters'].get('decode.prefix_evictions_total', 0) > 0, \
        'test must actually exercise cache eviction'
    assert eng.pool.free_blocks() == eng.pool.num_blocks, \
        'every page must return: sequences released, cache cleared'


def test_both_features_bit_identical_with_sampling(baseline):
    """Cache + speculation together, mixed greedy/sampled rows."""
    want = baseline(3)
    eng = _engine(prefix_cache=True, spec_k=3)
    eng.warmup()
    eng.start()
    streams = [eng.submit(**r) for r in _shared_prefix_requests(seed=3)]
    got = [s.result(timeout=120) for s in streams]
    eng.shutdown()
    assert got == want
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def test_both_features_over_an_int8_arena():
    """The three options at once (the combination only the deleted
    decode load tool ran): prefix cache and speculation over int8
    pages give the streams of plain sequential int8 decode, hit the
    cache, verify drafts, compile nothing after warm-up, pay one byte
    an element plus a row's two float32 scales, and free every page
    once drained."""
    from paddle_tpu import observe
    reqs = _shared_prefix_requests(seed=0)
    want = []
    for r in reqs:
        e = _engine(kv_dtype='int8')
        e.start()
        want.append(e.generate(timeout=120, **r))
        e.shutdown()
    observe.enable()
    eng = _engine(kv_dtype='int8', prefix_cache=True, spec_k=2)
    eng.warmup()
    m0 = _misses(observe.snapshot())
    eng.start()
    streams = [eng.submit(**r) for r in reqs]
    got = [s.result(timeout=120) for s in streams]
    eng.shutdown()
    counters = observe.snapshot()['counters']
    assert got == want
    assert _misses(observe.snapshot()) == m0
    assert counters.get(
        'decode.prefix_cache_lookups_total{outcome=hit}', 0) > 0
    assert counters.get('decode.prefix_tokens_reused_total', 0) > 0
    assert counters.get('decode.spec_steps_total', 0) > 0
    assert eng.kv_dtype == 'int8'
    # L * H * (dk + dv) elements a token + (k, v) scales a head, f32
    assert eng.kv_bytes_per_token == \
        SPEC.n_layer * SPEC.n_head * (SPEC.d_key + SPEC.d_value) + \
        SPEC.n_layer * SPEC.n_head * 2 * 4
    assert eng.resident_seqs_peak >= 1
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def test_env_knobs_read_per_call(monkeypatch):
    """PADDLE_TPU_PREFIX_CACHE / PADDLE_TPU_SPEC_K are read at engine
    construction (per call), never frozen at import."""
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE', '1')
    monkeypatch.setenv('PADDLE_TPU_SPEC_K', '2')
    eng = _engine()
    assert eng.prefix_cache is not None
    assert eng.spec_k == 2
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE', '0')
    monkeypatch.setenv('PADDLE_TPU_SPEC_K', '0')
    eng2 = _engine()
    assert eng2.prefix_cache is None
    assert eng2.spec_k == 0
    # constructor args win over the env
    monkeypatch.setenv('PADDLE_TPU_SPEC_K', '5')
    eng3 = _engine(spec_k=1, prefix_cache=True)
    assert eng3.spec_k == 1 and eng3.prefix_cache is not None


def test_statusz_decode_panel_prefix_spec_fields():
    from paddle_tpu import observe
    from paddle_tpu.observe.diagnostics import _decode_status
    observe.enable()
    eng = _engine(prefix_cache=True, spec_k=2)
    eng.start()
    prompt = [7, 3, 7, 1, 7, 4, 7, 2, 7, 5]
    eng.generate(prompt, max_new_tokens=6)
    # identical repeat: the prompt hits the cache, and the draft —
    # trained on the first stream — proposes its exact continuation
    eng.generate(prompt, max_new_tokens=6)
    doc = _decode_status(observe.snapshot())
    eng.shutdown()
    assert doc['prefix_cache_hit_rate'] is not None
    assert doc['prefix_cache_hit_rate'] > 0
    assert doc['prefix_tokens_reused_total'] > 0
    assert doc['spec_steps_total'] >= 1
    assert doc['spec_accepted_len_mean'] is not None
