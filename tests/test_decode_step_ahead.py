"""The decode worker runs one step ahead (engine.py's module docstring):
step n+1 is enqueued before step n's tokens are fetched. Toy widths on
the CPU. The depth-0 run each test compares with is the same engine
with ``_stays_in_flight`` held at False (``_depth_0``): every step is
then enqueued, fetched and emitted before anything else, the order
before this mechanism."""

import contextlib
import sys
import threading
import time

import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.serving import EngineClosedError
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm

F, S = lm.FULL, lm.SLIDING
_MOE = dict(n_experts=8, experts_held=4, first_expert=2,
            experts_per_token=3)
SPECS = {
    'post_ln': LMSpec(vocab_size=60, n_layer=2, n_head=2, d_key=8,
                      d_value=8, d_model=16, d_inner=32),
    'parallel_moe': LMSpec(
        vocab_size=64, n_layer=4, n_head=4, n_kv_head=2, d_key=8,
        d_value=8, d_model=16, d_inner=24, block='parallel_moe',
        layer_types=[S] * 3 + [F], sliding_window=8, rope_theta=50000.0,
        n_shared_experts=2, **_MOE),
    'latent_moe': LMSpec(
        vocab_size=64, n_layer=5, d_model=32, d_inner=24,
        block='latent_moe', layer_types=[F, F, S, S, S], sliding_window=5,
        latent={F: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8,
                        d_rope=4, d_v=8, rope_theta=8e7),
                S: dict(n_head=2, q_rank=16, kv_rank=20, d_nope=12,
                        d_rope=4, d_v=8, rope_theta=5e4)},
        dense_layers=1, d_inner_dense=40, index_n_heads=3,
        index_head_dim=8, index_topk=8, n_shared_experts=1, **_MOE),
}
_WEIGHTS = {}


@pytest.fixture(autouse=True)
def _observe_clean():
    # also what an earlier file of this process left in the registry
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def _engine(block='post_ln', **kw):
    if block not in _WEIGHTS:
        _WEIGHTS[block] = random_weights(SPECS[block], seed=5)
    kw.setdefault('max_batch', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('num_blocks', 64)
    kw.setdefault('pages_per_seq', 10)
    # two prefill programs: the compiles are most of this file's time
    kw.setdefault('min_prompt_bucket', 16)
    return DecodeEngine(SPECS[block], weights=_WEIGHTS[block],
                        place=fluid.CPUPlace(), **kw)


@contextlib.contextmanager
def _depth_0(eng):
    eng._stays_in_flight = lambda: False
    try:
        yield eng
    finally:
        del eng._stays_in_flight


@pytest.fixture(scope='module')
def engines():
    """One started engine a block, for the tests that change nothing of
    it: built at a block's first use."""
    made = {}

    def get(block):
        if block not in made:
            made[block] = _engine(block).start()
        return made[block]
    yield get
    for eng in made.values():
        eng.shutdown()


def _requests(temperature):
    """Mixed lengths: more requests than slots, so some are admitted as
    others finish, and answers of 2 to 14 tokens, so rows leave the
    batch at different steps."""
    return [dict(prompt_ids=[(7 * i + j) % 50 + 1 for j in range(1 + 3 * i)],
                 max_new_tokens=2 + (5 * i) % 13,
                 temperature=temperature, seed=100 + i)
            for i in range(7)]


def _serve(eng, requests):
    """(tokens, finish reason, tokens streamed) a request: the first
    four submitted together, the others one by one as tokens of the
    first arrive, so they are admitted in the middle of a run."""
    streams = [eng.submit(**r) for r in requests[:4]]
    heard = {s: [] for s in streams}
    first = iter(streams[1])
    for r in requests[4:]:
        heard[streams[1]].append(next(first))
        streams.append(eng.submit(**r))
    out = []
    for s in streams:
        tokens = s.result(120)
        out.append((tokens, s.finish_reason,
                    len(heard.get(s, ())) + len(list(s))))
    assert eng.drain(120)
    return out


@pytest.mark.parametrize('temperature', [0.0, 0.8])
@pytest.mark.parametrize('block', sorted(SPECS))
def test_streams_are_those_of_the_depth_0_run(engines, block, temperature):
    observe.enable()
    requests = _requests(temperature)
    eng = engines(block)
    with _depth_0(eng):
        want = _serve(eng, requests)
    assert observe.get_counter('decode.steps_ahead_total') == 0
    got = _serve(eng, requests)
    assert got == want
    assert [(len(t), why, n) for t, why, n in got] == [
        (r['max_new_tokens'], 'max_tokens', r['max_new_tokens'])
        for r in requests]
    assert observe.get_counter('decode.steps_ahead_total') > 0
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def test_an_eos_seen_one_step_late_drops_the_extra_token(engines):
    """The step enqueued before the EOS was fetched holds the finished
    row: its token is neither emitted nor counted, its pages go back
    once, and their next owner decodes as if alone."""
    prompt, other = [3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5]
    with _depth_0(engines('post_ln')) as alone:
        free_run = alone.generate(prompt, max_new_tokens=24, timeout=120)
        other_alone = alone.generate(other, max_new_tokens=12, timeout=120)
    # the first token the stream has not shown before, past its start
    at = next(i for i in range(2, len(free_run))
              if free_run[i] not in free_run[:i])
    observe.enable()
    # 8 pages, all the first request's: the second runs on them
    eng = _engine(num_blocks=8).start()
    stream = eng.submit(prompt, max_new_tokens=24, eos_id=free_run[at])
    assert stream.result(120) == free_run[:at + 1]
    assert stream.finish_reason == 'eos'
    assert list(stream) == free_run[:at + 1]
    assert eng.drain(120) and eng._ahead is None
    # one token from the prefill and one a step, but for the last step:
    # enqueued before the EOS was seen, fetched, and dropped
    assert observe.get_counter('decode.tokens_total') == at + 1
    assert observe.get_counter('decode.steps_total') == at + 1
    assert observe.get_counter('decode.steps_ahead_total') == at
    assert observe.get_counter('decode.finished_total', reason='eos') == 1
    assert eng.pool.free_blocks() == eng.pool.num_blocks
    assert eng.generate(other, max_new_tokens=12, timeout=120) == \
        other_alone
    eng.shutdown()
    assert eng._broken is None
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def _watch(eng, owner, name, seen):
    """Record, at every call of ``owner.name``, the step in flight."""
    inner = getattr(owner, name)

    def watched(*args, **kw):
        seen.append(eng._ahead)
        return inner(*args, **kw)
    setattr(owner, name, watched)


def test_a_prefill_finds_the_pipeline_empty():
    observe.enable()
    eng = _engine().start()
    seen = []
    _watch(eng, eng, '_run_prefill', seen)
    long = eng.submit([1, 2, 3], max_new_tokens=30)
    tokens = iter(long)
    for _ in range(4):
        next(tokens)
    ahead = observe.get_counter('decode.steps_ahead_total')
    assert ahead > 0                 # a step was in flight as it arrived
    late = eng.submit([4, 5, 6, 7], max_new_tokens=5)
    assert len(late.result(120)) == 5 and len(long.result(120)) == 30
    eng.shutdown()
    assert seen == [None, None]
    assert observe.get_counter('decode.steps_ahead_total') > ahead


def test_a_preemption_finds_the_pipeline_empty(engines):
    observe.enable()
    requests = [dict(prompt_ids=[i + 1] * 3, max_new_tokens=12, seed=i,
                     temperature=0.5 * (i % 2)) for i in range(4)]
    with _depth_0(engines('post_ln')) as roomy:
        want = _serve(roomy, requests)
    # 4 rows x 4 pages asked of 9
    eng = _engine(num_blocks=9, pages_per_seq=4).start()
    seen = []
    _watch(eng, eng._sched, 'preempt', seen)
    got = _serve(eng, requests)
    eng.shutdown()
    assert got == want
    assert seen and all(step is None for step in seen)
    assert observe.get_counter('decode.preemptions_total') == len(seen)
    assert observe.get_counter('decode.steps_ahead_total') > 0
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def test_speculation_never_runs_ahead(engines):
    observe.enable()
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    want = engines('post_ln').generate(prompt, max_new_tokens=12,
                                       timeout=120)
    ahead = observe.get_counter('decode.steps_ahead_total')
    assert ahead > 0
    eng = _engine(spec_k=2).start()
    seen = []
    for name in ('_dispatch_verify', '_dispatch_decode'):
        _watch(eng, eng, name, seen)
    assert eng.generate(prompt, max_new_tokens=12, timeout=120) == want
    eng.shutdown()
    assert observe.get_counter('decode.spec_steps_total') >= 1
    assert seen and all(step is None for step in seen)
    assert observe.get_counter('decode.steps_ahead_total') == ahead


def test_a_page_read_waits_for_the_step_in_flight():
    eng = _engine().start()
    settled, inner = [], eng._settle

    def settle():
        step = eng._ahead
        inner()
        settled.append(step is None or step.tokens.is_ready())
    eng._settle = settle
    stream = eng.submit([1, 2, 3], max_new_tokens=36)
    tokens = iter(stream)
    next(tokens)
    done = threading.Event()

    def reader():
        while not done.is_set():
            pages = eng.read_pages([0, 1])
            assert set(pages) == set(eng._progs.arena_names)
    thread = threading.Thread(target=reader)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # the reader cuts into every step
    thread.start()
    try:
        assert len(stream.result(120)) == 36
    finally:
        sys.setswitchinterval(switch)
        done.set()
        thread.join(60)
    assert not thread.is_alive()
    eng.shutdown()
    assert len(settled) > 2 and all(settled)
    assert eng._broken is None


def test_shutdown_without_draining_leaves_nothing_in_flight():
    observe.enable()
    eng = _engine().start()
    stream = eng.submit([1, 2, 3], max_new_tokens=36)
    tokens = iter(stream)
    for _ in range(4):
        next(tokens)
    assert observe.get_counter('decode.steps_ahead_total') > 0
    eng.shutdown(drain=False)
    assert eng._ahead is None and eng._broken is None
    with pytest.raises(EngineClosedError):
        stream.result(10)
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def test_one_record_and_one_span_a_program_and_the_records_tile():
    observe.enable()
    eng = _engine()
    eng.warmup()
    compiled = observe.get_counter('executor.cache_miss_total')
    dispatched = []
    _watch(eng, eng, '_dispatch_decode', dispatched)
    t0 = time.perf_counter()
    _serve(eng.start(), _requests(0.0))
    eng.shutdown()
    wall = time.perf_counter() - t0
    programs = len(dispatched)
    steps = observe.histogram('decode.step_seconds')
    assert steps.aggregate()[0] == programs == \
        observe.get_counter('decode.steps_total')
    names = [e['name'] for e in observe.spans().events()]
    for span in ('decode.step', 'decode.step.build', 'decode.step.dispatch',
                 'decode.step.fetch', 'decode.step.emit'):
        assert names.count(span) == programs, span
    ahead = observe.get_counter('decode.steps_ahead_total')
    assert 0 < ahead < programs
    assert ahead == sum(1 for step in dispatched if step is not None)
    # the spans say which program they are of: a dispatch its
    # ``decode.step`` span's, a fetch that one's or, with it in flight,
    # the one's before; every program is fetched once
    spans = {name: [(e['ts'], e['ts'] + e['dur'], e['args']['step'])
                    for e in observe.spans().events() if e['name'] == name]
             for name in ('decode.step', 'decode.step.dispatch',
                          'decode.step.fetch')}

    def under(span):
        (step,) = [no for t0, t1, no in spans['decode.step']
                   if t0 <= span[0] and span[1] <= t1]
        return step
    assert sorted(no for _, _, no in spans['decode.step']) == \
        list(range(1, programs + 1))
    assert all(d[2] == under(d) for d in spans['decode.step.dispatch'])
    late = [under(f) - f[2] for f in spans['decode.step.fetch']]
    assert set(late) == {0, 1} and late.count(1) == ahead
    assert sorted(f[2] for f in spans['decode.step.fetch']) == \
        list(range(1, programs + 1))
    prefills = [e['args'] for e in observe.spans().events()
                if e['name'] in ('decode.prefill.run',
                                 'decode.prefill.chunk')]
    assert prefills and all(a['request_id'] > 0 for a in prefills)
    # consecutive records never overlap: together they fit in the run
    assert 0 < steps.aggregate()[1] <= wall
    assert observe.get_counter('executor.cache_miss_total') == compiled
