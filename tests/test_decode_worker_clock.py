"""The decode worker's clock (engine.py's module docstring): the four
spans that partition the worker thread's time move a ``StateClock``,
and three intervals that cross spans or threads are split by it: a
request's wait for admission, the gap between two tokens, and the
stretches in which no program is outstanding on the device. Each split
sums to what the older histogram records. Toy widths on the CPU."""

import time

import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.observe.spans import SpanRecorder, StateClock
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights

SPEC = LMSpec(vocab_size=60, n_layer=2, n_head=2, d_key=8, d_value=8,
              d_model=16, d_inner=32)
STATES = ('idle', 'admit', 'prefill', 'step')
_WEIGHTS = []


@pytest.fixture(autouse=True)
def _observe_clean():
    # also what an earlier file of this process left in the registry
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def _engine(**kw):
    if not _WEIGHTS:
        _WEIGHTS.append(random_weights(SPEC, seed=5))
    kw.setdefault('max_batch', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('num_blocks', 64)
    kw.setdefault('pages_per_seq', 10)
    # one prefill program of 8 rows: a prompt of 30 takes four chunks
    kw.setdefault('prefill_chunk', 8)
    kw.setdefault('min_prompt_bucket', 8)
    return DecodeEngine(SPEC, weights=_WEIGHTS[0], place=fluid.CPUPlace(),
                        **kw)


def _started(**kw):
    eng = _engine(**kw).start()
    time.sleep(0.05)        # the worker is in its first span: the clock runs
    return eng


def _by_state(name, kind='counter'):
    if kind == 'counter':
        return [observe.get_counter(name, state=s) for s in STATES]
    return [observe.histogram(name).total(state=s) for s in STATES]


def _total(name):
    return observe.histogram(name).aggregate()[1]


# ------------------------------------------------------------ the clock
def test_a_state_clock_tiles_the_time_from_its_first_enter():
    clock = StateClock(STATES)
    assert clock.at(5.0) == (0.0,) * 4
    clock.enter('idle', 10.0)
    assert clock.at(10.5) == (0.5, 0.0, 0.0, 0.0)       # mid-span
    clock.exit('idle', 11.0)
    # between two spans time runs on under the state that closed
    assert clock.at(11.25) == (1.25, 0.0, 0.0, 0.0)
    clock.enter('admit', 11.5)
    assert clock.at(11.5) == (1.5, 0.0, 0.0, 0.0)
    clock.exit('admit', 12.0)
    clock.enter('prefill', 12.0)
    clock.exit('prefill', 14.0)
    clock.enter('step', 14.0)
    assert clock.at(15.0) == (1.5, 0.5, 2.0, 1.0)
    # a reader that lost a race with the last transition: the instant
    # lies in the state that ran until then
    assert clock.at(13.5) == (1.5, 0.5, 1.5, 0.0)
    clock.exit('step', 16.0)
    assert clock.at(16.0) == (1.5, 0.5, 2.0, 2.0)
    assert sum(clock.at(99.0)) == 99.0 - 10.0
    # the parts of any interval sum to its length
    a, b = clock.at(10.25), clock.at(15.5)
    assert sum(b) - sum(a) == pytest.approx(15.5 - 10.25)


def test_the_clocks_totals_are_the_worker_seconds_sums():
    observe.enable()
    eng = _started()
    for burst in range(3):
        streams = [eng.submit([1 + i, 2, 3] * (1 + 3 * i),
                              max_new_tokens=6 + i) for i in range(3)]
        for s in streams:
            s.result(120)
        time.sleep(0.03)
    eng.shutdown()
    clock = eng._clock.at(eng._clock._now[2])   # as the last span ended
    sums = _by_state('decode.worker_seconds', 'histogram')
    spans = sum(1 for e in observe.spans().events()
                if e['name'] in ('decode.' + s for s in STATES))
    assert all(s > 0 for s in sums)
    for state, mine, theirs in zip(STATES, clock, sums):
        # the histogram's sum and the loop's time between two spans
        assert theirs <= mine + 1e-9, state
    assert sum(clock) - sum(sums) < 200e-6 * spans
    assert sum(clock) == pytest.approx(sum(sums), rel=0.05)


# ---------------------------------------------------------- the splits
def _chunked(eng):
    """A sequence decodes while a prompt of four chunks is prefilled,
    and a third request waits behind that prefill."""
    running = eng.submit([1, 2, 3], max_new_tokens=24)
    tokens = iter(running)
    for _ in range(3):
        next(tokens)
    long = eng.submit(list(range(1, 31)), max_new_tokens=4)
    late = eng.submit([4, 5, 6, 7], max_new_tokens=4)
    for s in (running, long, late):
        s.result(120)
    assert observe.get_counter('decode.prefill_chunks') >= 6
    return 0


def _preempted(eng):
    """Four rows of four pages asked of nine: some are preempted and
    admitted again, and wait from their first submit."""
    streams = [eng.submit([i + 1] * 3, max_new_tokens=12, seed=i)
               for i in range(4)]
    for s in streams:
        s.result(120)
    preempted = observe.get_counter('decode.preemptions_total')
    assert preempted > 0
    return preempted


@pytest.mark.parametrize('serve, geometry', [
    (_chunked, {}), (_preempted, {'num_blocks': 9, 'pages_per_seq': 4})],
    ids=['behind_a_chunked_prefill', 'preempted_and_readmitted'])
def test_the_parts_sum_to_what_the_older_histograms_record(serve, geometry):
    observe.enable()
    eng = _started(**geometry)
    readmitted = serve(eng)
    assert eng.drain(120)
    eng.shutdown()
    waits = _by_state('decode.queue_wait_seconds')
    queue = observe.histogram('decode.queue_seconds')
    assert queue.aggregate()[0] == \
        observe.get_counter('decode.prefills_total')
    assert sum(waits) == pytest.approx(_total('decode.queue_seconds'),
                                       rel=1e-3)
    gaps = _by_state('decode.token_gap_seconds')
    assert observe.get_counter('decode.token_gaps_total') == \
        observe.histogram('decode.inter_token_seconds').aggregate()[0] > 0
    assert sum(gaps) == pytest.approx(
        _total('decode.inter_token_seconds'), rel=1e-3)
    by = dict(zip(STATES, zip(waits, gaps)))
    if readmitted:
        # a row admitted again waited through the steps it ran in
        assert by['step'][0] > 0
    else:
        # behind another request's prefill: a wait and a gap
        assert by['prefill'][0] > 0 and by['prefill'][1] > 0
    assert by['step'][1] > 0


# ------------------------------------------------------ device_empty
def _ring():
    """The ring's spans by name, as (start, end, args) in seconds."""
    by_name = {}
    for e in observe.spans().events():
        if e.get('ph') == 'X':
            by_name.setdefault(e['name'], []).append(
                (e['ts'] / 1e6, (e['ts'] + e['dur']) / 1e6,
                 e.get('args') or {}))
    return by_name


def _outstanding(ring):
    """The stretches in which a program of the worker's was on the
    device, from the spans that say which program they are of: a step
    from the end of its dispatch to the end of its fetch, a prefill from
    the end of its first chunk's ``executor.run`` to the end of its
    ``executor.fetch``."""
    out = []
    fetched = {a['step']: e for _, e, a in ring['decode.step.fetch']}
    for _, e, a in ring['decode.step.dispatch']:
        out.append((e, fetched[a['step']]))
    for s, e, a in ring['decode.prefill.run']:
        inside = [x for name in ('executor.run', 'executor.fetch')
                  for x in ring[name] if s <= x[0] and x[1] <= e]
        assert a['request_id'] > 0
        out.append((min(x[1] for x in inside), max(x[1] for x in inside)))
    return sorted(out)


def test_no_device_empty_stretch_holds_an_outstanding_program():
    observe.enable()
    eng = _started()
    _chunked(eng)
    time.sleep(0.03)
    eng.submit([9, 8, 7], max_new_tokens=5).result(120)
    eng.shutdown()
    ring = _ring()
    empty = ring['decode.device_empty']
    busy = _outstanding(ring)
    assert len(ring['decode.prefill.chunk']) >= 4
    # a stretch ends where the enqueue's call returns, which the ring
    # sees as the end of a span some microseconds before
    for s, e, _ in empty:
        assert not [b for b in busy if min(e, b[1]) - max(s, b[0]) > 5e-4]
    # the account is whole: what is in neither is the enqueues' own time
    wall = max(e for _, e, _ in empty) - min(s for s, _, _ in empty)
    merged = []         # a step is enqueued while the one before runs
    for s, e in busy:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    covered = sum(e - s for s, e, _ in empty) + \
        sum(e - s for s, e in merged)
    assert covered <= wall + 5e-4 * len(empty)
    assert covered >= 0.9 * wall
    # the histogram holds the same stretches, split by state
    parts = _by_state('decode.device_empty_seconds', 'histogram')
    assert sum(parts) == pytest.approx(sum(e - s for s, e, _ in empty),
                                       rel=1e-3)
    assert parts[0] > 0.03          # the sleep: an engine with nothing to run
    assert sum(parts) <= sum(_by_state('decode.worker_seconds', 'histogram'))


def test_a_step_steadily_in_flight_leaves_no_stretch_between_steps():
    observe.enable()
    eng = _started()
    assert len(eng.submit([1, 2, 3], max_new_tokens=30).result(120)) == 30
    eng.shutdown()
    ring = _ring()
    dispatched = sorted(e for _, e, _ in ring['decode.step.dispatch'])
    fetched = sorted(e for _, e, _ in ring['decode.step.fetch'])
    assert observe.get_counter('decode.steps_ahead_total') >= 25
    between = [(s, e) for s, e, _ in ring['decode.device_empty']
               if dispatched[0] < e and s < fetched[-1]]
    assert between == []
    # before the prefill, between it and the first step, after the last
    assert len(ring['decode.device_empty']) == 3


# ------------------------------------------------------- observe off
class _Untouchable(object):
    def __getattr__(self, name):
        raise AssertionError('the clock was touched: %s' % name)


def test_with_observe_off_nothing_reads_the_clock():
    eng = _engine()
    eng._clock = _Untouchable()
    seen = []
    add = eng._sched.add
    eng._sched.add = lambda seq: (seen.append(seq), add(seq))[1]
    eng.start()
    streams = [eng.submit([1 + i, 2, 3], max_new_tokens=6)
               for i in range(3)]
    streams.append(eng.submit(list(range(1, 31)), max_new_tokens=3))
    for s in streams:
        assert s.result(120)
    eng.shutdown()
    assert eng._broken is None and len(seen) == 4
    for seq in seen:
        assert seq.clk_submit is None and seq.clk_last_token is None
    assert eng._empty_since is None and eng._gaps[0] == 0
    snap = observe.registry().snapshot()
    assert not snap['counters'] and not snap['histograms']
    assert observe.spans().events() == []


# -------------------------------------------- the ring on another clock
def _recorded(spans):
    recorder = SpanRecorder()
    for name, t0, t1 in spans:
        recorder.add_span(name, t0, t1)
    return recorder


def test_offset_to_finds_where_another_clock_stands():
    spans = [('decode.step.fetch', 100.0 + 0.004 * i,
              100.0 + 0.004 * i + 0.001 + 1e-6 * (i * i * 37 % 1009))
             for i in range(300)]
    spans += [('decode.idle', 99.0, 99.9), ('decode.prefill', 101.3, 101.9)]
    recorder = _recorded(spans)
    skew = 7_654_321_012_345            # ns; the copies begin 2 us early
    copies = [(name, int((recorder._epoch0 + t0) * 1e9) + skew - 2000,
               int((t1 - t0) * 1e9) + 3000)
              for name, t0, t1 in spans[40:240]]
    copies.append(('not.in.the.ring', 5, 5))
    got = recorder.offset_to(copies)
    assert got['matched'] == 200
    assert got['offset_ns'] == pytest.approx(skew - 2000, abs=1500)
    assert got['residual_us_p95'] < 2.0
    # too few spans held by both: nothing
    assert recorder.offset_to(copies[:19]) is None
    assert _recorded([]).offset_to(copies) is None
