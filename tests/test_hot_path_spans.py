"""The spans on the path every step takes: the decode worker's partition
of its wall time, the executor's dispatch, the names a compiled program
and its ops carry. A tiny LMSpec on the CPU; the profiler writes the
host plane with TraceAnnotations there too."""

import os
import re
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode.scheduler import Sequence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import tracelib          # noqa: E402

SPEC = LMSpec(vocab_size=60, n_layer=2, n_head=2, d_key=8, d_value=8,
              d_model=16, d_inner=32)
WEIGHTS = random_weights(SPEC, seed=3)
STATES = ('idle', 'admit', 'prefill', 'step')
NEW_NAMES = ('decode.worker_seconds', 'decode.step_build_seconds',
             'decode.step_dispatch_seconds', 'decode.step_fetch_seconds',
             'decode.step_emit_seconds', 'decode.step_live_tokens',
             'executor.run_seconds', 'executor.lookup_seconds',
             'executor.prepare_seconds', 'executor.enqueue_seconds',
             'executor.fetch_seconds', 'executor.lock_seconds',
             'executor.writeback_seconds', 'executor.enqueue_cpu_seconds')
PHASES = ('lookup', 'lock', 'prepare', 'enqueue', 'writeback')
PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11], [12, 13, 14, 15]]


@pytest.fixture(autouse=True)
def _observe_clean():
    yield
    observe.disable()
    observe.reset()


def _engine(**kw):
    return DecodeEngine(SPEC, max_batch=4, block_size=4, num_blocks=64,
                        pages_per_seq=4, weights=WEIGHTS,
                        place=fluid.CPUPlace(), **kw)


@pytest.fixture(scope='module')
def never_started():
    """One engine, built once, for the cases that only build a step's
    feeds from a batch made by hand."""
    eng = _engine()
    yield eng
    eng.shutdown(drain=False)


def _serve(prompts=PROMPTS, new=5, **kw):
    with _engine(**kw) as eng:
        streams = [eng.submit(p, max_new_tokens=new) for p in prompts]
        return [s.result(120) for s in streams]


def _inside(child, parent):
    return parent[1] <= child[1] and \
        child[1] + child[2] <= parent[1] + parent[2]


def test_worker_and_executor_spans_nest_on_one_host_line(tmp_path):
    import jax
    observe.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _serve()
    finally:
        jax.profiler.stop_trace()
    path = tracelib.find_xplane(str(tmp_path))
    host = tracelib.read_xplane(path)['host']
    by_name = {}
    for ev in host:
        by_name.setdefault(ev[0], []).append(ev)
    steps = by_name['decode.step']
    assert len(steps) >= 4
    for part in ('build', 'dispatch', 'fetch', 'emit'):
        children = by_name['decode.step.' + part]
        assert len(children) == len(steps)
        assert all(any(_inside(c, s) for s in steps) for c in children)
    dispatches = by_name['decode.step.dispatch']
    runs = [r for r in by_name['executor.run']
            if any(_inside(r, d) for d in dispatches)]
    assert len(runs) == len(dispatches)
    for part in PHASES:
        inner = by_name['executor.' + part]
        assert sum(1 for c in inner
                   if any(_inside(c, r) for r in runs)) == len(runs)
    # the prefill's three parts, its fetch inside decode.prefill.run
    prefills = by_name['decode.prefill']
    assert len(prefills) == len(PROMPTS)
    for part in ('build', 'run', 'emit'):
        assert all(any(_inside(c, p) for p in prefills)
                   for c in by_name['decode.prefill.' + part])
    assert any(_inside(f, r) for f in by_name['executor.fetch']
               for r in by_name['decode.prefill.run'])
    assert by_name['decode.idle'] and by_name['decode.admit']
    # identifiers ride as attrs: no name carries a number
    assert not [n for n in by_name
                if n.startswith(('decode.', 'executor.'))
                and re.search(r'\d', n)]
    # one thread, so one line of the host plane holds them all
    from jax.profiler import ProfileData
    lines = [set(ev.name for ev in line.events)
             for plane in ProfileData.from_file(path).planes
             if plane.name == tracelib.HOST_PLANE for line in plane.lines]
    holders = [names for names in lines if 'decode.step' in names]
    assert len(holders) == 1
    assert {'decode.step.fetch', 'decode.prefill.run', 'decode.idle',
            'decode.admit', 'executor.run', 'executor.enqueue'} \
        <= holders[0]


def test_worker_states_partition_the_workers_wall_time():
    observe.enable()
    eng = _engine()
    t0 = time.perf_counter()
    eng.start()
    for burst in range(3):
        streams = [eng.submit(p, max_new_tokens=8) for p in PROMPTS]
        for s in streams:
            s.result(120)
        time.sleep(0.05)            # idle between bursts
    eng.shutdown()
    wall = time.perf_counter() - t0
    hist = observe.histogram('decode.worker_seconds')
    parts = {st: hist.total(state=st) for st in STATES}
    assert all(parts[st] > 0 for st in STATES), parts
    assert sum(parts.values()) == pytest.approx(wall, rel=0.05), parts
    # the children of a step lie inside it, and dispatch + fetch is what
    # decode.step_seconds has always meant
    step = parts['step']
    inner = sum(observe.histogram('decode.step_%s_seconds' % part)
                .aggregate()[1]
                for part in ('build', 'dispatch', 'fetch', 'emit'))
    assert 0.5 * step < inner <= step
    run = sum(observe.histogram('decode.step_%s_seconds' % part)
              .aggregate()[1] for part in ('dispatch', 'fetch'))
    assert observe.histogram('decode.step_seconds').aggregate()[1] == \
        pytest.approx(run, rel=0.1)
    steps = observe.histogram('decode.step_seconds').aggregate()[0]
    assert hist.count(state='step') == steps == \
        observe.get_counter('decode.steps_total')


def test_tokens_do_not_depend_on_observe_and_off_means_nothing_recorded():
    off = _serve()
    assert observe.spans().events() == []
    snap = observe.snapshot()
    recorded = [k for kind in ('counters', 'gauges', 'histograms')
                for k in snap[kind]]
    assert not [k for k in recorded if k.startswith(NEW_NAMES)]
    observe.enable()
    assert _serve() == off
    names = {e['name'] for e in observe.spans().events()}
    assert {'decode.step', 'decode.step.fetch', 'decode.prefill.run',
            'executor.run', 'executor.enqueue'} <= names
    snap = observe.snapshot()
    for name in NEW_NAMES:
        assert any(k.startswith(name) for k in snap['histograms']), name
    step = [e for e in observe.spans().events()
            if e['name'] == 'decode.step'][0]
    assert step['args']['step'] == 1
    run = [e for e in observe.spans().events()
           if e['name'] == 'executor.run'][-1]
    assert run['args']['kind'] == 'single' and len(run['args']['key']) == 8


def test_speculative_steps_take_the_same_spans():
    observe.enable()
    plain = _serve(prompts=[[1, 2, 3, 1, 2, 3, 1, 2]], new=8)
    assert _serve(prompts=[[1, 2, 3, 1, 2, 3, 1, 2]], new=8,
                  spec_k=2) == plain
    assert observe.get_counter('decode.spec_steps_total') >= 1
    hist = observe.histogram('decode.worker_seconds')
    assert hist.count(state='step') == \
        observe.get_counter('decode.steps_total')


def _fit_a_line():
    """(executor, program, scope, feed, loss) of a small training
    program whose startup has run."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[13], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        cost = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(input=x, size=1), y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.scope.Scope()
    exe.run(startup, scope=scope)
    feed = {'x': np.zeros((4, 13), 'float32'),
            'y': np.zeros((4, 1), 'float32')}
    return exe, main, scope, feed, cost


def _samples(name, **labels):
    """A histogram's records in the order they were made (under the
    reservoir's cap nothing is sampled away)."""
    from paddle_tpu.observe.registry import _label_key
    return list(observe.histogram(name)._values[_label_key(labels)].samples)


def test_the_five_children_of_a_run_tile_it():
    exe, main, scope, feed, cost = _fit_a_line()
    exe.run(main, feed=feed, fetch_list=[cost], scope=scope)   # compiles
    observe.enable()
    calls = 20
    for i in range(calls):
        # one in two waits for its fetch: executor.fetch then lies under
        # executor.writeback, a grandchild
        exe.run(main, feed=feed, fetch_list=[cost], scope=scope,
                return_numpy=bool(i % 2))
    evs = [e for e in observe.spans().events() if e['ph'] == 'X']
    runs = [e for e in evs if e['name'] == 'executor.run']
    assert len(runs) == calls
    gaps = []
    for run in runs:
        lo, hi = run['ts'], run['ts'] + run['dur']
        kids = [e for e in evs if e['name'] in
                {'executor.' + p for p in PHASES}
                and lo <= e['ts'] and e['ts'] + e['dur'] <= hi]
        # each once, in this order, none over the next
        assert [e['name'] for e in sorted(kids, key=lambda e: e['ts'])] \
            == ['executor.' + p for p in PHASES]
        kids.sort(key=lambda e: e['ts'])
        for a, b in zip(kids, kids[1:]):
            assert a['ts'] + a['dur'] <= b['ts']
        gaps.append(run['dur'] - sum(e['dur'] for e in kids))
    fetches = [e for e in evs if e['name'] == 'executor.fetch']
    backs = [e for e in evs if e['name'] == 'executor.writeback']
    assert len(fetches) == calls // 2
    assert all(any(b['ts'] <= f['ts'] and f['ts'] + f['dur']
                   <= b['ts'] + b['dur'] for b in backs) for f in fetches)
    # what lies under no child is the span machinery's own microseconds
    # (the least of twenty: a pause between two children is not the
    # executor's)
    assert 0 <= min(gaps) < 100.0, gaps


def test_every_phase_says_which_program():
    exe, main, scope, feed, cost = _fit_a_line()
    twin = main.clone()
    twin.name = 'twin_step'
    observe.enable()
    for prog, calls in ((main, 3), (twin, 4)):
        for _ in range(calls):
            exe.run(prog, feed=feed, fetch_list=[cost], scope=scope,
                    return_numpy=False)
    hists = observe.snapshot()['histograms']
    for phase in PHASES + ('enqueue_cpu',):
        keys = {k: v['count'] for k, v in hists.items()
                if k.startswith('executor.%s_seconds' % phase)}
        warm = 1 if phase.startswith('enqueue') else 0   # the first compiles
        assert keys == {
            'executor.%s_seconds{program=main}' % phase: 3 - warm,
            'executor.%s_seconds{program=twin_step}' % phase: 4 - warm}, phase
    # the first dispatch of a key keeps its own series and labels
    assert sum(v['count'] for k, v in hists.items() if k.startswith(
        'executor.first_dispatch_seconds{')) == 2
    # the thread's CPU time of a call lies inside its wall time, record
    # by record, wherever the thread's clock is finer than a call (a
    # sandboxed host's may tick at 10 ms: a record is then 0 or a tick)
    seen, until = set(), time.perf_counter() + 0.02
    while time.perf_counter() < until:
        seen.add(time.thread_time())
    tick = 0.02 / max(len(seen) - 1, 1)
    for prog in ('main', 'twin_step'):
        wall = _samples('executor.enqueue_seconds', program=prog)
        cpu = _samples('executor.enqueue_cpu_seconds', program=prog)
        assert len(wall) == len(cpu) >= 2
        assert all(0 <= c <= w + 2e-6 + tick for c, w in zip(cpu, wall)), \
            (cpu, wall, tick)


def test_a_decode_step_and_a_prefill_feed_different_series():
    observe.enable()
    _serve()
    hists = observe.snapshot()['histograms']
    steps = observe.get_counter('decode.steps_total')
    for phase in PHASES + ('enqueue_cpu',):
        by_program = {k[k.index('{'):]: v['count'] for k, v in hists.items()
                      if k.startswith('executor.%s_seconds{' % phase)}
        assert '{program=decode_step}' in by_program, (phase, by_program)
        assert any(k.startswith('{program=prefill_') for k in by_program)
        assert all(k.startswith('{program=') and ',' not in k
                   for k in by_program)
    # warm-up compiled the step, so every step of the worker is a record
    assert hists['executor.lookup_seconds{program=decode_step}']['count'] \
        >= steps > 0


def test_observe_off_builds_no_label_dict(monkeypatch):
    exe, main, scope, feed, cost = _fit_a_line()
    built = []
    real = fluid.Executor._phase_labels
    monkeypatch.setattr(fluid.Executor, '_phase_labels', staticmethod(
        lambda program: built.append(program) or real(program)))
    exe.run(main, feed=feed, fetch_list=[cost], scope=scope)
    exe.run_steps(2, main, feed=feed, fetch_list=[cost], scope=scope)
    assert built == []
    assert observe.spans().events() == []
    observe.enable()
    exe.run(main, feed=feed, fetch_list=[cost], scope=scope)
    assert built == [main]
    assert real(None) == {'program': 'main'}        # the default program


def test_a_span_that_fails_at_its_exit_does_not_keep_the_dispatch_lock():
    """``executor.lock``'s exit records a histogram; a series of that
    name under another type raises there, after the acquire. The lock
    comes back all the same, so the next dispatch does not hang."""
    exe, main, scope, feed, cost = _fit_a_line()
    exe.run(main, feed=feed, fetch_list=[cost], scope=scope)   # compiles
    observe.enable()
    observe.inc('executor.lock_seconds')        # a counter of that name
    with pytest.raises(TypeError):
        exe.run(main, feed=feed, fetch_list=[cost], scope=scope)
    assert exe._dispatch_lock.acquire(blocking=False)
    exe._dispatch_lock.release()
    observe.disable()
    assert exe.run(main, feed=feed, fetch_list=[cost], scope=scope)


def _lowered(exe, program, feed, fetch, scope=None):
    import jax
    fn, scope_vals, feed_vals = exe.compile_step(
        program, feed=feed, fetch_list=[fetch], scope=scope)
    return jax.jit(fn).lower(scope_vals, feed_vals,
                             np.int32(0)).as_text(debug_info=True)


def test_programs_and_ops_carry_names_a_trace_can_be_searched_by():
    eng = _engine(spec_k=2)
    mb, pps = eng.max_batch, eng.pages_per_seq
    feeds = {'lens': np.zeros((mb,), 'int32'),
             'tables': np.full((mb, pps), eng.num_blocks, 'int32'),
             'temps': np.zeros((mb,), 'float32'),
             'seeds': np.zeros((mb,), 'int32')}
    text = _lowered(
        eng._exe, eng._progs.decode,
        dict({'dec_' + k: v for k, v in feeds.items()},
             dec_tokens=np.zeros((mb,), 'int64')),
        eng._progs.decode_fetch, eng._scope)
    assert 'module @jit_decode_step' in text
    assert 'jit(decode_step)/paged_decode_step/' in text
    text = _lowered(
        eng._exe, eng._progs.verify,
        dict({'sv_' + k: v for k, v in feeds.items()},
             sv_tokens=np.zeros((mb, 3), 'int64')),
        eng._progs.verify_fetch, eng._scope)
    assert 'module @jit_spec_verify' in text
    # one Program, one module per prefill bucket
    for bucket in (2, 8):
        eng._run_prefill(np.zeros((1, bucket), 'int64'), 1, 0,
                         np.full((1, pps), eng.num_blocks, 'int32'),
                         0.0, 0)
        assert eng._progs.prefill.name == 'prefill_%d' % bucket
    text = _lowered(
        eng._exe, eng._progs.prefill,
        {'pf_ids': np.zeros((1, 8), 'int64'),
         'pf_len': np.ones((1,), 'int32'),
         'pf_cached': np.zeros((1,), 'int32'),
         'pf_table': np.full((1, pps), eng.num_blocks, 'int32'),
         'pf_temp': np.zeros((1,), 'float32'),
         'pf_seed': np.zeros((1,), 'int32')},
        eng._progs.prefill_fetch, eng._scope)
    assert 'module @jit_prefill_8' in text
    assert 'jit(prefill_8)/paged_prefill/' in text

    # a training program: forward, backward and optimizer ops
    exe, main, scope, feed, cost = _fit_a_line()
    text = _lowered(exe, main, feed, cost, scope)
    assert 'module @jit_train_step' in text
    for scope_name in ('jvp(mul)/dot_general',
                       'transpose(jvp(mul))/dot_general',
                       'jvp(square_error_cost)/', 'sgd/'):
        assert 'jit(train_step)/' + scope_name in text, scope_name
    text = _lowered(exe, main.clone(for_test=True), feed, cost, scope)
    assert 'module @jit_infer_step' in text
    assert 'jit(infer_step)/mul/dot_general' in text
    # a name is no part of the key: two programs of one name, two keys
    twin = main.clone()
    twin.name = main.name = 'same'
    exe.run(main, feed=feed, fetch_list=[cost], scope=scope)
    exe.run(twin, feed=feed, fetch_list=[cost], scope=scope)
    assert exe.last_cache_miss
    exe.run(twin, feed=feed, fetch_list=[cost], scope=scope)
    assert not exe.last_cache_miss


@pytest.mark.parametrize('lengths,per_row,read,held,off_counts', [
    # 4 slots x 4 pages of 4: the table's 16 columns fit one column
    # block, so a live row holds one pair; 3 pairs fill one iteration
    # of 8: 8 x 4 pages a layer gathered, 3 x 4 held, 2 layers
    ((5, 9, 2), 1, 2 * 8 * 4, 2 * 3 * 4, False),
    ((15,), 1, 2 * 8 * 4, 2 * 1 * 4, False),
    ((), 1, 0, 0, False),                 # nothing live: no pair runs
    # speculation, k + 1 = 3 rows a slot: 12 rows, 9 live -> 9 pairs,
    # two iterations
    ((5, 9, 2), 3, 2 * 2 * 8 * 4, 2 * 9 * 4, False),
    ((5, 9, 2), 1, 0, 0, True),           # observe off: nothing counted
])
def test_attn_pages_of_a_hand_built_batch(lengths, per_row, read, held,
                                          off_counts, never_started):
    """``decode.attn_pages_read`` is what the step's pairs of (row,
    column block) gather, eight an iteration, and ``_held`` what they
    hold, summed over the layers, beside the pages its tables can
    address."""
    if not off_counts:
        observe.enable()
    batch = []
    for i, length in enumerate(lengths):
        seq = Sequence(i + 1, [7] * length, 4, 0.0, i, None)
        seq.cache_len = length
        batch.append(seq)
    never_started._step_feeds(batch, per_row)
    counters = observe.snapshot()['counters']
    assert counters.get('decode.attn_pages_read', 0) == read
    assert counters.get('decode.attn_pages_held', 0) == held
    assert counters.get('decode.attn_pages_reachable', 0) == \
        (0 if off_counts else 2 * 4 * per_row * 4)


def test_live_tokens_of_a_hand_built_batch():
    observe.enable()
    eng = _engine()
    batch = []
    for i, length in enumerate((5, 9, 2)):
        seq = Sequence(i + 1, [7] * length, 4, 0.0, i, None)
        seq.cache_len = length
        batch.append(seq)
    lens, tables, temps, seeds = eng._step_feeds(batch)
    assert lens.tolist() == [5, 9, 2, 0]
    assert (tables == eng.num_blocks).all() and seeds.tolist() == [0, 1, 2, 0]
    hist = observe.histogram('decode.step_live_tokens')
    assert (hist.count(), hist.total()) == (1, 16.0)
    # and through the worker: both admitted before the first step, so
    # the steps attend over 5 + 9, then 6 + 10 positions
    eng.submit([1] * 5, max_new_tokens=3)
    eng.submit([2] * 9, max_new_tokens=3)
    eng.start()
    eng.shutdown()
    assert (hist.count(), hist.total()) == (3, 16.0 + 14 + 16)
