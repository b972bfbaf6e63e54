"""Telemetry subsystem (paddle_tpu.observe): registry semantics, JSONL
round-trip, Chrome-trace span nesting, the instrumented Trainer/Executor
path (compile-cache miss-then-hit, phase timings, reader/fault counters),
the disabled-path overhead bound, and the profiler-on-observe rebuild."""

import json
import os
import re
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _observe_clean():
    """Leave the global telemetry state exactly as tests expect: gate
    off, sinks unset, registry/spans/goodput empty."""
    from paddle_tpu import observe
    yield
    observe._SINK['path'] = None
    observe._SINK['trace_path'] = None
    observe.disable()
    observe.reset()


# ------------------------------------------------------------- registry
def test_counter_gauge_histogram_semantics():
    from paddle_tpu.observe.registry import Registry

    reg = Registry()
    c = reg.counter('requests_total')
    c.inc()
    c.inc(2)
    c.inc(5, shard='a')
    assert c.value() == 3
    assert c.value(shard='a') == 5
    assert reg.counter('requests_total') is c  # get-or-create

    g = reg.gauge('depth')
    g.set(4)
    g.set(7)
    g.set(1.5, ring='x')
    assert g.value() == 7
    assert g.value(ring='x') == 1.5
    assert g.value(ring='missing', default=-1) == -1

    h = reg.histogram('latency')
    for v in range(100):
        h.observe(float(v))
    st = h.stats()
    assert st['count'] == 100
    assert st['sum'] == sum(range(100))
    assert st['min'] == 0.0 and st['max'] == 99.0
    assert abs(st['p50'] - 50.0) <= 2.0
    assert abs(st['p95'] - 95.0) <= 2.0
    # labeled series are independent
    h.observe(1000.0, phase='feed')
    assert h.stats(phase='feed')['count'] == 1
    assert h.stats()['count'] == 100

    with pytest.raises(TypeError):
        reg.gauge('requests_total')   # name already a counter


def test_histogram_reservoir_bounded():
    from paddle_tpu.observe.registry import RESERVOIR_CAP, Registry

    reg = Registry()
    h = reg.histogram('h')
    n = RESERVOIR_CAP + 500
    for v in range(n):
        h.observe(float(v))
    st = h.stats()
    assert st['count'] == n          # exact stats survive the cap
    assert st['max'] == float(n - 1)
    lk = ()
    assert len(h._values[lk].samples) == RESERVOIR_CAP


def test_registry_jsonl_round_trip(tmp_path):
    from paddle_tpu.observe.registry import Registry

    reg = Registry()
    reg.counter('c').inc(3, shard='a')
    reg.gauge('g').set(1.5)
    h = reg.histogram('h')
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    path = str(tmp_path / 'm.jsonl')
    with open(path, 'a') as f:
        f.write(reg.to_json_line(ts=1.0, kind='snapshot') + '\n')
        f.write(reg.to_json_line(ts=2.0, kind='summary') + '\n')
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    rec = lines[-1]
    assert rec['kind'] == 'summary'
    assert rec['counters']['c{shard=a}'] == 3
    assert rec['gauges']['g'] == 1.5
    st = rec['histograms']['h']
    assert st['count'] == 3 and st['sum'] == 6.0
    assert st['min'] == 1.0 and st['max'] == 3.0
    # the summary table renders every metric
    table = reg.summary_table()
    assert 'c{shard=a}' in table and 'g' in table and 'h' in table


# ---------------------------------------------------------------- spans
def test_chrome_trace_valid_nested(tmp_path):
    from paddle_tpu import observe

    trace = str(tmp_path / 'trace.json')
    observe.enable(trace=trace)
    with observe.span('outer', phase='x'):
        time.sleep(0.002)
        with observe.span('inner'):
            time.sleep(0.002)
        with observe.span('inner2'):
            pass
        time.sleep(0.001)
    observe.disable()

    doc = json.load(open(trace))          # valid JSON or this raises
    # the collector's pauses are spans too, whenever one strikes
    evs = [e for e in doc['traceEvents'] if e['name'] != 'host.gc']
    assert len(evs) == 3
    by_name = {e['name']: e for e in evs}
    for e in evs:
        assert e['ph'] == 'X'
        assert set(('name', 'ts', 'dur', 'pid', 'tid')) <= set(e)
    outer, inner = by_name['outer'], by_name['inner']
    assert outer['tid'] == inner['tid']
    # correctly nested: inner lies inside outer on the same track
    assert inner['ts'] >= outer['ts'] - 1
    assert inner['ts'] + inner['dur'] <= outer['ts'] + outer['dur'] + 1
    assert by_name['inner2']['ts'] >= inner['ts'] + inner['dur'] - 1
    assert outer['args'] == {'phase': 'x'}


# ------------------------------------------------- instrumented trainer
def _tiny_trainer(fluid, ckpt_dir=None):
    def train_func():
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1)
        return fluid.layers.mean(fluid.layers.square_error_cost(pred, y))

    def opt():
        return fluid.optimizer.SGD(learning_rate=0.01)

    cfg = None
    if ckpt_dir is not None:
        cfg = fluid.CheckpointConfig(ckpt_dir, async_save=False,
                                     nan_policy=None)
    return fluid.Trainer(train_func, opt, place=fluid.CPUPlace(),
                         checkpoint_config=cfg)


def _label_keys(rendered):
    m = re.search(r'\bkey=([0-9a-f]{8})', rendered)
    return m.group(1) if m else None


def test_trainer_two_steps_miss_then_hit_and_jsonl(tmp_path):
    """The acceptance-criteria e2e: 2-step CPU train run with observe on
    emits (a) a metrics JSONL with compile-cache hit/miss counts,
    per-phase timings, and reader/fault counters, (b) a Chrome trace of
    valid nested spans; the step program compiles exactly once then
    hits."""
    import paddle_tpu as fluid
    from paddle_tpu import observe
    from paddle_tpu.fault import inject
    from paddle_tpu.reader.decorator import retry

    jsonl = str(tmp_path / 'metrics.jsonl')
    trace = str(tmp_path / 'trace.json')
    observe.enable(jsonl=jsonl, trace=trace)

    trainer = _tiny_trainer(fluid, ckpt_dir=str(tmp_path / 'ckpt'))
    rng = np.random.RandomState(0)
    batches = [{'x': rng.rand(8, 4).astype('float32'),
                'y': rng.rand(8, 1).astype('float32')} for _ in range(2)]

    def base_reader():
        for b in batches:
            yield b

    # one injected transient reader failure -> reader.retry_total fires
    reader = retry(inject.flaky(base_reader, fail_times=1, fail_after=1),
                   tries=3, backoff=0)
    events = []
    trainer.train(1, reader=reader, event_handler=events.append)
    observe.disable()

    snap = observe.snapshot()
    counters = snap['counters']

    # exactly 1 compile-cache miss then 1 hit for the step program (the
    # startup program is its own key and never re-runs)
    misses = {k: v for k, v in counters.items()
              if k.startswith('executor.cache_miss_total')}
    hits = {k: v for k, v in counters.items()
            if k.startswith('executor.cache_hit_total')}
    assert sum(hits.values()) == 1, (misses, hits)
    step_key = _label_keys(list(hits)[0])
    miss_for_step = [v for k, v in misses.items()
                     if _label_keys(k) == step_key]
    assert miss_for_step == [1], (misses, hits)
    assert len(misses) == 2        # startup + step program

    # reader/fault counters
    assert counters.get('reader.retry_total') == 1
    assert counters.get('fault.checkpoint_saves_total') == 1

    # per-phase step timings
    hists = snap['histograms']
    for phase in ('feed', 'compute', 'fetch'):
        name = 'trainer.phase_seconds{phase=%s}' % phase
        assert hists[name]['count'] == 2, (name, hists.keys())
    assert hists['trainer.step_seconds']['count'] == 2
    assert hists['fault.checkpoint_save_seconds{mode=sync}']['count'] == 1
    # compile wall per key: one first-dispatch record per cache miss
    fd = [v for k, v in hists.items()
          if k.startswith('executor.first_dispatch_seconds')]
    assert len(fd) == 2 and all(st['count'] == 1 for st in fd)

    # the JSONL on disk round-trips with the same content
    recs = [json.loads(l) for l in open(jsonl)]
    assert recs, 'no metrics JSONL lines written'
    final = recs[-1]
    assert final['kind'] == 'summary'
    assert any(k.startswith('executor.cache_hit_total')
               for k in final['counters'])
    assert any(k.startswith('trainer.phase_seconds')
               for k in final['histograms'])
    assert final['counters'].get('reader.retry_total') == 1
    assert 'run.goodput' in final['gauges']

    # EndStepEvent carries wall_time + telemetry
    ends = [e for e in events
            if isinstance(e, fluid.trainer.EndStepEvent)]
    assert len(ends) == 2
    for e in ends:
        assert e.wall_time > 0
        assert 'steps_per_sec_ema' in e.telemetry
    assert ends[-1].telemetry['goodput'] is not None

    # Chrome trace: valid JSON, nested spans (executor.trace inside the
    # first trainer.step)
    doc = json.load(open(trace))
    evs = doc['traceEvents']
    steps = [e for e in evs if e['name'] == 'trainer.step']
    traces = [e for e in evs if e['name'] == 'executor.trace']
    assert len(steps) == 2 and traces
    first = min(steps, key=lambda e: e['ts'])
    tr = traces[-1]   # the step program's trace (startup ran un-spanned)
    assert first['ts'] - 1 <= tr['ts']
    assert tr['ts'] + tr['dur'] <= first['ts'] + first['dur'] + 1


def test_guard_counters():
    import paddle_tpu as fluid  # noqa: F401  (platform boot)
    from paddle_tpu import observe
    from paddle_tpu.fault.guards import BadStepError, BadStepGuard

    observe.enable()
    g = BadStepGuard('raise')
    assert g.handle(np.float32(1.0), 1) == 'ok'
    with pytest.raises(BadStepError):
        g.handle(np.float32(np.nan), 2)
    assert observe.get_counter('fault.bad_steps_total') == 1
    assert observe.get_counter('fault.guard_triggers_total',
                               policy='raise', action='raise') == 1


# ------------------------------------------------------------- overhead
def test_disabled_path_overhead():
    from paddle_tpu import observe

    observe.disable()
    assert not observe.enabled()
    n = 100000
    # warm up
    for _ in range(1000):
        observe.inc('x')
    t0 = time.perf_counter()
    for _ in range(n):
        observe.inc('executor.cache_hit_total')
        observe.record('trainer.step_seconds', 1.0)
        observe.set_gauge('g', 1)
    dt = (time.perf_counter() - t0) / (3 * n)
    # one global read + return per call; generous bound for slow CI
    assert dt < 2e-6, 'disabled observe call costs %.3gs' % dt
    # and nothing was recorded
    assert observe.snapshot()['counters'] == {}


# ------------------------------------------------------------- profiler
def test_profiler_record_event_gated_and_registry_backed(tmp_path):
    from paddle_tpu import observe, profiler

    profiler.reset_profiler()
    with profiler.record_event('idle'):
        pass
    # not started: nothing recorded anywhere (the old bug appended to a
    # module list unconditionally)
    assert observe.registry().metrics('profiler.') == []

    profiler.start_profiler('All')
    with profiler.record_event('work'):
        time.sleep(0.001)
    with profiler.record_event('work'):
        pass
    path = str(tmp_path / 'profile.txt')
    profiler.stop_profiler(profile_path=path)
    text = open(path).read()
    assert 'work' in text
    row = [l for l in text.splitlines() if l.startswith('work')][0]
    assert re.search(r'\s2\s', row), row   # 2 calls aggregated
    # one substrate: the event is an observe histogram
    h = observe.registry().histogram('profiler.work')
    assert h.count() == 2

    # reset_profiler clears the observe registry too
    observe.registry().counter('other').inc()
    profiler.reset_profiler()
    assert observe.snapshot()['counters'] == {}
    assert observe.registry().metrics('profiler.') == []


def test_profiler_summarize_format_preserved():
    from paddle_tpu import profiler

    profiler.reset_profiler()
    profiler.start_profiler()
    with profiler.record_event('a'):
        time.sleep(0.002)
    with profiler.record_event('b'):
        pass
    s = profiler.summarize()
    profiler._active = False
    lines = s.splitlines()
    assert lines[0].split() == ['Event', 'Total(s)', 'Calls', 'Avg(s)']
    # sorted by total descending: the slept event first
    assert lines[1].startswith('a')


# -------------------------------------------------------- report CLI
def test_metrics_report_cli(tmp_path):
    """tools/metrics_report.py on a real JSONL: human table + --json."""
    import subprocess

    from paddle_tpu import observe

    jsonl = str(tmp_path / 'm.jsonl')
    observe.enable(jsonl=jsonl)
    observe.inc('executor.cache_miss_total', kind='single', key='deadbeef')
    for v in (0.01, 0.02, 0.03):
        observe.record('trainer.step_seconds', v)
    observe.set_gauge('run.goodput', 0.75)
    observe.set_gauge('trainer.mfu', 0.42)
    observe.flush()
    observe._SINK['path'] = None
    observe.disable()

    tool = os.path.join(REPO, 'tools', 'metrics_report.py')
    r = subprocess.run([sys.executable, tool, jsonl],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert 'trainer.step_seconds' in r.stdout
    assert 'P95' in r.stdout
    assert 'MFU 42.00%' in r.stdout and 'goodput 75.00%' in r.stdout

    r = subprocess.run([sys.executable, tool, jsonl, '--json'],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc['mfu'] == 0.42 and doc['goodput'] == 0.75
    st = doc['histograms']['trainer.step_seconds']
    assert st['count'] == 3 and st['max'] == 0.03

    # empty/garbage file: clean failure, not a traceback
    bad = str(tmp_path / 'empty.jsonl')
    open(bad, 'w').close()
    r = subprocess.run([sys.executable, tool, bad],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1


# ------------------------------------------------------------- mfu
def test_device_peak_flops_is_a_table_lookup(monkeypatch):
    """Keyed by the device_kind string the chip prints; an unknown TPU
    is an error, a CPU has no peak, and no env var overrides either."""
    import collections

    from paddle_tpu.observe.mfu import device_peak_flops

    Dev = collections.namedtuple('Dev', 'platform device_kind')
    monkeypatch.setenv('PADDLE_TPU_PEAK_TFLOPS', '100')
    monkeypatch.setenv('BENCH_PEAK_TFLOPS', '100')
    assert device_peak_flops(Dev('tpu', 'TPU v5 lite')) == 197e12
    with pytest.raises(KeyError, match='TPU v9'):
        device_peak_flops(Dev('tpu', 'TPU v9'))
    assert device_peak_flops(Dev('cpu', 'cpu')) is None
    assert device_peak_flops() is None       # the suite runs on CPU


def test_mfu_and_goodput_accounting():
    from paddle_tpu import observe
    from paddle_tpu.observe.mfu import GoodputTracker

    gp = GoodputTracker()
    gp.begin()
    gp.step(0.5, steps=5)
    gp.overhead('compile', 0.1)
    reg = observe.registry()
    gp.publish(reg)
    snap = reg.snapshot()
    assert snap['gauges']['run.productive_steps'] == 5
    assert snap['gauges']['run.overhead_seconds{kind=compile}'] == \
        pytest.approx(0.1)
    assert 0.0 < snap['gauges']['run.goodput'] <= 1.0


def test_cost_analysis_flops_forms():
    from paddle_tpu.observe.mfu import cost_analysis_flops

    assert cost_analysis_flops({'flops': 12.0}) == 12.0
    assert cost_analysis_flops({}) is None
    assert cost_analysis_flops('garbage') is None
