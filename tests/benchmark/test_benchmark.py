"""The benchmark's own arithmetic and its by-name resolution, off the
chip: the manifest is sound, every cell resolves to files, a new cell
is found as files alone, the trace reduction's interval arithmetic, the
load generator's schedule and clocking, percentiles, and the command's
behaviour without a TPU. No test here describes a TPU topology."""

import functools
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import loadgen, manifest, stats, tracelib  # noqa: E402
from benchmark import run as bench                         # noqa: E402

MANIFEST = manifest.load(REPO)
CELLS = [c['name'] for c in MANIFEST['workloads']]
TRAIN_CELL = 'tbig_nmt.train_seq128'      # cells are found by name
RUN = os.path.join(REPO, 'benchmark', 'run.py')


# ------------------------------------------------------------ manifest
# What a test holds of the manifest is a function of the manifest,
# ``shape_*(m)`` in each test file of this directory, by name and by
# membership, so that it holds of any manifest grown from the committed
# one by appending: ``test_the_next_cell_is_added_by_appending`` calls
# every one of them on such a copy.
def shape_the_manifest_meets_the_contract(m):
    assert manifest.problems(m) == []
    public = {k: v for k, v in m.items() if not k.startswith('_')}
    assert set(public) == {
        'command', 'paths', 'run_seconds', 'configs', 'workloads',
        'end_to_end', 'per_layer'}
    assert 1 <= m['run_seconds'] <= 51
    assert len(json.dumps(public, indent=2)) < 65536


def cell_resolves_to_files_by_name(m, cell):
    r = manifest.resolve(m, cell)
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['runner'] == os.path.join(
        m['_dir'], 'runners', r['config']['runner'] + '.py')
    assert r['config']['reference']
    # a cut is stated in both places, in the same order
    (entry,) = [c for c in m['configs']
                if c['name'] == r['cell']['config']]
    assert r['config']['reduced'] == entry['reduced']
    assert r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} >= {'setup_s'}
    assert len(r['end_to_end']) >= 2 and r['per_layer']
    for metric in r['per_layer']:
        assert callable(manifest.load_module(metric['reader']).read)


def shape_every_cell_resolves_to_files_by_name(m):
    for cell in m['workloads']:
        cell_resolves_to_files_by_name(m, cell['name'])


def pair_resolves_and_moves_what_the_cell_reports(m, name, cell, resolved):
    """One (metric, cell) pair the manifest lists: the cell resolves the
    metric to a data file with a ``doc`` and to its reader, and reports
    the end-to-end metric the entry moves."""
    (metric,) = [p for p in resolved['per_layer']
                 if p['entry']['name'] == name]
    assert metric['spec']['doc'] and os.path.isfile(metric['reader'])
    assert os.path.basename(metric['reader']) == \
        metric['spec']['reader'] + '.py'
    assert metric['entry']['moves'] in {
        e['name'] for e in resolved['end_to_end']}
    assert 'bound' not in metric['entry']


def shape_one_entry_a_reader(m):
    """No entry is another's copy under a second name: two entries whose
    data files are equal but for their ``doc`` move different end-to-end
    metrics, which the contract wants split (``train.recompiles`` /
    ``serve.recompiles``). An entry whose ``args`` carry one
    configuration's shapes stays that configuration's: a data file is
    found by the metric's name."""
    seen = {}
    for p in m['per_layer']:
        spec = manifest.read_json(os.path.join(
            m['_dir'], 'layer_metrics', p['name'] + '.json'))
        key = (json.dumps({k: v for k, v in spec.items() if k != 'doc'},
                          sort_keys=True), p['moves'])
        assert key not in seen, (p['name'], seen[key])
        seen[key] = p['name']


def test_manifest_meets_the_contract():
    shape_the_manifest_meets_the_contract(MANIFEST)
    assert os.path.getsize(os.path.join(REPO, 'BENCHMARK.json')) < 65536


def test_one_entry_a_reader():
    shape_one_entry_a_reader(MANIFEST)
    # every data file belongs to an entry: a copy's file went with it
    named = {p['name'] + '.json' for p in MANIFEST['per_layer']}
    assert set(os.listdir(os.path.join(
        REPO, 'benchmark', 'layer_metrics'))) == named
    # ... and every reader is some entry's
    used = {manifest.read_json(os.path.join(
        REPO, 'benchmark', 'layer_metrics', name))['reader'] + '.py'
        for name in named}
    assert {f for f in os.listdir(os.path.join(REPO, 'benchmark', 'readers'))
            if f.endswith('.py')} == used


def test_problems_are_found_when_planted():
    bad = json.loads(json.dumps({k: v for k, v in MANIFEST.items()
                                 if not k.startswith('_')}))

    def named(kind, name):
        (entry,) = [e for e in bad[kind] if e['name'] == name]
        return entry
    named('per_layer', 'train.mfu')['moves'] = 'no_such_metric'
    named('end_to_end', 'train_tokens_per_s')['unit'] = 'tokens per second'
    named('workloads', TRAIN_CELL)['name'] = 'has space'
    found = ' '.join(manifest.problems(bad))
    assert 'no_such_metric' in found and 'bad unit' in found \
        and 'has space' in found


@pytest.mark.parametrize('cell', CELLS)
def test_cell_resolves_to_files_by_name(cell):
    cell_resolves_to_files_by_name(MANIFEST, cell)


PAIRS = [(p['name'], c) for c in CELLS for p in MANIFEST['per_layer']
         if manifest.applies(p, c)]


@functools.lru_cache(maxsize=None)
def _resolved(cell):
    return manifest.resolve(MANIFEST, cell)


@pytest.mark.parametrize('name, cell', PAIRS)
def test_a_listed_pair_resolves_and_moves_what_the_cell_reports(name, cell):
    pair_resolves_and_moves_what_the_cell_reports(
        MANIFEST, name, cell, _resolved(cell))


def test_unknown_cell_is_an_error_that_names_the_cells():
    with pytest.raises(manifest.ManifestError, match=TRAIN_CELL):
        manifest.resolve(MANIFEST, 'no.such_cell')


def _copied_benchmark(tmp_path):
    """(root, benchmark directory) of a copy a test may add files to."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), root)
    bdir = os.path.join(root, 'benchmark')
    shutil.copytree(os.path.join(REPO, 'benchmark'), bdir,
                    ignore=shutil.ignore_patterns('__pycache__'))
    return root, bdir


def _add_toy_cell(root, bdir, runner, files, metric):
    """The cell ``toy.idle`` as a later PR would add it: a config, a
    traffic file, a reference, ``files`` (path under benchmark/ -> text:
    the runner, the reader, the metric's spec) and the manifest's
    entries, with one per-layer metric ``metric``."""
    files = dict({
        'configs/toy.json': json.dumps({
            'name': 'toy', 'runner': runner, 'reduced': [], 'model': {}}),
        'traffic/idle.json': json.dumps({'kind': 'idle', 'naps': 3}),
        'references/toy.py': 'ANSWER = 3\n'}, **files)
    for rel, text in files.items():
        with open(os.path.join(bdir, rel), 'w') as f:
            f.write(text)
    path = os.path.join(root, 'BENCHMARK.json')
    m = manifest.read_json(path)
    m['configs'].append({'name': 'toy', 'source': 'none', 'reduced': [],
                         'file': 'benchmark/configs/toy.json',
                         'why': 'throw-away'})
    m['workloads'].append({'name': 'toy.idle', 'config': 'toy',
                           'traffic': 'idle', 'chips': 1, 'why': 'x'})
    m['end_to_end'].append({'name': 'naps_per_s', 'unit': 'naps/s',
                            'better': 'higher', 'bound': 0.05,
                            'source': 'host_clock',
                            'workloads': ['toy.idle']})
    m['per_layer'].append({'name': metric, 'unit': 'count',
                           'better': 'higher', 'layer': 'toy',
                           'source': 'program_counter',
                           'moves': 'naps_per_s',
                           'workloads': ['toy.idle']})
    with open(path, 'w') as f:
        json.dump(m, f)


def test_a_new_cell_metric_reader_and_runner_are_found_as_files(
        tmp_path, capsys):
    """What a later PR does: new files, new entries, no edit."""
    root, bdir = _copied_benchmark(tmp_path)
    before = {p: open(os.path.join(dp, p), 'rb').read()
              for dp, _, fs in os.walk(bdir) for p in fs}
    _add_toy_cell(root, bdir, 'noop', {
        'runners/noop.py':
          'def run(ctx):\n'
          '    ctx.begin_window()\n'
          '    with ctx.span("bench.nap"):\n'
          '        pass\n'
          '    ctx.samples["naps"] = [ctx.traffic["naps"]]\n'
          '    ctx.end_window()\n'
          '    right = ctx.traffic["naps"] == ctx.reference.ANSWER\n'
          '    return {"correct": right, "attempted": 1, "failed": 0,\n'
          '            "end_to_end": {"naps_per_s": 1.0}}\n',
        'layer_metrics/toy.naps.json': json.dumps(
            {'reader': 'first_sample', 'args': {'gauge': 'naps'}}),
        'readers/first_sample.py':
          'def read(args, sources):\n'
          '    return sources["samples"][args["gauge"]][0]\n'},
        'toy.naps')

    grown = manifest.load(root)
    assert manifest.problems(grown) == []
    r = manifest.resolve(grown, 'toy.idle')
    assert r['runner'].endswith('runners/noop.py')
    assert r['reference'].endswith('references/toy.py')
    assert [p['entry']['name'] for p in r['per_layer']] == ['toy.naps']
    for trace, want in ((0, {'naps_per_s', 'setup_s'}), (1, {'toy.naps'})):
        assert bench.main(['--workload', 'toy.idle', '--seconds', '1',
                           '--trace', str(trace), '--rehearsal'],
                          root=root) == 0
        last = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert set(last['metrics']) == want and last['correct'] is True
    assert last['metrics']['toy.naps'] == {'value': 3, 'unit': 'count'}
    # nothing that was there was edited
    after = {p: open(os.path.join(dp, p), 'rb').read()
             for dp, _, fs in os.walk(bdir) for p in fs
             if '__pycache__' not in dp}
    assert all(after[p] == before[p] for p in before)
    # an old cell still resolves beside the new one
    assert manifest.resolve(grown, TRAIN_CELL)['cell']['name'] == TRAIN_CELL


def _shape_functions():
    """Every ``shape_*`` of the test files of this directory, by name.
    Found by a glob and not from a list, so that what a later PR's test
    file holds of the manifest is held of the grown one with no edit
    here."""
    import glob
    import importlib
    here = os.path.dirname(os.path.abspath(__file__))
    if here not in sys.path:
        sys.path.insert(0, here)
    out = {}
    for path in sorted(glob.glob(os.path.join(here, 'test_*.py'))):
        name = os.path.basename(path)[:-3]
        mod = importlib.import_module(name)
        for attr in sorted(vars(mod)):
            if attr.startswith('shape_') and callable(getattr(mod, attr)):
                out['%s::%s' % (name, attr)] = getattr(mod, attr)
    return out


def test_the_next_cell_is_added_by_appending(tmp_path):
    """What the next program PR hands in, on a copy of the manifest in
    memory whose files lie under ``tmp_path``: one more cell at the end
    of ``workloads`` and of the two end-to-end lists it reports, its name
    at the end of two shared per-layer lists, one more entry with its
    data file at the end of ``per_layer``. Nothing that was there is
    edited, and everything the test files hold of the manifest holds of
    the grown one."""
    root, bdir = _copied_benchmark(tmp_path)
    m = manifest.load(root)
    was = json.loads(json.dumps({k: v for k, v in m.items()
                                 if not k.startswith('_')}))
    files = {os.path.join(dp, p): open(os.path.join(dp, p), 'rb').read()
             for dp, _, fs in os.walk(bdir) for p in fs}
    cell = 'tbig_lm.chat_steady_appended'
    shutil.copy(os.path.join(bdir, 'traffic', 'chat_steady.json'),
                os.path.join(bdir, 'traffic', 'chat_steady_appended.json'))
    with open(os.path.join(bdir, 'layer_metrics',
                           'serve.requests_per_step_appended.json'), 'w') as f:
        json.dump({'reader': 'registry_ratio', 'args': {
            'counter': 'decode.requests_total', 'per': 'decode.steps_total'},
            'doc': 'Requests submitted per decode program.'}, f)
    m['workloads'].append({'name': cell, 'config': 'tbig_lm',
                           'traffic': 'chat_steady_appended', 'chips': 1,
                           'why': 'the replayed trace under another name'})
    joined = ['ttft_mean_ms', 'itl_mean_ms', 'serve.decode_step_ms',
              'serve.queue_wait_ms']
    for entry in m['end_to_end'] + m['per_layer']:
        if entry['name'] in joined:
            entry['workloads'].append(cell)
    m['per_layer'].append({
        'name': 'serve.requests_per_step_appended', 'unit': 'count',
        'better': 'higher', 'source': 'program_counter',
        'layer': 'decode engine', 'moves': 'itl_mean_ms',
        'workloads': [cell]})

    assert manifest.problems(m) == []
    for c in m['workloads']:
        manifest.resolve(m, c['name'])                  # every file found
    mine = {p['entry']['name']
            for p in manifest.resolve(m, cell)['per_layer']}
    assert mine == {'serve.decode_step_ms', 'serve.queue_wait_ms',
                    'serve.requests_per_step_appended'}
    for name in mine:       # the older pairs are cases of their own, above
        pair_resolves_and_moves_what_the_cell_reports(
            m, name, cell, manifest.resolve(m, cell))
    shapes = _shape_functions()
    assert len({name.split('::')[0] for name in shapes}) >= 6
    for name, holds in shapes.items():
        holds(m)
    # appended only: every list that was there is the head of what is
    for kind in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        for old, now in zip(was[kind], m[kind]):
            lists = {k for k in old if isinstance(old[k], list)}
            assert {k: v for k, v in old.items() if k not in lists} == \
                {k: v for k, v in now.items() if k not in lists}
            for k in lists:
                assert now[k][:len(old[k])] == old[k]
    assert all(open(path, 'rb').read() == was_there
               for path, was_there in files.items())


# --------------------------------------------------- interval arithmetic
EVENTS = [('fusion.1', 0, 40), ('all-reduce.3', 30, 30),
          ('fusion.2', 50, 20), ('all-gather.7', 90, 10)]


def test_union_and_idle_share():
    assert tracelib.merged([(5, 9), (0, 3), (2, 4), (9, 9)]) == \
        [(0, 4), (5, 9)]
    assert tracelib.busy_ns(EVENTS, 0, 100) == 80          # [0,70)+[90,100)
    assert tracelib.idle_share(EVENTS, 0, 100) == pytest.approx(0.2)
    assert tracelib.busy_ns(EVENTS, 60, 95) == 15          # clipped
    assert tracelib.idle_share([], 0, 100) == 1.0


def test_subtract_keeps_what_is_not_covered():
    assert tracelib.subtract([(0, 10), (20, 30)],
                             [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert tracelib.subtract([(0, 10)], []) == [(0, 10)]


def test_exposed_time_is_the_part_nothing_else_covers():
    rx = ['all-reduce', 'all-gather']
    assert tracelib.busy_ns(tracelib.matching(EVENTS, rx), 0, 100) == 40
    # all-reduce [30,60): fusion.1 covers to 40, fusion.2 from 50 -> 10;
    # all-gather [90,100) runs alone -> 10
    assert tracelib.exposed_ns(EVENTS, rx, 0, 100) == 20
    assert tracelib.exposed_ns(EVENTS, rx, 0, 50) == 10
    d = os.path.join(REPO, 'benchmark', 'readers')
    src = {'trace': {'window': (0, 100), 'first': EVENTS}, 'trace_steps': 2}
    share = manifest.load_module(os.path.join(d, 'trace_share.py'))
    # busy 0-70 and 90-100 = 80; the fusions cover 0-40 and 50-70 = 60
    assert share.read({'match': ['^fusion']}, src) == pytest.approx(75.0)
    assert share.read({'match': ['^fusion']}, {'trace': None}) is None
    # the committed patterns meet the names a chip's trace prints
    real = [('%copy.115 = f32[64,24,16,32,64]{4,3,2,1,0} copy(f32[64,24,16,'
             '32,64]{4,2,3,1,0} %bitcast.161)', 0, 30),
            ('%fusion.148 = f32[786432,64]{1,0} fusion(f32[1536,16,32,64]'
             '{3,2,1,0} %copy.1)', 30, 10),
            ('%slice-done.2 = f32[128,1024]{1,0} slice-done(%slice-start.2)',
             50, 10)]
    metrics = os.path.join(REPO, 'benchmark', 'layer_metrics')
    spec = manifest.read_json(os.path.join(
        metrics, 'serve.copy_busy_share.json'))
    assert share.read(spec['args'], {'trace': {
        'window': (0, 100), 'first': real}}) == pytest.approx(60.0)
    spec = manifest.read_json(os.path.join(metrics, 'train.copy_ms.json'))
    ops = manifest.load_module(os.path.join(d, 'trace_ops.py'))
    assert ops.read(spec['args'], {'trace': {
        'window': (0, 100), 'first': real}, 'trace_steps': 1}) == \
        pytest.approx(40 / 1e6)
    assert ops.read({'match': rx, 'mode': 'total'}, src) == \
        pytest.approx(40 / 1e6 / 2)
    assert ops.read({'match': rx, 'mode': 'exposed'}, src) == \
        pytest.approx(20 / 1e6 / 2)


def test_top_ops_and_idle_gaps_are_named():
    nested = EVENTS + [('while.5', 0, 70)]     # covers its body
    assert tracelib.top_ops(nested, 0, 100, 2) == \
        [['fusion.1', 40e-9], ['all-reduce.3', 30e-9]]
    host = [('bench.wait_oldest', 68, 25), ('other', 0, 100)]
    gaps = tracelib.idle_gaps(EVENTS, host, 0, 120,
                              ('bench.wait_oldest',), 5)
    assert gaps == [['bench.wait_oldest', 20e-9],
                    ['unattributed', 20e-9]]


def test_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(bench.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation('bench.dispatch'):
            jnp.ones((64, 64)).sum().block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    trace = tracelib.read_xplane(tracelib.find_xplane(str(tmp_path)))
    assert trace['devices'] == {}               # a CPU has no device plane
    assert tracelib.HOST_PLANE in trace['lines']
    lo, hi = tracelib.window_of(trace, bench.WINDOW_SPAN)
    assert hi - lo >= 10e6                      # the 10 ms nap, in ns
    inner = [ev for ev in trace['host'] if ev[0] == 'bench.dispatch']
    assert len(inner) == 1 and lo <= inner[0][1] < hi
    assert tracelib.find_xplane(str(tmp_path / 'nothing')) is None


# ---------------------------------------------------------- statistics
def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 50) == 50
    assert stats.percentile([3.0, 1.0, 2.0], 95) == 3.0
    assert stats.percentile([7.0], 50) == 7.0
    assert stats.percentile([], 95) is None
    assert stats.spread([98, 99, 100, 101, 102, 103]) == \
        pytest.approx(3.5 / 100.5)


def test_registry_readers_take_deltas_not_totals():
    before = {'counters': {'executor.cache_miss_total{kind=single}': 9},
              'histograms': {'decode.step_seconds':
                             {'sum': 1.0, 'count': 10}}}
    after = {'counters': {'executor.cache_miss_total{kind=single}': 9,
                          'executor.cache_miss_total{kind=multi}': 2,
                          'executor.cache_miss_total_x': 50},
             'histograms': {'decode.step_seconds':
                            {'sum': 1.6, 'count': 30},
                            'decode.step_seconds_x': {'sum': 9, 'count': 9}}}
    src = {'registry_before': before, 'registry_after': after}
    d = os.path.join(REPO, 'benchmark', 'readers')
    delta = manifest.load_module(os.path.join(d, 'registry_delta.py'))
    mean = manifest.load_module(os.path.join(d, 'registry_mean.py'))
    assert delta.read({'counter': 'executor.cache_miss_total'}, src) == 2
    assert mean.read({'histogram': 'decode.step_seconds', 'scale': 1000},
                     src) == pytest.approx(30.0)
    assert mean.read({'histogram': 'decode.none'}, src) is None
    off = {'registry_before': None, 'registry_after': None}
    assert delta.read({'counter': 'x'}, off) is None


def test_shape_functions_count_what_the_shapes_need():
    d = os.path.join(REPO, 'benchmark', 'shape_fns')
    flops = manifest.load_module(
        os.path.join(d, 'transformer_train_flops.py'))
    nmt = manifest.resolve(MANIFEST, TRAIN_CELL)['config']['model']
    per_token = flops.step_flops(
        1, 128, 128, nmt['vocab_size'], nmt['n_layer'], nmt['n_head'],
        nmt['d_key'], nmt['d_model'], nmt['d_inner']) / 128
    # 6 x the matmul parameters a target token meets (encoder counted
    # per source token, 1:1) + attention: 1.28 GFLOP
    assert per_token == pytest.approx(1.2819e9, rel=1e-3)
    peaks = manifest.read_json(os.path.join(REPO, 'benchmark',
                                            'peaks.json'))['devices']
    assert peaks['TPU v5 lite']['flops_bf16'] == 197e12
    src = {'measured': {'train_tokens_per_s': 76840.0}, 'cell':
           {'chips': 1}, 'config': {'model': nmt},
           'traffic': {'seq_len': 128}, 'peaks': peaks['TPU v5 lite'],
           'bench_dir': os.path.join(REPO, 'benchmark')}
    reader = manifest.load_module(os.path.join(
        REPO, 'benchmark', 'readers', 'shape_fn.py'))
    assert reader.read({'function': 'transformer_train_flops',
                        'peak': 'flops_bf16'}, src) == \
        pytest.approx(50.0, rel=1e-3)
    assert reader.read({'function': 'transformer_train_flops',
                        'peak': 'flops_bf16'},
                       dict(src, peaks=None)) is None
    live = manifest.load_module(os.path.join(d, 'decode_live_bytes.py'))
    lm = manifest.read_json(os.path.join(
        REPO, 'benchmark', 'configs', 'tbig_nmt.json'))['model']
    assert live.kv_bytes_per_token(lm) == 49152
    assert live.weight_bytes(lm) == 4 * (6 * (4 * 1024 * 1024
                                              + 2 * 1024 * 4096)
                                         + 2 * 32000 * 1024)


# ------------------------------------------------------ load generator
TRAFFIC = {'rate_rps': 20.0, 'prompt_len': [16, 512], 'preroll_s': 2,
           'answer_len': [16, 256], 'alpha': 1.3, 'pool_seed': 24}


def test_schedule_is_a_pure_function_of_the_seed():
    a = loadgen.schedule(TRAFFIC, 3000000007, 8.0)
    assert a == loadgen.schedule(TRAFFIC, 3000000007, 8.0)
    b = loadgen.schedule(TRAFFIC, 5, 8.0)
    assert a != b and len(a) == len(b) == 200
    # every seed sends the same lengths at the same instants; only the
    # prompts' tokens differ. 2 s of pre-roll, then the 8 s window
    assert [r[:4] for r in a] == [r[:4] for r in b]
    assert [r.token_seed for r in a] != [r.token_seed for r in b]
    assert sum(1 for r in a if r.due < 2.0) == 40
    assert a[0].due == 0.0 and a[40].due == 2.0 and all(
        x.due <= y.due for x, y in zip(a, a[1:])) and a[-1].due < 10.0
    assert [r.index for r in a] == list(range(200))
    assert all(16 <= r.prompt_len <= 512 and 16 <= r.answer_len <= 256
               for r in a)
    assert loadgen.prompt_tokens(a[3], 32000) == \
        loadgen.prompt_tokens(a[3], 32000)
    assert len(loadgen.prompt_tokens(a[3], 32000)) == a[3].prompt_len


class FakeClock(object):
    """Stands in for the ``time`` module inside ``loadgen``: a clock that
    only sleeping moves, so a stamp is exact whatever the host is doing."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        return self.now

    def sleep(self, seconds):
        assert seconds > 0
        self.now += seconds


class FakeStream(object):
    """Two tokens, 20 ms apart, the first 30 ms after submit."""

    def __init__(self, clock):
        self.clock = clock
        self.born = clock.perf_counter()
        self.given = 0


def fake_poll(stream):
    age = stream.clock.perf_counter() - stream.born
    ready = (age >= 0.03) + (age >= 0.05)
    tokens = [11, 12][stream.given:ready]
    stream.given = ready
    return tokens, ready == 2, None


def test_latency_is_clocked_from_due_and_lateness_is_reported(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(loadgen, 'time', clock)
    requests = [loadgen.Request(0, 0.00, 4, 2, 1),
                loadgen.Request(1, 0.01, 4, 2, 2),
                loadgen.Request(2, 0.02, 4, 2, 3)]

    def submit(request):
        if request.index == 0:
            clock.sleep(0.05)           # a stall in the system's intake
        if request.index == 2:
            raise RuntimeError('queue full')
        streams.append(FakeStream(clock))
        return streams[-1]

    streams = []
    t0 = clock.perf_counter()
    client = loadgen.drive(submit, fake_poll, requests, t0)
    assert client.live                  # one thread: nothing read yet
    assert client.finish(clock.perf_counter() + 5) == 0
    first, second, third = client.records
    assert first.complete and second.complete
    assert first.tokens == second.tokens == [11, 12]
    # the second request was due at 10 ms but sent after the 50 ms stall:
    # the lateness is reported, and its latency counts from when it was
    # due, so it includes the wait the stall imposed
    late = second.sent_at - second.due_at
    assert late == pytest.approx(0.04)
    assert second.ttft == second.token_at[0] - (t0 + 0.01)
    # tokens are stamped when polled, every 2 ms: each of the two at most
    # one poll after it exists (30 and 50 ms after the stream was made),
    # never before, never both in one poll; on this clock a poll is 2 ms
    # to the microsecond, so both sides of each bound are held
    poll = 0.002 + 1e-6
    for record, stream in zip((first, second), streams):
        for stamp, exists in zip(record.token_at, (0.03, 0.05)):
            assert -1e-6 <= stamp - (stream.born + exists) <= poll
        assert 0.02 - poll <= record.gaps[0] <= 0.02 + poll
    assert second.ttft == pytest.approx(late + 0.03, abs=poll)
    assert third.refused and not third.complete and third.ttft is None


# ------------------------------------------------------ the command
def _run(*args, **env):
    return subprocess.run(
        [sys.executable, RUN] + list(args), cwd=REPO, timeout=900,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS='cpu', **env))


def test_without_a_tpu_and_without_rehearsal_nothing_runs(capsys):
    with pytest.raises(SystemExit) as stop:
        bench.main(['--workload', TRAIN_CELL, '--seed', '1', '--seconds', '1',
                    '--trace', '0'])
    assert stop.value.code == 2
    said = capsys.readouterr()
    assert "found platform 'cpu'" in said.err
    assert '"metrics"' not in said.out and 'WINDOW' not in said.out


def test_rehearsal_prints_the_contract_line_and_no_cpu_time(tmp_path):
    r = _run('--workload', TRAIN_CELL, '--seed', '3000000001', '--seconds',
             '2', '--trace', '1', '--rehearsal', BENCH_RUN='7')
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    lines = r.stdout.splitlines()
    assert lines[0] == 'REHEARSAL platform=cpu'
    last = json.loads(lines[-1])
    assert {'correct', 'attempted', 'failed', 'metrics', 'device'} <= \
        set(last)
    assert last['rehearsal'] is True and last['correct'] is True
    window = json.loads([ln for ln in lines
                         if ln.startswith('WINDOW ')][-1][7:])
    # the inference clone agrees with the plain reference (bf16 amp)
    assert window['reference_agrees'] is True
    assert 0 < window['reference_logits_rel_rms'] < 0.02
    assert last['device']['platform'] == 'cpu'
    assert last['device']['memory_peak_bytes'] is None
    assert last['metrics']['train.recompiles'] == \
        {'value': 0, 'unit': 'count'}
    assert last['metrics']['train.dispatch_ms']['value'] is None
    assert 'train.mfu' not in last['metrics']   # no peak for a CPU
    assert not os.path.exists(os.path.join(REPO, '.bench_trace'))


def test_serve_cell_rehearses_in_process(capsys):
    cell = 'tbig_lm.chat_steady'
    if cell not in CELLS:
        pytest.skip('the manifest has no %s' % cell)
    assert bench.main(['--workload', cell, '--seed', '4', '--seconds',
                       '2', '--trace', '0', '--rehearsal']) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last['attempted'] > 10 and last['failed'] == 0
    assert last['correct'] is True      # also: served tokens = reference's
    assert set(last['metrics']) == {
        e['name'] for e in MANIFEST['end_to_end']
        if manifest.applies(e, cell)} > {'setup_s'}
    assert all(m['value'] is None for m in last['metrics'].values())


# ------------------------------------------------------- the references
def _reference(config):
    return manifest.load_module(os.path.join(
        REPO, 'benchmark', 'references', config + '.py'))


def test_lm_reference_holds_served_tokens_and_finds_a_dropped_sublayer():
    import jax
    from paddle_tpu.serving.decode import DecodeEngine, LMSpec
    ref = _reference('tbig_lm')
    engine = DecodeEngine(LMSpec(64, 2, 2, 8, 8, 16, 32), max_batch=2,
                          block_size=8, pages_per_seq=4, num_blocks=8,
                          max_prompt_len=16, prefix_cache=False)
    try:
        engine.warmup()
        engine.start()
        prompt = [5, 9, 33, 2, 17, 40, 8]
        answer = engine.generate(prompt, max_new_tokens=12, timeout=120)
        weights = jax.device_put(engine.export_weights())
    finally:
        engine.shutdown(drain=False)
    gaps, deviation = ref.token_gaps(weights, 2, prompt, answer, 32)
    assert len(gaps) == 12 and max(gaps) < 1e-4 and deviation > 0.1
    # the same tokens against a model without its attention sublayers
    broken = dict(weights)
    broken['lm_stack_slf_o.w'] = 0 * weights['lm_stack_slf_o.w']
    gaps, _ = ref.token_gaps(broken, 2, prompt, answer, 32)
    assert max(gaps) > 0.05


def test_nmt_reference_tolerance_tells_a_dropped_sublayer():
    import jax
    import numpy as np
    ref = _reference('tbig_nmt')
    tol = manifest.resolve(MANIFEST, 'tbig_nmt.train_seq128')[
        'config']['reference']['logits_rel_rms_tol']
    rng = np.random.RandomState(0)
    d, inner, vocab = 16, 32, 64

    def mat(*shape):
        return (rng.randn(*shape) / np.sqrt(shape[0])).astype('float32')
    w = {'out_proj.w': mat(d, vocab)}
    for side, subs in (('enc', ('slf',)), ('dec', ('slf', 'cross'))):
        w[side[:3].replace('enc', 'src').replace('dec', 'trg') + '_emb'] \
            = mat(vocab, d)
        for sub in subs:
            for m in 'qkv':
                w['%s_0_%s_%s.w' % (side, sub, m)] = mat(d, d)
            w['%s_0_%s_out.w' % (side, sub)] = mat(d, d)
        for i in range(len(subs) + 1):
            w['%s_0_pp%d_ln.w' % (side, i + 1)] = np.ones(d, 'float32')
            w['%s_0_pp%d_ln.b' % (side, i + 1)] = np.zeros(d, 'float32')
        w['%s_0_ffn_1.w' % side], w['%s_0_ffn_1.b' % side] = \
            mat(d, inner), np.zeros(inner, 'float32')
        w['%s_0_ffn_2.w' % side], w['%s_0_ffn_2.b' % side] = \
            mat(inner, d), np.zeros(d, 'float32')
    for name in ('src_emb', 'trg_emb'):
        w[name + '_pos_enc'] = mat(8, d)
    batch = {'src_word': rng.randint(1, vocab, (2, 8)),
             'src_length': np.array([8, 5]),
             'trg_word': rng.randint(1, vocab, (2, 8)),
             'lbl_word': rng.randint(1, vocab, (2, 8)),
             'lbl_weight': np.ones((2, 8), 'float32')}
    forward = jax.jit(ref.forward, static_argnums=(2, 3, 4, 5))
    logits, loss = forward(w, batch, 1, 2, 0.3, 0.1)
    assert logits.shape == (2, 8, vocab) and np.isfinite(float(loss))
    # causal: a later target token does not move an earlier position
    later = dict(batch, trg_word=batch['trg_word'].copy())
    later['trg_word'][:, -1] = 1
    moved = np.asarray(forward(w, later, 1, 2, 0.3, 0.1)[0] - logits)
    assert np.abs(moved[:, :-1]).max() == 0 and np.abs(moved[:, -1]).max() > 0
    # keys past src_length are masked
    padded = dict(batch, src_word=batch['src_word'].copy())
    padded['src_word'][1, 5:] = 7
    assert np.abs(np.asarray(
        forward(w, padded, 1, 2, 0.3, 0.1)[0] - logits)).max() == 0
    # dropping one sublayer is far outside the tolerance
    broken = dict(w, **{'dec_0_cross_out.w': 0 * w['dec_0_cross_out.w']})
    off = np.asarray(forward(broken, batch, 1, 2, 0.3, 0.1)[0] - logits)
    assert np.sqrt((off ** 2).mean()) / np.asarray(logits).std() > 5 * tol


def test_memory_is_arrays_plus_reserved_at_one_instant(capsys):
    got = bench.memory_fields([
        {'bytes_in_use': 10, 'bytes_reserved': 5, 'peak_bytes_in_use': 11,
         'peak_bytes_reserved': 5, 'bytes_limit': 100},
        {'bytes_in_use': 12, 'bytes_reserved': 7, 'peak_bytes_in_use': 20,
         'peak_bytes_reserved': 8, 'bytes_limit': 100}])
    assert got == {'memory_peak_bytes': 19, 'memory_arrays_peak_bytes': 20,
                   'memory_reserved_peak_bytes': 8,
                   'memory_limit_bytes': 100}
    assert bench.memory_fields([{}]) == {'memory_peak_bytes': None}
    assert capsys.readouterr().out.count('MEMORY') == 3


# ---------------------------------------------------- the traced tail
class _Profiler(object):
    """Stands in for jax.profiler's start and stop: what was called, and
    what the Context held at that instant."""

    def __init__(self, monkeypatch):
        import jax
        self.calls = []
        monkeypatch.setattr(jax.profiler, 'start_trace',
                            lambda *a, **k: self.calls.append('start'))
        monkeypatch.setattr(jax.profiler, 'stop_trace',
                            lambda: self.calls.append('stop'))


def _context(trace, seconds=0.2):
    import argparse
    args = argparse.Namespace(seed=0, seconds=seconds, trace=trace,
                              rehearsal=True)
    return bench.Context(manifest.resolve(MANIFEST, TRAIN_CELL), args, REPO)


@pytest.mark.parametrize('trace', [1, 0], ids=['traced', 'untraced'])
def test_tick_keeps_the_registry_as_the_traced_tail_begins(
        monkeypatch, trace):
    """One snapshot more, taken where ``t_trace`` is set and before the
    ``bench.window`` annotation opens, so that tail -> after brackets
    what ``trace['window']`` is cut from; an untraced run takes none and
    starts nothing."""
    from paddle_tpu import observe
    profiler = _Profiler(monkeypatch)
    ctx = _context(trace)
    taken = []

    def snapshot():
        taken.append((len(profiler.calls), ctx.t_trace is not None,
                      ctx._window_span is not None))
        return {'counters': {'n': len(taken)}}
    monkeypatch.setattr(observe, 'snapshot', snapshot)

    ctx.begin_window()
    ctx.tick()                          # the tail has not begun
    assert ctx.registry_tail is None and profiler.calls == []
    ctx.t_window -= 0.15                # 0.05 s left of 0.2: inside it
    ctx.tick()
    ctx.tick()                          # once only
    ctx.end_window()
    if not trace:
        assert taken == [] and profiler.calls == []
        assert ctx.registry == [None, None] and ctx.registry_tail is None
        assert ctx.t_trace is None
        return
    # before the window, as the tail began (profiler started, t_trace
    # set, the window's annotation not yet open), as the window ended
    assert taken == [(0, False, False), (1, True, False), (1, True, True)]
    assert profiler.calls == ['start', 'stop']
    assert [ctx.registry[0], ctx.registry_tail, ctx.registry[1]] == [
        {'counters': {'n': i}} for i in (1, 2, 3)]


def test_main_hands_the_tail_snapshot_to_the_readers(
        tmp_path, capsys, monkeypatch):
    """``sources['registry_tail']`` lies between ``registry_before`` and
    ``registry_after``, for a runner and a reader added as files."""
    from paddle_tpu import observe
    _Profiler(monkeypatch)
    root, bdir = _copied_benchmark(tmp_path)
    _add_toy_cell(root, bdir, 'ticker', {
        'runners/ticker.py':
          'def run(ctx):\n'
          '    ctx.begin_window()\n'
          '    while ctx.window_left() > 0:\n'
          '        ctx.tick()\n'
          '    ctx.end_window()\n'
          '    return {"correct": True, "attempted": 1, "failed": 0,\n'
          '            "end_to_end": {"naps_per_s": 1.0}}\n',
        'layer_metrics/toy.order.json': json.dumps(
            {'reader': 'snapshot_order', 'args': {}}),
        'readers/snapshot_order.py':
          'def read(args, sources):\n'
          '    return [sources[k]["counters"]["n"] for k in (\n'
          '        "registry_before", "registry_tail", "registry_after")]\n'},
        'toy.order')
    taken = []
    monkeypatch.setattr(observe, 'snapshot', lambda: taken.append(0) or {
        'counters': {'n': len(taken)}})
    assert bench.main(['--workload', 'toy.idle', '--seconds', '0.2',
                       '--trace', '1', '--rehearsal'], root=root) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last['metrics']['toy.order']['value'] == [1, 2, 3]


# ------------------------------------------------ a traced tail that is lost
def _chip_context(attempt, seconds=0.2):
    """A traced Context as a run on the chip has it (no rehearsal), the
    ``attempt`` as the supervisor hands it down (0: no supervisor)."""
    import argparse
    args = argparse.Namespace(seed=0, seconds=seconds, trace=1,
                              rehearsal=False, attempt=attempt)
    return bench.Context(manifest.resolve(MANIFEST, TRAIN_CELL), args, REPO)


@pytest.mark.parametrize('attempt,gives_up', [(1, True), (2, False),
                                              (0, False)],
                         ids=['first', 'last', 'unsupervised'])
@pytest.mark.parametrize('how', ['never_started', 'no_file',
                                 'no_device_line'])
def test_a_lost_trace_is_given_up_only_where_the_run_is_made_again(
        monkeypatch, tmp_path, capsys, attempt, gives_up, how):
    """The three ways a traced tail comes back without the device: the
    runner's thread never reached ``tick()`` inside it, the profiler
    wrote no file, the file has no ``XLA Ops`` line of the cell's chip.
    The first of two supervised attempts leaves by ``TraceLost`` as the
    window closes; the last, and a run with no supervisor, go on and
    carry the reason."""
    ctx = _chip_context(attempt)
    ctx._trace_dir = str(tmp_path / 'trace')
    if how != 'no_device_line':
        _Profiler(monkeypatch)              # starts and stops nothing
    ctx.begin_window()
    if how != 'never_started':
        ctx.t_window -= 0.15
        ctx.tick()
        import jax.numpy as jnp
        jnp.ones((8, 8)).sum().block_until_ready()
    if gives_up:
        with pytest.raises(bench.TraceLost):
            ctx.end_window()
    else:
        ctx.end_window()
    want = {'never_started': 'stood still', 'no_file': 'wrote no trace',
            'no_device_line': 'device ops of chips []'}[how]
    assert want in ctx.trace_lost
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith('TRACE_LOST ')]
    assert len(said) == 1
    assert json.loads(said[0][11:]) == {'why': ctx.trace_lost,
                                       'may_retry': gives_up}
    # what the window has to leave behind either way
    assert ctx.compiles_in_window == 0 and ctx.memory is not None


def test_a_trace_with_the_cells_chips_in_it_is_not_lost(monkeypatch, capsys):
    _Profiler(monkeypatch)
    monkeypatch.setattr(tracelib, 'find_xplane', lambda d: __file__)
    monkeypatch.setattr(tracelib, 'chips_with_ops', lambda p: [0])
    ctx = _chip_context(1)
    ctx.begin_window()
    ctx.t_window -= 0.15
    ctx.tick()
    ctx.end_window()
    assert ctx.trace_lost is None
    out = capsys.readouterr().out
    assert 'TRACE_LOST' not in out
    session = json.loads([ln for ln in out.splitlines()
                          if ln.startswith('TRACE_SESSION ')][0][14:])
    assert session['chips_with_ops'] == [0]
    assert session['start_s'] >= 0 and session['stop_s'] >= 0


class _Child(object):
    def __init__(self, code, log, argv):
        self.code = code
        log.append(argv)

    def wait(self):
        return self.code

    def poll(self):
        return self.code


@pytest.mark.parametrize('codes,runs,leaves', [
    ([0], 1, 0), ([bench.TRACE_LOST, 0], 2, 0), ([2], 1, 2),
    ([bench.TRACE_LOST, 1], 2, 1), ([-9], 1, 137),
    ([bench.TRACE_LOST, bench.TRACE_LOST, 0], 2, bench.TRACE_LOST)],
    ids=['kept', 'lost_once', 'no_chip', 'lost_then_failed', 'killed',
         'never_a_third'])
def test_supervise_makes_a_run_that_lost_its_trace_again_once(
        monkeypatch, codes, runs, leaves):
    import signal
    monkeypatch.setattr(signal, 'signal', lambda *a: None)
    spawned, left = [], list(codes)
    argv = ['--workload', 'x.y', '--seed', '7', '--seconds', '51',
            '--trace', '1']
    got = bench.supervise(
        argv, spawn=lambda cmd: _Child(left.pop(0), spawned, cmd))
    assert got == leaves and len(spawned) == runs
    for n, cmd in enumerate(spawned, 1):
        assert cmd == [sys.executable, RUN] + argv + ['--attempt', str(n)]


def test_a_traced_run_goes_through_a_child_that_finds_no_tpu():
    """``--trace 1`` with no ``--rehearsal``: the parent starts no jax,
    its child looks for the chip, finds a CPU and leaves with 2; no
    second attempt, no result line. ``--attempt`` is not in the help."""
    r = _run('--workload', TRAIN_CELL, '--seed', '1', '--seconds', '1',
             '--trace', '1')
    assert r.returncode == 2
    assert r.stderr.count("found platform 'cpu'") == 1
    assert '"metrics"' not in r.stdout and 'made again' not in r.stderr
    assert '--attempt' not in _run('--help').stdout


def test_chips_with_ops_of_a_cpu_trace_is_empty(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert tracelib.chips_with_ops(tracelib.find_xplane(str(tmp_path))) == []


def test_a_traced_tail_has_a_directory_of_its_own(monkeypatch):
    """Two runs of one checkout at a time (the tests' workers) do not
    share a trace directory: it is made as the tail begins, outside the
    checkout, and gone once the trace is taken."""
    _Profiler(monkeypatch)
    dirs = []
    for _ in range(2):
        ctx = _context(1)
        assert ctx._trace_dir is None and ctx.take_trace() is None
        ctx.begin_window()
        ctx.t_window -= 0.15
        ctx.tick()
        ctx.end_window()
        assert not os.path.abspath(ctx._trace_dir).startswith(REPO + os.sep)
        dirs.append(ctx._trace_dir)
        os.makedirs(ctx._trace_dir)             # as the profiler would
        assert ctx.take_trace() is None         # with no file in it
        assert not os.path.exists(ctx._trace_dir)
    assert dirs[0] != dirs[1]
