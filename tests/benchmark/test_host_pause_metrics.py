"""The eight per-layer metrics of PR 56: the executor's phases of a
decode step, the write-back of a training step, and the garbage
collector's count and cost in a traced run. Their entries and data
files, ``ring_gap_cover`` over a ``host.gc`` span on a synthetic trace
(no entry reads it: the share was 0 in every cell), and a traced
rehearsal in which the two counts read a number."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402
from benchmark import run as bench  # noqa: E402
from paddle_tpu import observe  # noqa: E402

MANIFEST = manifest.load(REPO)
# the last entry of per_layer that stood before these
ACCEPTED_LAST = 'serve.dsa_step_hbm_share'
TRAIN = ['tbig_nmt.train_seq128']
SERVING = ['tbig_lm.chat_steady', 'command_a_plus.mixed_len_steady',
           'dots3_note.long_ctx_steady', 'kimi_k2_6.doc_qa_sessions',
           'mellum2_12b.repo_ctx_steady',
           'granite_4_0_h_micro.chat_long_answers',
           'longcat_flash_chat.chat_decode_heavy',
           'glm_5_2.long_ctx_long_answers']
STEP = 'executor.%s_seconds{program=decode_step}'
FULL = '{generation=2}'
# in the order of the entries: name, reader, unit, source, the series read
EIGHT = [
    ('serve.step_exe_lookup_ms', 'registry_mean', 'ms', 'program_span',
     [STEP % 'lookup']),
    ('serve.step_exe_prepare_ms', 'registry_mean', 'ms', 'program_span',
     [STEP % 'prepare']),
    ('serve.step_exe_enqueue_ms', 'registry_mean', 'ms', 'program_span',
     [STEP % 'enqueue']),
    ('serve.gc_ms_per_step', 'registry_ratio', 'ms', 'program_span',
     ['host.gc_seconds_total', 'decode.steps_total']),
    ('serve.gc_full_pauses', 'registry_delta', 'count', 'program_counter',
     ['host.gc_total' + FULL]),
    ('train.exe_writeback_ms', 'registry_mean', 'ms', 'program_span',
     ['executor.writeback_seconds']),
    ('train.gc_ms_per_step', 'registry_ratio', 'ms', 'program_span',
     ['host.gc_seconds_total', 'executor.cache_hit_total']),
    ('train.gc_full_pauses', 'registry_delta', 'count', 'program_counter',
     ['host.gc_total' + FULL]),
]
BY_NAME = {row[0]: row for row in EIGHT}


def cells_of(name):
    return TRAIN if name.startswith('train.') else SERVING


PAIRS = [(row[0], cell) for row in EIGHT for cell in cells_of(row[0])]


@pytest.fixture(autouse=True)
def _observe_clean():
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


# ------------------------------------------------------- the manifest
def entry_lists_the_cell(m, name, cell):
    """One (metric, cell) pair: the entry is found by its name, the cell
    is on its list and reports what the entry moves, and the data file
    names the reader and the series."""
    _, reader, unit, source, series = BY_NAME[name]
    (entry,) = [p for p in m['per_layer'] if p['name'] == name]
    assert cell in entry['workloads']
    assert entry['unit'] == unit and entry['source'] == source
    assert entry['layer'] == 'executor' and entry['better'] == 'lower'
    assert entry['moves'] == ('train_tokens_per_s'
                              if name.startswith('train.')
                              else 'itl_mean_ms')
    resolved = manifest.resolve(m, cell)
    assert entry['moves'] in {e['name'] for e in resolved['end_to_end']}
    (metric,) = [r for r in resolved['per_layer']
                 if r['entry']['name'] == name]
    spec = metric['spec']
    assert spec['reader'] == reader and spec['doc']
    assert os.path.basename(metric['reader']) == reader + '.py'
    args = spec['args']
    assert [v for v in (args.get('histogram'), args.get('counter'),
                        args.get('per')) if v] + args.get('spans', []) \
        == series
    if unit == 'ms':
        assert args['scale'] == 1000


def shape_the_eight_are_entries_over_their_cells(m):
    """What this file holds of the manifest, for any manifest that has
    grown from the committed one (test_benchmark.py calls every
    ``shape_*`` of the test files on such a copy)."""
    assert manifest.problems(m) == []
    for name, cell in PAIRS:
        entry_lists_the_cell(m, name, cell)


def test_the_eight_were_appended_in_their_order():
    assert manifest.problems(MANIFEST) == []
    names = [p['name'] for p in MANIFEST['per_layer']]
    # after everything the accepted benchmark had, and in their order
    # relative to each other (found by name: where they stand in the list
    # is not held)
    assert [n for n in names if n in BY_NAME] == [row[0] for row in EIGHT]
    assert names.index(ACCEPTED_LAST) < names.index(EIGHT[0][0])


@pytest.mark.parametrize('name, cell', PAIRS)
def test_an_entry_lists_its_cells_and_moves_what_they_report(name, cell):
    entry_lists_the_cell(MANIFEST, name, cell)


def _read(name, sources):
    """What the metric's reader reads of ``sources`` with the arguments
    of its data file."""
    spec = manifest.read_json(os.path.join(
        REPO, 'benchmark', 'layer_metrics', name + '.json'))
    return manifest.load_module(os.path.join(
        REPO, 'benchmark', 'readers', spec['reader'] + '.py')).read(
            spec['args'], sources)


def test_the_series_are_the_ones_the_program_feeds():
    """The data files' exact keys against a registry the executor and
    the collector have fed."""
    import gc
    import numpy as np
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[3], dtype='float32')
        out = fluid.layers.fc(input=x, size=2)
    main.name = 'decode_step'
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.scope.Scope()
    exe.run(startup, scope=scope)
    observe.enable()
    before = observe.snapshot()
    for _ in range(3):
        exe.run(main, feed={'x': np.zeros((2, 3), 'float32')},
                fetch_list=[out], scope=scope)
        observe.inc('decode.steps_total')
    gc.collect()
    sources = {'registry_before': before,
               'registry_after': observe.snapshot(), 'trace': None}
    read = {row[0]: _read(row[0], sources) for row in EIGHT}
    assert all(v is not None and v >= 0 for v in read.values()), read
    assert read['serve.gc_full_pauses'] == read['train.gc_full_pauses'] >= 1
    # the forced collection is among the seconds counted, three steps' worth
    full = sources['registry_after']['histograms'][
        'host.gc_seconds{generation=2}']['sum']
    assert 1000 * full <= 3 * read['serve.gc_ms_per_step'] + 1e-9
    # a program without the series (the parent): nothing, or none counted
    bare = {'registry_before': {}, 'registry_after': {}, 'trace': None}
    for row in EIGHT:
        assert _read(row[0], bare) in (None, 0)


# -------------------------------- a pause over an idle gap of the trace
BASE = 7000.0                       # the ring's clock, seconds
OFFSET = 987_654_321_012_345        # the trace's clock stands this far off


def _ns(ms):
    return int((observe.spans()._epoch0 + BASE) * 1e9 + ms * 1e6) + OFFSET


def _idle_under_gc(sources):
    """``ring_gap_cover``, the reader the benchmark has, over the pauses'
    span: what a data file with these arguments would read."""
    return manifest.load_module(os.path.join(
        REPO, 'benchmark', 'readers', 'ring_gap_cover.py')).read(
            {'spans': ['^host\\.gc$']}, sources)


def test_idle_under_a_pause_is_the_pauses(capsys):
    """A window of 10..100 ms whose device idles 12..50 and 52..98; 30
    steps that both records hold place the ring; a collection of 20 ms
    struck inside an enqueue at 60..80."""
    ring = observe.spans()
    both = [('decode.step', 13.0 + i, 13.7 + 1.003 * i) for i in range(30)]
    for name, a, b in both + [('executor.enqueue', 55.0, 85.0)]:
        ring.add_span(name, BASE + a / 1e3, BASE + b / 1e3)
    ring.add_span('host.gc', BASE + 0.060, BASE + 0.080,
                  {'generation': 2, 'collected': 0, 'uncollectable': 0})
    host = [(name, _ns(a), int((b - a) * 1e6)) for name, a, b in both]
    device = [('%fusion.1', _ns(10), 2_000_000),
              ('%fusion.2', _ns(50), 2_000_000),
              ('%fusion.3', _ns(98), 2_000_000)]
    sources = {'trace': {'first': device, 'host': host,
                         'window': (_ns(10), _ns(100))},
               'registry_before': {}, 'registry_after': {}}
    assert _idle_under_gc(sources) == pytest.approx(
        100 * 20 / 84.0, abs=0.02)
    lines = capsys.readouterr().out.splitlines()
    (cover,) = [json.loads(ln[11:]) for ln in lines
                if ln.startswith('IDLE_COVER ')]
    # the longest gap, 52..98, lies under the enqueue and the pause in it
    assert [u[0] for u in cover['longest_gap']['under']] == \
        ['executor.enqueue', 'host.gc']
    # and the harness's gap rule names the pause once ^host\. is admitted
    from benchmark import tracelib
    placed = [(n, s, e - s) for n, s, e in tracelib.ring_on_trace(sources)]
    labels = bench.GAP_LABELS + (r'^host\.',)
    gaps = tracelib.idle_gaps(device, placed, _ns(55), _ns(85), labels, 1)
    assert gaps[0][0] == 'host.gc'
    # with no pause inside the tail the metric is left out
    ring.clear()
    for name, a, b in both:
        ring.add_span(name, BASE + a / 1e3, BASE + b / 1e3)
    sources.pop('ring_on_trace')
    assert _idle_under_gc(sources) is None


# ------------------------------------------------------ the rehearsal
@pytest.mark.parametrize('cell, count, times', [
    ('tbig_lm.chat_steady', 'serve.gc_full_pauses',
     ['serve.step_exe_lookup_ms', 'serve.step_exe_prepare_ms',
      'serve.step_exe_enqueue_ms', 'serve.gc_ms_per_step']),
    ('tbig_nmt.train_seq128', 'train.gc_full_pauses',
     ['train.exe_writeback_ms', 'train.gc_ms_per_step'])])
def test_the_traced_rehearsal_reads_the_counts(cell, count, times, capsys,
                                               monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '0')
    assert bench.main(['--workload', cell, '--seed', '5600000056',
                       '--seconds', '3', '--trace', '1',
                       '--rehearsal']) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last['correct'] is True and last['rehearsal'] is True
    # a count is a number on the CPU too; a host's clock is not written
    # under a time's name
    assert isinstance(last['metrics'][count]['value'], int)
    assert last['metrics'][count]['value'] >= 0
    for name in times:
        assert last['metrics'][name] == {'value': None, 'unit': 'ms'}, name
