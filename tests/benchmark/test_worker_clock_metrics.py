"""The nine per-layer metrics of the decode worker's clock (PR 39; one
entry a reader over the serving cells since PR 42): their entries and
data files, ``readers/ring_gap_cover.py`` on a synthetic trace and ring,
and a traced rehearsal in which the splits reach the line and sum to
the older histograms."""

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
# the cells whose engine feeds the worker's clock: each is on every list
SERVING = ['tbig_lm.chat_steady', 'command_a_plus.mixed_len_steady',
           'kimi_k2_6.doc_qa_sessions', 'dots3_note.long_ctx_steady']
# <x>: (reader, unit, the end-to-end metric it moves)
X = {
    'queue_under_prefill_ms': ('registry_ratio', 'ms', 'ttft_mean_ms'),
    'queue_under_step_ms': ('registry_ratio', 'ms', 'ttft_mean_ms'),
    'gap_under_prefill_ms': ('registry_ratio', 'ms', 'itl_mean_ms'),
    'gap_under_step_ms': ('registry_ratio', 'ms', 'itl_mean_ms'),
    'device_empty_idle_share': ('registry_sum_share', '%', 'ttft_mean_ms'),
    'device_empty_prefill_share': ('registry_sum_share', '%', 'itl_mean_ms'),
    'device_empty_step_share': ('registry_sum_share', '%', 'itl_mean_ms'),
    'idle_under_states_pct': ('ring_gap_cover', '%', 'itl_mean_ms'),
    'idle_in_device_empty_pct': ('ring_gap_cover', '%', 'itl_mean_ms'),
}
PAIRS = [('serve.' + x, cell) for x in sorted(X) for cell in SERVING]


@pytest.fixture(autouse=True)
def _observe_clean():
    # also what an earlier file of this process left in the registry
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


# ------------------------------------------------------- the manifest
def entry_lists_the_cell(m, name, cell):
    """One (metric, cell) pair: the entry is found by its name, the cell
    is on its list, and the cell reports what the entry moves."""
    (entry,) = [p for p in m['per_layer'] if p['name'] == name]
    reader, unit, moves = X[name[len('serve.'):]]
    assert cell in entry['workloads']
    assert entry['unit'] == unit and entry['moves'] == moves
    assert entry['layer'] == 'decode engine' and 'bound' not in entry
    assert entry['source'] == ('device_trace' if reader == 'ring_gap_cover'
                               else 'program_span')
    resolved = manifest.resolve(m, cell)
    assert moves in {e['name'] for e in resolved['end_to_end']}
    (metric,) = [r for r in resolved['per_layer']
                 if r['entry']['name'] == name]
    assert metric['spec']['reader'] == reader and metric['spec']['doc']
    assert os.path.basename(metric['reader']) == reader + '.py'


def shape_the_clocks_nine_are_entries_over_the_serving_cells(m):
    """What this file holds of the manifest, for any manifest that has
    grown from the committed one (test_benchmark.py calls every
    ``shape_*`` of the test files on such a copy)."""
    assert manifest.problems(m) == []
    for name, cell in PAIRS:
        entry_lists_the_cell(m, name, cell)


def test_the_manifest_is_sound_and_holds_the_nine_by_name():
    assert manifest.problems(MANIFEST) == []
    assert {'serve.' + x for x in X} <= {
        p['name'] for p in MANIFEST['per_layer']}


@pytest.mark.parametrize('name, cell', PAIRS)
def test_an_entry_lists_its_cells_and_moves_what_they_report(name, cell):
    entry_lists_the_cell(MANIFEST, name, cell)


def test_the_states_of_a_split_are_the_engines():
    """The data files name the series the engine feeds, state by
    state."""
    from paddle_tpu.serving.decode import engine
    for x, state in (('queue_under_prefill_ms', 'prefill'),
                     ('gap_under_step_ms', 'step'),
                     ('device_empty_idle_share', 'idle')):
        spec = manifest.read_json(os.path.join(
            REPO, 'benchmark', 'layer_metrics', 'serve.' + x + '.json'))
        series = spec['args'].get('counter') or spec['args']['part']
        assert series.endswith('{state=%s}' % state)
        assert state in engine._STATES
        if 'whole' in spec['args']:
            assert spec['args']['whole'] == [
                'decode.worker_seconds{state=%s}' % s
                for s in engine._STATES]


# ------------------------------- ring_gap_cover on a synthetic trace
BASE = 5000.0                       # the ring's clock, seconds
OFFSET = 123_456_789_012_345        # the trace's clock stands this far off
STATES = {'spans': ['^decode\\.(idle|admit|prefill|step)$']}
EMPTY = {'spans': ['^decode\\.device_empty$']}


def _reader():
    return manifest.load_module(os.path.join(
        REPO, 'benchmark', 'readers', 'ring_gap_cover.py'))


def _ns(ms):
    """A millisecond of the ring's clock past BASE, on the trace's."""
    return int((observe.spans()._epoch0 + BASE) * 1e9 + ms * 1e6) + OFFSET


def _synthetic(jitter_ns=lambda i: 0, keep=None, lose=0):
    """A window of 10..100 ms in which the device idles 12..50 and
    52..98. The ring holds an idle wait that began before the window and
    one that ends after it, which the trace lost, 25 steps with their
    fetches and a prefill, which the trace holds 1.5 us early and 3 us
    longer, and three ``decode.device_empty`` stretches, which it never
    had. ``lose`` steps are in the trace and not in the ring."""
    observe.reset()
    ring = observe.spans()
    both = [('decode.step', 13.0 + i, 13.75 + 1.001 * i) for i in range(25)]
    both += [('decode.step.fetch', a + 0.05, b - 0.03) for _, a, b in both]
    both.append(('decode.prefill', 60.0, 70.0))
    lost = [('decode.idle', 2.0, 13.0), ('decode.idle', 80.0, 120.0)]
    own = [('decode.device_empty', 2.0, 13.1),
           ('decode.device_empty', 40.0, 60.5),
           ('decode.device_empty', 80.0, 120.0)]
    for name, a, b in both[lose:] + lost + own:
        ring.add_span(name, BASE + a / 1e3, BASE + b / 1e3)
    host = [(name, _ns(a) - 1500 + jitter_ns(i), int((b - a) * 1e6) + 3000)
            for i, (name, a, b) in enumerate(both)][:keep]
    host.append(('bench.window', _ns(10), _ns(100) - _ns(10)))
    device = [('%fusion.1', _ns(10), 2_000_000),
              ('%fusion.2', _ns(50), 2_000_000),
              ('%fusion.3', _ns(98), 2_000_000)]
    return {'trace': {'first': device, 'host': host,
                      'window': (_ns(10), _ns(100))},
            'registry_before': {}, 'registry_after': {}}


def test_ring_spans_cut_by_the_windows_edges_own_their_idle(capsys):
    sources = _synthetic()
    read = _reader().read
    # idle 84 ms: the early wait 12..13, the steps 19.05 (they do not
    # quite tile 13..38), the prefill 60..70, the late wait 80..98
    assert read(STATES, sources) == pytest.approx(
        100 * (1 + 19.05 + 10 + 18) / 84, abs=0.02)
    # 12..13.1, 40..50, 52..60.5, 80..98
    assert read(EMPTY, sources) == pytest.approx(
        100 * (1.1 + 10 + 8.5 + 18) / 84, abs=0.02)
    # a child that is not counted takes its time from a counted parent
    assert read(dict(STATES, among=['^decode\\.']), sources) < \
        read(STATES, sources) - 10
    lines = capsys.readouterr().out.splitlines()
    clocks = [json.loads(ln[11:]) for ln in lines
              if ln.startswith('SPAN_CLOCK ')]
    assert len(clocks) == 1                   # measured once a run
    assert clocks[0]['matched'] == clocks[0]['copies'] == 51
    assert clocks[0]['offset_ns'] == pytest.approx(OFFSET - 1500, abs=600)
    assert clocks[0]['residual_us_p95'] < 1.0
    assert sum(ln.startswith('WORKER_CLOCK ') for ln in lines) == 1
    covers = [json.loads(ln[11:]) for ln in lines
              if ln.startswith('IDLE_COVER ')]
    gap = covers[0]['longest_gap']
    assert gap['s'] == pytest.approx(0.046, abs=1e-5) and \
        gap['at_s'] == pytest.approx(0.042, abs=1e-5)
    # the wait that was open as the profiler stopped lies over most of it
    assert gap['under'][0][0] in ('decode.idle', 'decode.device_empty')
    assert ['decode.idle', pytest.approx(0.018, abs=1e-5), True] in \
        gap['under']


@pytest.mark.parametrize('change', [
    dict(keep=19),                                      # too few in both
    dict(jitter_ns=lambda i: (i * 7919 % 800 - 400) * 1000),   # a wander
    dict(lose=5)],                       # the ring lost what the trace has
    ids=['too_few_matches', 'a_clock_that_wanders', 'a_ring_that_lost_spans'])
def test_a_bad_alignment_is_a_missing_metric(change, capsys):
    sources = _synthetic(**change)
    assert _reader().read(STATES, sources) is None
    assert _reader().read(EMPTY, sources) is None
    assert 'SPAN_CLOCK ' in capsys.readouterr().out


def test_the_reader_finds_nothing_on_a_program_without_the_means(
        monkeypatch, capsys):
    """The parent's recorder cannot measure its clock and its engine
    records no ``decode.device_empty``: nothing is read, nothing
    raised."""
    sources = _synthetic()
    events = [e for e in observe.spans().events()
              if e['name'] != 'decode.device_empty']

    class Parent(object):
        def events(self):
            return events
    monkeypatch.setattr(observe, 'spans', Parent)
    assert _reader().read(STATES, sources) is None
    assert 'no offset_to' in capsys.readouterr().out
    monkeypatch.undo()
    # the means, and no such span in the ring
    sources = _synthetic()
    ring = observe.spans()
    kept = [e for e in ring.events() if e['name'] != 'decode.device_empty']
    ring.clear()
    for e in kept:
        ring._append(e)
    assert _reader().read(EMPTY, sources) is None
    assert _reader().read(STATES, sources) is not None
    # no traced window (a rehearsal): nothing
    assert _reader().read(STATES, dict(sources, trace=None)) is None


# ------------------------------------------------------ the rehearsal
def test_the_traced_rehearsal_reads_the_splits(capsys, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '0')
    assert bench.main(['--workload', 'tbig_lm.chat_steady', '--seed',
                       '3900000039', '--seconds', '3', '--trace', '1',
                       '--rehearsal']) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    assert last['correct'] is True and last['rehearsal'] is True
    for x, (reader, _, _) in X.items():
        # no device: the trace's two are not read, and a host's clock is
        # not written under the others' names
        assert ('serve.' + x in last['metrics']) == \
            (reader != 'ring_gap_cover')
    assert last['metrics']['serve.gap_under_step_ms']['value'] is None
    (clock,) = [json.loads(ln[13:]) for ln in lines
                if ln.startswith('WORKER_CLOCK ')]
    assert clock['prefills'] > 10 and clock['token_gaps'] > 50
    assert sum(clock['queue_wait_seconds'].values()) == pytest.approx(
        clock['queue_seconds'], rel=1e-3)
    assert sum(clock['token_gap_seconds'].values()) == pytest.approx(
        clock['inter_token_seconds'], rel=1e-3)
    assert clock['token_gap_seconds']['step'] > 0
    assert 0 < sum(clock['device_empty_seconds'].values()) <= \
        sum(clock['worker_seconds'].values())
    assert clock['device_empty_seconds']['idle'] > 0
