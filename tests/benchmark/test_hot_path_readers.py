"""The three readers that turn the program's hot-path spans into
per-layer metrics, on synthetic ``(name, start, dur)`` lists, and the
manifest with their entries."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402

MANIFEST = manifest.load(REPO)
FAMILY = ['^decode\\.', '^executor\\.']


def _reader(name):
    return manifest.load_module(os.path.join(
        REPO, 'benchmark', 'readers', name + '.py'))


def _trace(device, host, window=(0, 1000)):
    return {'trace': {'first': device, 'host': host, 'window': window}}


# device busy 0-100, 200-300, 400-1000: idle 100-200 and 300-400
DEVICE = [('%fusion.1', 0, 100), ('%copy.2', 200, 100),
          ('%while.3', 400, 600), ('%fusion.4', 400, 50)]
# a step whose fetch wakes 20 late, Python until 190; then a prefill
# whose own fetch is a child that is not counted as the worker's Python
HOST = [('decode.step', 0, 190),
        ('decode.step.fetch', 10, 110),
        ('decode.step.emit', 120, 70),
        ('bench.wait_oldest', 0, 1000),
        ('decode.prefill', 290, 150),
        ('decode.prefill.run', 295, 95),
        ('executor.fetch', 296, 24),
        ('decode.prefill.emit', 395, 45)]


@pytest.mark.parametrize('args, want', [
    # every decode.* / executor.* span: all idle but 190-200 and 290
    ({'spans': FAMILY}, 100.0 * (90 + 100) / 200),
    # the worker's Python, innermost winning: emit owns 120-190; the
    # prefill's emit 395-400; decode.prefill itself is not counted
    ({'spans': ['^decode\\.step\\.emit$', '^decode\\.prefill\\.emit$'],
      'among': FAMILY}, 100.0 * (70 + 5) / 200),
    # under a fetch: 100-120 of the step's, 300-320 of the prefill's
    ({'spans': ['^decode\\.step\\.fetch$', '^executor\\.fetch$'],
      'among': FAMILY}, 100.0 * (20 + 20) / 200),
    # under a dispatch, the prefill's fetch taken out: 320-390
    ({'spans': ['^decode\\.step\\.dispatch$', '^decode\\.prefill\\.run$',
                '^executor\\.(run|lookup|prepare|enqueue)$'],
      'among': FAMILY}, 100.0 * 70 / 200),
    # without ``among`` a counted parent keeps its children's time
    ({'spans': ['^decode\\.prefill$']}, 100.0 * 100 / 200),
    # ... and keeps only 390-395, between two children, when they compete
    ({'spans': ['^decode\\.prefill$'], 'among': FAMILY},
     100.0 * 5 / 200),
    # no span of that name in the trace (the parent commit): nothing
    ({'spans': ['^scheduler\\.']}, None),
])
def test_trace_gap_cover_gives_idle_time_to_the_innermost_span(args, want):
    got = _reader('trace_gap_cover').read(args, _trace(DEVICE, HOST))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize('sources', [
    {'trace': None},
    {'trace': {'first': [], 'host': HOST}},                  # no window
    _trace([('%fusion.1', 0, 1000)], HOST),                  # never idle
])
def test_trace_gap_cover_has_nothing_to_read(sources):
    assert _reader('trace_gap_cover').read({'spans': FAMILY},
                                           sources) is None


@pytest.mark.parametrize('host, want_ms', [
    # 120 - 100 = 20 ns; the while's end is not inside the span
    ([('decode.step.fetch', 10, 110)], 20e-6),
    # two spans, the second ends 5 after copy.2: mean of 20 and 5
    ([('decode.step.fetch', 10, 110), ('decode.step.fetch', 210, 95)],
     12.5e-6),
    # a span with no device op ending inside it is left out
    ([('decode.step.fetch', 10, 110), ('decode.step.fetch', 310, 40)],
     20e-6),
    # nested ops: the last end inside wins (fusion.4 at 450)
    ([('decode.step.fetch', 390, 70)], 10e-6),
    # a span that starts before the traced window is left out
    ([('decode.step.fetch', -5, 125)], None),
    ([('decode.step.emit', 10, 110)], None),
    ([], None),
])
def test_trace_wake_is_span_end_minus_last_device_op_end(host, want_ms):
    got = _reader('trace_wake').read({'span': 'decode.step.fetch'},
                                     _trace(DEVICE, host))
    assert got == (None if want_ms is None else pytest.approx(want_ms))
    assert _reader('trace_wake').read({'span': 'decode.step.fetch'},
                                      {'trace': None}) is None


def _registry(**sums):
    return {'histograms': {
        'decode.worker_seconds{state=%s}' % state: {'sum': s, 'count': 1}
        for state, s in sums.items()}}


WHOLE = ['decode.worker_seconds{state=%s}' % state
         for state in ('idle', 'admit', 'prefill', 'step')]


@pytest.mark.parametrize('state, before, after, want', [
    # deltas, not totals: 4 of (1 + 0.5 + 4 + 4.5)
    ('prefill', _registry(idle=5, admit=1, prefill=2, step=3),
     _registry(idle=6, admit=1.5, prefill=6, step=7.5), 40.0),
    # a state that never recorded counts as zero, on either side
    ('admit', _registry(idle=1), _registry(idle=2, step=3), 0.0),
    ('step', {}, _registry(idle=1, step=3), 75.0),
    # nothing grew, or no such histogram (the parent commit): nothing
    ('step', _registry(step=3), _registry(step=3), None),
    ('step', {'histograms': {}}, {'histograms': {}}, None),
    ('step', None, None, None),                    # an untraced run
])
def test_registry_sum_share_is_one_label_over_the_partition(
        state, before, after, want):
    got = _reader('registry_sum_share').read(
        {'part': 'decode.worker_seconds{state=%s}' % state,
         'whole': WHOLE},
        {'registry_before': before, 'registry_after': after})
    assert got == (None if want is None else pytest.approx(want))


NEW_METRICS = {
    'tbig_lm.chat_steady': {
        'serve.worker_prefill_share', 'serve.worker_step_share',
        'serve.worker_idle_share', 'serve.step_build_ms',
        'serve.step_dispatch_ms', 'serve.step_fetch_ms',
        'serve.step_emit_ms', 'serve.fetch_wake_ms',
        'serve.idle_attributed_pct', 'serve.idle_under_host_pct',
        'serve.idle_under_fetch_pct', 'serve.live_tokens_per_step',
        'serve.idle_under_dispatch_pct'},
    'tbig_nmt.train_seq128': {
        'train.exe_lookup_ms', 'train.exe_prepare_ms',
        'train.exe_enqueue_ms'},
}


@pytest.mark.parametrize('cell', sorted(NEW_METRICS))
def test_manifest_is_sound_with_the_hot_path_metrics(cell):
    assert manifest.problems(MANIFEST) == []
    resolved = {m['entry']['name']: m
                for m in manifest.resolve(MANIFEST, cell)['per_layer']}
    assert NEW_METRICS[cell] <= set(resolved)
    for name in NEW_METRICS[cell]:
        entry = resolved[name]['entry']
        assert entry['workloads'] == [cell] and 'bound' not in entry
        assert resolved[name]['spec']['doc']
