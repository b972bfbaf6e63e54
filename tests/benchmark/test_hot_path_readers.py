"""What turns the program's hot-path spans into per-layer metrics and
into the names of the device's idle gaps, on synthetic ``(name, start,
dur)`` lists (the innermost-span rule, ``registry_sum_share``), and the
manifest with PR 25's entries."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest, tracelib  # noqa: E402

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


def _idle(device, lo=0, hi=1000):
    return tracelib.subtract([(lo, hi)], tracelib.merged(tracelib.clipped(
        tracelib.spans_of(device), lo, hi)))


@pytest.mark.parametrize('spans, among, want', [
    # every decode.* / executor.* span: all idle but 190-200 and 290
    (FAMILY, None, 90 + 100),
    # the worker's Python, innermost winning: emit owns 120-190; the
    # prefill's emit 395-400; decode.prefill itself is not counted
    (['^decode\\.step\\.emit$', '^decode\\.prefill\\.emit$'], FAMILY, 70 + 5),
    # under a fetch: 100-120 of the step's, 300-320 of the prefill's
    (['^decode\\.step\\.fetch$', '^executor\\.fetch$'], FAMILY, 20 + 20),
    # under a dispatch, the prefill's fetch taken out: 320-390
    (['^decode\\.step\\.dispatch$', '^decode\\.prefill\\.run$',
      '^executor\\.(run|lookup|prepare|enqueue)$'], FAMILY, 70),
    # without ``among`` a counted parent keeps its children's time
    (['^decode\\.prefill$'], None, 100),
    # ... and keeps only 390-395, between two children, when they compete
    (['^decode\\.prefill$'], FAMILY, 5),
    # no span of that name: nothing is owned
    (['^scheduler\\.'], None, 0),
])
def test_owned_gives_idle_time_to_the_innermost_span(spans, among, want):
    """``tracelib.owned``, the rule ``readers/ring_gap_cover.py`` and
    ``breakdown.idle_gaps`` share (until PR 42 ``trace_gap_cover``'s):
    of the device's idle 100-200 and 300-400, the nanoseconds under a
    counted span, a child that is not counted taking its time from a
    counted parent."""
    counted = set(tracelib.matching(HOST, spans))
    family = set(tracelib.matching(HOST, among or spans)) | counted
    mine = tracelib.owned([(s, s + d, (name, s, d) in counted)
                           for name, s, d in family])
    idle = _idle(DEVICE)
    assert tracelib.total(idle) == 200
    assert tracelib.total(idle) - tracelib.total(
        tracelib.subtract(idle, mine)) == want


@pytest.mark.parametrize('spans, want', [
    # of two that start together the one that ends first is innermost
    ([(0, 10, 'a'), (0, 4, 'b')], [(0, 4, 'b'), (4, 10, 'a')]),
    # the latest to start owns until it ends, then its parent again
    ([(0, 10, 'a'), (2, 5, 'b'), (3, 4, 'c')],
     [(0, 2, 'a'), (2, 3, 'b'), (3, 4, 'c'), (4, 5, 'b'), (5, 10, 'a')]),
    # a stretch under no span is in no triple
    ([(0, 2, 'a'), (5, 6, 'b')], [(0, 2, 'a'), (5, 6, 'b')]),
    ([], []),
])
def test_innermost_is_the_latest_to_start(spans, want):
    assert [(t0, t1, sp[2]) for t0, t1, sp in tracelib.innermost(spans)] \
        == want


@pytest.mark.parametrize('labels, want', [
    # the train runner's two spans, as until PR 42: the wait covers both
    (['^bench\\.(dispatch|wait_oldest)$'],
     ['bench.wait_oldest', 'bench.wait_oldest']),
    # with the worker's and the executor's spans the innermost names a
    # gap: 100-200 is the step's fetch to 120 and its emit from there;
    # 300-400 the prefill's run but for its fetch's 20 and the emit's 5
    (['^bench\\.(dispatch|wait_oldest)$'] + FAMILY,
     ['decode.step.emit', 'decode.prefill.run']),
    (['^decode\\.(idle|admit|prefill|step)$'],
     ['decode.step', 'decode.prefill']),
    (['^scheduler\\.'], ['unattributed', 'unattributed']),
])
def test_idle_gaps_are_named_after_the_innermost_span(labels, want):
    """``run.py``'s ``breakdown.idle_gaps`` under its ``GAP_LABELS``:
    the serving cells' gaps read ``unattributed`` until PR 42."""
    gaps = tracelib.idle_gaps(DEVICE, HOST, 0, 1000, labels, 5)
    assert gaps == [[name, 100e-9] for name in want]


def test_run_names_the_gaps_by_the_ring_laid_over_the_trace(monkeypatch):
    """``run.named_idle_gaps``: a wait that an edge of the tail cut is
    in the ring alone (the trace holds no event of a span it did not see
    open and close), and the gap under it is named after it."""
    from benchmark import run as bench
    # the engine's own account of an empty device is no worker span: it
    # is cut where programs arrive, not where spans nest, and names none
    ring = [('decode.idle', -50, 195), ('decode.step', 280, 420),
            ('decode.device_empty', 150, 310)]
    monkeypatch.setattr(tracelib, '_place_ring', lambda copies: ring)
    sources = _trace(DEVICE, [('bench.window', 0, 1000)])
    assert bench.named_idle_gaps(sources) == [
        ['decode.idle', 100e-9], ['decode.step', 100e-9]]
    # a ring that cannot be placed: the trace's own spans alone
    monkeypatch.setattr(tracelib, '_place_ring', lambda copies: None)
    assert [g[0] for g in bench.named_idle_gaps(_trace(DEVICE, HOST))] == [
        'decode.step.emit', 'decode.prefill.run']


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


# PR 25's entries, less the five PR 42 retired (``fetch_wake`` and the
# four ``idle_*`` shares read the next step's ops since PR 35 put a step
# in flight; ``serve.idle_under_states_pct``, ``idle_in_device_empty_pct``
# and the ``device_empty_*`` shares took their place)
NEW_METRICS = {
    'tbig_lm.chat_steady': {
        'serve.worker_prefill_share', 'serve.worker_step_share',
        'serve.worker_idle_share', 'serve.step_build_ms',
        'serve.step_dispatch_ms', 'serve.step_fetch_ms',
        'serve.step_emit_ms', 'serve.live_tokens_per_step'},
    'tbig_nmt.train_seq128': {
        'train.exe_lookup_ms', 'train.exe_prepare_ms',
        'train.exe_enqueue_ms'},
}


def cell_reports_the_hot_path_metrics(m, cell):
    resolved = {r['entry']['name']: r
                for r in manifest.resolve(m, cell)['per_layer']}
    assert NEW_METRICS[cell] <= set(resolved)
    for name in NEW_METRICS[cell]:
        entry = resolved[name]['entry']
        assert cell in entry['workloads'] and 'bound' not in entry
        assert resolved[name]['spec']['doc']


def shape_the_hot_path_metrics_are_reported(m):
    assert manifest.problems(m) == []
    for cell in NEW_METRICS:
        cell_reports_the_hot_path_metrics(m, cell)


@pytest.mark.parametrize('cell', sorted(NEW_METRICS))
def test_manifest_is_sound_with_the_hot_path_metrics(cell):
    assert manifest.problems(MANIFEST) == []
    cell_reports_the_hot_path_metrics(MANIFEST, cell)
