"""The interpreter's own pauses as the ring sees them: with observe on a
garbage collection is a ``host.gc`` span on the ring's clock and a
record of ``host.gc_seconds{generation}``; with observe off
``gc.callbacks`` holds nothing of ours."""

import gc
import os
import sys
import threading
import time

import pytest

from paddle_tpu import observe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import tracelib          # noqa: E402


@pytest.fixture(autouse=True)
def _observe_clean():
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def _pauses():
    return [e for e in observe.spans().events() if e['name'] == 'host.gc']


def _bounds(ev):
    return ev['ts'], ev['ts'] + ev['dur']


def test_a_forced_collection_is_one_span_and_one_record():
    observe.enable()
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    full = [e for e in _pauses() if e['args']['generation'] == 2]
    assert len(full) == 1
    (ev,) = full
    assert set(ev['args']) == {'generation', 'collected', 'uncollectable'}
    assert ev['ph'] == 'X' and ev['tid'] == threading.get_ident()
    # on the ring's clock, inside the call that forced it
    began = observe.spans().perf_time(ev)
    assert t0 <= began and began + ev['dur'] / 1e6 <= t1
    # collections do not nest: no other pause lies inside this one
    lo, hi = _bounds(ev)
    assert not [e for e in _pauses() if e is not ev
                and _bounds(e)[0] < hi and _bounds(e)[1] > lo]
    hist = observe.histogram('host.gc_seconds')
    assert hist.count(generation=2) == 1
    assert hist.total(generation=2) == pytest.approx(ev['dur'] / 1e6)
    assert observe.get_counter('host.gc_total', generation=2) == 1


def test_the_total_is_the_sum_of_the_generations():
    observe.enable()
    gc.disable()            # only the forced ones: none between two reads
    try:
        for generation in (0, 1, 2, 0, 2):
            gc.collect(generation)
        snap = observe.snapshot()
        pauses = _pauses()
    finally:
        gc.enable()
    by_generation = {k: v for k, v in snap['histograms'].items()
                     if k.startswith('host.gc_seconds{')}
    assert {'host.gc_seconds{generation=%d}' % g for g in (0, 1, 2)} <= \
        set(by_generation)
    assert snap['counters']['host.gc_seconds_total'] == pytest.approx(
        sum(h['sum'] for h in by_generation.values()))
    for key, h in by_generation.items():
        assert snap['counters'][key.replace('_seconds', '_total')] == \
            h['count']
    assert len(pauses) == 5 == sum(
        h['count'] for h in by_generation.values())


def test_observe_off_leaves_the_collectors_callbacks_alone():
    before = list(gc.callbacks)
    gc.collect()
    assert gc.callbacks == before and not _pauses()
    observe.enable()
    observe.enable()                    # twice on: one callback
    assert len(gc.callbacks) == len(before) + 1
    observe.reset()                     # the flag survives, so does it
    assert len(gc.callbacks) == len(before) + 1
    gc.collect()
    assert len(_pauses()) >= 1
    observe.disable()
    assert gc.callbacks == before
    seen = len(_pauses())
    gc.collect()
    assert len(_pauses()) == seen
    assert observe.get_counter('host.gc_total', generation=2) == 1


def test_a_collection_inside_an_open_span_is_the_innermost():
    observe.enable()
    with observe.span('outer.work'):
        with observe.span('inner.work'):
            time.sleep(0.002)
            gc.collect()
            time.sleep(0.002)
    spans = [_bounds(e) + (e['name'],) for e in observe.spans().events()
             if e['ph'] == 'X']
    (pause,) = [sp for sp in spans if sp[2] == 'host.gc'
                and sp[1] - sp[0] == max(s[1] - s[0] for s in spans
                                         if s[2] == 'host.gc')]
    owners = tracelib.innermost(spans)
    under = [sp[2] for t0, t1, sp in owners
             if t0 >= pause[0] and t1 <= pause[1]]
    assert under and set(under) == {'host.gc'}
    # and around it the span it struck
    assert [sp[2] for t0, t1, sp in owners if t1 <= pause[0]][-1] == \
        'inner.work'
    assert [sp[2] for t0, t1, sp in owners if t0 >= pause[1]][0] == \
        'inner.work'
    # the idle gap of a device that waited through it is the pause's
    host = [(name, int(a * 1e3), int((b - a) * 1e3)) for a, b, name in spans]
    lo, hi = int(pause[0] * 1e3), int(pause[1] * 1e3)
    gaps = tracelib.idle_gaps([], host, lo, hi,
                              (r'^host\.', r'^inner\.', r'^outer\.'), 1)
    assert gaps[0][0] == 'host.gc'


def test_a_collection_that_strikes_inside_the_rings_lock_does_not_hang():
    """The pause is appended from inside the collector, on whatever
    thread it struck: also one that holds the ring's or the registry's
    lock."""
    observe.enable()
    ring, reg = observe.spans(), observe.registry()
    done = []

    def strike():
        with ring._lock, reg._lock:
            gc.collect()
        done.append(True)
    t = threading.Thread(target=strike, daemon=True)
    t.start()
    t.join(10)
    assert done and len(_pauses()) >= 1


def test_a_snapshot_taken_while_pauses_are_recorded_is_whole():
    """A collection that strikes the thread inside ``snapshot()`` adds
    its series to the registry that is being read."""
    from paddle_tpu.observe.registry import Counter

    class Striking(Counter):
        def _snapshot_into(self, out):
            gc.collect()                # a pause, right here
            Counter._snapshot_into(self, out)

    observe.enable()
    reg = observe.registry()
    gc.disable()                        # no pause before that one
    try:
        observe.reset()                 # the pauses' series are not there
        reg._metrics['a.first'] = Striking('a.first', reg)
        reg._metrics['a.first'].inc()
        observe.inc('some.counter')
        snap = reg.snapshot()
    finally:
        gc.enable()
    assert snap['counters']['a.first'] == snap['counters']['some.counter'] == 1
    assert observe.get_counter('host.gc_total', generation=2) == 1
    assert 'host.gc_seconds{generation=2}' in reg.snapshot()['histograms']


def test_an_aggregate_taken_while_a_pause_is_recorded_is_whole():
    """A collection that strikes between two label sets of
    ``host.gc_seconds`` while ``aggregate()`` sums them (the profiler's
    ``summarize()``) adds its generation's to the histogram being read."""
    class Striking(object):
        total = 0.5

        @property
        def count(self):
            gc.collect()                # a pause, between two states
            return 1

    observe.enable()
    gc.disable()                        # no pause before that one
    try:
        observe.reset()
        observe.record('host.gc_seconds', 0.25, generation=0)
        hist = observe.registry()._metrics['host.gc_seconds']
        hist._values = dict([(('struck',), Striking())]
                            + list(hist._values.items()))
        count, total = hist.aggregate()
    finally:
        gc.enable()
    assert (count, total) == (2, 0.75)
    assert hist.count(generation=2) == 1
    assert hist.aggregate()[0] == 4     # and one more, from reading it


def test_events_carry_the_process_id_without_asking_for_it_each_time(
        monkeypatch):
    """``os.getpid()`` is a system call an event; the recorder reads it
    once a process (and again in a forked child)."""
    import importlib
    spans_mod = importlib.import_module('paddle_tpu.observe.spans')
    observe.enable()
    asked = []
    real = os.getpid
    monkeypatch.setattr(os, 'getpid', lambda: asked.append(1) or real())
    with observe.span('some.work'):
        pass
    observe.spans().add_instant('some.mark')
    assert asked == []
    assert {e['pid'] for e in observe.spans().events()} == {real()}
    monkeypatch.setattr(os, 'getpid', lambda: 424242)   # as after a fork
    spans_mod._refresh_pid()
    try:
        with observe.span('child.work'):
            pass
        assert observe.spans().events()[-1]['pid'] == 424242
    finally:
        monkeypatch.undo()
        spans_mod._refresh_pid()
