"""Of the first chip's idle nanoseconds in the traced part of the
window, the share, in percent, that lies under spans of the program's
own ring (``paddle_tpu.observe.spans()``) whose names match ``spans``,
each clipped to the window. The trace's own host events hold copies of
those spans; the ring also holds the spans the trace lost (an annotation
entered before the profiler started or still open as it stopped is
never in the trace: an idle wait that straddles an edge of the tail
vanishes whole) and the spans the program recorded with explicit bounds
(``decode.device_empty``). Where spans nest, the innermost owns the
time under it; ``among`` names every span that takes part in that
contest (default: ``spans``), so time under a child that is not counted
is not given to its counted parent (``tracelib.owned``).

The ring is on the program's clock, the trace on the profiler's. The
recorder measures where one stands against the other from the spans
both hold (``SpanRecorder.offset_to``: hundreds of them a tail), once a
run (``tracelib.ring_on_trace``, kept in ``sources``), and the line ``SPAN_CLOCK {"matched": n,
"copies": m, "offset_ns": ..., "residual_us_p95": ...}`` says how well.
Under 20 matched spans, with a residual above 200 us, with more than
one in twenty of the trace's copies finding no span of the ring at that
offset, or on a program whose recorder cannot measure it, nothing is
read: a bad alignment shows as a
missing metric, not a wrong one. A line ``IDLE_COVER`` says, for each
metric, the idle and the covered seconds, and where the longest idle
gap lay and which spans of the ring lie over it (``cut`` where such a
span began before the window or ended after it). A line
``WORKER_CLOCK``, once a run, says what the splits of the worker's clock
grew by over the window beside the sums they should add up to:
``decode.queue_wait_seconds{state}`` beside ``decode.queue_seconds``,
``decode.token_gap_seconds{state}`` beside
``decode.inter_token_seconds``, ``decode.device_empty_seconds{state}``
beside ``decode.worker_seconds{state}``.
args: {"spans": [regex, ...], "among": [regex, ...]}."""

import json
import re

from benchmark import stats, tracelib

STATES = ('idle', 'admit', 'prefill', 'step')
_SAID = 'worker_clock_said'


def say(tag, **fields):
    print('%s %s' % (tag, json.dumps(fields, sort_keys=True)), flush=True)


def say_splits(before, after):
    def grown(kind, series):
        a = stats.registry_pooled(after, kind, series)
        b = stats.registry_pooled(before, kind, series)
        return a - b if kind == 'counters' else a[0] - b[0]

    def by_state(kind, name):
        return {s: grown(kind, '%s{state=%s}' % (name, s)) for s in STATES}
    say('WORKER_CLOCK',
        queue_wait_seconds=by_state('counters', 'decode.queue_wait_seconds'),
        queue_seconds=grown('histograms', 'decode.queue_seconds'),
        token_gap_seconds=by_state('counters', 'decode.token_gap_seconds'),
        inter_token_seconds=grown('histograms',
                                  'decode.inter_token_seconds'),
        token_gaps=grown('counters', 'decode.token_gaps_total'),
        prefills=grown('counters', 'decode.prefills_total'),
        device_empty_seconds=by_state('histograms',
                                      'decode.device_empty_seconds'),
        worker_seconds=by_state('histograms', 'decode.worker_seconds'))


def read(args, sources):
    if _SAID not in sources:
        sources[_SAID] = True
        say_splits(sources['registry_before'], sources['registry_after'])
    trace = sources['trace']
    if not trace or 'window' not in trace:
        return None
    ring = tracelib.ring_on_trace(sources)
    if ring is None:
        return None
    lo, hi = trace['window']
    busy = tracelib.merged(tracelib.clipped(
        tracelib.spans_of(trace['first']), lo, hi))
    idle = tracelib.subtract([(lo, hi)], busy)
    counted = [re.compile(p) for p in args['spans']]
    among = [re.compile(p) for p in args.get('among', args['spans'])]
    family, found = [], False
    for name, s, e in ring:
        if e <= lo or s >= hi:
            continue
        mine = any(r.search(name) for r in counted)
        if mine or any(r.search(name) for r in among):
            family.append((max(s, lo), min(e, hi), mine))
            found = found or mine
    if not idle or not found:
        return None
    uncovered = tracelib.subtract(idle, tracelib.owned(family))
    share = 1.0 - tracelib.total(uncovered) / float(tracelib.total(idle))
    gap = max(idle, key=lambda g: g[1] - g[0])
    say('IDLE_COVER', spans=args['spans'],
        idle_s=tracelib.total(idle) / 1e9,
        covered_s=share * tracelib.total(idle) / 1e9,
        longest_gap=dict(at_s=(gap[0] - lo) / 1e9,
                         s=(gap[1] - gap[0]) / 1e9,
                         under=lying_over(ring, gap, lo, hi)))
    return 100.0 * share


def lying_over(ring, gap, lo, hi, n=4):
    """The ``n`` spans of the ring that cover most of ``gap``:
    ``[[name, seconds of the gap under it, cut], ...]``, ``cut`` where
    the span began before the traced window or ended after it."""
    over = sorted(((min(e, gap[1]) - max(s, gap[0]), name, s < lo or e > hi)
                   for name, s, e in ring if s < gap[1] and e > gap[0]),
                  reverse=True)[:n]
    return [[name, ns / 1e9, cut] for ns, name, cut in over]
