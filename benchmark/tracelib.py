"""From a profiler trace to numbers: interval arithmetic on plain lists
of ``(name, start_ns, dur_ns)``, and the one function that reads an
``.xplane.pb`` into such lists with nothing but jax.

What a TPU trace holds (looked at by hand, PERF.md section 6): one plane
per chip named ``/device:TPU:<n>``; in it the line ``XLA Ops`` has one
event per executed HLO op, ``XLA Modules`` one per whole program and
``Steps`` one per step, all nested over the same time. Only ``XLA Ops``
is read as device work, so nothing is counted twice. The host's threads
are lines of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation``
events appear there under their own names.
"""

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
HOST_PLANE = '/host:CPU'
# laying the program's ring over the trace (``ring_on_trace``)
MIN_MATCHED = 20
MAX_RESIDUAL_US = 200.0
MIN_SHARE = 0.95       # of the trace's copies of ring spans, those placed


def merged(intervals):
    """Union of ``(start, end)`` pairs as a sorted list of disjoint
    ``(start, end)``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clipped(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def spans_of(events):
    return [(s, s + d) for _, s, d in events]


def busy_ns(events, lo, hi):
    """Nanoseconds of [lo, hi) in which at least one event ran."""
    return total(merged(clipped(spans_of(events), lo, hi)))


def idle_share(events, lo, hi):
    return 1.0 - busy_ns(events, lo, hi) / float(hi - lo)


def subtract(a, b):
    """The parts of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def matching(events, patterns):
    rx = [re.compile(p) for p in patterns]
    return [ev for ev in events if any(r.search(ev[0]) for r in rx)]


def exposed_ns(events, patterns, lo, hi):
    """Of the time the matching events take in [lo, hi), the part during
    which no other event runs on that device."""
    hit = set(matching(events, patterns))
    rest = [ev for ev in events if ev not in hit]
    return total(subtract(merged(clipped(spans_of(hit), lo, hi)),
                          merged(clipped(spans_of(rest), lo, hi))))


def top_ops(events, lo, hi, n=10):
    """The n names with most device time in [lo, hi), as
    ``[[name, seconds], ...]``. Events of one name are summed; nested
    control-flow ops (``while``, ``conditional``) cover their bodies and
    are left out so a body is not counted twice."""
    by_name = {}
    for name, s, d in events:
        if s + d <= lo or s >= hi or CONTROL_FLOW.match(name):
            continue
        name = short_name(name)
        by_name[name] = by_name.get(name, 0) + min(s + d, hi) - max(s, lo)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def short_name(name, width=96):
    """The trace names an op by its whole HLO line; keep the op's name
    and the start of its result type: ``fusion.12 (f32[1024,32000], ...``."""
    op, _, rest = name.partition(' = ')
    kind = LAYOUT.sub('', rest.split(' fusion(')[0].split(' custom-call(')[0])
    label = op.lstrip('%') + (' ' + kind if rest else '')
    return label if len(label) <= width else label[:width - 3] + '...'


LAYOUT = re.compile(r'\{[^{}]*\}')
CONTROL_FLOW = re.compile(r'^%?(while|conditional|call)([.\d]*)( |=|$)')


def innermost(spans):
    """``[(t0, t1, span)]``: each stretch between two edges of ``spans``
    (tuples that begin ``(start, end, ...)``) in which one of them is
    open, with the innermost open one: the latest to start and, of two
    that start together, the one that ends first."""
    spans = sorted(spans, key=lambda sp: sp[:2])
    cuts = sorted({t for sp in spans for t in sp[:2]})
    out, active, i = [], [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= t0:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > t0]
        if active:
            out.append((t0, t1, max(active,
                                    key=lambda sp: (sp[0], -sp[1]))))
    return out


def owned(spans):
    """The disjoint sorted intervals in which the innermost of
    ``spans``, ``(start, end, counted)`` triples, is a counted one: time
    under a child that is not counted is not given to its counted
    parent."""
    return merged([(t0, t1) for t0, t1, sp in innermost(spans) if sp[2]])


def idle_gaps(events, host_events, lo, hi, labels, n=5):
    """The n longest gaps of [lo, hi) in which no event ran, each named
    after the host span that owns most of it, else ``unattributed``:
    ``[[label, seconds], ...]``. The spans that take part are those
    whose names match one of the regexes ``labels``; where they nest,
    the innermost owns the time under it (``innermost``)."""
    busy = merged(clipped(spans_of(events), lo, hi))
    gaps = subtract([(lo, hi)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(s, s + d, name) for name, s, d in matching(host_events, labels)
            if d > 0]
    out = []
    for s, e in gaps[:n]:
        by_name = {}
        for t0, t1, span in innermost(
                [sp for sp in host if sp[0] < e and sp[1] > s]):
            under = min(t1, e) - max(t0, s)
            if under > 0:
                by_name[span[2]] = by_name.get(span[2], 0) + under
        best = max(by_name, key=by_name.get) if by_name else 'unattributed'
        out.append([best, (e - s) / 1e9])
    return out


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    return found[-1] if found else None


def chips_with_ops(path):
    """The chips whose plane has an ``XLA Ops`` line with an event in
    it, sorted: whether a trace holds device work at all, found without
    reading its events into lists."""
    from jax.profiler import ProfileData
    found = []
    for plane in ProfileData.from_file(path).planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev and any(line.name == OPS_LINE and
                       next(iter(line.events), None) is not None
                       for line in plane.lines):
            found.append(int(dev.group(1)))
    return sorted(found)


def read_xplane(path):
    """``{'devices': {chip index: [ops events]}, 'host': [events],
    'lines': {plane name: {line name: event count}}}`` of one trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, lines = {}, [], {}
    for plane in data.planes:
        seen = lines.setdefault(plane.name, {})
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = None
            if dev and line.name == OPS_LINE:
                events = devices.setdefault(int(dev.group(1)), [])
            elif plane.name == HOST_PLANE:
                events = host
            count = 0
            for ev in line.events:
                count += 1
                if events is not None:
                    events.append((ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)))
            seen[line.name] = seen.get(line.name, 0) + count
    return {'devices': devices, 'host': host, 'lines': lines}


def window_of(trace, label):
    """[lo, hi) in trace nanoseconds: the host span ``label`` where the
    trace has it, else the extent of the device events."""
    spans = [ev for ev in trace['host'] if ev[0] == label]
    if spans:
        _, s, d = max(spans, key=lambda ev: ev[2])
        return s, s + d
    every = [ev for evs in trace['devices'].values() for ev in evs]
    if not every:
        return None
    return (min(s for _, s, _ in every),
            max(s + d for _, s, d in every))


def ring_on_trace(sources):
    """The completed spans of the program's own ring
    (``paddle_tpu.observe.spans()``) on the trace's clock, ``[(name,
    start_ns, end_ns)]``, or None where the two clocks cannot be set
    against each other. Measured once a run and kept in ``sources`` for
    whoever asks next: the harness's ``breakdown.idle_gaps`` and
    ``readers/ring_gap_cover.py``, whose docstring says how the offset
    is found and when it is refused."""
    if 'ring_on_trace' not in sources:
        sources['ring_on_trace'] = _place_ring(sources['trace']['host'])
    return sources['ring_on_trace']


def _place_ring(copies):
    from paddle_tpu import observe

    def say(**fields):
        print('SPAN_CLOCK %s' % json.dumps(fields, sort_keys=True),
              flush=True)
    recorder = observe.spans()
    measure = getattr(recorder, 'offset_to', None)
    if measure is None:
        say(matched=0, why='the recorder has no offset_to')
        return None
    found = measure(copies, MIN_MATCHED)
    if found is None:
        say(matched=0, why='under %d spans in both records' % MIN_MATCHED)
        return None
    say(**found)
    if found['residual_us_p95'] > MAX_RESIDUAL_US or \
            found['matched'] < MIN_SHARE * found['copies']:
        return None
    offset = found['offset_ns']
    return [(ev['name'], int(ev['ts'] * 1e3 + offset),
             int((ev['ts'] + ev['dur']) * 1e3 + offset))
            for ev in recorder.events()
            if ev.get('ph') == 'X' and ev['dur'] > 0]
