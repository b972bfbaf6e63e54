"""Dependency-free metrics registry: labeled counters, gauges, and
histograms with a JSONL snapshot format and an end-of-run summary table.

Reference analog: the reference framework's profiler/statistics plumbing
(paddle/fluid/platform/profiler.cc aggregates named event totals into a
sorted table); TPU-native, the interesting numbers are host-side — cache
hits, compile seconds, phase wall times, barrier waits — so the registry
is pure Python and shared by every layer (executor, trainer, reader,
fault, parallel) plus the legacy profiler API, which is re-implemented
on top of the Histogram primitive.

Design points:

- One metric object per name; label sets materialize lazily per
  (sorted label items) key, Prometheus-style. Rendered names look like
  ``executor.cache_miss_total{key=1a2b3c4d}``.
- Histograms keep exact count/sum/min/max plus a bounded reservoir
  (RESERVOIR_CAP samples, Vitter's algorithm R with a fixed seed) so
  snapshot quantiles stay O(1) memory in unbounded runs.
- Everything is guarded by one registry lock: reader threads, the
  checkpoint commit thread, and the training loop all record into the
  same registry.
"""

import json
import math
import random
import re
import threading

__all__ = ['Counter', 'Gauge', 'Histogram', 'Registry', 'RESERVOIR_CAP',
           'parse_rendered', 'prometheus_exposition', 'relabel_snapshot']

RESERVOIR_CAP = 4096


def _label_key(labels):
    return tuple(sorted(labels.items()))


def _render(name, label_key):
    if not label_key:
        return name
    return '%s{%s}' % (name, ','.join('%s=%s' % (k, v)
                                      for k, v in label_key))


def parse_rendered(rendered):
    """Inverse of the snapshot naming: ``name{k=v,k2=v2}`` ->
    ``(name, {k: v})`` (label values come back as strings)."""
    if '{' not in rendered:
        return rendered, {}
    name, _, rest = rendered.partition('{')
    labels = {}
    for part in rest.rstrip('}').split(','):
        if not part:
            continue
        k, _, v = part.partition('=')
        labels[k] = v
    return name, labels


def relabel_snapshot(snapshot, **labels):
    """Return a copy of a Registry.snapshot()-shaped dict with ``labels``
    merged into every rendered series name — the federation step that
    turns N per-replica snapshots into one fleet view without series
    collisions (``worker.queue_depth`` from replica r0 and r1 become
    ``worker.queue_depth{host=...,replica=r0}`` / ``{...replica=r1}``).
    Injected labels win on key conflict; non-metric top-level keys
    (ts/pid/host/kind) pass through untouched; values are not copied
    deeply — treat the result as read-only."""
    out = {}
    for kind, series in snapshot.items():
        if kind not in ('counters', 'gauges', 'histograms') or \
                not isinstance(series, dict):
            out[kind] = series
            continue
        relabeled = {}
        for rendered, v in series.items():
            name, old = parse_rendered(rendered)
            merged = dict(old)
            merged.update(labels)
            relabeled[_render(name, _label_key(merged))] = v
        out[kind] = relabeled
    return out


# ------------------------------------------- Prometheus text exposition
# Pure functions over the snapshot() dict shape, so the same renderer
# serves the live /metrics endpoint AND tools/metrics_report.py --prom
# converting an on-disk JSONL record (which is the same shape).
_PROM_BAD = re.compile(r'[^a-zA-Z0-9_:]')


def _prom_name(name):
    n = _PROM_BAD.sub('_', name)
    if n and n[0].isdigit():
        n = '_' + n
    return n


def _prom_labels(labels):
    if not labels:
        return ''
    parts = []
    for k in sorted(labels):
        v = str(labels[k]).replace('\\', '\\\\').replace('"', '\\"') \
            .replace('\n', '\\n')
        parts.append('%s="%s"' % (_prom_name(k), v))
    return '{%s}' % ','.join(parts)


def _prom_num(v):
    if isinstance(v, bool):
        return '1' if v else '0'
    if isinstance(v, int):
        return str(v)
    v = float(v)
    if math.isnan(v):
        return 'NaN'
    if math.isinf(v):
        return '+Inf' if v > 0 else '-Inf'
    return format(v, '.10g')


def prometheus_exposition(snapshot):
    """Render a Registry.snapshot()-shaped dict as Prometheus text
    exposition (format 0.0.4). Counters and gauges map directly (metric
    names mangled to the legal charset: dots become underscores);
    histograms render as summaries — ``{quantile="0.5|0.9|0.95|0.99"}``
    series from the reservoir plus exact ``_sum``/``_count``; a
    histogram carrying a worst-bucket exemplar (trace id of the largest
    observed sample) renders it OpenMetrics-style on the 0.99 quantile
    line: ``... # {trace_id="<id>"} <value>``. Extra snapshot keys
    (ts/pid/host/kind) are ignored."""
    lines = []
    for kind, prom_type in (('counters', 'counter'), ('gauges', 'gauge')):
        grouped = {}
        for rendered, v in snapshot.get(kind, {}).items():
            if not isinstance(v, (int, float)):
                continue
            name, labels = parse_rendered(rendered)
            grouped.setdefault(name, []).append((labels, v))
        for name in sorted(grouped):
            pn = _prom_name(name)
            lines.append('# TYPE %s %s' % (pn, prom_type))
            for labels, v in sorted(grouped[name],
                                    key=lambda lv: sorted(lv[0].items())):
                lines.append('%s%s %s'
                             % (pn, _prom_labels(labels), _prom_num(v)))
    grouped = {}
    for rendered, st in snapshot.get('histograms', {}).items():
        if not isinstance(st, dict):
            continue
        name, labels = parse_rendered(rendered)
        grouped.setdefault(name, []).append((labels, st))
    for name in sorted(grouped):
        pn = _prom_name(name)
        lines.append('# TYPE %s summary' % pn)
        for labels, st in sorted(grouped[name],
                                 key=lambda lv: sorted(lv[0].items())):
            ex = st.get('exemplar') if isinstance(st.get('exemplar'),
                                                  dict) else None
            for q, key in (('0.5', 'p50'), ('0.9', 'p90'),
                           ('0.95', 'p95'), ('0.99', 'p99')):
                v = st.get(key)
                if v is None:
                    continue
                ql = dict(labels)
                ql['quantile'] = q
                line = '%s%s %s' % (pn, _prom_labels(ql), _prom_num(v))
                if q == '0.99' and ex is not None and \
                        ex.get('trace_id') is not None:
                    line += ' # %s %s' % (
                        _prom_labels({'trace_id': ex['trace_id']}),
                        _prom_num(ex.get('value') or 0.0))
                lines.append(line)
            lines.append('%s_sum%s %s' % (pn, _prom_labels(labels),
                                          _prom_num(st.get('sum') or 0.0)))
            lines.append('%s_count%s %s'
                         % (pn, _prom_labels(labels),
                            _prom_num(int(st.get('count') or 0))))
    return '\n'.join(lines) + '\n'


class _Metric(object):
    kind = None

    def __init__(self, name, registry, help=''):
        self.name = name
        self.help = help
        self._registry = registry
        self._lock = registry._lock
        self._values = {}


class Counter(_Metric):
    """Monotonically increasing count (per label set)."""

    kind = 'counter'

    def inc(self, n=1, **labels):
        lk = _label_key(labels)
        with self._lock:
            self._values[lk] = self._values.get(lk, 0) + n

    def value(self, **labels):
        with self._lock:
            return self._values.get(_label_key(labels), 0)

    def _snapshot_into(self, out):
        for lk, v in list(self._values.items()):
            out[_render(self.name, lk)] = v


class Gauge(_Metric):
    """Last-set value (per label set)."""

    kind = 'gauge'

    def set(self, value, **labels):
        lk = _label_key(labels)
        with self._lock:
            self._values[lk] = value

    def add(self, n, **labels):
        lk = _label_key(labels)
        with self._lock:
            self._values[lk] = self._values.get(lk, 0) + n

    def value(self, default=None, **labels):
        with self._lock:
            return self._values.get(_label_key(labels), default)

    def _snapshot_into(self, out):
        for lk, v in self._values.items():
            out[_render(self.name, lk)] = v


class _HistState(object):
    __slots__ = ('count', 'total', 'min', 'max', 'samples', 'rng',
                 'exemplar')

    def __init__(self, seed):
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self.samples = []
        self.rng = random.Random(seed)
        # worst-bucket exemplar: the trace id of the largest value ever
        # observed WITH an exemplar — a p99 spike on /metrics links
        # straight to the trace that caused it (/tracez?trace_id=)
        self.exemplar = None

    def observe(self, v, exemplar=None):
        v = float(v)
        self.count += 1
        self.total += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v
        if exemplar is not None and (self.exemplar is None
                                     or v >= self.exemplar['value']):
            self.exemplar = {'value': v, 'trace_id': str(exemplar)}
        if len(self.samples) < RESERVOIR_CAP:
            self.samples.append(v)
        else:
            j = self.rng.randrange(self.count)
            if j < RESERVOIR_CAP:
                self.samples[j] = v

    def stats(self):
        out = {'count': self.count, 'sum': self.total,
               'min': self.min, 'max': self.max,
               'mean': self.total / self.count if self.count else None}
        s = sorted(self.samples)
        for q, key in ((0.5, 'p50'), (0.9, 'p90'), (0.95, 'p95'),
                       (0.99, 'p99')):
            out[key] = s[min(len(s) - 1, int(q * len(s)))] if s else None
        if self.exemplar is not None:
            out['exemplar'] = dict(self.exemplar)
        return out


class Histogram(_Metric):
    """Streaming distribution: exact count/sum/min/max + reservoir
    quantiles (per label set)."""

    kind = 'histogram'

    def observe(self, value, exemplar=None, **labels):
        lk = _label_key(labels)
        with self._lock:
            st = self._values.get(lk)
            if st is None:
                st = self._values[lk] = _HistState(hash((self.name, lk)))
            st.observe(value, exemplar=exemplar)

    def stats(self, **labels):
        with self._lock:
            st = self._values.get(_label_key(labels))
            return st.stats() if st is not None else None

    def count(self, **labels):
        with self._lock:
            st = self._values.get(_label_key(labels))
            return st.count if st is not None else 0

    def total(self, **labels):
        with self._lock:
            st = self._values.get(_label_key(labels))
            return st.total if st is not None else 0.0

    def aggregate(self):
        """(count, sum) across every label set — the profiler's
        summarize() substrate."""
        with self._lock:
            # a copy: a collection's first record of a generation lands
            # here through the same (reentrant) lock, mid-iteration
            states = list(self._values.values())
            return (sum(st.count for st in states),
                    sum(st.total for st in states))

    def _snapshot_into(self, out):
        for lk, st in list(self._values.items()):
            out[_render(self.name, lk)] = st.stats()


class Registry(object):
    """Home of every metric. Metric constructors are get-or-create so
    call sites never coordinate; asking for an existing name with a
    different type raises."""

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics = {}

    def _get(self, cls, name, help):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, self, help)
            elif not isinstance(m, cls):
                raise TypeError('metric %r already registered as %s, not %s'
                                % (name, m.kind, cls.kind))
            return m

    def counter(self, name, help=''):
        return self._get(Counter, name, help)

    def gauge(self, name, help=''):
        return self._get(Gauge, name, help)

    def histogram(self, name, help=''):
        return self._get(Histogram, name, help)

    def metrics(self, prefix=''):
        with self._lock:
            return [m for n, m in sorted(self._metrics.items())
                    if n.startswith(prefix)]

    def clear(self):
        with self._lock:
            self._metrics = {}

    # ------------------------------------------------------------ export
    def snapshot(self):
        """{'counters': {rendered_name: n}, 'gauges': {...},
        'histograms': {rendered_name: stats_dict}} — JSON-ready."""
        out = {'counters': {}, 'gauges': {}, 'histograms': {}}
        with self._lock:
            # copies: a collection that strikes this thread here records
            # its pause (observe._on_gc), which may add a series
            for m in list(self._metrics.values()):
                m._snapshot_into(out[m.kind + 's'])
        return out

    def to_json_line(self, **extra):
        rec = dict(extra)
        rec.update(self.snapshot())
        return json.dumps(rec, sort_keys=True, default=str)

    def summary_table(self):
        """End-of-run human summary: counters and gauges one per line,
        histograms with count/mean/p50/p95/max."""
        snap = self.snapshot()
        lines = []
        if snap['counters']:
            lines.append('%-52s %14s' % ('Counter', 'Value'))
            for name, v in sorted(snap['counters'].items()):
                lines.append('%-52s %14s' % (name, v))
        if snap['gauges']:
            lines.append('%-52s %14s' % ('Gauge', 'Value'))
            for name, v in sorted(snap['gauges'].items()):
                sv = '%.6g' % v if isinstance(v, float) else str(v)
                lines.append('%-52s %14s' % (name, sv))
        if snap['histograms']:
            lines.append('%-52s %8s %12s %12s %12s %12s'
                         % ('Histogram', 'Count', 'Mean', 'P50', 'P95',
                            'Max'))
            for name, st in sorted(snap['histograms'].items()):
                lines.append(
                    '%-52s %8d %12.6g %12.6g %12.6g %12.6g'
                    % (name, st['count'], st['mean'] or 0.0,
                       st['p50'] or 0.0, st['p95'] or 0.0,
                       st['max'] or 0.0))
        return '\n'.join(lines)
