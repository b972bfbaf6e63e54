"""Summarize a paddle_tpu.observe metrics JSONL.

Reads the snapshot/summary lines written by ``observe.enable(jsonl=...)``
(one JSON object per line, pid-tagged: several processes may append
to one file) and prints a human summary: p50/p95/max per
histogram, final counter/gauge values, and the MFU/goodput headline.

    python tools/metrics_report.py run.jsonl
    python tools/metrics_report.py run.jsonl --json | jq .mfu

By default the newest ``kind: "summary"`` line is reported (the
end-of-run state); ``--all-pids`` reports the newest summary per pid,
``--per-host`` per host (merged multihost JSONLs — records carry a
``host`` = jax.process_index() field), ``--snapshot`` takes the newest
line of any kind. ``--json`` emits one machine-readable object for
scripting, ``--slo`` renders the SLO panel (per-route objectives,
error-budget burn rate, goodput, and the top-5 slowest sampled trace
ids — each one a ``/tracez?trace_id=`` timeline), ``--tenants``
renders the multi-tenant isolation panel (per-tenant
admitted/shed/preempted/evicted-pages from the ``tenant.*`` counters,
plus the co-located trainer's yield ledger), and ``--prom``
converts the chosen record to Prometheus text exposition (drop it in a node_exporter textfile-collector dir and
offline runs feed the same dashboards as live ``/metrics`` scrapes) —
fast tests exercise all three paths so this tool cannot bit-rot.

See ``tools/flight_report.py`` for the crash-forensics companion (the
flight recorder's postmortem JSON).
"""

import argparse
import glob
import json
import os
import sys


def _registry_mod():
    """paddle_tpu/observe/registry.py loaded standalone (stdlib-only
    module; importing it via the package would drag in jax)."""
    import importlib.util
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'paddle_tpu', 'observe', 'registry.py')
    spec = importlib.util.spec_from_file_location(
        '_paddle_tpu_observe_registry', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_records(path):
    """Parse records, skipping torn lines (concurrent appenders).

    ``path`` may be a single JSONL file, a directory (every ``*.jsonl``
    inside is merged — the shape a cross-host run leaves behind: the
    parent's sink plus one ``<stem>-<replica>.jsonl`` per worker
    process), or a glob pattern. Merged records are ordered by ``ts``
    so counter-delta timelines stay monotonic; each record's ``host``
    field says which process emitted it."""
    if os.path.isdir(path):
        paths = sorted(glob.glob(os.path.join(path, '*.jsonl')))
    elif any(ch in path for ch in '*?['):
        paths = sorted(glob.glob(path))
    else:
        paths = [path]
    out = []
    for p in paths:
        with open(p) as f:
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                try:
                    rec = json.loads(ln)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    if len(paths) > 1:
        out.sort(key=lambda r: (r.get('ts') is None, r.get('ts') or 0))
    return out


def pick(records, any_kind=False):
    """Newest summary record (fallback: newest of any kind)."""
    if not any_kind:
        summaries = [r for r in records if r.get('kind') == 'summary']
        if summaries:
            return summaries[-1]
    return records[-1] if records else None


def derive(rec):
    """Flat scripting-friendly view of one record."""
    gauges = rec.get('gauges', {})
    out = {
        'ts': rec.get('ts'),
        'pid': rec.get('pid'),
        'host': rec.get('host', 0),
        'kind': rec.get('kind'),
        'counters': rec.get('counters', {}),
        'gauges': gauges,
        'histograms': rec.get('histograms', {}),
        'mfu': gauges.get('trainer.mfu'),
        'goodput': gauges.get('run.goodput'),
        'step_flops': gauges.get('executor.step_flops'),
        'steps_per_sec_ema': gauges.get('trainer.steps_per_sec_ema'),
        'host_blocked_seconds':
            gauges.get('trainer.host_blocked_seconds'),
        'device_blocked_seconds':
            gauges.get('trainer.device_blocked_seconds'),
    }
    # pipelined-loop overlap: 1 - (host-blocked + device-blocked)/wall.
    # The trainer publishes its own per-train() figure; reconstruct
    # from the blocked ledgers when only those made it into the record.
    overlap = gauges.get('trainer.pipeline_overlap_fraction')
    if overlap is None:
        hb = out['host_blocked_seconds']
        db = out['device_blocked_seconds']
        wall = gauges.get('run.wall_seconds')
        if hb is not None and db is not None and wall:
            overlap = max(0.0, 1.0 - (hb + db) / wall)
    out['overlap_fraction'] = overlap
    return out


def _fmt_val(v):
    if isinstance(v, float):
        return '%.6g' % v
    return str(v)


# ------------------------------------------------------------ SLO view
def derive_slo(rec):
    """Per-route SLO panel from one record's slo.* metrics: declared
    objective, burn rate, goodput, predicted p99, and the top-5
    slowest sampled trace ids (slo.slowest_seconds{route,trace_id}
    gauges — each names a /tracez?trace_id= timeline)."""
    parse = _registry_mod().parse_rendered
    routes = {}

    def ent(route):
        return routes.setdefault(route or '?', {
            'latency_budget_s': None, 'availability_target': None,
            'window_s': None, 'burn_rate': None, 'goodput_rps': None,
            'predicted_p99_s': None, 'requests_total': 0,
            'in_slo_total': 0, 'violations_total': 0, 'slowest': []})

    gmap = {'slo.latency_budget_seconds': 'latency_budget_s',
            'slo.availability_target': 'availability_target',
            'slo.window_seconds': 'window_s',
            'slo.burn_rate': 'burn_rate',
            'slo.goodput_rps': 'goodput_rps',
            'slo.predicted_p99_seconds': 'predicted_p99_s'}
    for rendered, v in rec.get('gauges', {}).items():
        name, labels = parse(rendered)
        if name in gmap:
            ent(labels.get('route'))[gmap[name]] = v
        elif name == 'slo.slowest_seconds':
            ent(labels.get('route'))['slowest'].append(
                {'seconds': v, 'trace_id': labels.get('trace_id')})
    cmap = {'slo.requests_total': 'requests_total',
            'slo.in_slo_total': 'in_slo_total',
            'slo.violations_total': 'violations_total'}
    for rendered, v in rec.get('counters', {}).items():
        name, labels = parse(rendered)
        if name in cmap:
            ent(labels.get('route'))[cmap[name]] = v
    for r in routes.values():
        r['slowest'].sort(key=lambda s: -(s['seconds'] or 0.0))
        del r['slowest'][5:]
    return {'ts': rec.get('ts'), 'pid': rec.get('pid'),
            'host': rec.get('host', 0), 'routes': routes}


def render_slo(rec):
    doc = derive_slo(rec)
    lines = []
    if not doc['routes']:
        return 'no slo.* metrics in this record'
    for route in sorted(doc['routes']):
        r = doc['routes'][route]
        obj = 'objective: p(lat <= %ss) >= %s over %ss window' % (
            _fmt_val(r['latency_budget_s'] or 0.0),
            _fmt_val(r['availability_target'] or 0.0),
            _fmt_val(r['window_s'] or 0.0))
        lines.append('== route %r — %s' % (route, obj))
        lines.append('   burn rate %s   goodput %s rps   '
                     'predicted p99 %s s'
                     % (_fmt_val(r['burn_rate'] or 0.0),
                        _fmt_val(r['goodput_rps'] or 0.0),
                        _fmt_val(r['predicted_p99_s'])
                        if r['predicted_p99_s'] is not None else '?'))
        lines.append('   requests %d   in-SLO %d   violations %d'
                     % (r['requests_total'], r['in_slo_total'],
                        r['violations_total']))
        if r['slowest']:
            lines.append('   slowest sampled requests:')
            for s in r['slowest']:
                lines.append('     %10.6fs  trace_id=%s  '
                             '(/tracez?trace_id=%s)'
                             % (s['seconds'], s['trace_id'],
                                s['trace_id']))
    return '\n'.join(lines)


# --------------------------------------------------------- tenant view
# render order for the isolation panel: most protected class first
_TENANT_PRIORITIES = ('interactive', 'standard', 'batch')


def derive_tenants(rec):
    """Multi-tenant isolation panel from one record's tenant.*
    metrics: per-tenant admitted/shed (with the shed-reason split:
    'requests' vs 'tokens' bucket), decode preemptions, prefix-cache
    pages evicted, and the co-located trainer's yield ledger
    (tenant.trainer_yields_total / tenant.trainer_yielded /
    trainer.yield_seconds)."""
    parse = _registry_mod().parse_rendered
    tenants = {}

    def ent(labels):
        e = tenants.setdefault(labels.get('tenant', '?'), {
            'priority': None, 'admitted': 0, 'shed': 0,
            'shed_reasons': {}, 'preempted': 0, 'evicted_pages': 0})
        if labels.get('priority'):
            e['priority'] = labels['priority']
        return e

    trainer = {}
    for rendered, v in rec.get('counters', {}).items():
        name, labels = parse(rendered)
        if name == 'tenant.admitted':
            ent(labels)['admitted'] += v
        elif name == 'tenant.shed':
            e = ent(labels)
            e['shed'] += v
            reason = labels.get('reason', '?')
            e['shed_reasons'][reason] = \
                e['shed_reasons'].get(reason, 0) + v
        elif name == 'tenant.preempted':
            ent(labels)['preempted'] += v
        elif name == 'tenant.evicted_pages':
            ent(labels)['evicted_pages'] += v
        elif name == 'tenant.trainer_yields_total':
            trainer['yields'] = trainer.get('yields', 0) + v
    for rendered, v in rec.get('gauges', {}).items():
        name, _labels = parse(rendered)
        if name == 'tenant.trainer_yielded':
            trainer['yielded'] = v
    for rendered, stats in rec.get('histograms', {}).items():
        name, _labels = parse(rendered)
        if name == 'trainer.yield_seconds':
            trainer['yield_seconds'] = {
                k: stats.get(k) for k in ('count', 'mean', 'max')}
    return {'ts': rec.get('ts'), 'pid': rec.get('pid'),
            'host': rec.get('host', 0), 'tenants': tenants,
            'trainer': trainer}


def render_tenants(rec):
    doc = derive_tenants(rec)
    if not doc['tenants'] and not doc['trainer']:
        return 'no tenant.* metrics in this record'
    lines = ['== per-tenant admission / scheduling '
             '(most protected class first)']
    lines.append('%-16s %-12s %10s %10s %10s %12s'
                 % ('Tenant', 'Priority', 'Admitted', 'Shed',
                    'Preempted', 'EvictedPgs'))

    def order(item):
        name, e = item
        prio = e['priority']
        rank = _TENANT_PRIORITIES.index(prio) \
            if prio in _TENANT_PRIORITIES else 1
        return (rank, name)

    for name, e in sorted(doc['tenants'].items(), key=order):
        lines.append('%-16s %-12s %10d %10d %10d %12d'
                     % (name, e['priority'] or '?', e['admitted'],
                        e['shed'], e['preempted'],
                        e['evicted_pages']))
        if e['shed_reasons']:
            lines.append('     shed by: %s' % '  '.join(
                '%s=%d' % (k, v) for k, v in
                sorted(e['shed_reasons'].items())))
    t = doc['trainer']
    if t:
        lines.append('== co-located trainer')
        ys = t.get('yield_seconds') or {}
        lines.append('   yields %s   currently yielded %s   '
                     'parked mean %s s max %s s'
                     % (t.get('yields', 0),
                        int(t['yielded']) if 'yielded' in t else '?',
                        _fmt_val(ys.get('mean')),
                        _fmt_val(ys.get('max'))))
    return '\n'.join(lines)


# ---------------------------------------------------------- fleet view
_FLEET_STATES = {0: 'UP', 1: 'DRAINING', 2: 'QUARANTINED', 3: 'DEAD'}


def derive_fleet(records):
    """Fleet-controller timeline from a metrics JSONL: the replica
    census over time (from the periodic snapshot records the autoscale
    chaos scenarios flush), scale-out/in/heal/quarantine counter deltas per
    snapshot, the final per-replica state machine, and the hedge
    ledger (hedge+failover dispatch rate vs the retry budget). Works
    on counters/gauges alone — no flight ring needed offline."""
    parse = _registry_mod().parse_rendered

    def census_of(rec):
        out = {}
        for rendered, v in rec.get('gauges', {}).items():
            name, labels = parse(rendered)
            if name == 'controller.replicas':
                out.setdefault(labels.get('route', '?'), {})[
                    labels.get('state', '?')] = v
        return out

    def totals_of(rec, names):
        out = dict.fromkeys(names, 0)
        for rendered, v in rec.get('counters', {}).items():
            name, _ = parse(rendered)
            if name in out:
                out[name] += v
        return out

    cnames = ('controller.scale_out_total', 'controller.scale_in_total',
              'controller.heals_total', 'controller.quarantines_total',
              'controller.deaths_total',
              'controller.spawn_failures_total')
    census_timeline, events = [], []
    prev = dict.fromkeys(cnames, 0)
    t0 = None
    for rec in records:
        census = census_of(rec)
        if not census and not any(
                parse(k)[0].startswith('controller.')
                for k in rec.get('counters', {})):
            continue
        ts = rec.get('ts')
        if t0 is None:
            t0 = ts
        t = round(ts - t0, 3) if (ts is not None and
                                  t0 is not None) else None
        if census:
            census_timeline.append({'t': t, 'census': census})
        totals = totals_of(rec, cnames)
        delta = {k.split('.')[1].replace('_total', ''):
                 totals[k] - prev[k]
                 for k in cnames if totals[k] != prev[k]}
        if delta:
            events.append(dict({'t': t}, **delta))
        prev = totals

    last = None
    for rec in records:
        if any(parse(k)[0].startswith('controller.')
               for k in list(rec.get('gauges', {}))
               + list(rec.get('counters', {}))):
            last = rec
    replicas, hedge = {}, {}
    if last is not None:
        for rendered, v in last.get('gauges', {}).items():
            name, labels = parse(rendered)
            if name == 'controller.replica_state':
                replicas[labels.get('replica', '?')] = \
                    _FLEET_STATES.get(int(v), '?')
            elif name == 'router.retry_budget_tokens':
                hedge['retry_budget_tokens'] = v
        hedges = requests = dispatches = failovers = mismatches = 0
        for rendered, v in last.get('counters', {}).items():
            name, _ = parse(rendered)
            if name == 'router.hedge_total':
                hedges += v
            elif name == 'router.requests_total':
                requests += v
            elif name == 'router.dispatch_total':
                dispatches += v
            elif name == 'router.failover_total':
                failovers += v
            elif name == 'router.hedge_mismatch_total':
                mismatches += v
        hedge.update({
            'hedges': hedges, 'requests': requests,
            'failovers': failovers, 'mismatches': mismatches,
            'hedge_fraction': round(hedges / requests, 6)
            if requests else None,
        })
        totals = totals_of(last, cnames)
    else:
        totals = dict.fromkeys(cnames, 0)
    # per-process census of a cross-host run: every replica worker
    # heartbeats worker.up / worker.ready / worker.queue_depth into its
    # own JSONL (host = replica name); the newest record per host wins
    workers = {}
    # controller-estimated per-replica clock offsets (the NTP-style
    # heartbeat exchange) — the numbers tools/fleet_trace.py wants as
    # its per-input :OFFSET_S suffixes
    clock_offsets = {}
    for rec in records:
        doc = None
        for rendered, v in rec.get('gauges', {}).items():
            name, labels = parse(rendered)
            if name.startswith('worker.'):
                if doc is None:
                    doc = {'pid': rec.get('pid')}
                doc[name.split('.', 1)[1]] = v
            elif name == 'rpc.clock_offset_seconds':
                clock_offsets[labels.get('replica', '?')] = v
        if doc is not None:
            workers[str(rec.get('host', '?'))] = doc
    depths = [w['queue_depth'] for w in workers.values()
              if isinstance(w.get('queue_depth'), (int, float))]
    return {
        'census_timeline': census_timeline,
        'scale_events': events,
        'replicas': replicas,
        'workers': workers,
        'queue_depth_skew': round(max(depths) - min(depths), 6)
        if depths else None,
        'clock_offsets': clock_offsets,
        'totals': {k.split('.', 1)[1]: v for k, v in totals.items()},
        'hedge': hedge,
        'phases': derive_phases(records),
    }


def derive_phases(records):
    """Phase-split view of a disaggregated fleet from the snapshot
    JSONL: per-phase replica census (``router.phase_replicas*``
    gauges), handoff count/latency/bytes/dedup (``handoff.*``), and
    the TTFT-vs-inter-token attribution (how much of TTFT the
    prefill+handoff hop explains vs the decode replica's inter-token
    cadence — ``handoff.ttft_attributed_seconds`` against
    ``decode.ttft_seconds`` / ``decode.inter_token_seconds``).
    Empty-dict when the JSONL has no phase/handoff metrics (a
    colocated fleet)."""
    parse = _registry_mod().parse_rendered
    last = None
    for rec in records:
        keys = list(rec.get('gauges', {})) + \
            list(rec.get('counters', {}))
        if any(parse(k)[0].startswith('handoff.')
               or parse(k)[0].startswith('router.phase_')
               for k in keys):
            last = rec
    if last is None:
        return {}
    phases = {}
    for rendered, v in last.get('gauges', {}).items():
        name, labels = parse(rendered)
        if name in ('router.phase_replicas',
                    'router.phase_replicas_ready'):
            ph = phases.setdefault(labels.get('phase', '?'), {})
            key = 'replicas_ready' if name.endswith('_ready') \
                else 'replicas'
            ph[key] = v
    handoff = {}
    for rendered, v in last.get('counters', {}).items():
        name, labels = parse(rendered)
        if name == 'router.phase_dispatch_total':
            ph = phases.setdefault(labels.get('phase', '?'), {})
            ph['dispatched'] = ph.get('dispatched', 0) + v
        elif name == 'handoff.count_total':
            handoff['count'] = handoff.get('count', 0) + v
        elif name == 'handoff.bytes_total':
            handoff['bytes'] = handoff.get('bytes', 0) + v
        elif name == 'handoff.pages_installed_total':
            handoff['pages_installed'] = \
                handoff.get('pages_installed', 0) + v
        elif name == 'handoff.pages_deduped_total':
            handoff['pages_deduped'] = \
                handoff.get('pages_deduped', 0) + v
    attribution = {}
    for rendered, stats in last.get('histograms', {}).items():
        name, labels = parse(rendered)
        if name == 'handoff.seconds':
            handoff['seconds'] = {k: stats.get(k) for k in
                                  ('count', 'mean', 'p50', 'p99')}
        elif name == 'handoff.ttft_attributed_seconds':
            attribution['prefill_plus_handoff'] = {
                k: stats.get(k) for k in ('count', 'mean', 'p99')}
        elif name == 'decode.ttft_seconds':
            key = 'ttft_cached' if labels.get('cached') == '1' \
                else 'ttft_cold'
            attribution[key] = {k: stats.get(k)
                                for k in ('count', 'mean', 'p99')}
        elif name == 'decode.inter_token_seconds':
            attribution['inter_token'] = {
                k: stats.get(k) for k in ('count', 'mean', 'p99')}
    return {'census': phases, 'handoff': handoff,
            'attribution': attribution}


def render_fleet(records):
    doc = derive_fleet(records)
    if not doc['census_timeline'] and not doc['replicas'] and \
            not doc['scale_events'] and not doc.get('phases') and \
            not doc.get('workers'):
        return 'no controller.* or phase/handoff metrics in this JSONL'
    lines = ['== fleet controller timeline']
    for ev in doc['scale_events']:
        what = ', '.join('%s +%d' % (k, v) for k, v in
                         sorted(ev.items()) if k != 't')
        lines.append('   t=%-8s %s' % (ev.get('t'), what))
    if doc['census_timeline']:
        lines.append('== replica census (state counts over time, '
                     'per route)')
        for row in doc['census_timeline']:
            cells = []
            for route in sorted(row['census']):
                c = row['census'][route]
                cells.append('%s[%s]' % (route, ' '.join(
                    '%s=%d' % (k, v) for k, v in sorted(c.items()))))
            lines.append('   t=%-8s %s' % (row['t'], '  '.join(cells)))
    if doc['replicas']:
        lines.append('== final replica states')
        for name in sorted(doc['replicas']):
            lines.append('   %-24s %s' % (name, doc['replicas'][name]))
    if doc.get('workers'):
        lines.append('== worker processes (child-emitted gauges)')
        for host in sorted(doc['workers']):
            w = doc['workers'][host]
            lines.append('   %-24s pid %-8s up %-3s ready %-3s '
                         'queue_depth %s'
                         % (host, w.get('pid', '?'),
                            int(w.get('up', 0)),
                            int(w.get('ready', 0)),
                            w.get('queue_depth', '?')))
        if doc.get('queue_depth_skew') is not None:
            lines.append('   queue depth skew (max-min): %s'
                         % doc['queue_depth_skew'])
    if doc.get('clock_offsets'):
        lines.append('== per-replica clock offsets (controller '
                     'heartbeat estimate, s)')
        for name in sorted(doc['clock_offsets']):
            lines.append('   %-24s %+.*f' % (name, 6,
                                             doc['clock_offsets'][name]))
    h = doc['hedge']
    if h:
        lines.append('== hedged requests vs retry budget')
        lines.append('   requests %s   hedges %s (%s of traffic)   '
                     'failovers %s   mismatches %s   tokens left %s'
                     % (h.get('requests'), h.get('hedges'),
                        ('%.2f%%' % (100 * h['hedge_fraction']))
                        if h.get('hedge_fraction') is not None else '?',
                        h.get('failovers'), h.get('mismatches'),
                        h.get('retry_budget_tokens')))
    ph = doc.get('phases') or {}
    if ph.get('census'):
        lines.append('== phase split (disaggregated fleet)')
        for phase in sorted(ph['census']):
            c = ph['census'][phase]
            lines.append('   %-8s replicas %s (ready %s)  '
                         'dispatched %s'
                         % (phase, c.get('replicas', '?'),
                            c.get('replicas_ready', '?'),
                            c.get('dispatched', 0)))
        h = ph.get('handoff', {})
        if h:
            sec = h.get('seconds') or {}
            lines.append('   handoffs %s   pages installed %s / '
                         'deduped %s   bytes %s   latency mean %s '
                         'p99 %s'
                         % (h.get('count', 0),
                            h.get('pages_installed', 0),
                            h.get('pages_deduped', 0),
                            h.get('bytes', 0),
                            _fmt_val(sec.get('mean')),
                            _fmt_val(sec.get('p99'))))
        att = ph.get('attribution', {})
        if att:
            lines.append('== TTFT vs inter-token attribution')
            for key in ('prefill_plus_handoff', 'ttft_cold',
                        'ttft_cached', 'inter_token'):
                if key in att:
                    s = att[key]
                    lines.append(
                        '   %-22s n=%-6s mean %s   p99 %s'
                        % (key, s.get('count'),
                           _fmt_val(s.get('mean')),
                           _fmt_val(s.get('p99'))))
    t = doc['totals']
    lines.append('== totals: %s' % '  '.join(
        '%s=%d' % (k, v) for k, v in sorted(t.items())))
    return '\n'.join(lines)


def render(rec):
    lines = []
    d = derive(rec)
    head = []
    if d['mfu'] is not None:
        head.append('MFU %.2f%%' % (100.0 * d['mfu']))
    if d['goodput'] is not None:
        head.append('goodput %.2f%%' % (100.0 * d['goodput']))
    if d['steps_per_sec_ema'] is not None:
        head.append('%.4g steps/s' % d['steps_per_sec_ema'])
    if d['overlap_fraction'] is not None:
        head.append('overlap %.2f%%' % (100.0 * d['overlap_fraction']))
    if d['step_flops'] is not None:
        head.append('%.4g FLOPs/step' % d['step_flops'])
    lines.append('== %s (host %s, pid %s, ts %s) %s' % (
        d['kind'] or 'record', d['host'], d['pid'], d['ts'],
        ('— ' + ', '.join(head)) if head else ''))
    hists = d['histograms']
    if hists:
        lines.append('%-52s %8s %12s %12s %12s'
                     % ('Histogram', 'Count', 'P50', 'P95', 'Max'))
        for name in sorted(hists):
            st = hists[name]
            lines.append('%-52s %8d %12.6g %12.6g %12.6g'
                         % (name, st.get('count', 0),
                            st.get('p50') or 0.0, st.get('p95') or 0.0,
                            st.get('max') or 0.0))
    if d['gauges']:
        lines.append('%-52s %14s' % ('Gauge', 'Value'))
        for name in sorted(d['gauges']):
            lines.append('%-52s %14s' % (name, _fmt_val(d['gauges'][name])))
    if d['counters']:
        lines.append('%-52s %14s' % ('Counter', 'Value'))
        for name in sorted(d['counters']):
            lines.append('%-52s %14s'
                         % (name, _fmt_val(d['counters'][name])))
    return '\n'.join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(
        description='Summarize a paddle_tpu.observe metrics JSONL.')
    p.add_argument('path', help='metrics JSONL file')
    p.add_argument('--json', action='store_true',
                   help='emit one machine-readable JSON object')
    p.add_argument('--snapshot', action='store_true',
                   help='use the newest record of any kind, not just '
                        'the newest end-of-run summary')
    p.add_argument('--all-pids', action='store_true',
                   help='report the newest record per pid (several '
                        'processes, one file)')
    p.add_argument('--per-host', action='store_true',
                   help='report the newest record per host '
                        '(jax.process_index() — merged multihost '
                        'JSONLs)')
    p.add_argument('--prom', action='store_true',
                   help='emit the chosen record(s) as Prometheus text '
                        'exposition (textfile-collector format)')
    p.add_argument('--slo', action='store_true',
                   help='render the SLO panel: per-route objectives, '
                        'burn rate, goodput, and the top-5 slowest '
                        'sampled trace ids')
    p.add_argument('--fleet', action='store_true',
                   help='render the fleet-controller timeline: replica '
                        'census and scale/heal/quarantine events over '
                        'the JSONL\'s snapshots, final per-replica '
                        'states, and hedge rate vs retry budget')
    p.add_argument('--tenants', action='store_true',
                   help='render the multi-tenant isolation panel: '
                        'per-tenant admitted/shed/preempted/evicted '
                        'pages by priority class, and the co-located '
                        'trainer yield ledger')
    args = p.parse_args(argv)
    if args.json and args.prom:
        sys.stderr.write('metrics_report: --json and --prom are '
                         'mutually exclusive\n')
        return 2
    if (args.slo or args.fleet or args.tenants) and args.prom:
        sys.stderr.write('metrics_report: --slo/--fleet/--tenants and '
                         '--prom are mutually exclusive\n')
        return 2

    records = load_records(args.path)
    if not records:
        sys.stderr.write('metrics_report: no parseable records in %s\n'
                         % args.path)
        return 1
    if args.all_pids or args.per_host:
        group_key = (lambda r: r.get('host', 0)) if args.per_host \
            else (lambda r: r.get('pid'))
        by_key = {}
        for r in records:
            if args.snapshot or r.get('kind') == 'summary':
                by_key[group_key(r)] = r
        chosen = [by_key[k] for k in sorted(by_key, key=str)] \
            or [records[-1]]
    else:
        chosen = [pick(records, any_kind=args.snapshot)]

    try:
        if args.fleet:
            # the timeline wants EVERY record, not one chosen summary
            if args.json:
                print(json.dumps(derive_fleet(records)))
            else:
                print(render_fleet(records))
        elif args.slo:
            if args.json:
                docs = [derive_slo(r) for r in chosen]
                print(json.dumps(docs[0] if len(docs) == 1 else docs))
            else:
                print('\n\n'.join(render_slo(r) for r in chosen))
        elif args.tenants:
            if args.json:
                docs = [derive_tenants(r) for r in chosen]
                print(json.dumps(docs[0] if len(docs) == 1 else docs))
            else:
                print('\n\n'.join(render_tenants(r) for r in chosen))
        elif args.json:
            docs = [derive(r) for r in chosen]
            print(json.dumps(docs[0] if len(docs) == 1 else docs))
        elif args.prom:
            expo = _registry_mod().prometheus_exposition
            sys.stdout.write(''.join(expo(r) for r in chosen))
        else:
            print('\n\n'.join(render(r) for r in chosen))
    except BrokenPipeError:      # `... | head` is a normal way to use this
        try:
            sys.stdout.close()
        except Exception:
            pass
    return 0


if __name__ == '__main__':
    sys.exit(main())
