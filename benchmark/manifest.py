"""BENCHMARK.json and the files it names, found by name only.

A cell is one entry of ``workloads``. Everything that belongs to one
configuration, one traffic mix, one per-layer metric, one reader or one
runner is a file of its own under the benchmark directory (``paths[0]``):

    configs/<config>.json          the ``file`` of the configs entry
    references/<config>.py         its plain float32 reference
    traffic/<traffic>.json
    runners/<runner>.py            the config's ``runner``
    layer_metrics/<metric>.json    {"reader": ..., "args": {...}}
    readers/<reader>.py            read(args, sources) -> number | None
    shape_fns/<function>.py        compute(config, traffic, sources)

so a later PR adds a cell, a metric or a reader as new files plus new
entries, and edits nothing that is here.
"""

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT_RE = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = ('device_trace', 'program_span', 'program_counter', 'host_clock')


class ManifestError(ValueError):
    pass


def read_json(path):
    with open(path, encoding='utf-8') as f:
        return json.load(f)


def load(root):
    """The manifest of the checkout at ``root``, with ``root`` and the
    benchmark directory resolved."""
    path = os.path.join(root, 'BENCHMARK.json')
    if not os.path.isfile(path):
        raise ManifestError('no BENCHMARK.json in %s' % root)
    m = read_json(path)
    m['_root'] = root
    m['_dir'] = os.path.join(root, m['paths'][0])
    return m


def _by_name(entries, name, what):
    for e in entries:
        if e['name'] == name:
            return e
    raise ManifestError('%s %r is not in BENCHMARK.json (has: %s)' % (
        what, name, ', '.join(e['name'] for e in entries)))


def _existing(path, what):
    if not os.path.isfile(path):
        raise ManifestError('%s: no file %s' % (what, path))
    return path


def applies(metric, cell_name):
    return 'workloads' not in metric or cell_name in metric['workloads']


def resolve(m, cell_name):
    """Every file the cell needs, checked to exist: the cell's entry,
    its config and traffic (parsed), the paths of its runner and of its
    config's plain reference, and for each of its per-layer metrics the metric's file (parsed) and reader path."""
    cell = _by_name(m['workloads'], cell_name, 'workload')
    cfg_entry = _by_name(m['configs'], cell['config'], 'config')
    d = m['_dir']
    config = read_json(_existing(os.path.join(m['_root'], cfg_entry['file']),
                                 'config %s' % cell['config']))
    traffic = read_json(_existing(
        os.path.join(d, 'traffic', cell['traffic'] + '.json'),
        'traffic %s' % cell['traffic']))
    runner = _existing(os.path.join(d, 'runners', config['runner'] + '.py'),
                       'runner %s' % config['runner'])
    layer = []
    for metric in m['per_layer']:
        if not applies(metric, cell_name):
            continue
        spec = read_json(_existing(
            os.path.join(d, 'layer_metrics', metric['name'] + '.json'),
            'per-layer metric %s' % metric['name']))
        reader = _existing(
            os.path.join(d, 'readers', spec['reader'] + '.py'),
            'reader %s' % spec['reader'])
        layer.append({'entry': metric, 'spec': spec, 'reader': reader})
    end_to_end = [e for e in m['end_to_end'] if applies(e, cell_name)]
    reference = _existing(
        os.path.join(d, 'references', cell['config'] + '.py'),
        'reference of %s' % cell['config'])
    return {'cell': cell, 'config': config, 'traffic': traffic,
            'runner': runner, 'reference': reference,
            'end_to_end': end_to_end, 'per_layer': layer}


def load_module(path):
    """Import one of the benchmark's by-name files (runner, reader,
    shape function) from its path."""
    name = 'bench_%s_%s' % (os.path.basename(os.path.dirname(path)),
                            os.path.basename(path)[:-3].replace('.', '_'))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def problems(m):
    """What the contract would refuse in the manifest itself, as a list
    of sentences (empty when sound): names, units, sources, and that
    every ``moves`` names an end-to-end metric reported in each of the
    metric's cells."""
    out = []
    cells = [c['name'] for c in m['workloads']]
    e2e = {e['name']: e for e in m['end_to_end']}

    def cells_of(metric):
        return metric.get('workloads', cells)

    for kind in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        names = [e['name'] for e in m[kind]]
        for n in names:
            if not NAME_RE.match(n):
                out.append('%s name %r has characters outside the '
                           'contract' % (kind, n))
        if len(set(names)) != len(names):
            out.append('%s has a repeated name' % kind)
    for c in m['workloads']:
        for key in ('config', 'traffic'):
            if not NAME_RE.match(c[key]):
                out.append('cell %s: bad %s %r' % (c['name'], key, c[key]))
        if c['chips'] not in (1, 4):
            out.append('cell %s: chips %r' % (c['name'], c['chips']))
        if not 1 <= len(c['why']) <= 200:
            out.append('cell %s: why of %d characters'
                       % (c['name'], len(c['why'])))
    for e in m['end_to_end'] + m['per_layer']:
        if not UNIT_RE.match(e['unit']):
            out.append('metric %s: bad unit %r' % (e['name'], e['unit']))
        if e['better'] not in ('lower', 'higher'):
            out.append('metric %s: better=%r' % (e['name'], e['better']))
        if e['source'] not in SOURCES:
            out.append('metric %s: source %r' % (e['name'], e['source']))
        for w in e.get('workloads', ()):
            if w not in cells:
                out.append('metric %s lists unknown cell %s'
                           % (e['name'], w))
    if 'setup_s' not in e2e or 'workloads' in e2e.get('setup_s', {}):
        out.append('setup_s must be an end-to-end metric of every cell')
    for e in m['end_to_end']:
        if not 0 < e.get('bound', 0) <= 0.1:
            out.append('metric %s: bound %r' % (e['name'], e.get('bound')))
        if e['source'] not in ('host_clock', 'device_trace'):
            out.append('end-to-end %s: source %r' % (e['name'], e['source']))
    for p in m['per_layer']:
        target = e2e.get(p['moves'])
        if target is None:
            out.append('metric %s moves unknown %r' % (p['name'], p['moves']))
            continue
        missing = [c for c in cells_of(p) if c not in cells_of(target)]
        if missing:
            out.append('metric %s moves %s, which cells %s do not report'
                       % (p['name'], p['moves'], missing))
    for c in cells:
        if not any(applies(e, c) for e in m['end_to_end']
                   if e['name'] != 'setup_s'):
            out.append('cell %s reports no end-to-end metric but setup_s' % c)
        if not any(applies(p, c) for p in m['per_layer']):
            out.append('cell %s reports no per-layer metric' % c)
    used = {c['config'] for c in m['workloads']}
    for cfg in m['configs']:
        if cfg['name'] not in used:
            out.append('config %s is used by no cell' % cfg['name'])
    four = sum(1 for c in m['workloads'] if c['chips'] == 4)
    if four > max(1, len(cells) // 4):
        out.append('%d of %d cells ask for four chips' % (four, len(cells)))
    return out
