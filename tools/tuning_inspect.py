"""Inspect a paddle_tpu kernel-tuning table — stdlib only, no jax.

Prints what the autotuner actually decided before you bet a serving
fleet on it: every (op, shape, dtype) key per device kind, the winning
variant, the measured candidate timings (and the winner's margin over
the runner-up), whether the entry was measured in-process or recorded
for replay, and the writer's jax version. Runs on a bastion host with
nothing but python3 — the same contract as ``tools/ckpt_inspect.py``.

    python tools/tuning_inspect.py .jax_cache/tuning.json
    python tools/tuning_inspect.py TABLE --json | jq .tables
    python tools/tuning_inspect.py TABLE --op flash_attention
    python tools/tuning_inspect.py TABLE --device-kind 'TPU v5 lite'

Schema: paddle_tpu/tuning/table.py (format_version 1). Companion of
``tools/ckpt_inspect.py`` (checkpoints), ``tools/flight_report.py``
(postmortems) and ``tools/metrics_report.py`` (metrics JSONL).
"""

import argparse
import json
import os
import sys

FORMAT_VERSION = 1   # mirrors paddle_tpu.tuning.table.FORMAT_VERSION

# The distributed linear-algebra op family (ISSUE 15): panel/block-size
# entries recorded by tuning.decide_summa_panel / decide_linalg_block.
LINALG_OPS = ('summa_matmul', 'blocked_cholesky', 'blocked_qr')

# Matmul compute-dtype entries (ISSUE 19): fp8(e4m3)-cast vs native,
# recorded by tuning.decide_matmul_dtype. The winner decides whether
# ops.fp8_matmul dispatches at that shape (PADDLE_TPU_FP8_MATMUL
# overrides the table either way).
MATMUL_DTYPE_OPS = ('matmul_dtype',)


def _variant_label(variant):
    if not isinstance(variant, dict):
        return str(variant)
    impl = variant.get('impl', '?')
    extras = ' '.join('%s%s' % (k.replace('block_', 'b'), v)
                      for k, v in sorted(variant.items()) if k != 'impl')
    return ('%s %s' % (impl, extras)).strip()


def inspect(path):
    if not os.path.exists(path):
        raise SystemExit('%s: no such file' % path)
    try:
        with open(path) as f:
            data = json.load(f)
    except ValueError as e:
        return {'kind': 'paddle_tpu_tuning_table', 'path': path,
                'status': 'corrupted: %s' % e}
    status = 'ok'
    ver = data.get('format_version') if isinstance(data, dict) else None
    if ver != FORMAT_VERSION:
        status = ('format_version %r != %d (the loader ignores this '
                  'table and re-measures)' % (ver, FORMAT_VERSION))
    tables = data.get('tables') if isinstance(data, dict) else None
    tables = tables if isinstance(tables, dict) else {}
    doc = {
        'kind': 'paddle_tpu_tuning_table',
        'path': path,
        'status': status,
        'format_version': ver,
        'jax': (data.get('jax') if isinstance(data, dict) else None),
        'device_kinds': sorted(tables),
        'n_entries': sum(len(t) for t in tables.values()
                         if isinstance(t, dict)),
        'tables': {},
    }
    for kind, entries in sorted(tables.items()):
        if not isinstance(entries, dict):
            continue
        rows = {}
        for key, ent in sorted(entries.items()):
            timings = {k: v for k, v in (ent.get('timings') or {}).items()
                       if isinstance(v, (int, float))}
            ran = sorted(v for v in timings.values() if v >= 0)
            margin = None
            if len(ran) >= 2 and ran[0] > 0:
                margin = round(ran[1] / ran[0], 3)
            rows[key] = {
                'winner': _variant_label(ent.get('winner')),
                'winner_variant': ent.get('winner'),
                'timings_ms': {k: (round(v * 1e3, 4) if v >= 0 else
                                   'failed')
                               for k, v in sorted(timings.items())},
                'margin_over_runner_up': margin,
                'mode': ent.get('mode'),
                'ts': ent.get('ts'),
            }
        doc['tables'][kind] = rows

    # linalg family summary: the panel/block winners and their margins
    # in one table — what you check before trusting a pod-scale matmul
    # to a replayed tuning table
    doc['linalg'] = {}
    for kind, rows in doc['tables'].items():
        fam = {}
        for key, e in rows.items():
            if not key.startswith(LINALG_OPS):
                continue
            variant = e.get('winner_variant') or {}
            fam[key] = {
                'op': key.split('|', 1)[0],
                'size': variant.get('panel', variant.get('block')),
                'winner': e['winner'],
                'margin_over_runner_up': e.get('margin_over_runner_up'),
                'mode': e.get('mode'),
            }
        if fam:
            doc['linalg'][kind] = fam

    # matmul dtype summary: where the tuner measured fp8 to win — the
    # shapes at which fp8_matmul will actually dispatch off this table
    doc['matmul_dtype'] = {}
    for kind, rows in doc['tables'].items():
        fam = {}
        for key, e in rows.items():
            if not key.startswith(MATMUL_DTYPE_OPS):
                continue
            variant = e.get('winner_variant') or {}
            fam[key] = {
                'op': key.split('|', 1)[0],
                'shape': key.split('|')[1] if '|' in key else None,
                'winner': variant.get('impl', e['winner']),
                'margin_over_runner_up': e.get('margin_over_runner_up'),
                'mode': e.get('mode'),
            }
        if fam:
            doc['matmul_dtype'][kind] = fam
    return doc


def render(doc):
    out = []
    out.append('tuning table  %s' % doc['path'])
    out.append('  status          %s' % doc.get('status'))
    out.append('  format_version  %s' % doc.get('format_version'))
    out.append('  writer jax      %s' % doc.get('jax'))
    out.append('  device kinds    %s'
               % (', '.join(doc.get('device_kinds', [])) or '(none)'))
    out.append('  entries         %d' % doc.get('n_entries', 0))
    for kind, rows in sorted(doc.get('tables', {}).items()):
        out.append('  [%s]' % kind)
        for key, e in rows.items():
            margin = e.get('margin_over_runner_up')
            out.append('    %s' % key)
            out.append('      winner  %-24s %s%s'
                       % (e['winner'],
                          ('x%.2f vs runner-up' % margin) if margin
                          else '',
                          ('  (%s)' % e['mode']) if e.get('mode') else ''))
            for label, ms in e.get('timings_ms', {}).items():
                out.append('        %-28s %s'
                           % (label, ms if ms == 'failed'
                              else '%.4f ms' % ms))
    if doc.get('linalg'):
        out.append('  linalg panel/block winners')
        for kind, fam in sorted(doc['linalg'].items()):
            out.append('    [%s]' % kind)
            for key, e in sorted(fam.items()):
                margin = e.get('margin_over_runner_up')
                out.append('      %-14s size %-6s %s%s  (%s)'
                           % (e['op'], e.get('size'), key.split('|')[1]
                              if '|' in key else '',
                              (' x%.2f vs runner-up' % margin)
                              if margin else '', e.get('mode')))
    if doc.get('matmul_dtype'):
        out.append('  matmul dtype winners')
        for kind, fam in sorted(doc['matmul_dtype'].items()):
            out.append('    [%s]' % kind)
            for key, e in sorted(fam.items()):
                margin = e.get('margin_over_runner_up')
                out.append('      %-8s %-24s %s%s  (%s)'
                           % (e.get('winner'), e.get('shape') or '',
                              key.split('|')[2] if key.count('|') >= 2
                              else '',
                              (' x%.2f vs runner-up' % margin)
                              if margin else '', e.get('mode')))
    return '\n'.join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='Inspect a paddle_tpu kernel-tuning table '
                    '(PADDLE_TPU_TUNING_TABLE).')
    ap.add_argument('path', help='tuning table JSON file')
    ap.add_argument('--json', action='store_true',
                    help='emit the full machine-readable document')
    ap.add_argument('--op', help='only keys of this op '
                                 '(prefix match, e.g. flash_attention)')
    ap.add_argument('--device-kind', help='only this device kind')
    ap.add_argument('--linalg', action='store_true',
                    help='only the distributed linear-algebra family '
                         '(summa_matmul / blocked_cholesky / '
                         'blocked_qr panel+block winners)')
    ap.add_argument('--matmul-dtype', action='store_true',
                    help='only the matmul compute-dtype entries '
                         '(fp8 vs native winners per shape)')
    args = ap.parse_args(argv)
    doc = inspect(args.path)
    if args.device_kind is not None:
        doc['tables'] = {k: v for k, v in doc.get('tables', {}).items()
                         if k == args.device_kind}
        doc['linalg'] = {k: v for k, v in doc.get('linalg', {}).items()
                         if k == args.device_kind}
        doc['matmul_dtype'] = {
            k: v for k, v in doc.get('matmul_dtype', {}).items()
            if k == args.device_kind}
    if args.op:
        doc['tables'] = {
            kind: {key: e for key, e in rows.items()
                   if key.startswith(args.op)}
            for kind, rows in doc.get('tables', {}).items()}
    if args.linalg:
        doc['tables'] = {
            kind: {key: e for key, e in rows.items()
                   if key.startswith(LINALG_OPS)}
            for kind, rows in doc.get('tables', {}).items()}
    if args.matmul_dtype:
        doc['tables'] = {
            kind: {key: e for key, e in rows.items()
                   if key.startswith(MATMUL_DTYPE_OPS)}
            for kind, rows in doc.get('tables', {}).items()}
    if args.json:
        json.dump(doc, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write('\n')
    else:
        print(render(doc))
    return 0


if __name__ == '__main__':
    sys.exit(main())
