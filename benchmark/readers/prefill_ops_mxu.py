"""Share, in percent, of the chip's matrix peak that a family of ops
reaches inside prefills: the least operations those prefills need,
which a named shape function counts, over a peak, against the device
time the ops took, both sides over the same prefills of the traced
tail.

The prefills: the worker's ``decode.prefill.run`` spans that lie whole
inside the traced window. A prefill's chunks are enqueued back to back
and only the last is waited for, inside that span, and the worker
empties its decode pipeline before it admits, so every device op of a
prefill starts under its span and nothing else does
(``readers/step_ops_roofline.py`` is this for ``decode.step`` spans
and bytes). The operations: the runner hands over, for the same spans
in order, the (query, key) pairs each prefill's attention weighs
(``sources['prefill_attn_pairs_in_tail']``, from the spans' own
``attn_pairs``, which the trace's events do not keep); where the two
lists differ in length (a span that straddles an edge by the two
clocks' difference) or the program's spans carry no count, there is
nothing to read. A counter over the window would not do: a 32k prefill
takes longer than the traced tail, and whichever edge it straddles its
count and its time would be of different chunks.

Nothing is clipped: a share above 100 means the operations are counted
too high or the ops too few.
args: {"function": shape function with least_flops(pairs, config),
"match": [regex, ...], "peak": key of peaks.json}."""

import os

from benchmark import manifest, tracelib

SPAN = 'decode.prefill.run'


def span_op_ns(device, host, patterns, lo, hi):
    """(nanoseconds of matching device ops that started under a prefill
    span inside [lo, hi], the number of those spans)."""
    spans = sorted((s, s + d) for name, s, d in host
                   if name == SPAN and s >= lo and s + d <= hi)
    ns, i = 0, 0
    for _, s, d in sorted(tracelib.matching(device, patterns),
                          key=lambda ev: ev[1]):
        while i < len(spans) and spans[i][1] <= s:
            i += 1
        if i < len(spans) and spans[i][0] <= s:
            ns += d
    return ns, len(spans)


def read(args, sources):
    trace, peaks = sources['trace'], sources['peaks']
    pairs = sources.get('prefill_attn_pairs_in_tail')
    if not trace or 'window' not in trace or peaks is None or not pairs:
        return None
    ns, spans = span_op_ns(trace['first'], trace['host'], args['match'],
                           *trace['window'])
    if not ns or spans != len(pairs):
        return None
    least = manifest.load_module(os.path.join(
        sources['bench_dir'], 'shape_fns', args['function'] + '.py')
    ).least_flops(sum(pairs), sources['config'])
    return 100.0 * (least / peaks[args['peak']]) / (ns / 1e9)
