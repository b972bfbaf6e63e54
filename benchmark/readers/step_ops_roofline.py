"""Roofline share, in percent, of a family of ops in the decode step: the
least bytes they have to read in a step, which a named shape function
counts, over a peak, against the device time they took per step, both
sides taken from the traced tail of the window
(``readers/moe_ffn_roofline.py`` is this for the expert products, with
its own count).

The least bytes: ``shape_fns/<function>.py``'s ``per_step(before, after,
**function_args)`` between ``registry_tail``, the snapshot
``Context.tick`` takes as the profiler starts, and ``registry_after``:
the steps whose ops are timed, but for the one in flight at either
edge. Never the whole window's counters. Without a tail snapshot, or on
a program without the counters, there is nothing to read.

The time: device ops of the first chip whose HLO line matches ``match``
and which started under one of the worker's ``decode.step`` spans that
lie whole inside the traced window (``moe_ffn_roofline.step_op_ns``: a
prefill never runs under a step's span), per such span.

Nothing is clipped: a share above 100 means the bytes are counted too
high or the ops too few.
args: {"function": name, "function_args": {...}, "match": [regex, ...],
"peak": key of peaks.json}."""

import os

from benchmark import manifest
from benchmark.readers.moe_ffn_roofline import step_op_ns


def read(args, sources):
    trace, peaks = sources['trace'], sources['peaks']
    tail = sources.get('registry_tail')
    if not trace or 'window' not in trace or peaks is None or tail is None:
        return None
    least = manifest.load_module(os.path.join(
        sources['bench_dir'], 'shape_fns', args['function'] + '.py')
    ).per_step(tail, sources['registry_after'],
               **args.get('function_args', {}))
    ns, steps = step_op_ns(trace['first'], trace['host'], args['match'],
                           *trace['window'])
    if least is None or not ns:
        return None
    return 100.0 * (least / peaks[args['peak']]) / (ns / 1e9 / steps)
