"""Device time of the ops whose names match, on the first chip of the
trace, per step of the traced part of the window, in milliseconds.
``mode`` "total" sums their time (overlapping ones once); "exposed"
keeps the part during which no other op ran on that chip.
args: {"match": [regex, ...], "mode": "total" | "exposed"}."""

from benchmark import tracelib


def read(args, sources):
    trace = sources['trace']
    steps = sources.get('trace_steps')
    if not trace or 'window' not in trace or not steps:
        return None
    lo, hi = trace['window']
    events = trace['first']
    if args.get('mode', 'total') == 'exposed':
        ns = tracelib.exposed_ns(events, args['match'], lo, hi)
    else:
        ns = tracelib.busy_ns(tracelib.matching(events, args['match']),
                              lo, hi)
    return ns / 1e6 / steps
