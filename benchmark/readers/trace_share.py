"""Share, in percent, of the first chip's busy time in the traced part
of the window that ops whose names match take (overlapping ones once):
which family of ops the device's time goes to.
args: {"match": [regex, ...]}."""

from benchmark import tracelib


def read(args, sources):
    trace = sources['trace']
    if not trace or 'window' not in trace:
        return None
    lo, hi = trace['window']
    busy = tracelib.busy_ns(trace['first'], lo, hi)
    if not busy:
        return None
    return 100.0 * tracelib.busy_ns(
        tracelib.matching(trace['first'], args['match']), lo, hi) / busy
