"""How long after the device finished the host span that waited for it
returned: for each host span named ``span`` that lies in the traced
part of the window, its end minus the end of the last op of the first
chip that ends inside it; the mean, in milliseconds. A span inside
which no device op ends is left out; None where none is left.
args: {"span": name}."""

import bisect


def read(args, sources):
    trace = sources['trace']
    if not trace or 'window' not in trace:
        return None
    lo, hi = trace['window']
    ends = sorted(s + d for _, s, d in trace['first'])
    waits = []
    for name, s, d in trace['host']:
        if name != args['span'] or s < lo or s + d > hi:
            continue
        i = bisect.bisect_right(ends, s + d)
        if i and ends[i - 1] >= s:
            waits.append(s + d - ends[i - 1])
    if not waits:
        return None
    return sum(waits) / len(waits) / 1e6
