"""Of the first chip's idle nanoseconds in the traced part of the
window, the share, in percent, that lies under host spans whose names
match ``spans``: who owned the device's idle time. Where host spans
nest, the innermost one (the latest to start) owns the time under it;
``among`` names every span that takes part in that contest (default:
``spans`` themselves), so time under a child that is not counted is not
given to its counted parent. None where there is no traced window, no
idle time, or no span of ``spans`` in the trace.
args: {"spans": [regex, ...], "among": [regex, ...]}."""

from benchmark import tracelib


def owned(spans):
    """The disjoint sorted intervals in which the innermost of
    ``spans``, ``(start, end, counted)`` triples, is a counted one."""
    spans = sorted(spans)
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out, active, i = [], [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= t0:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > t0]
        # the latest to start is the innermost; of two that start
        # together, the one that ends first
        if active and max(active, key=lambda sp: (sp[0], -sp[1]))[2]:
            out.append((t0, t1))
    return tracelib.merged(out)


def read(args, sources):
    trace = sources['trace']
    if not trace or 'window' not in trace:
        return None
    lo, hi = trace['window']
    busy = tracelib.merged(tracelib.clipped(
        tracelib.spans_of(trace['first']), lo, hi))
    idle = tracelib.subtract([(lo, hi)], busy)
    counted = set(tracelib.matching(trace['host'], args['spans']))
    if not idle or not counted:
        return None
    family = tracelib.matching(trace['host'],
                               args.get('among', args['spans']))
    mine = owned([(s, s + d, (name, s, d) in counted)
                  for name, s, d in set(family) | counted if d > 0])
    uncovered = tracelib.subtract(idle, mine)
    return 100.0 * (1.0 - tracelib.total(uncovered)
                    / float(tracelib.total(idle)))
