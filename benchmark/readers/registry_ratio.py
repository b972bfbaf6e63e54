"""Growth of one counter of the program's observe registry over the
window divided by the growth of another (label variants summed), times
``scale``: a share or a mean per event of what the program counted. None
where the denominator did not grow, as in a program that has no such
counter.
args: {"counter": name, "per": name, "scale": multiplier, default 1}."""

from benchmark import stats


def read(args, sources):
    before, after = sources['registry_before'], sources['registry_after']
    if after is None:
        return None

    def grown(name):
        return (stats.registry_pooled(after, 'counters', name)
                - stats.registry_pooled(before, 'counters', name))

    per = grown(args['per'])
    if per <= 0:
        return None
    return args.get('scale', 1) * grown(args['counter']) / float(per)
