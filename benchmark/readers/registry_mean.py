"""Mean of a histogram of the program's observe registry over the
window, the label variants of the name pooled (stats.registry_mean).
args: {"histogram": name, "scale": multiplier, default 1}."""

from benchmark import stats


def read(args, sources):
    if sources['registry_after'] is None:
        return None
    mean = stats.registry_mean(sources['registry_before'],
                               sources['registry_after'], args['histogram'])
    return None if mean is None else mean * args.get('scale', 1)
