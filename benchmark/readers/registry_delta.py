"""Growth of a counter of the program's observe registry over the
window, its label variants summed. args: {"counter": name}."""

from benchmark import stats


def read(args, sources):
    if sources['registry_after'] is None:
        return None
    return (stats.registry_pooled(sources['registry_after'], 'counters',
                                  args['counter'])
            - stats.registry_pooled(sources['registry_before'], 'counters',
                                    args['counter']))
