"""The least bytes a decode step's attention has to read of a latent
paged cache, by cache kind, per step between two snapshots of the
program's registry.

The engine counts them where it builds the step's batch, from the rows'
own lengths (``decode.cache_bytes_read{kind=...}``, summed over the
layers of the kind, at the row's own width and the arena's itemsize):

    lm_latent_full     the latent rows of the positions the learned
                       selection keeps: min(length, index_topk) a row
    lm_index_full      every cached position's index key: the indexer
                       scores them all to choose
    lm_latent_sliding  the latent rows inside the window:
                       min(length, sliding_window) a row

and the steps in ``decode.steps_total``. A form that reads more (every
cached row of a full layer under a mask, rows padded to whole lane
tiles) shows a smaller share of the roofline; none can read less.
"""

from benchmark import stats

COUNTER = 'decode.cache_bytes_read'


def _grown(before, after, name):
    return (stats.registry_pooled(after, 'counters', name)
            - stats.registry_pooled(before, 'counters', name))


def per_step(before, after, kinds):
    """Mean bytes a step, over the steps counted between the snapshots,
    of the cache ``kinds``; None where no step or no byte was counted
    (an untraced run, or a program without the counters)."""
    steps = _grown(before, after, 'decode.steps_total')
    read = sum(_grown(before, after, '%s{kind=%s}' % (COUNTER, kind))
               for kind in kinds)
    if steps <= 0 or read <= 0:
        return None
    return read / float(steps)
