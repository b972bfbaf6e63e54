"""Growth of one labelled histogram's sum over the window as a share,
in percent, of the growth of a list of them: which part of a time that
the program partitions by label went to one label. The series are named
as the registry renders them, ``name{label=value}``; one that never
recorded counts as zero.
args: {"part": series, "whole": [series, ...]}."""

from benchmark import stats


def read(args, sources):
    before, after = sources['registry_before'], sources['registry_after']
    if after is None:
        return None

    def grown(series):
        return (stats.registry_pooled(after, 'histograms', series)[0]
                - stats.registry_pooled(before, 'histograms', series)[0])

    whole = sum(grown(series) for series in args['whole'])
    if whole <= 0:
        return None
    return 100.0 * grown(args['part']) / whole
