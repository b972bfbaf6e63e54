"""Percentiles and spreads, as the benchmark's contract defines them."""

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0 < q < 100) by the nearest-rank rule: the
    smallest value with at least q% of the sample at or below it. No
    interpolation, so a tail is always a latency some request had."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, int(math.ceil(q / 100.0 * len(ordered))))
    return ordered[rank - 1]


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def registry_pooled(snapshot, kind, name):
    """From a snapshot of the program's observe registry: (sum, count)
    of a histogram, or the value of a counter, pooled over ``name`` and
    every ``name{labels}``."""
    hits = [v for k, v in (snapshot or {}).get(kind, {}).items()
            if k == name or k.startswith(name + '{')]
    if kind == 'histograms':
        return (sum(h['sum'] for h in hits), sum(h['count'] for h in hits))
    return sum(hits)


def registry_mean(before, after, name):
    """Mean of a histogram between two snapshots: the registry
    accumulates from process start and its quantiles are
    reservoir-sampled, so only sums and counts are read."""
    s0, n0 = registry_pooled(before, 'histograms', name)
    s1, n1 = registry_pooled(after, 'histograms', name)
    return (s1 - s0) / (n1 - n0) if n1 > n0 else None
