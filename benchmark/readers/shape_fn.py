"""What the shapes say the work needs each second on one chip, as a
share of that chip's peak, in percent. The arithmetic is a file of its
own, ``shape_fns/<function>.py`` with ``compute(sources)`` returning
units per second per chip (FLOPs or bytes; None where a rate it needs
was not measured); ``peak`` names the entry of peaks.json it is held
against. Nothing is clipped: a share above 100 means the count is
wrong. args: {"function": name, "peak": key}."""

import os

from benchmark import manifest


def read(args, sources):
    if sources['peaks'] is None:
        return None
    per_second = manifest.load_module(os.path.join(
        sources['bench_dir'], 'shape_fns',
        args['function'] + '.py')).compute(sources)
    if per_second is None:
        return None
    return 100.0 * per_second / sources['peaks'][args['peak']]
