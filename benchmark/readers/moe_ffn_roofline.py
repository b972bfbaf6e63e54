"""Roofline share, in percent, of the decode step's routed and shared
expert products: the least bytes they have to read in a step, over the
HBM peak, against the device time they took per step in the traced tail.

The least bytes: in every layer each expert held here whose three
matrices some row chose, plus the shared experts, which every row takes:
``layers x (touched + shared) x 3 x hidden x width x itemsize``, with
``touched`` the window's mean of experts chosen per layer-step
(counters ``decode.moe_experts_touched`` / ``decode.moe_layer_steps``).
A formulation that reads every expert held, chosen or not, reads more
and shows a smaller share; none can read less, so the share cannot pass
100.

The time: device ops of the first chip whose HLO line matches ``match``
(the stacked expert weights among the operands) and which started under
one of the worker's ``decode.step`` spans that lie whole inside the
traced window: prefill programs hold products of the same operands, and
a prefill never runs under a step's span (the worker is one thread and a
prefill ends in a fetch). The steps counted are those spans.
args: {"match": [regex, ...], "peak": key of peaks.json}."""

from benchmark import stats, tracelib

ITEMSIZE = {'bfloat16': 2, 'float32': 4}


def least_bytes_per_step(config, touched):
    return (config['num_hidden_layers']
            * (touched + config['num_shared_experts']) * 3
            * config['hidden_size'] * config['intermediate_size']
            * ITEMSIZE[config['dtype']])


def step_op_ns(device, host, patterns, lo, hi):
    """(nanoseconds of matching device ops that started under a
    ``decode.step`` span inside [lo, hi], the number of those spans)."""
    steps = sorted((s, s + d) for name, s, d in host
                   if name == 'decode.step' and s >= lo and s + d <= hi)
    if not steps:
        return 0, 0
    ns, i = 0, 0
    for _, s, d in sorted(tracelib.matching(device, patterns),
                          key=lambda ev: ev[1]):
        while i < len(steps) and steps[i][1] <= s:
            i += 1
        if i < len(steps) and steps[i][0] <= s:
            ns += d
    return ns, len(steps)


def read(args, sources):
    trace, peaks = sources['trace'], sources['peaks']
    before, after = sources['registry_before'], sources['registry_after']
    if not trace or 'window' not in trace or peaks is None or after is None:
        return None

    def grown(name):
        return (stats.registry_pooled(after, 'counters', name)
                - stats.registry_pooled(before, 'counters', name))

    layer_steps = grown('decode.moe_layer_steps')
    ns, steps = step_op_ns(trace['first'], trace['host'], args['match'],
                           *trace['window'])
    if layer_steps <= 0 or not ns:
        return None
    touched = grown('decode.moe_experts_touched') / float(layer_steps)
    least_s = least_bytes_per_step(sources['config'], touched) / \
        peaks[args['peak']]
    return 100.0 * least_s / (ns / 1e9 / steps)
