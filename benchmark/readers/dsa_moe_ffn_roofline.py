"""``readers/moe_ffn_roofline.py`` for the latent_moe block under a
carried selection (glm_5_2): roofline share, in percent, of the decode
step's routed and shared expert products. The same rule: the least
bytes they have to read in a step, over the HBM peak, against the device
time they took per step, both sides taken from the traced tail of the
window (``registry_tail`` to ``registry_after`` for the experts chosen;
the matched ops that started under a ``decode.step`` span whole inside
the traced window for the time: ``moe_ffn_roofline.step_op_ns``, used as
it is).

The least bytes, with this configuration's keys: in every routed layer
that is run (``shape_fns/dsa_decode_live_bytes.py::routed_layers``: the
layers of the cut less its leading dense ones) each expert held here
whose three matrices some live row chose, counted once however many rows
chose it, plus the shared experts, which every row takes: ``routed
layers x (touched + n_shared_experts) x 3 x hidden_size x
moe_intermediate_size x itemsize``. A formulation that reads every
expert held, chosen or not, reads more and shows a smaller share;
nothing is clipped.
args: {"match": [regex, ...], "peak": key of peaks.json}."""

from benchmark.readers.moe_ffn_roofline import step_op_ns
from benchmark.shape_fns import dsa_decode_live_bytes as shapes
from benchmark.shape_fns.moe_decode_live_bytes import experts_touched


def least_bytes_per_step(config, touched):
    return shapes.routed_layers(config) * shapes.expert_bytes(config) * (
        touched + config['n_shared_experts'])


def read(args, sources):
    trace, peaks = sources['trace'], sources['peaks']
    tail = sources.get('registry_tail')
    if not trace or 'window' not in trace or peaks is None or tail is None:
        return None
    touched = experts_touched(tail, sources['registry_after'])
    ns, steps = step_op_ns(trace['first'], trace['host'], args['match'],
                           *trace['window'])
    if touched is None or not ns:
        return None
    least_s = least_bytes_per_step(sources['config'], touched) / \
        peaks[args['peak']]
    return 100.0 * least_s / (ns / 1e9 / steps)
