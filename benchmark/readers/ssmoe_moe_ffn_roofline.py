"""``readers/moe_ffn_roofline.py`` for the expert layers of the
one-sublayer ssm_hybrid block (nemotron_3_super): roofline share, in
percent, of the decode step's routed expert products inside the latent.
The same rule: the least bytes they have to read in a step, over the HBM
peak, against the device time they took per step, both sides taken from
the traced tail of the window (``registry_tail`` to ``registry_after``
for the experts chosen; the matched ops that started under a
``decode.step`` span whole inside the traced window for the time:
``moe_ffn_roofline.step_op_ns``, used as it is).

The least bytes, with this configuration's keys: in every expert layer
of the cut (``shape_fns/ssmoe_decode_live_bytes.py::layers_of``) each
expert held here that some live row chose, counted once however many
rows chose it, its two matrices of ``moe_latent_size x
moe_intermediate_size``. The shared expert and the projections into the
latent and out of it are on the hidden width, run as plain products and
are not counted here, in the bytes or in the time. A formulation that reads
every expert held, chosen or not, reads more and shows a smaller share;
nothing is clipped.
args: {"match": [regex, ...], "peak": key of peaks.json}."""

from benchmark.readers.moe_ffn_roofline import step_op_ns
from benchmark.shape_fns import ssmoe_decode_live_bytes as shapes
from benchmark.shape_fns.moe_decode_live_bytes import experts_touched


def least_bytes_per_step(config, touched):
    return shapes.layers_of(config, 'E') * shapes.expert_bytes(config) \
        * touched


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
