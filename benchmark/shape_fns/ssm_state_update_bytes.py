"""The least bytes a decode step's state update has to move, per step
between two snapshots of the program's registry.

A live row's step through one Mamba-2 layer reads its slot's state and
writes it back, and reads and writes the convolution's K - 1 kept rows:

    row_layer_bytes = 2 x (d_state x n_heads x d_head x 4)        float32
                    + 2 x ((d_conv - 1) x (n_heads x d_head + 2 d_state) x itemsize)

(``row_layer_bytes(config)``; 2 x 2,097,152 + 2 x 26,112 = 4,246,528 at
the published widths in bfloat16). The engine counts the (live row,
layer) pairs where it builds the step's batch
(``decode.step_state_rows_total``) and the steps in
``decode.steps_total``. A form that gathers the rows' states, updates
them and scatters them back moves each twice more and shows a smaller
share of the roofline; none can move less.

``per_step`` is what ``readers/step_ops_roofline.py`` calls; it is given
the bytes a (row, layer) as an argument of the metric's file, and
``tests/benchmark/test_granite_4_0_h_micro.py`` holds that number to
``row_layer_bytes`` of the configuration.
"""

from benchmark import stats

ITEMSIZE = {'float32': 4, 'bfloat16': 2}
COUNTER = 'decode.step_state_rows_total'


def row_layer_bytes(config):
    inner = config['mamba_n_heads'] * config['mamba_d_head']
    state = config['mamba_d_state'] * inner * 4
    conv = (config['mamba_d_conv'] - 1) * (
        inner + 2 * config['mamba_n_groups'] * config['mamba_d_state']) \
        * ITEMSIZE[config['dtype']]
    return 2 * state + 2 * conv


def _grown(before, after, name):
    return (stats.registry_pooled(after, 'counters', name)
            - stats.registry_pooled(before, 'counters', name))


def row_layers_per_step(before, after):
    """Mean (live row, layer) pairs a step between the snapshots; None
    where no step or no pair was counted (an untraced run, or a program
    without the counter)."""
    steps = _grown(before, after, 'decode.steps_total')
    pairs = _grown(before, after, COUNTER)
    if steps <= 0 or pairs <= 0:
        return None
    return pairs / float(steps)


def per_step(before, after, row_layer_bytes):
    pairs = row_layers_per_step(before, after)
    return None if pairs is None else pairs * row_layer_bytes
