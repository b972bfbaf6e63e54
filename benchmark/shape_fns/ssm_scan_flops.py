"""The least operations the selective-state recurrence of prefill chunks
needs: for every (row, Mamba-2 layer) step a chunk's scan takes, the
recurrence itself,

    S = decay * S + (dt x) outer B        3 x heads x d_head x d_state
    y = S C                               2 x heads x d_head x d_state

a multiply and an add counted apart: ``5 x heads x d_head x d_state``
(2.62 M at the published widths). ``pairs`` is those steps, already
summed over the layers (the engine counts them where it builds a
prefill: the ``scan_rows`` of its ``decode.prefill.run`` span and of
each chunk's ``decode.prefill.chunk`` span, live rows x layers;
``runners/serve_ssm.py`` hands them to ``readers/prefill_ops_mxu.py``
under its key ``pairs``). No form does less. The chunked form, which is
what puts the work on the matrix unit, does more: inside a chunk of Q
rows the masked product is ``2 Q (d_state + heads x d_head)`` a row
beside ``4 x heads x d_head x d_state`` for the carried state's part and
the state the chunk leaves: 4.2 M a row-layer at Q = 256, 1.6 times the
count here, and its decays are Q x Q x heads exponentials on the vector
unit. So a share of the matrix peak read from this count is of the work
that had to be done, not of the work the form does.
"""


def least_flops(pairs, config):
    return float(pairs) * 5 * config['mamba_n_heads'] * \
        config['mamba_d_head'] * config['mamba_d_state']
