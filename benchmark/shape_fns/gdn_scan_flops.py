"""The least operations the gated delta rule of prefill chunks needs
(qwen3_next's key names): for every (row, linear-attention layer) step a
chunk takes, the recurrence itself, a value head of V over keys of K,

    S = alpha S                           K x V
    m = S^T k;  o = S^T q                 2 x 2 x K x V
    S = S + k d^T                         2 x K x V        (d = beta (v - m): V more)

a multiply and an add counted apart: ``7 x value heads x K x V`` (3.67 M
at the published widths: 32 heads of 128 x 128). ``pairs`` is those
steps, already summed over the layers (the ``scan_rows`` of the chunks'
spans, which ``runners/serve_ssm.py::chunks_dispatched`` hands to
``readers/prefill_ops_mxu.py`` under its key ``pairs``). No form does
less. The chunked form, which is what puts the work on the matrix unit,
does more: inside a scan chunk of Q rows the two Q x Q products with
keys, the triangular inverse by ``log2 Q`` squarings, ``W``, ``U`` and
the masked product are ``2 Q (3 K + 2 V) + 4 Q^2 log2 Q`` a (row, head)
beside ``6 K V`` for the carried state's three products: 0.27 M a (row,
head) at Q = 64 against 0.11 M here, and all of it is float32 at the
highest precision, six passes of the bfloat16 matrix unit. So a share of
the matrix peak read from this count is of the work that had to be
done, not of the work the form does.
"""


def least_flops(pairs, config):
    return float(pairs) * 7 * config['linear_num_value_heads'] * \
        config['linear_key_head_dim'] * config['linear_value_head_dim']
