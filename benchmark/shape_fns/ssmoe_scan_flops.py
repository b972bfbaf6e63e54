"""``shape_fns/ssm_scan_flops.py`` under nemotron_h's key names
(nemotron_3_super): ``5 x mamba_num_heads x mamba_head_dim x
ssm_state_size`` a (row, Mamba-2 layer) step of a prefill chunk's scan,
5.24 M at the published widths (128 heads of 64 over a state of 128).
B and C come in ``n_groups`` groups of heads, which changes what is
read and not what is computed. ``pairs`` is the chunks' ``scan_rows``,
which ``runners/serve_ssm.py::chunks_dispatched`` hands to
``readers/prefill_ops_mxu.py``.
"""

from benchmark.shape_fns import ssm_scan_flops


def least_flops(pairs, config):
    return ssm_scan_flops.least_flops(pairs, {
        'mamba_n_heads': config['mamba_num_heads'],
        'mamba_d_head': config['mamba_head_dim'],
        'mamba_d_state': config['ssm_state_size']})
