"""``shape_fns/decode_live_bytes.py`` for the ssm_hybrid block
(granite_4_0_h_micro): bytes per second that the decode step has to
move. Per step every weight once (the tied embedding is read whole as
the head; the gains and the small vectors with it), the state and the
convolution rows that each live row reads and writes in each Mamba-2
layer (``ssm_state_update_bytes.row_layer_bytes``), and the K and V of
the positions the attention layers attend over; over the mean time of a
step. Both sides are the window's (``registry_before`` to
``registry_after``). It is not a kernel's roofline share.
"""

from benchmark import stats
from benchmark.shape_fns import ssm_state_update_bytes as state

ITEMSIZE = state.ITEMSIZE


def weight_bytes(config):
    """Every weight held, once: what a step has to read of them."""
    d, f = config['hidden_size'], config['shared_intermediate_size']
    heads, inner = config['mamba_n_heads'], \
        config['mamba_n_heads'] * config['mamba_d_head']
    conv = inner + 2 * config['mamba_n_groups'] * config['mamba_d_state']
    kv = config['num_key_value_heads'] * (d // config['num_attention_heads'])
    item = ITEMSIZE[config['dtype']]
    kinds = config['layer_types'][:config['num_hidden_layers']]
    n_mamba = sum(1 for k in kinds if k == 'mamba')
    mlp = item * 3 * d * f + 4 * 2 * d                 # and the two gains
    mamba = item * (d * (inner + conv + heads) + inner * d
                    + config['mamba_d_conv'] * conv) \
        + 4 * (conv + 3 * heads + inner)               # float32 vectors
    attention = item * (2 * d * d + 2 * d * kv)
    return (len(kinds) * mlp + n_mamba * mamba
            + (len(kinds) - n_mamba) * attention
            + item * config['vocab_size'] * d + 4 * d)


def kv_bytes(config, live_tokens):
    kinds = config['layer_types'][:config['num_hidden_layers']]
    heads = config['num_attention_heads']
    return sum(1 for k in kinds if k == 'attention') * live_tokens * 2 * \
        config['num_key_value_heads'] * (config['hidden_size'] // heads) \
        * ITEMSIZE[config['engine']['kv_dtype']]


def compute(sources):
    before, after = sources['registry_before'], sources['registry_after']
    seconds = stats.registry_mean(before, after, 'decode.step_seconds')
    live = stats.registry_mean(before, after, 'decode.step_live_tokens')
    pairs = state.row_layers_per_step(before, after)
    if not seconds or live is None or pairs is None:
        return None
    config = sources['config']
    return (weight_bytes(config) + pairs * state.row_layer_bytes(config)
            + kv_bytes(config, live)) / seconds
