"""``shape_fns/decode_live_bytes.py`` for the ssm_hybrid block whose
layer is one sublayer (nemotron_3_super): bytes per second that the
decode step has to move. Per step: every weight it has to read, once
(the Mamba-2 and attention layers' matrices, each expert layer's router,
its projection into the latent and out of it and its shared expert, the
head read whole; of the routed experts the ones some live row chose,
``touched`` a layer, two matrices each: an expert nobody chose is not
read); the state and the convolution rows that each live row reads and
writes in each Mamba-2 layer (``row_layer_bytes``); and the K and V of
the positions the attention layers attend over; over the mean time of a
step. Both sides are the window's (``registry_before`` to
``registry_after``). It is not a kernel's roofline share.
"""

from benchmark import stats
from benchmark.shape_fns import ssm_state_update_bytes as state
from benchmark.shape_fns.moe_decode_live_bytes import experts_touched

ITEMSIZE = state.ITEMSIZE


def layers_of(config, kind):
    """The layers of ``kind`` ('M', '*' or 'E') in the cut."""
    return config['hybrid_override_pattern'].count(kind)


def conv_width(config):
    """What the convolution runs over: x, and B and C of every group."""
    return config['mamba_num_heads'] * config['mamba_head_dim'] \
        + 2 * config['n_groups'] * config['ssm_state_size']


def row_layer_bytes(config):
    """What a live row's step through one Mamba-2 layer moves: its
    slot's float32 state read and written, and the convolution's kept
    rows (2 x 4,194,304 + 2 x 61,440 at the published widths):
    ``ssm_state_update_bytes.row_layer_bytes`` under nemotron_h's key
    names."""
    return state.row_layer_bytes({
        'mamba_n_heads': config['mamba_num_heads'],
        'mamba_d_head': config['mamba_head_dim'],
        'mamba_d_state': config['ssm_state_size'],
        'mamba_d_conv': config['conv_kernel'],
        'mamba_n_groups': config['n_groups'], 'dtype': config['dtype']})


def expert_bytes(config):
    """One routed expert: two matrices inside the latent."""
    return 2 * config['moe_latent_size'] * config['moe_intermediate_size'] \
        * ITEMSIZE[config['dtype']]


def weight_bytes(config, touched):
    """What a step has to read of the weights, with ``touched`` routed
    experts an expert layer (``n_routed_experts``: every weight held)."""
    d, item = config['hidden_size'], ITEMSIZE[config['dtype']]
    heads = config['mamba_num_heads']
    inner, conv = heads * config['mamba_head_dim'], conv_width(config)
    q = config['num_attention_heads'] * config['head_dim']
    kv = config['num_key_value_heads'] * config['head_dim']
    mamba = item * (d * (inner + conv + heads) + inner * d
                    + config['conv_kernel'] * conv) \
        + 4 * (conv + 3 * heads + inner)               # float32 vectors
    attention = item * (2 * d * q + 2 * d * kv)
    experts = item * (
        d * config['published']['n_routed_experts']
        + 2 * d * config['moe_latent_size']
        + 2 * d * config['moe_shared_expert_intermediate_size']) \
        + 4 * config['published']['n_routed_experts'] \
        + touched * expert_bytes(config)
    return (layers_of(config, 'M') * mamba + layers_of(config, '*')
            * attention + layers_of(config, 'E') * experts
            + 4 * d * (config['num_hidden_layers'] + 1)  # the gains
            + item * config['vocab_size'] * d)           # the head


def kv_bytes(config, live_tokens):
    return layers_of(config, '*') * live_tokens * 2 * \
        config['num_key_value_heads'] * config['head_dim'] \
        * ITEMSIZE[config['engine']['kv_dtype']]


def compute(sources):
    before, after = sources['registry_before'], sources['registry_after']
    seconds = stats.registry_mean(before, after, 'decode.step_seconds')
    live = stats.registry_mean(before, after, 'decode.step_live_tokens')
    pairs = state.row_layers_per_step(before, after)
    touched = experts_touched(before, after)
    if not seconds or live is None or pairs is None or touched is None:
        return None
    config = sources['config']
    return (weight_bytes(config, touched) + pairs * row_layer_bytes(config)
            + kv_bytes(config, live)) / seconds
