"""``shape_fns/decode_live_bytes.py`` for the delta_hybrid block
(qwen3_next): bytes per second that the decode step has to move. Per
step: every weight it has to read, once (the linear-attention and the
full-attention layers' matrices, each layer's router and its shared
expert with its gate, the head read whole, the gains; of the routed
experts the ones some live row chose, ``touched`` a layer, three
matrices each: an expert nobody chose is not read); the state and the
convolution rows that each live row reads and writes in each
linear-attention layer (``row_layer_bytes``); and the K and V of the
positions the full-attention layers attend over; over the mean time of a
step. Both sides are the window's (``registry_before`` to
``registry_after``). It is not a kernel's roofline share.
"""

from benchmark import stats
from benchmark.shape_fns import ssm_state_update_bytes as state
from benchmark.shape_fns.moe_decode_live_bytes import experts_touched

ITEMSIZE = state.ITEMSIZE


def layers_of(config, kind):
    """The layers of ``kind`` ('linear' or 'full') in the cut: layer
    ``i`` of the published model is full attention where ``(i + 1) %
    full_attention_interval == 0``."""
    first, every = config['first_layer'], config['full_attention_interval']
    full = sum(1 for i in range(config['num_hidden_layers'])
               if (first + i + 1) % every == 0)
    return full if kind == 'full' else config['num_hidden_layers'] - full


def conv_width(config):
    """What the convolution runs over: q and k of every key head, v."""
    return 2 * config['linear_num_key_heads'] * config['linear_key_head_dim'] \
        + config['linear_num_value_heads'] * config['linear_value_head_dim']


def row_layer_bytes(config):
    """What a live row's step through one linear-attention layer moves:
    its slot's float32 state ``[value heads, K, V]`` read and written,
    and the convolution's kept rows (2 x 2,097,152 + 2 x 49,152 at the
    published widths in bfloat16)."""
    kept = (config['linear_conv_kernel_dim'] - 1) * conv_width(config) \
        * ITEMSIZE[config['dtype']]
    return 2 * 4 * config['linear_num_value_heads'] \
        * config['linear_key_head_dim'] * config['linear_value_head_dim'] \
        + 2 * kept


def expert_bytes(config):
    """One routed expert's three matrices."""
    return 3 * config['hidden_size'] * config['moe_intermediate_size'] \
        * ITEMSIZE[config['dtype']]


def weight_bytes(config, touched):
    """What a step has to read of the weights, with ``touched`` routed
    experts a layer (``num_experts``: every weight held but the
    embedding, of which a step reads a row a sequence)."""
    d, item = config['hidden_size'], ITEMSIZE[config['dtype']]
    heads = config['linear_num_value_heads']
    inner, conv = heads * config['linear_value_head_dim'], conv_width(config)
    q = config['num_attention_heads'] * config['head_dim']
    kv = config['num_key_value_heads'] * config['head_dim']
    linear = item * (d * (conv + inner) + d * 2 * heads + inner * d
                     + config['linear_conv_kernel_dim'] * conv) \
        + 4 * (2 * heads + config['linear_value_head_dim'])
    full = item * (3 * d * q + 2 * d * kv) + 4 * 2 * config['head_dim']
    experts = item * (
        d * config['published']['num_experts'] + d
        + 3 * d * config['shared_expert_intermediate_size']) \
        + touched * expert_bytes(config)
    return (layers_of(config, 'linear') * linear
            + layers_of(config, 'full') * full
            + config['num_hidden_layers'] * experts
            + 4 * d * (2 * config['num_hidden_layers'] + 1)  # the gains
            + item * config['vocab_size'] * d)               # the head


def kv_bytes(config, live_tokens):
    return layers_of(config, 'full') * live_tokens * 2 * \
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
