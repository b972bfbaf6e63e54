"""Bytes per second that the decode step of a routed-expert block has to
move: every weight held here once a step (attention, router, the experts
held and the shared ones, the tied head) plus the K/V of the positions a
step attends over, over the mean time of a step (which ends in a fetch,
so it is the synchronous step, host code between steps left out). A
sliding layer's positions are capped at the window: the engine records
both sums where it builds the batch (``decode.step_live_tokens``,
``decode.step_window_tokens``). The configuration is read by the keys of
the source's config.json. It is not a kernel's roofline share: the step
may move far more than this (it gathers every page of the table)."""

from benchmark import stats

ITEMSIZE = {'bfloat16': 2, 'float32': 4}


def weight_bytes(config):
    d, f = config['hidden_size'], config['intermediate_size']
    q = config['num_attention_heads'] * config['head_dim']
    kv = config['num_key_value_heads'] * config['head_dim']
    experts = config['num_experts'] + config['num_shared_experts']
    per_layer = (2 * d * q + 2 * d * kv
                 + d * config['published']['num_experts']
                 + experts * 3 * d * f)
    item = ITEMSIZE[config['dtype']]
    gains = 4 * d * (config['num_hidden_layers'] + 1)       # float32
    return item * (config['num_hidden_layers'] * per_layer
                   + config['vocab_size'] * d) + gains


def kv_bytes_per_token_layer(config):
    return 2 * config['num_key_value_heads'] * config['head_dim'] * \
        ITEMSIZE[config['engine']['kv_dtype']]


def live_kv_bytes(config, live_tokens, window_tokens):
    kinds = config['layer_types'][:config['num_hidden_layers']]
    sliding = sum(1 for k in kinds if k == 'sliding_attention')
    return kv_bytes_per_token_layer(config) * (
        sliding * window_tokens + (len(kinds) - sliding) * live_tokens)


def compute(sources):
    before, after = sources['registry_before'], sources['registry_after']
    means = [stats.registry_mean(before, after, name) for name in (
        'decode.step_seconds', 'decode.step_live_tokens',
        'decode.step_window_tokens')]
    if not means[0] or means[1] is None or means[2] is None:
        return None
    config = sources['config']
    return (weight_bytes(config)
            + live_kv_bytes(config, means[1], means[2])) / means[0]
