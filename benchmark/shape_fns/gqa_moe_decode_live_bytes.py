"""``shape_fns/moe_decode_live_bytes.py`` for the gqa_moe block
(mellum2_12b), whose config.json sizes an expert by
``moe_intermediate_size``, has no shared expert and an untied head:
bytes per second that the decode step has to move. Per step the
attention matrices, the router, the embedding's rows aside, the head and
the gains once, the routed experts that some live row chose (``touched``
a layer: an expert no row chose need not be read), and the K/V of the
positions the step attends over (a sliding layer's capped at its
window); over the mean time of a step. Both sides are the window's
(``registry_before`` to ``registry_after``): the same counters and
histograms as the other file's, whose ``experts_touched`` and
``live_kv_bytes`` are used as they are (they read keys this
configuration has: ``layer_types``, ``num_key_value_heads``,
``head_dim``, ``engine.kv_dtype``). It is not a kernel's roofline share.

``expert_bytes`` is also what ``readers/gqa_moe_ffn_roofline.py`` counts
by, between other snapshots."""

from benchmark import stats
from benchmark.shape_fns import moe_decode_live_bytes as shared

ITEMSIZE = shared.ITEMSIZE


def expert_bytes(config):
    """One expert's three matrices."""
    return (3 * config['hidden_size'] * config['moe_intermediate_size']
            * ITEMSIZE[config['dtype']])


def weight_bytes(config, touched):
    """What a step has to read of the weights, with ``touched`` routed
    experts a layer (``num_experts``: every weight held but the
    embedding, of which a step reads a row a sequence)."""
    d = config['hidden_size']
    q = config['num_attention_heads'] * config['head_dim']
    kv = config['num_key_value_heads'] * config['head_dim']
    item = ITEMSIZE[config['dtype']]
    per_layer = item * (2 * d * q + 2 * d * kv + d * config['num_experts']) \
        + touched * expert_bytes(config)
    gains = 4 * d * (2 * config['num_hidden_layers'] + 1)     # float32
    return (config['num_hidden_layers'] * per_layer
            + item * config['vocab_size'] * d + gains)


def compute(sources):
    before, after = sources['registry_before'], sources['registry_after']
    means = [stats.registry_mean(before, after, name) for name in (
        'decode.step_seconds', 'decode.step_live_tokens',
        'decode.step_window_tokens')]
    touched = shared.experts_touched(before, after)
    if not means[0] or means[1] is None or means[2] is None \
            or touched is None:
        return None
    config = sources['config']
    return (weight_bytes(config, touched)
            + shared.live_kv_bytes(config, means[1], means[2])) / means[0]
