"""``shape_fns/moe_decode_live_bytes.py`` for the latent_moe block under
a carried selection (glm_5_2), read from GLM-5.2's own config.json keys:
bytes per second that the decode step has to move. Per step, once: the
attention of every layer run (W_qa, W_qb, W_kva, the two halves of W_kvb,
W_o), the indexer of the layers that score (``indexer_types`` 'full'
among the layers run: W^I_q, W^I_k, W^I_w), the leading dense FFN, the
routers and the shared experts of the routed layers, the head (of the
embedding a step reads a row a sequence) and the gains; the routed
experts that some live row chose (``touched`` a layer: an expert no row
chose need not be read); and of the cache what the selection leaves no
form a way around: every held position's index key in the layers that
score, and ``min(length, index_topk)`` latent rows a live row in every
layer, at the rows' own widths (576 and 128 values, not the 640 a latent
row is stored at); over the mean time of a step. Both sides are the
window's (``registry_before`` to ``registry_after``): the counters
``decode.moe_experts_touched`` / ``decode.moe_layer_steps`` (the other
file's ``experts_touched``, used as it is), ``decode.cache_bytes_read``
by kind over ``decode.steps_total`` (``latent_decode_bytes.per_step``:
the engine counts them from the step's own lengths, the scoring layers
and all layers apart) and the histogram ``decode.step_seconds``. It is
not a kernel's roofline share: the step may move more than this (the
masked form reads every page a row holds), never less.

``expert_bytes`` and ``routed_layers`` are also what
``readers/dsa_moe_ffn_roofline.py`` counts by, between other
snapshots."""

from benchmark import stats
from benchmark.shape_fns import latent_decode_bytes as cache
from benchmark.shape_fns import moe_decode_live_bytes as shared

ITEMSIZE = shared.ITEMSIZE
KINDS = ('lm_latent_full', 'lm_index_full')


def layers_run(config):
    """(layers, those that score, the leading dense ones) of the cut."""
    first, depth = config['first_layer'], config['num_hidden_layers']
    run = slice(first, first + depth)
    return (depth, config['indexer_types'][run].count('full'),
            config['mlp_layer_types'][run].count('dense'))


def routed_layers(config):
    depth, _, dense = layers_run(config)
    return depth - dense


def expert_bytes(config):
    """One expert's three matrices (a routed one, or the shared one)."""
    return (3 * config['hidden_size'] * config['moe_intermediate_size']
            * ITEMSIZE[config['dtype']])


def attention_params(config):
    d, heads = config['hidden_size'], config['num_attention_heads']
    q, r = config['q_lora_rank'], config['kv_lora_rank']
    nope, rope, v = (config['qk_nope_head_dim'], config['qk_rope_head_dim'],
                     config['v_head_dim'])
    return (d * q + q * heads * (nope + rope) + d * (r + rope)
            + heads * r * (nope + v) + heads * v * d)


def indexer_params(config):
    d, q = config['hidden_size'], config['q_lora_rank']
    heads, width = config['index_n_heads'], config['index_head_dim']
    return q * heads * width + d * width + d * heads


def weight_bytes(config, touched):
    """What a step has to read of the weights, with ``touched`` routed
    experts a routed layer."""
    d, item = config['hidden_size'], ITEMSIZE[config['dtype']]
    depth, scoring, dense = layers_run(config)
    routed = depth - dense
    wide = config['published']['n_routed_experts']
    matrices = (depth * attention_params(config)
                + scoring * indexer_params(config)
                + dense * 3 * d * config['intermediate_size']
                + routed * d * wide + config['vocab_size'] * d)
    # float32: two norms a layer and the two latents', the index key's
    # gain and bias, the routers' selection bias, the final norm
    gains = 4 * (depth * (2 * d + config['q_lora_rank']
                          + config['kv_lora_rank'])
                 + scoring * 2 * config['index_head_dim']
                 + routed * wide + d)
    return (item * matrices + gains
            + routed * (touched + config['n_shared_experts'])
            * expert_bytes(config))


def compute(sources):
    before, after = sources['registry_before'], sources['registry_after']
    seconds = stats.registry_mean(before, after, 'decode.step_seconds')
    touched = shared.experts_touched(before, after)
    rows = cache.per_step(before, after, KINDS)
    if not seconds or touched is None or rows is None:
        return None
    return (weight_bytes(sources['config'], touched) + rows) / seconds
