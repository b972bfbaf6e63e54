"""``shape_fns/moe_decode_live_bytes.py`` for the shortcut_moe block
(longcat_flash_chat), whose config.json counts layers by ``num_layers``,
sizes a dense FFN by ``ffn_hidden_size`` and an expert by
``expert_ffn_hidden_size``, has two latent attentions and two dense FFNs
a layer, no shared expert and an untied head: bytes per second that the
decode step has to move. Per step, once: both sublayers' attention
matrices and dense FFNs, the router (as wide as the real and the
identity experts together), the head (of the embedding a step reads a
row a sequence) and the gains; the real routed experts that some live
row chose (``touched`` a layer: an expert no row chose need not be read,
and an identity expert has nothing to read); and the latent rows of the
positions the step attends over, in each of a layer's two cache layers,
at the row's own width (``kv_lora_rank + qk_rope_head_dim``, not the
640 it is stored at); over the mean time of a step. Both sides are the
window's (``registry_before`` to ``registry_after``): the counters
``decode.moe_experts_touched`` / ``decode.moe_layer_steps`` (the other
file's ``experts_touched``, used as it is) and the histograms
``decode.step_seconds`` and ``decode.step_live_tokens``. It is not a
kernel's roofline share: the step may move more than this, never less.

``expert_bytes`` is also what ``readers/scmoe_moe_ffn_roofline.py``
counts by, between other snapshots."""

from benchmark import stats
from benchmark.shape_fns import moe_decode_live_bytes as shared

ITEMSIZE = shared.ITEMSIZE
SUBLAYERS = 2


def expert_bytes(config):
    """One real expert's three matrices."""
    return (3 * config['hidden_size'] * config['expert_ffn_hidden_size']
            * ITEMSIZE[config['dtype']])


def attention_params(config):
    """One latent attention: W_qa, W_qb, W_kva, the two halves of W_kvb
    and W_o."""
    d, heads = config['hidden_size'], config['num_attention_heads']
    q, r = config['q_lora_rank'], config['kv_lora_rank']
    nope, rope, v = (config['qk_nope_head_dim'], config['qk_rope_head_dim'],
                     config['v_head_dim'])
    return (d * q + q * heads * (nope + rope) + d * (r + rope)
            + heads * r * (nope + v) + heads * v * d)


def weight_bytes(config, touched):
    """What a step has to read of the weights, with ``touched`` routed
    experts a layer."""
    d, item = config['hidden_size'], ITEMSIZE[config['dtype']]
    wide = config['published']['n_routed_experts'] + \
        config['zero_expert_num']
    per_layer = item * (SUBLAYERS * (attention_params(config)
                                     + 3 * d * config['ffn_hidden_size'])
                        + d * wide) + touched * expert_bytes(config)
    # float32: two norms a sublayer, the two latents' norms, the final
    # norm and the router's selection bias
    gains = 4 * (config['num_layers'] * (SUBLAYERS * (
        2 * d + config['q_lora_rank'] + config['kv_lora_rank']) + wide) + d)
    return (config['num_layers'] * per_layer
            + item * config['vocab_size'] * d + gains)


def live_cache_bytes(config, live_tokens):
    row = config['kv_lora_rank'] + config['qk_rope_head_dim']
    return (SUBLAYERS * config['num_layers'] * row
            * ITEMSIZE[config['engine']['kv_dtype']] * live_tokens)


def compute(sources):
    before, after = sources['registry_before'], sources['registry_after']
    seconds, live = (stats.registry_mean(before, after, name) for name in (
        'decode.step_seconds', 'decode.step_live_tokens'))
    touched = shared.experts_touched(before, after)
    if not seconds or live is None or touched is None:
        return None
    config = sources['config']
    return (weight_bytes(config, touched)
            + live_cache_bytes(config, live)) / seconds
