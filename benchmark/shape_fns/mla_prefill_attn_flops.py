"""The least operations the latent attention of prefill chunks needs:
for every (query, key at or below it) pair a layer weighs, one score
over ``qk_nope_head_dim + qk_rope_head_dim`` and one weighted value of
``v_head_dim`` a head, a multiply and an add each:

    pairs x heads x (nope + rope + v) x 2

with ``pairs`` already summed over the layers (the engine counts them
where it builds a prefill: the ``attn_pairs`` of its
``decode.prefill.run`` span, of each chunk's ``decode.prefill.chunk``
span, and the counter ``decode.prefill_attn_pairs``; for kimi_k2_6 every layer reads every
position, so a prefill of positions a .. b - 1 has layers x sum_{t=a}^{b-1}
(t + 1) of them). This is the expanded form's count (keys and values a
head, 192 and 128 wide). No form does less: the absorbed form, which
scores against the cached row (576) and sums latents (512), does
(576 + 512) / (192 + 128) = 3.4 times that, and a form that multiplies
whole 512 x 512 blocks under a mask about twice its own count at a
chunk's own depth.
"""


def least_flops(pairs, config):
    return float(pairs) * config['num_attention_heads'] * 2 * (
        config['qk_nope_head_dim'] + config['qk_rope_head_dim']
        + config['v_head_dim'])
