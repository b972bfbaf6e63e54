"""The shortcut_moe block of the paged decode ops (LMSpec
block='shortcut_moe': longcat_flash): a layer of two latent attentions
and two dense FFNs with one shortcut-connected expert branch beside
them, under a router a third of whose outputs are identity experts.

A layer ``l`` has sublayers ``j`` in (0, 1), each with its own latent
attention, its two RMSNorms and its dense gated SiLU FFN, and one expert
branch that leaves from the first sublayer's normed state and rejoins
at the layer's end:

    a0 = x  + Attn[l,0](RMSNorm_in[l,0](x))
    n0 = RMSNorm_post[l,0](a0)
    s  = MoE[l](n0)
    b0 = a0 + FFN[l,0](n0)
    a1 = b0 + Attn[l,1](RMSNorm_in[l,1](b0))
    n1 = RMSNorm_post[l,1](a1)
    y  = a1 + FFN[l,1](n1) + s

so nothing after ``n0`` waits for the experts until the last add: in a
deployment their exchange runs under the first FFN and the whole second
sublayer.

**Attention** is ``LatentMoEBlock._attention`` as it is (dense: every
position at or below a row's own; absorbed in the decode step and a
short chunk, expanded in a long one; ``lora_rescale`` on, no gate), run
twice a layer. Each run has cache rows of its own: the one arena
``lm_latent_full`` is ``[2 L, NB, bs, row]`` and sublayer ``j`` of layer
``l`` writes and reads cache layer ``2 l + j``, which is also its place
in the attention, norm and dense FFN stacks
(``serving/decode/model.py``: ``shortcut_param_shapes``,
``LMSpec.cache_layers_of``). A token keeps two rows a layer.

**The expert branch.** ``p = softmax(n0 W_r)`` over the real and the
identity experts together (512 + 256 outputs as published), float32;
the ``top_k`` largest of ``p + e`` are chosen (``e``: a selection-only
bias) and weigh ``g_i = scale x p_i``, not normalised over the chosen
(``moe_held_ops.route_softmax_topk``). A chosen index below the real
experts' count is a gated SiLU FFN, computed where it is held
(``held_gates`` / ``routed_experts``: the (expert, row tile) list and
its kernel see only those); one at or past it is an identity expert:

    s = sum_{chosen i real, held here} g_i E_i(n0)
        + (sum_{chosen i identity} g_i) n0

The identity term is one multiply a row (``identity_weight``) whatever
the row chose: it makes no assignment, no row tile, reads no weight, and
a row all of whose choices are identities runs no expert at all. It is a
function of the row alone, so the chip that owns the row adds it in
whole, as a shared expert would be. No shared expert.

The router's statistics carry, beside ``load_stats``' four, the live
rows by how many real experts each chose (0 .. ``top_k``).
"""

import jax.numpy as jnp

from . import moe_held_ops as moe
from .latent_moe_ops import LatentMoEBlock, _at, rms_norm

SUBLAYERS = 2


class ShortcutMoEBlock(LatentMoEBlock):
    """What ``_extend_rows`` asks of a block for LMSpec
    block='shortcut_moe'; module docstring. ``ln1`` holds the
    sublayers' input norms and ``ln2`` their post-attention norms."""

    routed_slots = ('Router', 'RouterBias')
    dense_everywhere = True

    def __init__(self, ctx):
        super(ShortcutMoEBlock, self).__init__(ctx)
        self.zero_experts = int(ctx.attr('zero_experts', 0))

    def _layer(self, h, arenas, step, kind, layer, of_kind):
        s = stats = None
        for j in range(SUBLAYERS):
            sub = layer * SUBLAYERS + j
            attn, arenas, _ = self._attention(
                rms_norm(h, _at(self.ln1, sub), self.eps), arenas, step,
                kind, sub)
            h = h + attn
            n = rms_norm(h, _at(self.ln2, sub), self.eps)
            if j == 0:
                s, stats = self._routed(n, layer, step.valid)
            h = h + self._dense(n, sub)
        return h + s, arenas, stats

    def _routed(self, n, i, valid):
        router, bias = (_at(self.w[slot], i) for slot in self.routed_slots)
        if valid is None:
            valid = jnp.ones((n.shape[0],), bool)
        n_real = router.shape[1] - self.zero_experts
        chosen, weight = moe.route_softmax_topk(
            n, router, self.top_k, bias=bias, scale=self.routed_scale)
        held = self.routed[0].shape[1]
        gate, hit = moe.held_gates(chosen, weight, self.first, held)
        m = moe.routed_experts(n, gate, hit, valid, min(self.top_k, held),
                               *self.routed, layer=i)
        m += moe.identity_weight(chosen, weight, n_real)[:, None] * n
        return m, moe.load_stats(hit, valid, chosen, n_real)
