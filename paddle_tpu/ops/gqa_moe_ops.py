"""The gqa_moe block of the paged decode ops (LMSpec block='gqa_moe':
mellum): grouped per-head attention under a window or over every
position by layer kind, each kind's K and V in arenas and under a block
table of their own, and softmax-routed experts in every layer.

A layer is ``h = x + Attn_kind(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``.

**Attention.** ``n_head`` query heads over the KV heads a cached row
holds, no bias; q and k are rotated over the whole head in half-split
pairs (i, i + d/2) by the frequency table of the layer's kind (the plain
powers of theta for the sliding layers, YaRN's table for the full ones:
``serving/decode/model.py``: ``LMSpec.rope_tables``, whose
``attention_factor`` on cos and sin reaches the scores as its square on
the softmax scale); a sliding layer's query at ``p`` sees keys ``p -
window < j <= p``, a full layer's every ``j <= p``.

**Two page pools.** All layers have the one weight shape, so every
matrix is one stack over all layers; what differs by kind is where a
token's K and V rows live. The full layers' arenas ``[full layers, NB,
bs, Hkv * d]`` keep every page of a sequence; the sliding layers'
``[sliding layers, NB_sliding, bs, Hkv * d]`` are indexed by a table of
their own, whose entries below a row's window the engine has given back
to the pool (``serving/decode/kv_pool.py``: ``KVPool.trim``) and points
past it: the attention's lower bound keeps every column block wholly
below the window out of its loops, and in the block that holds the bound
a given-back entry is gathered (clipped to a real page: some other
sequence's finite rows) and masked to exactly 0, like the columns past a
row's length. ``segments`` runs the published order
(``paged_decode_ops.period_segments``): one ``lax.scan`` over the whole
periods of layer kinds, a period's layers unrolled in the body, each
with its kind's arenas, table and placement.

**MoE.** Router logits over every published expert, softmax, the
``top_k`` largest, weights normalised over those
(``moe_held_ops.route_softmax_topk``); the experts held here computed by
the products that follow the routing (``routed_experts``); no shared
expert.
"""

import jax
import jax.numpy as jnp

from . import moe_held_ops as moe
from .latent_moe_ops import _at, rms_norm
from .paged_decode_ops import (_attention_of, _mm, _mm_t, _write_in_place,
                               period_segments)

FULL, SLIDING = 'full_attention', 'sliding_attention'
_TAG = {FULL: 'Full', SLIDING: 'Sliding'}
_STACKS = ('Ln1W', 'Ln2W', 'SlfQ', 'SlfK', 'SlfV', 'SlfO', 'Router')


def rope_half_at(x, pos, inv):
    """x [N, heads, D] float32 at positions ``pos`` [N]: half-split pairs
    (i, i + D/2), pair i turned by pos * inv[i] (``inv`` [D / 2]
    float32: a kind's frequency table). Column i of the first half
    becomes ``a_i cos - b_i sin`` and of the second ``a_i sin + b_i
    cos``: ``x cos + swapped(x) sin`` with the halves swapped by a roll
    and the first one's sign turned. Joining two halves of 64 columns
    with a concatenate aborts the v5e's compiler where the function is
    a program of its own (``IsFusibleUnalignedDUS``; compiled here for a
    described chip, PR 43): the roll has no such join."""
    d = x.shape[-1]
    angle = pos.astype(jnp.float32)[:, None] * jnp.tile(inv, 2)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    sign = jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0)
    return x * cos + jnp.roll(x, d // 2, axis=-1) * sign * sin


class GqaMoEBlock(object):
    """What ``_extend_rows`` asks of a block (embed, segments, logits)
    for LMSpec block='gqa_moe'; module docstring."""

    def __init__(self, ctx):
        self.emb = ctx.input('Emb')
        self.head = ctx.input('Head')
        self.final_ln = ctx.input('FinalLN')
        self.n_head = int(ctx.attr('n_head', 1))
        self.eps = float(ctx.attr('norm_eps', 1e-6))
        self.top_k = int(ctx.attr('top_k', 1))
        self.first = int(ctx.attr('first_expert', 0))
        self.window = int(ctx.attr('window', 0))
        self.plan = (tuple(ctx.attr('lead')), tuple(ctx.attr('period')),
                     int(ctx.attr('n_periods')), tuple(ctx.attr('tail')))
        kinds = [k for k in (FULL, SLIDING)
                 if k in self.plan[0] + self.plan[1] + self.plan[3]]
        # K and V of each kind side by side; a kind's pool is the one
        # whose table's op input carries its tag, else the first
        # (``pools``: (table suffix, an arena of the pool) in order)
        self.arena_slots = tuple(
            slot + _TAG[k] for k in kinds for slot in ('KCache', 'VCache'))
        named = tuple(ctx.attr('pools'))
        self.pool_of = {k: named.index(_TAG[k]) if _TAG[k] in named else 0
                        for k in kinds}
        self.arena_of = {k: 2 * i for i, k in enumerate(kinds)}
        self.pools = tuple(
            (suffix, next(self.arena_of[k] for k in kinds
                          if self.pool_of[k] == i))
            for i, suffix in enumerate(named))
        self.freq = {k: jnp.asarray(
            ctx.attr(_TAG[k].lower() + '_rope_freq'), jnp.float32)
            for k in kinds}
        self.softmax_mult = {k: float(
            ctx.attr(_TAG[k].lower() + '_softmax_mult')) for k in kinds}
        self.w = {slot: ctx.input(slot) for slot in _STACKS}
        # the routed experts stay stacked: each row tile of their product
        # slices its (layer, expert) out where it lies (moe_held_ops)
        self.routed = tuple(ctx.input(s) for s in
                            ('ExpGate', 'ExpUp', 'ExpDown'))

    # ------------------------------------------------------ the two ends
    def embed(self, tokens, pos):
        return jnp.take(self.emb, tokens, axis=0).astype(jnp.float32)

    def logits(self, h):
        return _mm_t(rms_norm(h, self.final_ln, self.eps), self.head)

    # ---------------------------------------------------- the layer loop
    def segments(self, step):
        return period_segments(
            self.plan, lambda h, arenas, kind, layer, of_kind:
            self._layer(h, arenas, step, kind, layer, of_kind))

    def _layer(self, h, arenas, step, kind, layer, of_kind):
        w = {slot: _at(stack, layer) for slot, stack in self.w.items()}
        rows, pos = h.shape[0], step.pos
        tables, place = step.pools[self.pool_of[kind]]
        n1 = rms_norm(h, w['Ln1W'], self.eps)
        d = w['SlfQ'].shape[0] // self.n_head
        # the query projection is kept transposed (gqa_param_shapes)
        q = rope_half_at(_mm_t(n1, w['SlfQ']).reshape(rows, -1, d), pos,
                         self.freq[kind])
        k = rope_half_at(_mm(n1, w['SlfK']).reshape(rows, -1, d), pos,
                         self.freq[kind])
        a = self.arena_of[kind]
        held = _write_in_place(
            arenas[a:a + 2],
            [k.reshape(rows, -1).astype(arenas[a].dtype),
             _mm(n1, w['SlfV']).astype(arenas[a + 1].dtype)],
            of_kind, place)
        arenas = arenas[:a] + tuple(held) + arenas[a + 2:]
        # a query at position pos sees keys pos - window < j <= pos
        lo = jnp.maximum(pos + 1 - self.window, 0) if kind == SLIDING \
            else None
        with jax.named_scope('attn_' + _TAG[kind].lower()):
            attn = _attention_of(tables)(
                q, held[0], held[1], tables, step.lens,
                sm_scale=d ** -0.5 * self.softmax_mult[kind],
                layer=of_kind, lo=lo)
        h = h + _mm(attn.reshape(rows, -1), w['SlfO'])
        n2 = rms_norm(h, w['Ln2W'], self.eps)
        valid = step.valid if step.valid is not None \
            else jnp.ones((rows,), bool)
        chosen, weight = moe.route_softmax_topk(n2, w['Router'], self.top_k)
        n_held = self.routed[0].shape[1]
        gate, hit = moe.held_gates(chosen, weight, self.first, n_held)
        m = moe.routed_experts(n2, gate, hit, valid,
                               min(self.top_k, n_held), *self.routed,
                               layer=layer)
        return h + m, arenas, moe.load_stats(hit, valid)
