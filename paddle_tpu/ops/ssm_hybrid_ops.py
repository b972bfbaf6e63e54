"""The ssm_hybrid block of the paged decode ops (LMSpec
block='ssm_hybrid'): Mamba-2 layers whose state is one slot a sequence
beside position-free attention layers in the paged cache, in two forms
of layer.

**A mixer and then a dense gated MLP** (granitemoehybrid without
experts): ``h = x + r Mixer_kind(RMSNorm(x))``, ``y = h + r
MLP(RMSNorm(h))`` with ``r`` the residual multiplier; the embedding is
scaled, tied, and the logits scaled.

**One sublayer a layer** (``mixer_only``: nemotron_h): ``y = x + r
Mixer_kind(RMSNorm(x))`` and nothing behind it, the kinds being the two
mixers and a third, an expert layer, which owns no cache; the head is
a matrix of its own where the embedding is not tied.

**Attention layers.** ``n_head`` query heads over the KV heads a cached
row holds, no bias, no rotation (the layers carry no position), the
softmax scale a multiplier of the configuration's own; K and V rows
written in place through the block table and attended through the paged
attention every block uses (``_write_in_place``, ``_attention_of``).

**Mamba-2 layers.** ``[z; u; dt] = n W_in``; a depthwise causal
convolution over ``u`` and silu give ``x``, ``B`` and ``C``; the
recurrence over the sequence's state (``ops/ssm_ops.py``: its chunked
form for a prefill chunk, one step a row for a decode batch, both
reading and writing the slot where it lies); the skip term; the gate
``silu(z)``, then an RMSNorm over the whole inner width, or with
``ssm_groups`` G (``B`` and ``C`` a group of ``H / G`` heads each) over
each group's ``H P / G`` channels with the gain's own slice; ``W_out``. What
a sequence keeps is in two arenas indexed by its slot
(``serving/decode/model.py``: cache kinds with a size a sequence): the
state and the convolution's last K - 1 inputs. The ops take the slot
from the state pool's table input (``BlockTable(s)State``: one entry a
row; rows that hold none point at the spare slot past the pool) and, in
a prefill, whether the chunk is the sequence's first (``Cached`` == 0:
the state starts from zeros whatever the slot's last owner left). A
padded row of a chunk has ``dt = 0`` and lies behind the rows the
convolution keeps, so it changes neither.

**Expert layers** (LatentMoE). The router reads the hidden width:
sigmoid scores over every published expert, the ``top_k`` largest of
score + bias, the chosen scores normalised and times ``routed_scale``
(``moe_held_ops.route_sigmoid_topk``). Every row is projected into the
latent (``moe_latent_in``), the experts held here add their part inside
it (``moe_routed_relu2``: ``relu(u W1_e)^2 W2_e``, two matrices an
expert, through ``moe_held_ops.routed_experts`` with no gate matrix),
and that partial sum is projected out (``moe_latent_out``); one shared
expert ``relu(n V1)^2 V2`` on the hidden width at weight 1
(``moe_shared_relu2``). The exchange that would sum the chips' partial
sums travels at the latent's width and is not here.

``segments`` runs the published order
(``paged_decode_ops.period_segments``): one ``lax.scan`` over the whole
periods of layer kinds, a period's layers unrolled in the body.
Products take their operands at the weights' dtype (``_mm``); the
residual stream, the norms, softplus, the decays, the state and the
gated norm are float32.
"""

import jax
import jax.numpy as jnp

from . import moe_held_ops as moe
from . import ssm_ops
from .latent_moe_ops import _at, rms_norm
from .paged_decode_ops import (_attention_of, _mm, _mm_t, _write_in_place,
                               period_segments)

MAMBA, ATTENTION, MOE = 'mamba', 'attention', 'moe'
_MLP = ('Ln2W', 'MlpGate', 'MlpUp', 'MlpDown')
_STACKS = {
    None: ('Ln1W',),
    ATTENTION: ('SlfQ', 'SlfK', 'SlfV', 'SlfO'),
    MAMBA: ('SsmIn', 'SsmConvW', 'SsmConvB', 'SsmDtB', 'SsmALog', 'SsmD',
            'SsmNorm', 'SsmOut'),
    # the routed experts' two stacks stay whole (``self.routed``)
    MOE: ('Router', 'RouterBias', 'LatIn', 'LatOut', 'ShrUp', 'ShrDown'),
}


class SsmHybridBlock(object):
    """What ``_extend_rows`` asks of a block (embed, segments, logits)
    for LMSpec block='ssm_hybrid'; module docstring."""

    pools = (('', 0),)        # K and V under the one block table

    def __init__(self, ctx):
        self.emb = ctx.input('Emb')
        # the head: the embedding where it is tied
        self.head = ctx.input('Head') if ctx.has_input('Head') else self.emb
        self.final_ln = ctx.input('FinalLN')
        self.n_head = int(ctx.attr('n_head', 1))
        self.eps = float(ctx.attr('norm_eps', 1e-5))
        self.heads = int(ctx.attr('ssm_heads', 1))
        self.groups = int(ctx.attr('ssm_groups', 1))
        self.mixer_only = bool(ctx.attr('mixer_only', 0))
        self.n_state = int(ctx.attr('ssm_state', 1))
        self.chunk = int(ctx.attr('ssm_chunk', 256))
        self.embed_scale = float(ctx.attr('embed_scale', 1.0))
        self.residual = float(ctx.attr('residual_scale', 1.0))
        self.attn_scale = float(ctx.attr('attn_scale', 1.0))
        self.logit_scale = float(ctx.attr('logit_scale', 1.0))
        self.plan = (tuple(ctx.attr('lead')), tuple(ctx.attr('period')),
                     int(ctx.attr('n_periods')), tuple(ctx.attr('tail')))
        kinds = set(self.plan[0] + self.plan[1] + self.plan[3])
        self.arena_slots = ('KCache', 'VCache') * (ATTENTION in kinds) + \
            ('SsmState', 'SsmConv') * (MAMBA in kinds)
        self.arena_of = {ATTENTION: 0,
                         MAMBA: 2 * (ATTENTION in kinds)}
        self.w = {kind: {slot: ctx.input(slot) for slot in slots
                         + _MLP * (kind is None and not self.mixer_only)}
                  for kind, slots in _STACKS.items()
                  if kind is None or kind in kinds}
        if MOE in kinds:
            self.top_k = int(ctx.attr('top_k', 1))
            self.first = int(ctx.attr('first_expert', 0))
            self.routed_scale = float(ctx.attr('routed_scale', 1.0))
            # stacked: each row tile of their product slices its (layer,
            # expert) out where it lies (moe_held_ops)
            self.routed = (ctx.input('ExpUp'), ctx.input('ExpDown'))
        if MAMBA in kinds:
            # a slot index a row: the decode step's, or the one of a
            # prefill, whose first chunk starts from zeros
            step = ctx.has_input('BlockTablesState')
            self.slots = ctx.input(
                'BlockTablesState' if step else 'BlockTableState'
            ).reshape(-1).astype(jnp.int32)
            self.fresh = None if step else \
                ctx.input('Cached').reshape(()) == 0

    # ------------------------------------------------------ the two ends
    def embed(self, tokens, pos):
        return jnp.take(self.emb, tokens, axis=0).astype(jnp.float32) \
            * self.embed_scale

    def logits(self, h):
        return _mm_t(rms_norm(h, self.final_ln, self.eps), self.head) \
            * self.logit_scale

    # ---------------------------------------------------- the layer loop
    def segments(self, step):
        return period_segments(
            self.plan, lambda h, arenas, kind, layer, of_kind:
            self._layer(h, arenas, step, kind, layer, of_kind))

    def _layer(self, h, arenas, step, kind, layer, of_kind):
        shared = {slot: _at(stack, layer)
                  for slot, stack in self.w[None].items()}
        w = {slot: _at(stack, of_kind)
             for slot, stack in self.w[kind].items()}
        n1 = rms_norm(h, shared['Ln1W'], self.eps)
        if kind == MOE:
            mixed, stats = self._experts(n1, step, w, of_kind)
            return h + self.residual * mixed, arenas, stats
        mixer = self._mamba if kind == MAMBA else self._attention
        mixed, arenas = mixer(n1, arenas, step, w, of_kind)
        h = h + self.residual * mixed
        if self.mixer_only:
            return h, arenas, None
        n2 = rms_norm(h, shared['Ln2W'], self.eps)
        m = _mm(jax.nn.silu(_mm(n2, shared['MlpGate']))
                * _mm(n2, shared['MlpUp']), shared['MlpDown'])
        return h + self.residual * m, arenas, None

    def _experts(self, n, step, w, of_kind):
        """The expert layer ``of_kind`` over ``n`` [rows, D]: (the layer's
        output, the router's statistics)."""
        valid = step.valid if step.valid is not None \
            else jnp.ones((n.shape[0],), bool)
        chosen, weight = moe.route_sigmoid_topk(
            n, w['Router'], self.top_k, bias=w['RouterBias'],
            scale=self.routed_scale)
        n_held = self.routed[0].shape[1]
        gate, hit = moe.held_gates(chosen, weight, self.first, n_held)
        with jax.named_scope('moe_latent_in'):
            u = _mm(n, w['LatIn'])
        with jax.named_scope('moe_routed_relu2'):
            r = moe.routed_experts(u, gate, hit, valid,
                                   min(self.top_k, n_held), None,
                                   *self.routed, layer=of_kind)
        with jax.named_scope('moe_latent_out'):
            out = _mm(r, w['LatOut'])
        with jax.named_scope('moe_shared_relu2'):
            out = out + _mm(jnp.square(jax.nn.relu(_mm(n, w['ShrUp']))),
                            w['ShrDown'])
        return out, moe.load_stats(hit, valid)

    def _attention(self, n, arenas, step, w, of_kind):
        rows = n.shape[0]
        a = self.arena_of[ATTENTION]
        held = _write_in_place(
            arenas[a:a + 2],
            [_mm(n, w['SlfK']).astype(arenas[a].dtype),
             _mm(n, w['SlfV']).astype(arenas[a + 1].dtype)],
            of_kind, step.place)
        arenas = arenas[:a] + tuple(held) + arenas[a + 2:]
        q = _mm(n, w['SlfQ']).reshape(rows, self.n_head, -1)
        with jax.named_scope('attn_nope'):
            attn = _attention_of(step.tables)(
                q, held[0], held[1], step.tables, step.lens,
                sm_scale=self.attn_scale, layer=of_kind)
        return _mm(attn.reshape(rows, -1), w['SlfO']), arenas

    def _mamba(self, n, arenas, step, w, of_kind):
        rows = n.shape[0]
        a = self.arena_of[MAMBA]
        state, conv = arenas[a], arenas[a + 1]
        inner = w['SsmOut'].shape[0]
        proj = _mm(n, w['SsmIn'])
        z, u, dt = proj[:, :inner], proj[:, inner:-self.heads], \
            proj[:, -self.heads:]
        valid = step.valid if step.valid is not None \
            else jnp.ones((rows,), bool)
        # a row that is not live takes no step: it decays nothing, adds
        # nothing
        dt = jnp.where(valid[:, None], jax.nn.softplus(
            dt + w['SsmDtB'].astype(jnp.float32)[None, :]), 0.0)
        neg = -jnp.exp(w['SsmALog'].astype(jnp.float32))
        taps = w['SsmConvW'].shape[0]
        if self.fresh is None:
            # a decode batch: a row's window is its slot's rows and its
            # own input behind them
            held = jnp.take(_at(conv, of_kind), self.slots, axis=0)
            window = jnp.concatenate(
                [held.reshape(rows, taps - 1, -1),
                 u.astype(conv.dtype)[:, None, :]], axis=1)
        else:
            window = ssm_ops.conv_window(conv, of_kind, self.slots[0], u,
                                         self.fresh)
        mixed = ssm_ops.causal_conv(window, w['SsmConvW'], w['SsmConvB'],
                                    rows)
        x = mixed[:, :inner].reshape(rows, self.heads, -1)
        wide = self.groups * self.n_state
        b, c = mixed[:, inner:inner + wide], mixed[:, inner + wide:]
        if self.groups > 1:
            # a group's B and C: [rows, G, N]
            b, c = (v.reshape(rows, self.groups, -1) for v in (b, c))
        if self.fresh is None:
            y, state, conv = ssm_ops.ssm_decode_update(
                state, conv, of_kind, self.slots, valid, x, b, c, dt, neg,
                window)
        else:
            y, state = ssm_ops.ssm_chunk_scan(
                state, of_kind, self.slots[0], x, b, c, dt, neg, self.fresh,
                self.chunk, w['SsmIn'].dtype)
            # the last K - 1 valid inputs: window rows length .. length +
            # K - 2, which reach into the carried rows under K - 1 rows
            length = jnp.sum(valid.astype(jnp.int32))
            conv = ssm_ops.keep_conv_rows(
                conv, of_kind, self.slots[0],
                jax.lax.dynamic_slice_in_dim(window, length, taps - 1))
        arenas = arenas[:a] + (state, conv) + arenas[a + 2:]
        y = y + w['SsmD'].astype(jnp.float32)[None, :, None] * x
        gated = y.reshape(rows, -1) * jax.nn.silu(z)
        if self.groups > 1:
            # the norm's statistics a group of channels
            normed = rms_norm(
                gated.reshape(rows, self.groups, -1),
                w['SsmNorm'].reshape(self.groups, -1), self.eps
            ).reshape(rows, -1)
        else:
            normed = rms_norm(gated, w['SsmNorm'], self.eps)
        return _mm(normed, w['SsmOut']), arenas
