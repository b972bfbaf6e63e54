"""The delta_hybrid block of the paged decode ops (LMSpec
block='delta_hybrid': qwen3_next): linear-attention layers under the
gated delta rule, whose cache is a matrix a head in one slot a sequence,
beside gated full-attention layers in the paged cache, and routed
experts with a gated shared expert in every layer.

A layer is ``h = x + Mixer_kind(RMSNorm0(x))``, ``y = h +
MoE(RMSNorm0(h))``; ``RMSNorm0`` is the zero-centred norm ``x / rms(x)
(1 + w)``; an untied head behind a final ``RMSNorm0``.

**Gated DeltaNet layers** (``linear_attention``). ``[q; k; v; z] = n
W_in`` (G key heads of K for ``q`` and ``k``, H value heads of V for
``v`` and ``z``; value head ``h`` reads key head ``h // (H / G)``), ``[b;
a] = n W_ba``; a depthwise causal convolution over ``[q; k; v]`` with no
bias, then silu; ``q <- l2norm(q) / sqrt(K)``, ``k <- l2norm(k)``,
``beta = sigmoid(b)``, the decay's logarithm ``g = -exp(A_log)
softplus(a + dt_bias)``; the gated delta rule over the sequence's state
(``ops/gated_delta_ops.py``: its chunked form for a prefill chunk, one
step a row for a decode batch, both reading and writing the slot where
it lies); an RMSNorm with a plain gain over each head's V, **then** the
gate ``silu(z)``; ``W_out``. What a sequence keeps is in two arenas
indexed by its slot, as a Mamba-2 layer's (``ops/ssm_hybrid_ops.py``):
the state ``[H, K, V]`` float32 and the convolution's last taps - 1
inputs. A row that is not live has ``g = 0`` and ``beta = 0``: it moves
nothing.

**Gated attention layers** (``full_attention``). ``n_head`` query heads
over the KV heads a cached row holds, no bias; a query and a gate a head
(two matrices, both held transposed as ``gqa_moe``'s query projection);
``q`` and ``k`` through ``RMSNorm0`` over each head with gains of their
own; the first ``rotary_dim`` columns of a head turned in half-split
pairs (i, i + rotary_dim / 2), the others not (``rope_part_at``); K and
V rows written in place through the block table and attended through the
paged attention every block uses; the result times ``sigmoid(gate)``;
``W_o``.

**Experts.** ``moe_held_ops``' softmax router over every published
expert, the ``top_k`` largest, normalised over those; the experts held
here through ``routed_experts``; plus one shared gated SiLU expert that
every row takes whole, times ``sigmoid(n w_sg)`` (a gate of its own,
float32).

``segments`` runs the published order (``period_segments``). Products
with a weight take their operands at the weights' dtype (``_mm``); the
residual stream, the norms, the router, both gates' sigmoids, the
decays, the state and the gated norm are float32.
"""

import jax
import jax.numpy as jnp

from . import gated_delta_ops as delta
from . import moe_held_ops as moe
from . import ssm_ops
from .latent_moe_ops import _at, rms_norm
from .paged_decode_ops import (_attention_of, _mm, _mm_t, _write_in_place,
                               period_segments)

LINEAR, FULL = 'linear_attention', 'full_attention'
_STACKS = {
    # the routed experts' three stacks stay whole (``self.routed``)
    None: ('Ln1W', 'Ln2W', 'Router', 'ShrGate', 'ShrUp', 'ShrDown',
           'ShrSg'),
    FULL: ('SlfQ', 'SlfGate', 'SlfK', 'SlfV', 'SlfO', 'SlfQLn', 'SlfKLn'),
    LINEAR: ('GdnIn', 'GdnBA', 'GdnConvW', 'GdnDtB', 'GdnALog', 'GdnNorm',
             'GdnOut'),
}
L2_EPS = 1e-6


def rms_norm0(x, gain, eps):
    """The zero-centred norm: ``x / rms(x) (1 + gain)``."""
    return rms_norm(x, 1.0 + gain.astype(jnp.float32), eps)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def rope_part_at(x, pos, inv):
    """x [N, heads, D] float32 at positions ``pos`` [N]: the first ``2
    len(inv)`` columns of a head turned in half-split pairs (i, i +
    len(inv)), pair i by ``pos * inv[i]``, the columns behind them left
    as they are. Written over the whole head with two rolls and no join
    (``gqa_moe_ops.rope_half_at`` says why): a column's partner is the
    one ``len(inv)`` ahead of it in the first half of the turned part
    and behind it in the second; past the turned part cos is 1 and sin
    0."""
    d, half = x.shape[-1], inv.shape[0]
    col = jnp.arange(d)
    turned = col < 2 * half
    angle = pos.astype(jnp.float32)[:, None] * jnp.where(
        turned, jnp.tile(inv, d // half + 1)[:d], 0.0)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    partner = jnp.where(col < half, jnp.roll(x, -half, axis=-1),
                        jnp.roll(x, half, axis=-1))
    return x * cos + partner * jnp.where(col < half, -1.0, 1.0) * sin


class DeltaHybridBlock(object):
    """What ``_extend_rows`` asks of a block (embed, segments, logits)
    for LMSpec block='delta_hybrid'; module docstring."""

    pools = (('', 0),)        # K and V under the one block table

    def __init__(self, ctx):
        self.emb = ctx.input('Emb')
        self.head = ctx.input('Head')
        self.final_ln = ctx.input('FinalLN')
        self.eps = float(ctx.attr('norm_eps', 1e-6))
        self.heads = int(ctx.attr('ssm_heads', 1))
        self.key_heads = int(ctx.attr('ssm_groups', 1))
        self.n_key = int(ctx.attr('ssm_state', 1))
        self.chunk = int(ctx.attr('ssm_chunk', 64))
        self.top_k = int(ctx.attr('top_k', 1))
        self.first = int(ctx.attr('first_expert', 0))
        self.freq = jnp.asarray(ctx.attr('rope_freq'), jnp.float32)
        self.plan = (tuple(ctx.attr('lead')), tuple(ctx.attr('period')),
                     int(ctx.attr('n_periods')), tuple(ctx.attr('tail')))
        kinds = set(self.plan[0] + self.plan[1] + self.plan[3])
        self.arena_slots = ('KCache', 'VCache') * (FULL in kinds) + \
            ('SsmState', 'SsmConv') * (LINEAR in kinds)
        self.arena_of = {FULL: 0, LINEAR: 2 * (FULL in kinds)}
        self.w = {kind: {slot: ctx.input(slot) for slot in slots}
                  for kind, slots in _STACKS.items()
                  if kind is None or kind in kinds}
        # stacked: each row tile of their product slices its (layer,
        # expert) out where it lies (moe_held_ops)
        self.routed = tuple(ctx.input(s) for s in
                            ('ExpGate', 'ExpUp', 'ExpDown'))
        if LINEAR in kinds:
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
        return jnp.take(self.emb, tokens, axis=0).astype(jnp.float32)

    def logits(self, h):
        return _mm_t(rms_norm0(h, self.final_ln, self.eps), self.head)

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
        n1 = rms_norm0(h, shared['Ln1W'], self.eps)
        mixer = self._delta if kind == LINEAR else self._attention
        mixed, arenas = mixer(n1, arenas, step, w, of_kind)
        h = h + mixed
        out, stats = self._experts(
            rms_norm0(h, shared['Ln2W'], self.eps), step, shared, layer)
        return h + out, arenas, stats

    def _experts(self, n, step, w, layer):
        """The experts of layer ``layer`` over ``n`` [rows, D]: (their
        output, the router's statistics)."""
        valid = step.valid if step.valid is not None \
            else jnp.ones((n.shape[0],), bool)
        chosen, weight = moe.route_softmax_topk(n, w['Router'], self.top_k)
        n_held = self.routed[0].shape[1]
        gate, hit = moe.held_gates(chosen, weight, self.first, n_held)
        with jax.named_scope('moe_routed'):
            out = moe.routed_experts(n, gate, hit, valid,
                                     min(self.top_k, n_held), *self.routed,
                                     layer=layer)
        with jax.named_scope('moe_shared_gated'):
            shared = _mm(jax.nn.silu(_mm(n, w['ShrGate']))
                         * _mm(n, w['ShrUp']), w['ShrDown'])
            # the shared expert's own gate: float32, as the router
            opened = jax.nn.sigmoid(jnp.sum(
                n * w['ShrSg'].astype(jnp.float32)[None, :], axis=-1,
                keepdims=True))
        return out + opened * shared, moe.load_stats(hit, valid)

    def _attention(self, n, arenas, step, w, of_kind):
        rows = n.shape[0]
        a = self.arena_of[FULL]
        d = w['SlfQLn'].shape[0]
        # the query's and the gate's projections are kept transposed
        q = rope_part_at(rms_norm0(
            _mm_t(n, w['SlfQ']).reshape(rows, -1, d), w['SlfQLn'],
            self.eps), step.pos, self.freq)
        k = rope_part_at(rms_norm0(
            _mm(n, w['SlfK']).reshape(rows, -1, d), w['SlfKLn'],
            self.eps), step.pos, self.freq)
        held = _write_in_place(
            arenas[a:a + 2],
            [k.reshape(rows, -1).astype(arenas[a].dtype),
             _mm(n, w['SlfV']).astype(arenas[a + 1].dtype)],
            of_kind, step.place)
        arenas = arenas[:a] + tuple(held) + arenas[a + 2:]
        with jax.named_scope('attn_gated'):
            attn = _attention_of(step.tables)(
                q, held[0], held[1], step.tables, step.lens,
                sm_scale=d ** -0.5, layer=of_kind)
            attn = attn.reshape(rows, -1) \
                * jax.nn.sigmoid(_mm_t(n, w['SlfGate']))
        return _mm(attn, w['SlfO']), arenas

    def _delta(self, n, arenas, step, w, of_kind):
        rows = n.shape[0]
        a = self.arena_of[LINEAR]
        state, conv = arenas[a], arenas[a + 1]
        keys = self.key_heads * self.n_key
        inner = w['GdnOut'].shape[0]
        proj = _mm(n, w['GdnIn'])
        u, z = proj[:, :2 * keys + inner], proj[:, 2 * keys + inner:]
        ba = _mm(n, w['GdnBA'])
        valid = step.valid if step.valid is not None \
            else jnp.ones((rows,), bool)
        # a row that is not live takes no step: it decays nothing,
        # writes nothing
        beta = jnp.where(valid[:, None],
                         jax.nn.sigmoid(ba[:, :self.heads]), 0.0)
        g = jnp.where(valid[:, None], -jnp.exp(
            w['GdnALog'].astype(jnp.float32))[None, :] * jax.nn.softplus(
                ba[:, self.heads:]
                + w['GdnDtB'].astype(jnp.float32)[None, :]), 0.0)
        taps = w['GdnConvW'].shape[0]
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
        mixed = ssm_ops.causal_conv(
            window, w['GdnConvW'], jnp.zeros((u.shape[1],), jnp.float32),
            rows)
        q = l2_norm(mixed[:, :keys].reshape(rows, self.key_heads, -1)) \
            * self.n_key ** -0.5
        k = l2_norm(mixed[:, keys:2 * keys].reshape(rows, self.key_heads,
                                                    -1))
        v = mixed[:, 2 * keys:].reshape(rows, self.heads, -1)
        if self.fresh is None:
            o, state, conv = delta.delta_decode_update(
                state, conv, of_kind, self.slots, valid, q, k, v, g, beta,
                window)
        else:
            o, state = delta.delta_chunk_scan(
                state, of_kind, self.slots[0], q, k, v, g, beta, self.fresh,
                self.chunk)
            # the last taps - 1 valid inputs: window rows length ..
            # length + taps - 2, which reach into the carried rows under
            # taps - 1 rows
            length = jnp.sum(valid.astype(jnp.int32))
            conv = ssm_ops.keep_conv_rows(
                conv, of_kind, self.slots[0],
                jax.lax.dynamic_slice_in_dim(window, length, taps - 1))
        arenas = arenas[:a] + (state, conv) + arenas[a + 2:]
        # the norm first, then the gate
        normed = rms_norm(o, w['GdnNorm'], self.eps).reshape(rows, -1) \
            * jax.nn.silu(z)
        return _mm(normed, w['GdnOut']), arenas
