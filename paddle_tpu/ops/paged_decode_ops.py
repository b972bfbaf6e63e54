"""Paged incremental-decode ops for the decode-serving engine.

Three IR ops over a decoder-only transformer whose KV
cache lives in a paged pool (ops/pallas/paged_attention.py layouts):

- ``paged_prefill`` — extend a sequence whose first ``Cached`` tokens
  already have KV materialized (prefix-cache hit; ``Cached == 0`` is
  the cold case) by a padded suffix [1, S]: write each suffix
  position's K/V into the sequence's pages through its block table,
  attend each suffix query against the table at its own absolute
  length (the one-table form: S queries over the blocks of the table
  the chunk can see, per-query lengths cached+1 .. cached+Len; the
  padded rows see nothing), and emit the next token. S is
  bucketed by the engine so the signature set is small and warmable.
- ``paged_decode_step`` — one token for EVERY slot of a fixed-size
  decode batch [B]: append each sequence's K/V at its own position
  (one row written in place through the block table; rows whose table
  entry is >= NB drop their write, which is how empty slots ride along
  for free),
  ragged paged attention at per-sequence true lengths, then greedy or
  temperature sampling per row. ONE feed signature regardless of which
  sequences occupy which slots — the continuous-batching scheduler
  swaps sequences in and out without ever producing a new XLA
  signature (zero steady-state cache misses).
- ``paged_spec_verify`` — speculative-decoding verification: score
  ``k+1`` tokens (the pending token + k draft proposals) for every
  slot of the [B] batch in ONE ragged paged-attention pass over
  ``B*(k+1)`` mixed-length rows (row (b, j) attends at length
  lens[b]+j+1 — exactly the ragged shape the paged attention was
  built for). ``k`` is a static attr, so the verify step is one more fixed
  signature beside the decode step's. Writes K/V for all k+1
  positions; the engine's longest-accepted-prefix rule decides how
  many become real (rejected positions sit above the advanced
  ``cache_len`` and are overwritten before they can be read).

What differs between model families is a *block* object
(``_PostLNBlock``: the 2017 decoder block, per-row math as in
transformer_ops.py's ``_incremental_layer_scan``; ``_ParallelMoEBlock``:
cohere2_moe, LMSpec block='parallel_moe'; ``LatentMoEBlock`` in
ops/latent_moe_ops.py: dots3_note, block='latent_moe', whose layer
kinds have unlike shapes and three arenas, so it brings its own layer
functions and order, ``segments``; ``GqaMoEBlock`` in
ops/gqa_moe_ops.py: mellum, block='gqa_moe', whose layer kinds keep
their K and V in page pools of their own, each under its own block
table: an op then has a table input a pool of its block, ``pools``;
``SsmHybridBlock`` in ops/ssm_hybrid_ops.py: block='ssm_hybrid', whose
Mamba-2 layers keep a state a sequence in arenas indexed by a slot and
not by pages, read and written where it lies by ops/ssm_ops.py;
``ShortcutMoEBlock`` in ops/shortcut_moe_ops.py: block='shortcut_moe',
a layer of two latent attentions with cache rows of their own and an
expert branch beside them; ``DeltaHybridBlock`` in
ops/delta_hybrid_ops.py: block='delta_hybrid', linear-attention layers
under the gated delta rule, whose state is a matrix a head in the same
pool of slots, beside gated attention in pages):
embedding, the q/k/v
projections, what follows attention, the per-layer lower bound on the
columns a row sees, and the logits. Everything else — placement, the
in-place arena writes, the one ``lax.scan`` over [L, ...]-stacked
weights with the arenas as carry, the attention in column blocks
bounded by what each row holds (ops/pallas/paged_attention.py:
many tables with one query each for the decode step and spec verify,
one table with many queries for every prefill) — is shared
through ``_extend_rows``. A layer's kind (window, rotary) is scanned
data beside the weights, never a second program.

How the arenas travel: the stacked K/V arenas [L, NB, bs, Hkv*D] (and
the [L, NB, bs, H] scale arenas of the quantized dtypes) are the
scan's CARRY, beside ``h``; only the weights and the layer index are
scanned. A layer writes its new rows with ``dynamic_update_slice`` at
(layer, page, slot, 0) and attends through gathers at (layer, a
block of the tables), so no program slices a layer out, stacks one
back or hands an arena to a scatter: the op's KCacheOut/VCacheOut are
the final carry, which the executor's donation aliases to the inputs,
the only instructions that touch arena-sized data are the attention's
gathers, and what they produce is a block's pages
(``serving/decode/hlo_check.py`` counts the others in a compiled
program; chip_smoke.py fails above zero). The shape is what makes that
possible on a TPU: a token's row is H*D contiguous lane-dense elements,
so the compiler keeps the arena row-major and a row is a whole number
of tiles (with D minor-most it would lay the page axis minor and
re-lay the arena at every program's entry and exit).

Every per-row computation is independent of the
other rows — all three ops attend through the same inner form, over
column blocks that sit at absolute multiples of their width, and a
block a row sees nothing of leaves its state bit for bit as it was —
so a sequence's token stream is bit-identical whether it decodes alone,
packed into a full batch, resumed from a cached prefix, or advanced
k-at-a-time under speculation: the invariant
tests/test_decode_serving.py's e2es assert.

Sampling: token at position i draws from
``categorical(fold_in(PRNGKey(seed), i), logits / temp)`` (greedy at
temp == 0), so a request's stream depends only on (seed, positions),
never on batch composition, speculation depth, or a global step
counter.

Quantized arenas (docs/quantization.md): when the K/V arenas are int8
or fp8, ``_extend_rows`` quantizes each written row independently
(one fp32 scale per (token, head) row into the KScale/VScale arenas,
carried and written like the pages; deterministic rounding) and the
attention gather dequantizes through
the same table indices — so every invariant above, including
bit-consistency across batching/speculation/caching, holds unchanged
at the quantized dtypes.
"""

import collections

import jax
import jax.numpy as jnp

from ..core.registry import register
from .transformer_ops import ENC_SLOTS, _slot_to_input

LM_SLOTS = ENC_SLOTS   # decoder-only block reuses the encoder slot layout


def _split_heads(x, n_head):
    """[..., H*D] -> [..., H, D]."""
    return x.reshape(x.shape[:-1] + (n_head, x.shape[-1] // n_head))


def _ln(h, p, slot):
    from .pallas.layer_norm import fused_layer_norm
    return fused_layer_norm(h, p[slot + '_w'], p[slot + '_b'], eps=1e-5,
                            begin_norm_axis=-1)


def _ffn(h, p):
    return jax.nn.relu(h @ p['ffn_w1'] + p['ffn_b1']) @ p['ffn_w2'] + \
        p['ffn_b2']


def _stacked_weights(ctx, slots):
    return {s: ctx.env[ctx.op.input(_slot_to_input(s))] for s in slots}


# What one op's rows are, for a layer: their positions, the block
# table(s), where their cache rows land, the length each attends at (0:
# not live) and which rows count. ``tables`` and ``place`` are the first
# page pool's; ``pools`` has every pool's (tables, place) in the
# block's order, for a block whose layer kinds lie in pools of their
# own.
_Step = collections.namedtuple(
    '_Step', ['pos', 'tables', 'place', 'lens', 'valid', 'pools'])


def _attention_of(tables):
    from .pallas.paged_attention import (paged_attention_blocked,
                                         paged_attention_one_table)
    return paged_attention_one_table if tables.ndim == 1 \
        else paged_attention_blocked


def period_segments(plan, layer_of):
    """``_extend_rows``' segments for a block whose layers run in the
    published order of their kinds: ``plan`` is ``LMSpec.layer_plan()``
    (lead, period, n_periods, tail) and ``layer_of(h, arenas, kind,
    layer, of_kind)`` runs layer ``layer`` (of all; ``of_kind`` among
    its kind's; ints or traced scalars) and returns (h, arenas, router
    statistics or None). The leading layers one by one, then one
    ``lax.scan`` over the whole periods of layer kinds (a period's
    layers unrolled inside the body, each at ``layers before + period x
    layers a period + its place``), then the remainder."""
    lead, period, n_periods, tail = plan
    kinds = set(lead + period + tail)

    def run(run_kinds, first_layer, before):
        """The layers of ``run_kinds`` in order from ``first_layer``,
        ``before[kind]`` layers of a kind ahead of them; as a scan's
        body, ``j`` whole runs of ``run_kinds`` further on."""
        def fn(carry, j):
            h, arenas = carry
            seen, stats = dict(before), []
            runs = 0 if j is None else j
            for m, kind in enumerate(run_kinds):
                layer = first_layer + runs * len(run_kinds) + m
                of_kind = seen[kind] + runs * run_kinds.count(kind)
                h, arenas, got = layer_of(h, arenas, kind, layer, of_kind)
                seen[kind] += 1
                if got is not None:
                    stats.append(got)
            return (h, arenas), jnp.stack(stats) if stats else None
        return fn

    before = {kind: 0 for kind in kinds}
    out = []
    if lead:
        out.append((run(lead, 0, before), None))
        before = {k: v + lead.count(k) for k, v in before.items()}
    if n_periods:
        out.append((run(period, len(lead), before),
                    jnp.arange(n_periods, dtype=jnp.int32)))
    if tail:
        # the remainder sits where period ``n_periods`` would
        start = len(lead) + n_periods * len(period)
        ahead = {k: v + n_periods * period.count(k)
                 for k, v in before.items()}
        out.append((run(tail, start, ahead), None))
    return out


class _UniformBlock(object):
    """A block whose layers are all of one shape, with K and V arenas of
    ``[L, ...]``: one ``lax.scan`` over the [L, ...]-stacked weights,
    the layer's kind (window, rotary) scanned data beside them. A
    subclass gives ``pre``, ``kv``, ``q``, ``lower_bound``, ``finish``."""

    # (op input suffix of a page pool's block table, an arena of it
    # among ``arena_slots``): the one pool every arena lies in
    pools = (('', 0),)

    def segments(self, step):
        n_layer = next(iter(self.params.values())).shape[0]
        return [(self._layer(step),
                 (self.params, jnp.arange(n_layer, dtype=jnp.int32)))]

    def _layer(self, step):
        from ..quant.core import quantize_rows
        attend = _attention_of(step.tables)
        n = step.pos.shape[0]

        def body(carry, sl):
            h, arenas = carry
            p, layer = sl
            kv_q = _arena_kv_dtype(arenas[0])
            nrm = self.pre(h, p)
            k_new, v_new = self.kv(nrm, p, step.pos)
            if kv_q is not None:
                kq, ks_row = quantize_rows(
                    _split_heads(k_new, self.n_head), kv_q)
                vq, vs_row = quantize_rows(
                    _split_heads(v_new, self.n_head), kv_q)
                rows = (kq.reshape(n, -1), vq.reshape(n, -1), ks_row, vs_row)
            else:
                rows = (k_new.astype(arenas[0].dtype),
                        v_new.astype(arenas[1].dtype))
            arenas = _write_in_place(arenas, rows, layer, step.place)
            attn = attend(
                self.q(nrm, p, step.pos), arenas[0], arenas[1], step.tables,
                step.lens,
                k_scales=arenas[2] if kv_q is not None else None,
                v_scales=arenas[3] if kv_q is not None else None,
                layer=layer, lo=self.lower_bound(p, step.pos))
            h, stats = self.finish(h, nrm, attn, p, step.valid)
            return (h, arenas), None if stats is None else stats[None]
        return body


class _PostLNBlock(_UniformBlock):
    """The 2017 decoder block: embedding scaled by sqrt(d_model) plus a
    position table, serial residual with LayerNorm after each sublayer,
    ReLU FFN, an output table of its own. One KV head per query head."""

    slots = LM_SLOTS
    arena_slots = ('KCache', 'VCache', 'KScale', 'VScale')

    def __init__(self, ctx):
        self.emb = ctx.input('Emb')
        self.pos_enc = ctx.input('PosEnc')
        self.wout = ctx.input('OutProj')
        self.n_head = ctx.attr('n_head', 1)
        self.params = _stacked_weights(ctx, self.slots)

    def embed(self, tokens, pos):
        return jnp.take(self.emb, tokens, axis=0) * \
            (self.emb.shape[-1] ** 0.5) + \
            jnp.take(self.pos_enc, pos, axis=0, mode='clip')

    def pre(self, h, p):
        return h

    def kv(self, n, p, pos):
        # [N, H*dk]: a row as the arena holds it
        return n @ p['slf_k'], n @ p['slf_v']

    def q(self, n, p, pos):
        return _split_heads(n @ p['slf_q'], self.n_head)

    def lower_bound(self, p, pos):
        return None

    def finish(self, h, n, attn, p, valid):
        h = _ln(h + attn.reshape(h.shape[0], -1).astype(h.dtype)
                @ p['slf_o'], p, 'ln1')
        return _ln(h + _ffn(h, p), p, 'ln2'), None

    def logits(self, h):
        return (h @ self.wout).astype(jnp.float32)


# the parallel block's stacked weights, by op input slot
MOE_SLOTS = ('ln_w', 'slf_q', 'slf_k', 'slf_v', 'slf_o', 'router',
             'exp_gate', 'exp_up', 'exp_down',
             'shr_gate', 'shr_up', 'shr_down')


def _mm(x, w):
    """x at the weight's dtype times w, accumulated in float32."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _mm_t(x, w):
    """``_mm`` with a weight held transposed, ``[out, in]``: contracted
    over its last axis, which is where the v5e reads it (serving/decode/
    model.py: ``HeldTransposed``, ``gqa_param_shapes``)."""
    return jax.lax.dot_general(
        x.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _rope_gptj(x, pos, theta):
    """x [N, heads, D] float32 at positions ``pos`` [N]: interleaved
    pairs (2i, 2i+1) turned by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    return _rope_gptj_at(
        x, pos, theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def _rope_gptj_at(x, pos, inv):
    """``_rope_gptj`` with pair i turned by pos * inv[i] (``inv``
    [D / 2] float32: a frequency table, as rope scaling gives)."""
    d = x.shape[-1]
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


class _ParallelMoEBlock(_UniformBlock):
    """The cohere2_moe block (LMSpec block='parallel_moe'): one
    bias-free LayerNorm feeding attention and the expert FFN side by
    side, y = x + attn + experts; grouped KV heads; per layer a window
    and a rotary flag (sliding layers rotate q and k and see the last
    ``window`` keys, full layers carry no position and see all), both
    scanned beside the weights, so one program serves either kind;
    sigmoid top-k routing over every published expert with the ones
    held here computed (ops/moe_held_ops.py) and the shared experts'
    mean added; a tied embedding, unscaled, behind a final LayerNorm.
    The residual stream, the norms' statistics, the router, the softmax
    and the logits are float32; products take their operands at the
    weights' dtype."""

    slots = MOE_SLOTS
    arena_slots = ('KCache', 'VCache')

    def __init__(self, ctx):
        self.emb = ctx.input('Emb')
        self.final_ln = ctx.input('FinalLN')
        self.n_head = ctx.attr('n_head', 1)
        self.eps = float(ctx.attr('norm_eps', 1e-5))
        self.theta = float(ctx.attr('rope_theta', 10000.0))
        self.top_k = int(ctx.attr('top_k', 1))
        self.first = int(ctx.attr('first_expert', 0))
        self.logit_scale = float(ctx.attr('logit_scale', 1.0))
        self.params = _stacked_weights(ctx, self.slots)
        # the routed experts stay stacked: each row tile of their product
        # slices its (layer, expert) out where it lies (moe_held_ops)
        self.routed = tuple(self.params.pop(s) for s in
                            ('exp_gate', 'exp_up', 'exp_down'))
        self.params['layer'] = jnp.arange(self.routed[0].shape[0],
                                          dtype=jnp.int32)
        # the layer kind, scanned beside the weights
        self.params['window'] = jnp.asarray(
            [int(w) for w in ctx.attr('windows')], jnp.int32)
        self.params['rotary'] = jnp.asarray(
            [bool(r) for r in ctx.attr('rotary')], bool)

    def _norm(self, x, gain):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + self.eps) * \
            gain.astype(jnp.float32)

    def embed(self, tokens, pos):
        return jnp.take(self.emb, tokens, axis=0).astype(jnp.float32)

    def pre(self, h, p):
        return self._norm(h, p['ln_w'])

    def _turned(self, x, p, pos):
        return jnp.where(p['rotary'], _rope_gptj(x, pos, self.theta), x)

    def kv(self, n, p, pos):
        k = _mm(n, p['slf_k'])
        d = p['slf_q'].shape[-1] // self.n_head
        k = self._turned(k.reshape(k.shape[0], -1, d), p, pos)
        return k.reshape(k.shape[0], -1), _mm(n, p['slf_v'])

    def q(self, n, p, pos):
        return self._turned(_split_heads(_mm(n, p['slf_q']), self.n_head),
                            p, pos)

    def lower_bound(self, p, pos):
        # a query at position pos sees keys pos - window < j <= pos
        return jnp.where(p['window'] > 0,
                         jnp.maximum(pos + 1 - p['window'], 0), 0)

    def finish(self, h, n, attn, p, valid):
        from . import moe_held_ops as moe
        a = _mm(attn.reshape(h.shape[0], -1), p['slf_o'])
        chosen, weight = moe.route_sigmoid_topk(n, p['router'], self.top_k)
        held = self.routed[0].shape[1]
        gate, hit = moe.held_gates(chosen, weight, self.first, held)
        m = moe.routed_experts(n, gate, hit, valid, min(self.top_k, held),
                               *self.routed, layer=p['layer'])
        n_shared = p['shr_gate'].shape[0]
        m += moe.gated_experts(
            n, jnp.full((n.shape[0], n_shared), 1.0 / n_shared),
            p['shr_gate'], p['shr_up'], p['shr_down'])
        return h + a + m, moe.load_stats(hit, valid)

    def logits(self, h):
        return _mm_t(self._norm(h, self.final_ln), self.emb) \
            * self.logit_scale


def _block_of(ctx):
    kind = ctx.attr('block', 'post_ln')
    if kind == 'parallel_moe':
        return _ParallelMoEBlock(ctx)
    if kind == 'latent_moe':
        from .latent_moe_ops import LatentMoEBlock
        return LatentMoEBlock(ctx)
    if kind == 'shortcut_moe':
        from .shortcut_moe_ops import ShortcutMoEBlock
        return ShortcutMoEBlock(ctx)
    if kind == 'gqa_moe':
        from .gqa_moe_ops import GqaMoEBlock
        return GqaMoEBlock(ctx)
    if kind == 'ssm_hybrid':
        from .ssm_hybrid_ops import SsmHybridBlock
        return SsmHybridBlock(ctx)
    if kind == 'delta_hybrid':
        from .delta_hybrid_ops import DeltaHybridBlock
        return DeltaHybridBlock(ctx)
    return _PostLNBlock(ctx)


# Where an op's N new rows land in an arena [L, NB, bs, W], as n runs
# of r consecutive slots of one page: run i covers (phys[i],
# off[i]..off[i]+r-1) and writes slot j only where ok[i, j].
# ``blocks(rows)`` lays the op's rows [N, W] out as those runs,
# [n, r, W]. Which of the two builders below an op uses rests on what
# it knows statically about its rows.
_Placement = collections.namedtuple('_Placement',
                                    ['phys', 'off', 'ok', 'blocks'])


def _single_rows(tables, pos, nb, bs):
    """Single tokens of many tables (decode step, spec verify): row i
    is one slot, position ``pos[i]`` of ``tables[i]``. A row past the
    table's capacity, or whose table entry is not a page (>= NB: empty
    batch slots), writes nothing."""
    p_cap = tables.shape[1]
    logical = jnp.clip(pos // bs, 0, p_cap - 1)
    phys = jnp.take_along_axis(tables, logical[:, None], axis=1)[:, 0]
    ok = (pos < p_cap * bs) & (phys >= 0) & (phys < nb)
    return _Placement(jnp.clip(phys, 0, nb - 1), pos % bs, ok[:, None],
                      lambda rows: rows[:, None, :])


def _page_runs(table, cached, length, n_rows, nb, bs):
    """Consecutive positions of one table (prefill): row t sits at
    position ``cached + t``, so the ``n_rows`` rows fill whole pages but
    the first and the last, and are written page by page — at most
    n_rows / bs + 1 updates instead of n_rows. Rows t >= ``length``
    (the padded tail), pages past the table's capacity and table
    entries that are not a page write nothing."""
    p_cap = table.shape[0]
    n_pages = -(-n_rows // bs) + 1
    shift = cached % bs                 # row 0's slot in the first page
    logical = cached // bs + jnp.arange(n_pages, dtype=jnp.int32)
    phys = jnp.take(table, jnp.clip(logical, 0, p_cap - 1))
    page_ok = (logical < p_cap) & (phys >= 0) & (phys < nb)
    t = jnp.arange(n_pages * bs, dtype=jnp.int32).reshape(n_pages, bs) \
        - shift                         # the row each slot would hold
    ok = page_ok[:, None] & (t >= 0) & (t < length)

    def blocks(rows):
        # shift + n_rows <= n_pages * bs: the rows always fit
        flat = jnp.zeros((n_pages * bs, rows.shape[1]), rows.dtype)
        flat = jax.lax.dynamic_update_slice(flat, rows, (shift, 0))
        return flat.reshape(n_pages, bs, -1)
    return _Placement(jnp.clip(phys, 0, nb - 1),
                      jnp.zeros((n_pages,), jnp.int32), ok, blocks)


def _write_in_place(arenas, rows, layer, place):
    """Write each ``rows[a]`` [N, W] into ``arenas[a]`` [L, NB, bs, W]
    at ``layer`` (one for all, or a tuple with each arena's own: an
    index arena holds fewer layers than the latent arena beside it
    where some layers attend over a carried selection) where ``place``
    says, run by run with
    ``dynamic_update_slice`` (which clamps and never drops, so a slot
    that must not be written gets the value that is there). Runs go in
    order, each reading the arena the one before it left, up to the
    last run that writes anything: the rows past a decode batch and
    the pages past a prompt's length cost no update. Nothing here is
    of arena size: a scatter would have the TPU re-lay its whole
    operand."""
    blocks = [place.blocks(r) for r in rows]
    layers = layer if isinstance(layer, tuple) else (layer,) * len(blocks)

    def one(i, arenas):
        out = []
        for arena, new, layer in zip(arenas, blocks, layers):
            at = (layer, place.phys[i], place.off[i], 0)
            run = (1, 1) + new.shape[1:]
            there = jax.lax.dynamic_slice(arena, at, run)
            mine = jax.lax.dynamic_index_in_dim(new, i, keepdims=False)
            keep = place.ok[i].reshape(1, 1, -1, 1)
            out.append(jax.lax.dynamic_update_slice(
                arena, jnp.where(keep, mine.reshape(run), there), at))
        return tuple(out)
    n_runs = place.phys.shape[0]
    writes = jnp.any(place.ok, axis=1)
    upper = jnp.max(jnp.where(writes, jnp.arange(1, n_runs + 1), 0))
    return jax.lax.fori_loop(0, upper, one, tuple(arenas))


def _arena_kv_dtype(kc):
    """Canonical quantized-arena dtype from the arena's jnp dtype, or
    None for the unquantized (fp32 / bf16) arenas."""
    name = str(kc.dtype)
    return name if name in ('int8', 'float8_e4m3fn') else None


def _sample_token(logits, seed, pos, temp):
    """logits [V] fp32 -> int32 token. temp == 0 is greedy; otherwise
    categorical at temperature with a (seed, position)-derived key."""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
    t = jnp.maximum(temp, 1e-6)
    sampled = jax.random.categorical(key, logits / t).astype(jnp.int32)
    return jnp.where(temp > 0.0, sampled, greedy)


def _lm_inputs(ctx):
    """(the op's block, its page arenas in the block's order)."""
    block = _block_of(ctx)
    return block, tuple(ctx.input(slot) for slot in block.arena_slots
                        if ctx.has_input(slot))


def _pool_tables(ctx, block, slot):
    """The op's block tables, one a page pool of the block: int32."""
    return [ctx.input(slot + suffix).astype(jnp.int32)
            for suffix, _ in block.pools]


def _pool_shapes(block, arenas):
    """(pages, slots a page) of each pool's arenas."""
    return [arenas[a].shape[1:3] for _, a in block.pools]


def _set_arena_outputs(ctx, block, arenas):
    for slot, arena in zip(block.arena_slots, arenas):
        ctx.set_output(slot + 'Out', arena)


@register('paged_decode_step')
def _paged_decode_step(ctx):
    block, arenas = _lm_inputs(ctx)

    tokens = ctx.input('Tokens').reshape(-1).astype(jnp.int32)     # [B]
    lens = ctx.input('SeqLens').reshape(-1).astype(jnp.int32)      # [B]
    tables = _pool_tables(ctx, block, 'BlockTables')          # [B, P] each
    temps = ctx.input('Temps').reshape(-1).astype(jnp.float32)
    seeds = ctx.input('Seeds').reshape(-1).astype(jnp.int32)

    # one new token per row at position lens (empty slots feed all->NB
    # tables, so phys lands out of bounds and every write drops)
    place = [_single_rows(t, lens, *shape)
             for t, shape in zip(tables, _pool_shapes(block, arenas))]
    h, arenas, stats = _extend_rows(
        block, arenas, tokens, lens, tables[0], place[0],
        valid=place[0].ok[:, 0], more=zip(tables[1:], place[1:]))
    nxt = jax.vmap(_sample_token)(block.logits(h), seeds, lens + 1, temps)
    ctx.set_output('NextTokens',
                   nxt.astype(ctx.out_dtype('NextTokens', 'int64')))
    if stats is not None:
        ctx.set_output('MoeStats', stats)        # [routed layers, 4] int32
    _set_arena_outputs(ctx, block, arenas)


def _extend_rows(block, arenas, tokens, pos, tables, place, valid=None,
                 more=()):
    """Shared core of all three ops: write N new tokens' cache rows at
    absolute positions ``pos`` where ``place`` (a _Placement over the
    same rows) says, attend each row at its own ragged length
    (``pos + 1``; 0 where ``valid`` says a row is not live, so that it
    costs no block) through per-row block ``tables`` [N, P] (or, for
    consecutive rows of one sequence, its one table [P]; ``more``: the
    (tables, place) of the block's page pools past the first), and return
    the last hidden rows [N, D], the updated ``arenas`` (a tuple in the
    block's order) and the block's per-layer statistics (None where it
    keeps none; ``valid`` [N] says which rows count). The arenas are
    carried through the layer loop and written in place (module
    docstring); ``block`` is what differs between model families:
    embedding, the projections, what follows attention, the per-layer
    bound on the columns a row sees, and the order its layers run in:
    ``block.segments(step)`` is a list of ``(layer function, xs)``, run
    one after another with ``(h, arenas)`` as carry, a segment with
    ``xs`` as one ``lax.scan`` over it (a block of one layer shape: the
    whole stack; one of several: its whole periods of layer kinds) and
    one without called once (a leading layer, a remainder). One
    compiled program per signature whatever the list.

    Quantized arenas (scale arenas [L, NB, bs, H] behind K and V):
    each new K/V row is quantized independently (one fp32 scale per
    (token, head) row, deterministic rounding — quant.core
    quantize_rows) before the write, and the attention gather
    dequantizes through the same table indices. Because rows quantize
    independently, every path (prefill, decode, spec-verify, cache
    hits) stores identical bits for identical tokens — the
    concurrent == sequential invariant survives at int8/fp8."""
    x = block.embed(tokens, pos)
    # a row that is not live attends at length 0: it costs no block
    lens = pos + 1 if valid is None else jnp.where(valid, pos + 1, 0)
    carry, stats = (x, tuple(arenas)), []
    step = _Step(pos, tables, place, lens, valid,
                 ((tables, place),) + tuple(more))
    for layer, xs in block.segments(step):
        if xs is None:
            carry, got = layer(carry, None)
        else:
            carry, got = jax.lax.scan(layer, carry, xs)
            if got is not None:             # [n, layers a call, 4]
                got = got.reshape((-1,) + got.shape[2:])
        if got is not None:
            stats.append(got)
    h, arenas = carry
    return h, arenas, jnp.concatenate(stats) if stats else None


@register('paged_prefill')
def _paged_prefill(ctx):
    block, arenas = _lm_inputs(ctx)

    ids = ctx.input('Ids').reshape(-1).astype(jnp.int32)   # [S] (padded)
    length = ctx.input('Len').reshape(()).astype(jnp.int32)
    cached = ctx.input('Cached').reshape(()).astype(jnp.int32)
    table = [t.reshape(-1) for t in
             _pool_tables(ctx, block, 'BlockTable')]               # [P] each
    temp = ctx.input('Temp').reshape(()).astype(jnp.float32)
    seed = ctx.input('Seed').reshape(()).astype(jnp.int32)
    s = ids.shape[0]

    # suffix position t lives at absolute position cached + t; its
    # query attends to everything at or below it — the cached pages
    # plus this step's own earlier writes — through the table gather
    pos = cached + jnp.arange(s, dtype=jnp.int32)
    place = [_page_runs(t, cached, length, s, *shape)
             for t, shape in zip(table, _pool_shapes(block, arenas))]
    last = jnp.maximum(length - 1, 0)
    # the sequence's pages gathered block by block for the whole chunk
    # (rows past ``length`` see nothing), and the one row that is
    # sampled projected onto the vocabulary
    h, arenas, stats = _extend_rows(
        block, arenas, ids, pos, table[0], place[0],
        valid=jnp.arange(s) < length, more=zip(table[1:], place[1:]))
    logits_last = block.logits(jax.lax.dynamic_slice_in_dim(
        h, last, 1))[0]                                         # [V]
    nxt = _sample_token(logits_last, seed, cached + length, temp)
    ctx.set_output('NextToken',
                   nxt.reshape(1).astype(ctx.out_dtype('NextToken',
                                                       'int64')))
    if stats is not None:
        ctx.set_output('MoeStats', stats)        # [routed layers, 4] int32
    _set_arena_outputs(ctx, block, arenas)


@register('paged_spec_verify')
def _paged_spec_verify(ctx):
    block, arenas = _lm_inputs(ctx)

    tokens = ctx.input('Tokens').astype(jnp.int32)         # [B, K1]
    lens = ctx.input('SeqLens').reshape(-1).astype(jnp.int32)   # [B]
    tables = _pool_tables(ctx, block, 'BlockTables')       # [B, P] each
    temps = ctx.input('Temps').reshape(-1).astype(jnp.float32)
    seeds = ctx.input('Seeds').reshape(-1).astype(jnp.int32)
    b, k1 = tokens.shape

    # flatten to B*K1 single-token rows: row (b, j) holds the j-th
    # speculative token at absolute position lens[b] + j and attends
    # at its own length — one ragged paged-attention batch scores the
    # whole tree of proposals (empty slots ride along exactly as in
    # the decode step: all-NB tables drop every write)
    j = jnp.arange(k1, dtype=jnp.int32)
    pos = (lens[:, None] + j[None, :]).reshape(-1)         # [B*K1]
    tables_rep = [jnp.repeat(t, k1, axis=0) for t in tables]   # [B*K1, P]
    place = [_single_rows(t, pos, *shape) for t, shape in
             zip(tables_rep, _pool_shapes(block, arenas))]
    h, arenas, _ = _extend_rows(
        block, arenas, tokens.reshape(-1), pos, tables_rep[0], place[0],
        valid=place[0].ok[:, 0], more=zip(tables_rep[1:], place[1:]))

    nxt = jax.vmap(_sample_token)(
        block.logits(h), jnp.repeat(seeds, k1), pos + 1,
        jnp.repeat(temps, k1))
    ctx.set_output('NextTokens',
                   nxt.reshape(b, k1).astype(
                       ctx.out_dtype('NextTokens', 'int64')))
    _set_arena_outputs(ctx, block, arenas)
