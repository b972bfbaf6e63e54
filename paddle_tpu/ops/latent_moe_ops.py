"""The latent_moe block of the paged decode ops (LMSpec
block='latent_moe': dots3_note, kimi_k2_6): latent attention over every
cached position, under a learned sparse selection or under a window, by
layer kind and configuration, over up to three kinds of cache.

A layer is ``h = x + Attn_kind(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.

**Latent attention** (both kinds, each at its own sizes). A token's
cache row is ``[c_kv ; k_rope]``: ``c_kv = s_kv RMSNorm(x W_kva[:, :r])``
and ``k_rope`` the last ``d_rope`` columns of ``x W_kva`` rotated
(interleaved pairs); one row a token a layer, which every head reads.
Queries go through a rank-``q_rank`` bottleneck, ``c_q = s_q RMSNorm(x
W_qa)``, ``[q_nope ; q_rope]_h = c_q W_qb`` (``W_qb`` and the indexer's
``W^I_q`` are held ``[out, q_rank]`` and contracted over their last
axis: ``latent_param_shapes``). Their stacks are shaped to heads, ``[n,
heads, d, q_rank]``, before a layer is taken (``_heads_at``), because
the v5e's compiler moves a split of the product's output onto the weight
and copies a layer's slice with a reshape behind it out of its stack,
where it reads a slice of the stack so shaped in place.

The attention takes one of two forms, by how many query rows share a
block table (``serving/decode/model.py``: ``latent_expands``, a function
of the kind's rank and head widths and the program's static row count;
the engine counts ``decode.prefill_chunks_expanded`` by the same
function):

- *absorbed*, for one query a table (the decode step, spec verify) and
  for a short chunk: the key up-projection is folded into the query,
  ``q'_h = [q_nope_h W_UK,h ; RoPE(q_rope_h)]`` (as wide as the row), so
  a score is one product of ``q'_h`` with the cached row, the weighted
  sum is over the rows' first ``r`` columns, and the value up-projection
  is applied to that sum, ``out_h = (sum_s p c_kv(s)) W_UV,h``: K and V
  are never expanded, and a decode step reads ``row width x itemsize``
  bytes a position and nothing else of the cache. A (query, key, head)
  costs ``2 (2 r + d_rope)`` operations and the running sum is
  ``[H, rows, r]``, as large as a score block;
- *expanded*, for a chunk of many rows (the model's own order of
  operations in a prefill): each column block of latent rows is
  gathered once, as above, and expanded there to
  ``K_h = [c_kv W_UK,h ; k_rope]`` and ``V_h = c_kv W_UV,h``, which the
  block's queries ``[q_nope_h ; RoPE(q_rope_h)]`` then attend head by
  head. Expanding a key costs ``2 r (d_nope + d_v)`` a head whatever the
  rows; a (query, key, head) then costs ``2 (d_nope + d_rope + d_v)``
  and the running sum is ``[H, rows, d_v]``. It pays where
  ``rows > r (d_nope + d_v) / (2 r - d_nope - d_v)``: 171 rows at rank
  512 over 128 + 128 (kimi_k2_6, dots3_note's full layers), 190 at rank
  1,024 over 192 + 128 (dots3_note's sliding layers), so the buckets
  256 and 512 expand and 64 and 128 stay absorbed (measured on the
  chip at kimi_k2_6's widths, PERF.md section 6, PR 37: 512 rows at
  depth 20k 14.2 -> 6.3 ms a layer, 256 rows 5.2 -> 4.0, 128 rows 2.6
  against 3.0 expanded). Nothing of the table's extent is expanded: a
  block's keys and values (512 x H x 320 values) live until its
  products are done.

Both forms run the same column blocks under the same bounds, mask and
running softmax (``ops/pallas/paged_attention.py``: the latent forms of
``_attend_blocks``). Where the configuration has it (``attn_gate``) a
sigmoid gate a head, from the layer's normed input, multiplies
``out_h`` before ``W_o``.

**Dense latent attention** (full layers with ``index_topk`` 0:
kimi_k2_6) is that loop with nothing left out: a row sees every
position at or below its own, through the one block table, column block
by column block under the running softmax, in the decode step (many
tables, one query each) and in a prefill chunk that starts at any
offset (one table, the chunk's rows as one group: after a cached span
of shared pages, after earlier chunks). There is no indexer, no index
key and no index arena. **Rope scaling** (YaRN, per kind): the angle a
position advances pair ``i`` by comes from the kind's frequency table
(``LatentShape.rope_frequencies``: the fast pairs as they are, the slow
ones stretched) and the softmax scale carries ``m^2``
(``softmax_multiplier``); a kind without scaling has the plain powers
of theta in its table and a multiplier of 1.

**The selection** (full layers; the DeepSeek-V3.2 indexer). A token
also caches an index key ``k^I = LayerNorm(x W^I_k)`` (its first
``d_rope`` columns rotated, half-split pairs), written in place like a
latent row. A query scores every cached position,
``I(t, s) = sum_j w_j ReLU(q^I_j . k^I(s))`` over ``index_n_heads``
heads (``q^I = c_q W^I_q``, ``w = x W^I_w / sqrt(heads x width)``), and
sees the ``index_topk`` positions ``s <= t`` of largest ``I``: exactly
those ``lax.top_k`` returns (ties to the lower position), found without
a sort (``select_topk``: the k-th largest score by bisection over the
scores' bit patterns, 32 counting passes, and the last tie it has room
for). Both halves take their bounds from what the rows hold
(``step.lens``), not from what a table addresses: a decode step's
scores run the (row, column block) pairs of its live rows, as its
attention does (``index_scores``), and the counting runs over a row
tile's live column blocks with the keys in VMEM
(``pallas/selection_kth.py``), not at all where no row of the tile
holds more than ``index_topk`` positions: such a row sees every
position it holds. The choice reaches the
attention as a mask over its column blocks: a position left out
contributes exactly 0, and the blocks are still the pages the row
holds, so the program reads every cached row of a full layer and
multiplies it (what a gather of the chosen rows would save is PERF.md's
to measure). Scores, their weights and the selection are float32; the
products take bfloat16 operands where the weights are bfloat16.

**A carried selection** (glm_5_2's IndexShare: ``LMSpec.indexer_types``).
Only some full layers score; a layer of the plan's kind ``CARRIED`` has
no indexer and no index key and attends over the selection that the
nearest scoring layer below it made: ``_attention`` returns the
selection it made or was given, and the layer loop carries it beside the
arenas (``segments``: a value handed from the leading layers into the
first period and a ``lax.scan`` carry from one period to the next, bool
``[rows, positions a table addresses]``; it never leaves the device).
The full layers' attention stacks and latent arena hold scoring and
carried layers alike, in order; the indexer's stacks and the index arena
hold the scoring layers alone, so a layer's place in each is read off
the plan (``_places``). The indexer rotates in half-split pairs or, where
the spec says so (``index_rope_interleave``), in interleaved pairs as
the attention does.

**The layer loop.** The kinds have different weight shapes, so each
kind's weights are a stack of their own (serving/decode/model.py:
``latent_param_shapes``), and so are the arenas: the full layers' latent
rows and index keys, the sliding layers' latent rows, each ``[layers of
the kind, NB, bs, width]`` under the one block table. ``segments`` gives
``_extend_rows`` the published order (``period_segments``): the leading
dense layers one by one, then one ``lax.scan`` over the whole periods of
layer kinds (a period's layers unrolled inside the body, each indexing
its kind's stacks at ``layers before + period x layers a period + its
place``), then the remainder. The arenas are the carry throughout, written in
place.

**FFN.** The leading ``dense_layers`` a gated SiLU FFN; the others
sigmoid top-k routing with a selection-only bias over every published
expert, the experts held here computed (ops/moe_held_ops.py) and the
shared experts added at weight 1.
"""

import functools

import jax
import jax.numpy as jnp

from ..serving.decode.model import CARRIED, latent_expands
from . import moe_held_ops as moe
from .paged_decode_ops import (_attention_of, _mm, _mm_t, _rope_gptj,
                               _rope_gptj_at, _write_in_place,
                               period_segments)
from .pallas import selection_kth
from .pallas.paged_attention import (BLOCK_ROWS, paged_attention_one_table,
                                     pages_held, pages_per_block, pairs_at,
                                     row_pairs)

FULL, SLIDING = 'full_attention', 'sliding_attention'
# a layer of the plan's kind CARRIED attends as a full layer does, in the
# full layers' stacks and arena, over a selection made below it
_TAG = {FULL: 'Full', SLIDING: 'Swa', CARRIED: 'Full'}
# op input slots: the attention of a kind (prefixed Full / Swa), the
# full layers' indexer, the two FFNs
_ATTN = ('QA', 'QLn', 'QB', 'KvA', 'KvLn', 'KvBK', 'KvBV', 'O')
_INDEX = ('IdxQ', 'IdxK', 'IdxKLnW', 'IdxKLnB', 'IdxW')
_DENSE = ('DenseGate', 'DenseUp', 'DenseDown')
_ROUTED = ('Router', 'RouterBias', 'ShrGate', 'ShrUp', 'ShrDown')
_NEG = -jnp.inf


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain.astype(jnp.float32)


def rope_half(x, pos, theta):
    """x [N, heads, D] float32 at positions ``pos`` [N]: half-split pairs
    (i, i + D/2) turned by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def kth_and_cut_dense(scores, lens, k):
    """What ``pallas/selection_kth.py::kth_and_cut`` returns, by passes
    over every column whatever the rows hold: the form of every platform
    but the TPU, and what the kernel is held to."""
    n, c = scores.shape
    col = jnp.arange(c, dtype=jnp.int32)[None, :]
    lowest = selection_kth.LOWEST
    key = jnp.where(col < lens[:, None], selection_kth.ordered_keys(scores),
                    lowest)

    def grow(i, kth):
        cand = kth ^ (jnp.int32(1) << (31 - i))
        enough = jnp.sum(key >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, kth)
    kth = jax.lax.fori_loop(0, 32, grow, jnp.full((n,), lowest, jnp.int32))
    room = k - jnp.sum(key > kth[:, None], axis=1, dtype=jnp.int32)
    ties = jnp.cumsum(key == kth[:, None], axis=1, dtype=jnp.int32)
    cut = jnp.argmax(ties >= room[:, None], axis=1).astype(jnp.int32)
    counts = lens > k
    return jnp.where(counts, kth, lowest), \
        jnp.where(counts & (ties[:, -1] > room), cut, c)


def select_topk(scores, k, lens=None):
    """``scores`` [N, C] float32, ``lens`` [N] (the columns a row holds,
    from the left; None: all) -> bool [N, C]: of a row that holds more
    than ``k`` columns the ``k`` largest among them, ties to the lower
    column, which is the set ``lax.top_k(scores[:lens], k)`` indexes;
    of any other row every column it holds. Nothing at or past a row's
    length is chosen. No sort: the k-th largest key and the last tie the
    choice has room for are found by counting (``kth_and_cut``: on a TPU
    one kernel over the row tiles' live columns, in VMEM;
    ``kth_and_cut_dense`` elsewhere), and the choice is one pass over
    the scores against the two."""
    n, c = scores.shape
    col = jnp.arange(c, dtype=jnp.int32)[None, :]
    if lens is None:
        lens = jnp.full((n,), c, jnp.int32)
    held = col < lens[:, None]
    if c <= k:
        return held
    kth, cut = jax.lax.platform_dependent(
        scores, lens, tpu=functools.partial(selection_kth.kth_and_cut, k=k),
        default=functools.partial(kth_and_cut_dense, k=k))
    key = selection_kth.ordered_keys(scores)
    return held & ((key > kth[:, None])
                   | ((key == kth[:, None]) & (col <= cut[:, None])))


def index_scores(q, w, arena, layer, tables, lens, per):
    """The indexer's scores of every cached position: ``q`` [N, Hi, Di]
    and ``w`` [N, Hi] float32, the index keys' ``arena`` [layers, NB, bs,
    Di], ``tables`` [N, P] (one query each) or [P] (N queries of one
    sequence), ``lens`` [N] -> float32 [N, P * bs], -inf at and past a
    row's length. Column blocks of ``per`` pages. One table: from the
    first block to the one that holds the largest length, each block's
    keys gathered once for all N rows. Many tables: the (row, column
    block) pairs the live rows hold (``row_pairs``, the list a step's
    attention runs), ``BLOCK_ROWS`` of them an iteration, each pair's
    keys gathered through its own row's table and scored against that
    row's query alone: a row that is not live costs nothing and a short
    row its own blocks. The rest stays -inf."""
    nb, bs = arena.shape[1], arena.shape[2]
    n = q.shape[0]
    pages = tables.shape[-1]
    bk = per * bs
    tables = jnp.clip(tables, 0, nb - 1)
    qi = q.astype(arena.dtype)
    exact = jax.lax.Precision.HIGHEST if qi.dtype == jnp.float32 else None

    def scored(qs, ws, keys):
        """[R, Hi, Di] queries each against its own [R, bk, Di] keys (or
        all against the one [bk, Di]) -> [R, bk]."""
        dots = jnp.einsum('nhd,kd->nhk' if keys.ndim == 2 else
                          'nhd,nkd->nhk', qs, keys, precision=exact,
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * ws[:, :, None], axis=1)

    if tables.ndim == 1:
        def block(j, out):
            at = jax.lax.dynamic_slice_in_dim(tables, j * per, per)
            got = scored(qi, w, arena[layer, at].reshape(bk, -1))
            return jax.lax.dynamic_update_slice(out, got, (0, j * bk))
        out = jax.lax.fori_loop(
            0, (jnp.max(lens) + bk - 1) // bk, block,
            jnp.full((n, pages * bs), _NEG, jnp.float32))
    else:
        n_blocks = pages // per
        _, last, ends = row_pairs(jnp.zeros_like(lens), lens, bk, n_blocks)
        tables = tables.reshape(n, n_blocks, per)

        def pairs(i, out):
            # this iteration's pairs of the list: a pair's row (past the
            # rows: the fill of a last iteration, which writes nothing),
            # its column block and its pages through its row's table
            row, block = pairs_at(
                i * BLOCK_ROWS + jnp.arange(BLOCK_ROWS), last, ends)
            at = jnp.minimum(row, n - 1)
            block = jnp.where(row < n, block, 0)
            got = scored(qi[at], w[at], arena[layer, tables[at, block]]
                         .reshape(BLOCK_ROWS, bk, -1))
            return out.at[row, block].set(got, mode='drop')
        out = jax.lax.fori_loop(
            0, -(-ends[-1] // BLOCK_ROWS), pairs,
            jnp.full((n, n_blocks, bk), _NEG, jnp.float32)
        ).reshape(n, pages * bs)
    return jnp.where(jnp.arange(pages * bs)[None, :] < lens[:, None],
                     out, _NEG)


def selection_reach(lens, one_table, pages, bs, k, by_kernel, xp=jnp):
    """How far one scoring layer's bounds engage for rows of lengths
    ``lens`` [N] under tables of ``pages`` pages of ``bs``, from the
    functions of the lengths that bound its loops: (the positions whose
    index keys ``index_scores`` gathers: one table's column blocks up to
    the longest length, or the column blocks of many tables' pairs (a
    last iteration's fill, under ``BLOCK_ROWS`` pairs of a clipped page
    whose scores go nowhere, is not counted); the (row, column) keys a
    counting pass of ``select_topk`` reads: ``columns_counted`` where
    the kernel counts (``by_kernel``), every column of every row in the
    dense form; the columns the rows' tables address)."""
    columns = pages * bs
    if one_table:
        bk = pages_per_block(pages, bs) * bs
        gathered = -(-lens.max() // bk) * bk
    else:
        gathered = pages_held(xp.zeros_like(lens), lens, pages, bs, xp) * bs
    addressed = len(lens) * columns
    if columns <= k:
        counted = 0
    elif by_kernel:
        counted = selection_kth.columns_counted(lens, k, columns, xp)
    else:
        counted = addressed
    return int(gathered), int(counted), addressed


def _at(stack, i):
    """Layer ``i`` (an int or a traced scalar) of a kind's stack, sliced
    where it lies."""
    return jax.lax.dynamic_index_in_dim(stack, i, axis=0, keepdims=False)


def _heads_at(c_q, stack, i, heads):
    """``c_q`` [N, q_rank] times layer ``i`` (an int or a traced scalar)
    of a stack held transposed, ``[n, heads x d, q_rank]``: float32 [N,
    heads, d], which is ``_mm_t(c_q, _at(stack, i))`` split into heads.
    The stack is shaped to its heads (a bitcast of the parameter) and
    the layer taken after, never the other way round (module
    docstring)."""
    n, out, q_rank = stack.shape
    w = _at(stack.reshape(n, heads, out // heads, q_rank), i)
    return jax.lax.dot_general(
        c_q.astype(w.dtype), w, (((1,), (2,)), ((), ())),
        preferred_element_type=jnp.float32)


class LatentMoEBlock(object):
    """What ``_extend_rows`` asks of a block (embed, segments, logits)
    for LMSpec block='latent_moe'; module docstring."""

    all_arena_slots = ('LatentFull', 'IndexFull', 'LatentSliding')
    pools = (('', 0),)          # every arena under the one block table
    # what a subclass with another FFN arrangement changes: the routed
    # layers' slots beside the stacked experts, and whether only the
    # leading layers have a dense FFN
    routed_slots = _ROUTED
    dense_everywhere = False

    def __init__(self, ctx):
        self.emb = ctx.input('Emb')
        self.head = ctx.input('Head')
        self.final_ln = ctx.input('FinalLN')
        self.ln1, self.ln2 = ctx.input('Ln1W'), ctx.input('Ln2W')
        self.eps = float(ctx.attr('norm_eps', 1e-5))
        self.top_k = int(ctx.attr('top_k', 1))
        self.first = int(ctx.attr('first_expert', 0))
        self.window = int(ctx.attr('window', 0))
        self.index_heads = int(ctx.attr('index_n_heads', 0))
        self.index_topk = int(ctx.attr('index_topk', 0))
        self.rescale = bool(ctx.attr('lora_rescale', 1))
        self.gated = bool(ctx.attr('attn_gate', 1))
        self.attn_slots = _ATTN + (('Gate',) if self.gated else ())
        self.routed_scale = float(ctx.attr('routed_scale', 1.0))
        self.plan = (tuple(ctx.attr('lead')), tuple(ctx.attr('period')),
                     int(ctx.attr('n_periods')), tuple(ctx.attr('tail')))
        self.index_interleaved = bool(ctx.attr('index_rope_interleave', 0))
        self.block_size = int(ctx.attr('block_size'))
        order = self.plan[0] + self.plan[1] * self.plan[2] + self.plan[3]
        # the attention's kinds; a carried selection is a full layer's
        kinds = set(FULL if k == CARRIED else k for k in order)
        # where some layer attends over a selection made below it, each
        # full layer's place in the attention stacks (scoring and carried
        # layers in order) and in the indexer's (the scoring ones)
        self.carries = CARRIED in order
        self._places = None
        if self.carries:
            attends = [k in (FULL, CARRIED) for k in order]
            scores = [k == FULL for k in order]
            self._places = tuple(
                [sum(which[:i]) for i in range(len(order))]
                for which in (attends, scores))
        self.arena_slots = tuple(
            s for s in self.all_arena_slots
            if (FULL if 'Full' in s else SLIDING) in kinds
            and (s != 'IndexFull' or self.index_topk))
        self.shape, self.theta, self.w = {}, {}, {}
        self.freq, self.softmax_mult = {}, {}
        for kind in kinds:
            tag = _TAG[kind].lower()
            self.shape[kind] = tuple(ctx.attr(tag + '_shape'))
            self.theta[kind] = float(ctx.attr(tag + '_theta'))
            # the pairs' frequencies as a table (rope scaling or the
            # plain powers), and m^2
            self.freq[kind] = ctx.attr(tag + '_rope_freq')
            self.softmax_mult[kind] = float(
                ctx.attr(tag + '_softmax_mult'))
            for slot in self.attn_slots:
                self.w[_TAG[kind] + slot] = ctx.input(_TAG[kind] + slot)
        lead, period, n_periods, tail = self.plan
        slots = (_INDEX if FULL in kinds and self.index_topk else ()) + \
            (_DENSE if lead or self.dense_everywhere else ()) + \
            (self.routed_slots if period or tail else ())
        for slot in slots:
            self.w[slot] = ctx.input(slot)
        # the routed experts stay stacked: each row tile of their product
        # slices its (layer, expert) out where it lies (moe_held_ops)
        self.routed = tuple(ctx.input(s) for s in
                            ('ExpGate', 'ExpUp', 'ExpDown')) \
            if period or tail else None

    # ------------------------------------------------------ the two ends
    def embed(self, tokens, pos):
        return jnp.take(self.emb, tokens, axis=0).astype(jnp.float32)

    def logits(self, h):
        return _mm_t(rms_norm(h, self.final_ln, self.eps), self.head)

    # ---------------------------------------------------- the layer loop
    def segments(self, step):
        out = period_segments(
            self.plan, lambda h, arenas, kind, layer, of_kind:
            self._layer(h, arenas, step, kind, layer, of_kind))
        if not self.carries:
            return out
        # the selection rides the layer loop beside the arenas: nothing
        # is chosen before the first layer, which scores
        columns = step.tables.shape[-1] * self.block_size
        blank = jnp.zeros((step.pos.shape[0], columns), bool)
        return [(lambda c, _: ((c[0], (c[1], blank)), None), None)] + out \
            + [(lambda c, _: ((c[0], c[1][0]), None), None)]

    def _place(self, which, layer):
        """Layer ``layer``'s (an int or a traced scalar) place in the
        full layers' attention stacks (``which`` 0) or in the indexer's
        (1), under a carried selection."""
        table = self._places[which]
        return table[layer] if isinstance(layer, int) \
            else jnp.asarray(table, jnp.int32)[layer]

    def _layer(self, h, arenas, step, kind, layer, of_kind):
        """Layer ``layer`` (of all; ``of_kind`` among its kind's), with
        static or traced indices: (h, arenas, router statistics or
        None). Under a carried selection ``arenas`` is (the arenas, the
        selection in force)."""
        chosen = scored = None
        if self.carries:
            arenas, chosen = arenas
            if kind != SLIDING:
                of_kind = self._place(0, layer)
            if kind == FULL:
                scored = self._place(1, layer)
        n1 = rms_norm(h, _at(self.ln1, layer), self.eps)
        attn, arenas, chosen = self._attention(n1, arenas, step, kind,
                                               of_kind, chosen, scored)
        if self.carries:
            arenas = (arenas, chosen)
        h = h + attn
        n2 = rms_norm(h, _at(self.ln2, layer), self.eps)
        n_lead = len(self.plan[0])
        if isinstance(layer, int) and layer < n_lead:
            return h + self._dense(n2, layer), arenas, None
        m, stats = self._routed(n2, layer - n_lead, step.valid)
        return h + m, arenas, stats

    # --------------------------------------------------------- attention
    def _attention(self, n, arenas, step, kind, i, chosen=None, scored=None):
        """The attention of a layer of ``kind`` at place ``i`` of its
        kind's stacks and arena, over the rows' normed inputs ``n``:
        (its output, the arenas, the selection it made or, where it
        made none, the one it was given). A ``CARRIED`` layer attends
        over ``chosen`` as given; a scoring layer writes its index keys
        at place ``scored`` of the indexer's stacks (``i`` where every
        full layer scores)."""
        carried = kind == CARRIED
        kind = FULL if carried else kind
        # the layer of each arena written: one for all, or the latent
        # arena's and the index arena's
        at = i if scored is None else (i, scored)
        scored = i if scored is None else scored
        heads, d_nope, d_rope = self.shape[kind]
        pos = step.pos
        # QB is sliced where it is multiplied (_heads_at)
        w = {slot: _at(self.w[_TAG[kind] + slot], i)
             for slot in self.attn_slots if slot != 'QB'}

        def turned(x):
            return _rope_gptj_at(
                x, pos, jnp.asarray(self.freq[kind], jnp.float32))
        rows = n.shape[0]
        d_model, q_rank = w['QA'].shape
        rank = w['KvLn'].shape[0]
        s_q = (d_model / q_rank) ** 0.5 if self.rescale else 1.0
        s_kv = (d_model / rank) ** 0.5 if self.rescale else 1.0

        c_q = rms_norm(_mm(n, w['QA']), w['QLn'], self.eps) * s_q
        # the projections out of the query's rank are held transposed
        # (latent_param_shapes)
        q = _heads_at(c_q, self.w[_TAG[kind] + 'QB'], i, heads)
        down = _mm(n, w['KvA'])
        c_kv = rms_norm(down[:, :rank], w['KvLn'], self.eps) * s_kv
        k_rope = turned(down[:, None, rank:])[:, 0]
        mine = [self.arena_slots.index(
            'LatentFull' if kind == FULL else 'LatentSliding')]
        # a row is stored in whole lane tiles (CacheKind.stored): the
        # columns past [c_kv ; k_rope] are written as zeros
        spare = arenas[mine[0]].shape[-1] - rank - d_rope
        new = [jnp.concatenate(
            [c_kv, k_rope, jnp.zeros((rows, spare), jnp.float32)], -1)]
        lo = None
        selects = kind == FULL and self.index_topk > 0 and not carried
        if selects:
            mine.append(self.arena_slots.index('IndexFull'))
            q_i, w_i, k_i = self._index_rows(n, c_q, scored, pos, kind)
            new.append(k_i)
        elif kind == SLIDING:
            # a query at position pos sees keys pos - window < j <= pos
            lo = jnp.maximum(pos + 1 - self.window, 0)
        held = tuple(arenas[a] for a in mine)
        held = _write_in_place(
            held, [r.astype(a.dtype) for r, a in zip(new, held)],
            at, step.place)
        arenas = list(arenas)
        for a, arena in zip(mine, held):
            arenas[a] = arena
        if selects:
            per = pages_per_block(step.tables.shape[-1], held[1].shape[2])
            chosen = select_topk(
                index_scores(q_i, w_i, held[1], scored, step.tables,
                             step.lens, per), self.index_topk, step.lens)
        q_rope = turned(q[..., d_nope:])
        attend = dict(
            sm_scale=(d_nope + d_rope) ** -0.5 * self.softmax_mult[kind],
            layer=i, lo=lo, latent=rank,
            chosen=chosen if selects or carried else None)
        # the form follows the rows that share a table (module docstring)
        if step.tables.ndim == 1 and latent_expands(
                rank, d_nope, w['KvBV'].shape[-1], rows):
            out = paged_attention_one_table(
                jnp.concatenate([q[..., :d_nope], q_rope], -1), held[0],
                None, step.tables, step.lens,
                expand=(w['KvBK'], w['KvBV']), **attend)    # [N, H, d_v]
        else:
            # the absorbed query: as wide as the cached row, zeros over
            # its spare columns
            q_abs = jnp.einsum('nhd,hdr->nhr',
                               q[..., :d_nope].astype(w['KvBK'].dtype),
                               w['KvBK'], preferred_element_type=jnp.float32)
            mixed = _attention_of(step.tables)(
                jnp.concatenate(
                    [q_abs, q_rope,
                     jnp.zeros((rows, heads, spare), jnp.float32)], -1),
                held[0], None, step.tables, step.lens, **attend)  # [N, H, r]
            out = jnp.einsum('nhr,hrv->nhv', mixed.astype(w['KvBV'].dtype),
                             w['KvBV'], preferred_element_type=jnp.float32)
        if self.gated:
            out = out * jax.nn.sigmoid(_mm(n, w['Gate']))[:, :, None]
        return _mm(out.reshape(rows, -1), w['O']), tuple(arenas), chosen

    def _index_rows(self, n, c_q, i, pos, kind):
        """(index queries [N, Hi, Di], their heads' weights [N, Hi], the
        rows' own index keys [N, Di]), float32, of the indexer at place
        ``i`` of its stacks. The first ``d_rope`` columns of queries and
        keys are rotated by the kind's theta, in half-split pairs or
        (``index_rope_interleave``) in interleaved ones."""
        theta, d_rope = self.theta[kind], self.shape[kind][2]
        turn = _rope_gptj if self.index_interleaved else rope_half
        w = {slot: _at(self.w[slot], i) for slot in _INDEX if slot != 'IdxQ'}
        heads = self.index_heads
        q = _heads_at(c_q, self.w['IdxQ'], i, heads)
        q = jnp.concatenate([turn(q[..., :d_rope], pos, theta),
                             q[..., d_rope:]], -1)
        k = _mm(n, w['IdxK'])
        mean = jnp.mean(k, -1, keepdims=True)
        k = (k - mean) * jax.lax.rsqrt(
            jnp.mean(jnp.square(k - mean), -1, keepdims=True) + self.eps) \
            * w['IdxKLnW'].astype(jnp.float32) + \
            w['IdxKLnB'].astype(jnp.float32)
        k = jnp.concatenate(
            [turn(k[:, None, :d_rope], pos, theta)[:, 0],
             k[:, d_rope:]], -1)
        weight = _mm(n, w['IdxW']) * (heads * q.shape[-1]) ** -0.5
        return q, weight, k

    # --------------------------------------------------------------- FFN
    def _dense(self, n, i):
        gate, up, down = (_at(self.w[s], i) for s in _DENSE)
        return _mm(jax.nn.silu(_mm(n, gate)) * _mm(n, up), down)

    def _routed(self, n, i, valid):
        w = {slot: _at(self.w[slot], i) for slot in _ROUTED}
        if valid is None:
            valid = jnp.ones((n.shape[0],), bool)
        chosen, weight = moe.route_sigmoid_topk(
            n, w['Router'], self.top_k, bias=w['RouterBias'],
            scale=self.routed_scale)
        held = self.routed[0].shape[1]
        gate, hit = moe.held_gates(chosen, weight, self.first, held)
        m = moe.routed_experts(n, gate, hit, valid, min(self.top_k, held),
                               *self.routed, layer=i)
        n_shared = w['ShrGate'].shape[0]
        m += moe.gated_experts(n, jnp.ones((n.shape[0], n_shared)),
                               w['ShrGate'], w['ShrUp'], w['ShrDown'])
        return m, moe.load_stats(hit, valid)
