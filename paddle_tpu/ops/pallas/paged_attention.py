"""Ragged paged attention for autoregressive decode serving.

One decode step attends one query token per sequence against that
sequence's KV cache, which lives in a pool of fixed-size blocks
("pages") in HBM — the paged-KV design from PAPERS "Ragged Paged
Attention". Each sequence owns a *block table* (logical page i ->
physical page id) and a true length; batches are ragged (every row has
a different live length) so a dense [B, Tmax] cache would pay padding
FLOPs and, worse, padding HBM. Pages decouple cache capacity from
per-sequence reservation: a 17-token sequence holds ceil(17/bs) pages,
not Tmax slots.

The attention reads the pages its rows hold and no others, in whole
pages of about BLOCK_COLS columns gathered through the table at (layer,
table) and consumed where they are gathered (``_block_products``:
float32 scores, normaliser and accumulator): in plain XLA on every
backend, but for the many-tables latent form on a TPU, whose pair list
goes through one Pallas kernel (``paged_decode_attention.py``;
``pairs_form`` is the static rule, ``jax.lax.platform_dependent`` the
choice; the XLA loop is what the kernel is held to,
tests/test_latent_decode_kernel.py). Two forms:
``paged_attention_blocked`` — many tables, one query each (decode
step, spec verify). Its unit of work is a *pair* (row, column block):
a live row holds one pair for each column block between its bounds,
a row that is not live none (``row_pairs``). One loop takes
BLOCK_ROWS consecutive pairs an iteration, each through its own
table at its own block, as so many independent problems, and a row's
pairs advance its softmax state one after another in column order,
within an iteration as across two, so a short row beside a long one
costs its own blocks and a row's result is its own columns' alone
(the kernel takes the same list a pair a grid step, the next pair's
pages on their way in while this one multiplies, and merges by the
same rule: 3.1-3.8 us a pair in the loop, 1.5-2.1 in the kernel at
longcat_flash_chat's and kimi_k2_6's shape, PERF.md section 6, PR 51);
``paged_attention_one_table`` — one table, many queries (every
prefill): the chunk's rows as one group over the blocks the chunk can
see, under one running softmax (``_attend_blocks``).
The loop bounds come from the step's own inputs (``block_bounds``), in
one compiled program per signature; nothing of the extent [B, P] or
[S, P] is gathered, re-tiled or multiplied (PERF.md section 6, PR 29;
before it the gather covered every page of every table whatever the
rows held; until PR 41 a block of BLOCK_ROWS rows ran every column
block to its longest row). On the TPU the split of H*D into [H, D] is
still a re-tiling of a block's gathered pages when D is under a lane
tile (ROADMAP S3c).

Parity of both forms with a dense masked oracle
(``paged_attention_reference``, which no serving program calls) across
mixed lengths, head layouts and arena dtypes is asserted in
tests/test_paged_attention_blocked.py, tests/test_decode_serving.py and
tests/test_pallas_kernels.py.

The latent forms (``latent=r``; ops/latent_moe_ops.py): one arena whose
row ``[c_kv ; k_rope]`` every head reads, and an optional per-row choice
of columns (``chosen``) within the bounds, through the same blocks.
*Absorbed* (both forms; the decode step, spec verify, a short chunk):
keys the whole row and values its first ``r`` columns, the two
up-projections applied by the caller to the query and to the result.
*Expanded* (``expand=(W_UK, W_UV)``, the one-table form only: a chunk of
many rows): a block's gathered rows are expanded where they are
consumed to per-head keys ``[c_kv W_UK,h ; k_rope]`` and values
``c_kv W_UV,h``, head-major out of the products, and the block is the
per-head form from there. Which of the two a program runs is a function
of its static row count and the kind's widths
(``serving/decode/model.py``: ``latent_expands``); ``_attend_blocks``
has what each costs.

A block table may have holes below a row's lower bound. Where a layer
kind's arenas lie in a page pool of their own whose pages go back to
the pool behind the window (``serving/decode/kv_pool.py``:
``KVPool.trim``; ops/gqa_moe_ops.py), each call takes the table of its
layer's kind, and the entries of pages given back point past the pool
like unowned ones. Every column block wholly below ``lo`` is outside
the loops (``block_bounds``, ``row_pairs``) and is never gathered; in
the one block that holds ``lo`` such an entry is gathered clipped to a
real page (whoever owns it now: finite values, an arena starts as zeros
and only ever takes finite rows) and its columns contribute exactly 0,
as the columns past a row's length do.

Layouts:
    q            [B, H, D]      one query token per sequence (latent,
                 absorbed: D the stored row's width; expanded:
                 d_nope + d_rope)
    k/v_pages    [L, NB, bs, H*D]  the pooled page arena, all layers:
                 token-major inside a page, heads and head width merged
                 into one lane-dense minor axis (a token's K row is
                 H*D contiguous elements), so the TPU keeps the arena
                 row-major and a row can be written in place
                 (ops/paged_decode_ops.py). ``layer`` picks the layer.
                 Latent: one arena, [L, NB, bs, W] with W the row
                 [c_kv ; k_rope] stored in whole lane tiles.
    expand       (W_UK [H, d_nope, r], W_UV [H, r, d_v]) one layer's
                 up-projections, at the weights' dtype
    k/v_scales   [L, NB, bs, H] per-row fp32 scales (quantized arenas)
    block_tables [B, P] int32   physical page ids; >= NB means "no page"
    seq_lens     [B]  int32     live tokens (this token included)
"""

import functools

import jax
import jax.numpy as jnp

from .paged_decode_attention import pair_attention

_NEG_INF = -1e9


# The two block sizes, neither swept against a trace: BLOCK_ROWS is the
# pairs of (row, column block) an iteration of the many-tables form
# takes (the hardware's sublane tile; the name is from when they were 8
# rows), a column block about the width the one-table form has run at
# on the chip since PR 28.
BLOCK_ROWS = 8
BLOCK_COLS = 512


@functools.lru_cache(maxsize=None)
def pages_per_block(n_pages, bs, block_cols=BLOCK_COLS):
    """Whole pages a column block: the largest divisor of the table's
    ``n_pages`` that holds at most ``block_cols`` columns, so every
    block is full and block j covers the absolute columns
    [j * pages * bs, (j + 1) * pages * bs). Kept once found: the
    engine's counters ask for it every step."""
    return max(p for p in range(1, n_pages + 1)
               if n_pages % p == 0 and p * bs <= max(block_cols, bs))


def block_bounds(lo, hi, rows, block, n_blocks, xp=jnp):
    """The column blocks each group of ``rows`` consecutive rows runs,
    from what the rows hold: ``lo``/``hi`` [G * rows] ints (a row sees
    columns lo <= j < hi; hi <= lo sees nothing) -> (first [G], last
    [G]): from the block that holds the group's smallest ``lo`` to the
    one that holds its largest ``hi``; last = first - 1 where no row of
    the group sees anything. A pure function of the lengths over ``xp``
    (jnp inside the program, numpy where the engine counts
    ``decode.attn_pages_read``), so the count is of the loops that run."""
    live = hi > lo
    lo_g = xp.where(live, lo, n_blocks * block).reshape(-1, rows).min(axis=1)
    hi_g = xp.where(live, hi, 0).reshape(-1, rows).max(axis=1)
    first = xp.clip(lo_g // block, 0, n_blocks - 1)
    last = xp.clip((hi_g - 1) // block, 0, n_blocks - 1)
    return first, xp.where(hi_g > 0, last, first - 1)


def row_pairs(lo, hi, block, n_blocks, xp=jnp):
    """Many tables' work as a list of (row, column block) pairs: a live
    row holds one pair for each column block from the one that holds
    its ``lo`` to the one that holds its ``hi`` (``block_bounds`` with
    one row a group), a row that sees nothing holds none. The list goes
    row after row, a row's blocks in ascending column order. Returns
    (first [B], last [B], ends [B]): each row's own bounds and the
    pairs held up to and including it, so the list has ``ends[-1]``
    pairs and row r holds pairs ends[r] - (last[r] - first[r] + 1) <=
    t < ends[r]."""
    first, last = block_bounds(lo, hi, 1, block, n_blocks, xp)
    return first, last, xp.cumsum(last - first + 1)


def pairs_at(t, last, ends, xp=jnp):
    """Pairs ``t`` [N] of ``row_pairs``' list -> (row [N], block [N]).
    ``row`` is the first row whose ``ends`` pass t (rows that hold no
    pair are stepped over); t past the list gives row = len(ends), the
    fill of a last iteration, and a block of no meaning."""
    row = (ends[None, :] <= t[:, None]).sum(axis=1)
    at = xp.minimum(row, len(ends) - 1)
    return row, last[at] - (ends[at] - 1 - t)


def pages_held(lo, hi, n_pages, bs, xp=jnp):
    """Pages the column blocks of the rows' own bounds hold, one
    layer: pairs x ``pages_per_block``. The least any form that goes in
    whole column blocks gathers."""
    per = pages_per_block(n_pages, bs)
    return row_pairs(lo, hi, per * bs, n_pages // per, xp)[2][-1] * per


def pages_covered(lo, hi, n_pages, bs, xp=jnp):
    """Pages one layer of ``paged_attention_blocked`` gathers for
    rows that see columns lo <= j < hi of tables of ``n_pages``: every
    iteration of its loop takes BLOCK_ROWS pairs of ``pages_per_block``
    pages, the fill of the last included."""
    taken = BLOCK_ROWS * pages_per_block(n_pages, bs)
    return -(-pages_held(lo, hi, n_pages, bs, xp) // taken) * taken


def pairs_form(platform, latent, quantized=False):
    """The form ``paged_attention_blocked`` takes over its pair list, a
    static rule of what the call is handed and the platform its program
    is lowered for: 'kernel' (paged_decode_attention.py) for the latent
    form over an unquantized arena on a TPU, 'loop' for everything
    else. The engine counts a step's pairs under it
    (``decode.attn_pairs``)."""
    return 'kernel' if platform == 'tpu' and latent and not quantized \
        else 'loop'


def _block_products(q, arenas, layer, per, latent=None, expand=None):
    """What both forms do to one column block of R tables, S queries
    each (``_attend_blocks`` has the layouts and what ``latent`` and
    ``expand`` mean): q [R, S, H, D] (scaled) -> (gathered, scored,
    mixed, shape, d_v). ``gathered(at)``: the block's keys and values
    through page ids ``at`` [R, per], dequantized where the arenas are
    quantized; ``scored(keys, seen)``: float32 scores [R, Hkv, G, S, bk]
    with every column ``seen`` (broadcastable to them) leaves out at
    _NEG_INF; ``mixed(w, values)``: the product of weights
    [R, Hkv, G, S, bk] at the values' dtype with the values, float32
    [R, Hkv, G, S, d_v]; ``shape`` (R, Hkv, G, S), the axes of a
    softmax state."""
    k_pages = arenas[0]
    v_pages = k_pages if latent else arenas[1]
    r, s, h, d = q.shape
    bs = k_pages.shape[2]
    n_kv = 1 if expand else k_pages.shape[-1] // d
    d_v = expand[1].shape[-1] if expand else latent or d
    bk = per * bs
    quantized = len(arenas) == 4
    group = h // n_kv
    qg = jnp.transpose(
        q.astype(jnp.float32 if quantized else k_pages.dtype).reshape(
            r, s, n_kv, group, d), (0, 2, 3, 1, 4))     # [R, Hkv, G, S, D]
    # float32 operands multiply as float32 (the forms replaced ran them
    # on the vector unit at full precision; left to the default the
    # compiler rounds the whole arena to bfloat16 ahead of the gather)
    exact = jax.lax.Precision.HIGHEST if qg.dtype == jnp.float32 else None
    # Splitting gathered rows into [Hkv, D] has the compiler re-lay
    # the block head-major first (v5e compile, PR 28 and PR 29). Where
    # a head is whole lane tiles it can be sliced out of a row as it
    # lies instead, at the price of joining the heads' scores: taken
    # where the scores are the smaller of the two (one query a table)
    by_head = not expand and d % 128 == 0 and \
        h * s * 4 < n_kv * d * jnp.dtype(k_pages.dtype).itemsize
    # a block's keys and values: [R, bk, n, width] one KV head a group,
    # or [R, H, bk, width] a head of its own (expanded)
    score, mix = ('rngsd,rgkd->rngsk', 'rngsk,rgkd->rngsd') if expand \
        else ('rngsd,rknd->rngsk', 'rngsk,rknd->rngsd')

    def pages(arena, at):
        # [R, per, bs, W]: whole pages; adjacent axes merged only
        return arena[layer, at].reshape(r, bk, -1)

    def heads(x, width):
        """[R, bk, Hkv * width] -> one [R, bk, n, width] per product."""
        if by_head:
            return [x[:, :, n * width:(n + 1) * width][:, :, None]
                    for n in range(n_kv)]
        return [x.reshape(r, bk, n_kv, width)]

    def expanded(rows):
        """[R, bk, W] latent rows -> ([[R, H, bk, d_nope + d_rope]],
        [[R, H, bk, d_v]]) at the rows' dtype, head-major out of the
        products; the rotated columns are every head's alike."""
        w_uk, w_uv = expand
        d_rope = d - w_uk.shape[1]
        c_kv = rows[..., :latent].astype(w_uk.dtype)
        rope = rows[:, None, :, latent:latent + d_rope]
        k_nope = jnp.einsum('hdc,rkc->rhkd', w_uk, c_kv, precision=exact,
                            preferred_element_type=jnp.float32)
        v = jnp.einsum('hcv,rkc->rhkv', w_uv, c_kv, precision=exact,
                       preferred_element_type=jnp.float32)
        return [jnp.concatenate(
            [k_nope.astype(rows.dtype),
             jnp.broadcast_to(rope, k_nope.shape[:3] + (d_rope,))],
            -1)], [v.astype(rows.dtype)]

    def gathered(at):
        if expand:
            kb, vb = expanded(pages(k_pages, at))
        else:
            kb = heads(pages(k_pages, at), d)
            vb = [x[..., :latent] for x in kb] if latent \
                else heads(pages(v_pages, at), d)
        if quantized:
            kb = [x.astype(jnp.float32) * sc for x, sc in
                  zip(kb, heads(pages(arenas[2], at), 1))]
            vb = [x.astype(jnp.float32) * sc for x, sc in
                  zip(vb, heads(pages(arenas[3], at), 1))]
        return kb, vb

    def scored(kb, seen):
        each = qg.shape[1] // len(kb)
        scores = jnp.concatenate([
            jnp.einsum(score,
                       qg[:, i * each:(i + 1) * each], x, precision=exact,
                       preferred_element_type=jnp.float32)
            for i, x in enumerate(kb)], axis=1)
        return jnp.where(seen, scores, _NEG_INF)

    def mixed(w, vb):
        each = qg.shape[1] // len(vb)
        return jnp.concatenate([
            jnp.einsum(mix,
                       w[:, i * each:(i + 1) * each], x, precision=exact,
                       preferred_element_type=jnp.float32)
            for i, x in enumerate(vb)], axis=1)

    return gathered, scored, mixed, (r, n_kv, group, s), d_v


def _attend_blocks(q, arenas, layer, tables, lo, hi, first, last, per,
                   latent=None, chosen=None, expand=None):
    """The one-table form's loop: R tables with S queries each (a
    prefill chunk: R = 1, S = bucket).
    q [R, S, H, D] (scaled), ``arenas`` (K, V[, K scales, V scales]),
    tables [R, P] (clipped), lo/hi [R, S]. Column blocks ``first`` ..
    ``last`` of ``per`` whole pages go one after another under a running
    softmax (maximum, normaliser, weighted sum; float32): each
    iteration gathers [R, per pages] through the tables and is consumed
    there. A block no column of which a row sees leaves that row's
    state bit for bit as it was, and a block sits at an absolute
    multiple of its width, so a row's result depends on its own
    columns only. Operands of the two products at the arena's dtype
    (float32 once dequantized), scores, normaliser and accumulator
    float32. Returns [R, S, H, D] float32; rows that saw nothing 0.

    The latent form (``latent`` = r, ``arenas`` one arena of rows
    ``[c_kv ; k_rope]``), *absorbed* (``expand`` None): every head reads
    the one row, the keys are the whole row (D is its width) and the
    values its first r columns, so a page is gathered once and nothing
    is expanded; returns [R, S, H, r]. Its accumulator is as large as
    a score block ([H, S, r] beside [H, S, bk]): the form for few
    queries a table.

    The latent form *expanded* (``expand`` = (W_UK [H, d_nope, r],
    W_UV [H, r, d_v]); q [R, S, H, d_nope + d_rope] as the query
    projection gives it): a block's rows are gathered once, as above,
    and expanded there to a key and a value a head,
    ``K_h = [c_kv W_UK,h ; k_rope]`` and ``V_h = c_kv W_UV,h``, each the
    result of one product that has the head as its leading axis (no
    split of gathered rows: ``by_head`` below says what that costs);
    from there the block is the per-head form, one KV head a query
    head, with the values' width apart from the keys' and an
    accumulator of [H, S, d_v]. Expanding costs the same whatever S
    is and each (query, key) is then cheaper: the form for many
    queries a table (``serving/decode/model.py``: ``latent_expands``
    has both costs and the rule; the caller applies it). Only a
    block's keys and values are alive at a time, bk x H x
    (d_nope + d_rope + d_v) values. Returns [R, S, H, d_v]. The state
    keeps the absorbed form's axes ([R, 1, H, S]: a score block is
    [R, 1, H, S, bk] either way).

    ``chosen`` (a function of a block's first column ->
    bool [R, S, bk], or None) narrows what a row sees within
    [lo, hi) to a subset of its own choosing (a learned selection): a
    column it leaves out contributes exactly 0, as one outside the
    bounds does."""
    r, s = q.shape[:2]
    bk = per * arenas[0].shape[2]
    gathered, scored, mixed, shape, d_v = _block_products(
        q, arenas, layer, per, latent, expand)

    def block(j, state):
        top, norm, acc = state
        kb, vb = gathered(
            jax.lax.dynamic_slice_in_dim(tables, j * per, per, 1))
        col = j * bk + jnp.arange(bk)
        seen = (col >= lo[..., None]) & (col < hi[..., None])
        if chosen is not None:
            seen &= chosen(j * bk)
        seen = seen[:, None, None]                         # [R, 1, 1, S, bk]
        scores = scored(kb, seen)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        w = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        norm = keep * norm + jnp.sum(w, axis=-1)
        w = w.astype(vb[0].dtype)
        acc = keep[..., None] * acc + mixed(w, vb)
        return new_top, norm, acc

    init = (jnp.full(shape, _NEG_INF, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (d_v,), jnp.float32))
    _, norm, acc = jax.lax.fori_loop(first, last + 1, block, init)
    out = acc / jnp.where(norm == 0.0, 1.0, norm)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(r, s, -1, d_v)


def _seen_from_to(lo, seq_lens):
    """(lo, hi) [N] int32: a row sees columns lo <= j < hi (lo None: 0)."""
    hi = jnp.asarray(seq_lens).reshape(-1).astype(jnp.int32)
    return (jnp.zeros_like(hi) if lo is None
            else jnp.asarray(lo).reshape(-1).astype(jnp.int32)), hi


def _arenas(k_pages, v_pages, k_scales, v_scales):
    given = (k_pages,) if v_pages is None else (k_pages, v_pages) \
        if k_scales is None else (k_pages, v_pages, k_scales, v_scales)
    return tuple(jnp.asarray(a) for a in given)


def paged_attention_blocked(q, k_pages, v_pages, block_tables, seq_lens,
                            sm_scale=None, k_scales=None, v_scales=None,
                            layer=0, lo=None, block_cols=BLOCK_COLS,
                            latent=None, chosen=None):
    """Many tables, one query each: q [B, H, D], ``block_tables`` [B, P]
    (entries >= NB mean "no page" and are never read), ``seq_lens`` [B].
    Nothing of the extent [B, P] is gathered: the work is the list of
    (row, column block) pairs the live rows hold (``row_pairs``: row
    after row, a row's blocks from the one that holds its ``lo`` to the
    one that holds its length, in ascending order), and one loop of
    ceil(pairs / BLOCK_ROWS) iterations takes BLOCK_ROWS consecutive
    pairs at a time: [BLOCK_ROWS, pages of a block] gathered through
    each pair's own table at its own block, scores
    [BLOCK_ROWS, Hkv, G, 1, columns of a block] and a softmax partial
    (maximum, normaliser, weighted sum; float32) a pair. A row's
    partials are merged into its state one after another in column
    order, the same way whether two of them fall in one iteration or
    in two (the open row's state rides in the loop's carry), and a
    row's last pair writes its result. The fill of the last iteration
    sees nothing. A row with seq_lens <= lo (an empty batch slot:
    callers pass length 0) holds no pair, costs nothing and yields 0.

    Columns outside [lo, seq_lens) contribute exactly 0, so the result
    is independent of the garbage content of unowned/partial pages, and
    a row's result does not depend on what else the batch holds: a
    pair's partial is its own block's, a block sits at an absolute
    multiple of its width, and the merges are the row's own in its own
    order.

    Quantized arenas: ``k_scales``/``v_scales`` [L, NB, bs, H] carry
    one fp32 scale per stored (page, slot, head) K/V row; a block's
    pages are dequantized to fp32 through the same table indices
    before the attention math (int8/fp8 only ever live in HBM).
    Grouped heads: where the arena's row holds fewer heads than ``q``
    has (row width = Hkv * D), query head h reads KV head
    h // (H / Hkv). ``lo`` [B] int32 is a lower bound on the columns a
    row sees (a sliding window); None sees every column below
    ``seq_lens``. The latent form (``v_pages`` None, ``latent`` the
    values' width; ``_attend_blocks`` has the layouts) returns
    [B, H, latent]; ``chosen`` bool [B, P * bs] narrows each row's
    columns further."""
    nb, bs = k_pages.shape[1], k_pages.shape[2]
    b, p = block_tables.shape
    h, d = q.shape[1], q.shape[2]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    per = pages_per_block(p, bs, block_cols)
    bk, n_blocks = per * bs, p // per
    lo, hi = _seen_from_to(lo, seq_lens)
    first, last, ends = row_pairs(lo, hi, bk, n_blocks)
    arenas = _arenas(k_pages, v_pages, k_scales, v_scales)
    q = (jnp.asarray(q) * scale).astype(
        jnp.float32 if len(arenas) == 4 else k_pages.dtype)
    # The whole list, once a call and nothing of it inside the loop:
    # each pair's row, block, bounds, whether it opens or closes its
    # row and where its result goes (past the rows unless it closes
    # one), and its pages through its row's table
    t = jnp.arange(-(-b * n_blocks // BLOCK_ROWS) * BLOCK_ROWS)
    row, block = pairs_at(t, last, ends)
    at, live = jnp.minimum(row, b - 1), row < b
    block = jnp.where(live, block, 0)
    listed = jnp.stack([
        at, block, lo[at], jnp.where(live, hi[at], 0),
        live & (block == first[at]),
        jnp.where(live & (block == last[at]), row, b + t % BLOCK_ROWS)], 1)
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, nb - 1).reshape(
        b, n_blocks, per)[at, block]
    if chosen is not None:
        chosen = jnp.asarray(chosen).reshape(b, n_blocks, bk)
    if pairs_form('tpu', latent, k_scales is not None) == 'kernel':
        # the form follows the platform the program is lowered for
        out = jax.lax.platform_dependent(
            q, arenas, jnp.asarray(layer, jnp.int32), listed, tables,
            ends[-1], chosen,
            tpu=functools.partial(_pairs_by_kernel, per, latent),
            default=functools.partial(_pairs_by_loop, per, latent))
        # the kernel writes no row that holds no pair
        return jnp.where((hi > lo)[:, None, None], out, 0.0)
    return _pairs_by_loop(per, latent, q, arenas, layer, listed, tables,
                          ends[-1], chosen)


def _pairs_by_kernel(per, latent, q, arenas, layer, listed, tables, count,
                     chosen):
    """The list's pairs through the kernel, which takes a pair's ``goes``
    as whether it closes its row and leaves a row that holds no pair
    unwritten."""
    return pair_attention(
        q, arenas[0], layer,
        listed.T.at[5].set(listed[:, 5] < q.shape[0]), tables.reshape(-1),
        count, chosen, per=per, rank=latent)


def _pairs_by_loop(per, latent, q, arenas, layer, listed, tables, count,
                   chosen):
    """The list's first ``count`` pairs, BLOCK_ROWS an iteration
    (``paged_attention_blocked`` says how). ``listed`` [N, 6]: a pair's
    row, block, bounds, whether it opens its row, and where its result
    goes (its row if it closes it, else past the rows)."""
    b, h, d = q.shape
    bk = per * arenas[0].shape[2]

    def pairs(i, state):
        def mine(x):
            return jax.lax.dynamic_slice_in_dim(x, i * BLOCK_ROWS,
                                                BLOCK_ROWS, 0)
        at, block, lo_p, hi_p, opens, goes = mine(listed).T
        gathered, scored, mixed, _, _ = _block_products(
            q[at][:, None], arenas, layer, per, latent)
        kb, vb = gathered(mine(tables))
        col = block[:, None] * bk + jnp.arange(bk)
        seen = (col >= lo_p[:, None]) & (col < hi_p[:, None])
        if chosen is not None:
            seen &= chosen[at, block]
        seen = seen[:, None, None, None]                   # [8, 1, 1, 1, bk]
        scores = scored(kb, seen)
        top = jnp.max(scores, axis=-1)
        w = jnp.where(seen, jnp.exp(scores - top[..., None]), 0.0)
        norm = jnp.sum(w, axis=-1)
        acc = mixed(w.astype(vb[0].dtype), vb)
        # A row's pairs advance its state one after another in column
        # order, here as from one iteration to the next: the open
        # row's state rides in the carry
        (row_top, row_norm, row_acc), out = state
        done = []
        for k in range(BLOCK_ROWS):
            fresh = opens[k] > 0
            row_top = jnp.where(fresh, _NEG_INF, row_top)
            row_norm = jnp.where(fresh, 0.0, row_norm)
            row_acc = jnp.where(fresh, 0.0, row_acc)
            # the side that holds the larger maximum is taken as it
            # is (its factor would be exactly 1), so each sum has one
            # product and rounds alike wherever in an iteration it
            # falls, whether or not a backend fuses the two
            older = row_top >= top[k]
            after = jnp.maximum(row_top, top[k])
            keep, scale_k = jnp.exp(row_top - after), jnp.exp(top[k] - after)
            row_norm = jnp.where(older, row_norm + scale_k * norm[k],
                                 keep * row_norm + norm[k])
            row_acc = jnp.where(
                older[..., None], row_acc + scale_k[..., None] * acc[k],
                keep[..., None] * row_acc + acc[k])
            row_top = after
            done.append(row_acc / jnp.where(row_norm == 0.0, 1.0,
                                            row_norm)[..., None])
        return (row_top, row_norm, row_acc), out.at[goes].set(
            jnp.stack(done).reshape(BLOCK_ROWS, h, -1))

    n_kv = arenas[0].shape[-1] // d
    d_v = latent or d
    shape = (n_kv, h // n_kv, 1)
    row_state = (jnp.full(shape, _NEG_INF, jnp.float32),
                 jnp.zeros(shape, jnp.float32),
                 jnp.zeros(shape + (d_v,), jnp.float32))
    _, out = jax.lax.fori_loop(
        0, -(-count // BLOCK_ROWS), pairs,
        (row_state, jnp.zeros((b + BLOCK_ROWS, h, d_v), jnp.float32)))
    return out[:b]


def _columns_of(chosen, bk):
    """``chosen`` bool [R, S, P * bs] as ``_attend_blocks`` asks for it:
    the block of ``bk`` columns from a given first column."""
    return lambda at: jax.lax.dynamic_slice_in_dim(chosen, at, bk, 2)


def paged_attention_one_table(q, k_pages, v_pages, table, seq_lens,
                              sm_scale=None, k_scales=None, v_scales=None,
                              layer=0, lo=None, block_cols=BLOCK_COLS,
                              latent=None, chosen=None, expand=None):
    """One table, many queries: consecutive rows of ONE sequence (a
    prefill chunk) against that sequence's pages: q [S, H, D],
    ``table`` [P], row s sees columns lo[s] <= j < seq_lens[s]
    (seq_lens <= lo: a padded row, sees nothing and yields 0). The same
    blocks under the same running softmax as
    ``paged_attention_blocked``, with the S rows as one group: from the
    block that holds the smallest ``lo`` to the one that holds the
    largest ``hi`` and no further, each block's
    pages gathered once for all S rows. A chunk at the start of a
    prompt multiplies one block and not the table's whole extent, a
    chunk deep in a sliding layer its window's blocks, and the scores
    alive at a time are [H, S, block]. Which blocks run depends on the
    chunk's place in its own sequence only. Grouped heads, quantized
    arenas, the latent form and ``chosen`` [S, P * bs] as in
    ``paged_attention_blocked``. ``expand`` (the latent form's two
    up-projections; ``_attend_blocks``) has each block's rows expanded
    to per-head keys and values where they are gathered: q is then
    [S, H, d_nope + d_rope] and the result [S, H, d_v]. Only this form
    takes it: with one query a table the absorbed form is the cheaper
    (``LatentShape.expands``)."""
    nb, bs = k_pages.shape[1], k_pages.shape[2]
    s, h, d = q.shape
    scale = sm_scale if sm_scale is not None else d ** -0.5
    per = pages_per_block(table.shape[0], bs, block_cols)
    lo, hi = _seen_from_to(lo, seq_lens)
    first, last = block_bounds(lo, hi, s, per * bs, table.shape[0] // per)
    out = _attend_blocks(
        (q * scale)[None], _arenas(k_pages, v_pages, k_scales, v_scales),
        layer, jnp.clip(table.astype(jnp.int32), 0, nb - 1)[None],
        lo[None], hi[None], first[0], last[0], per, latent,
        None if chosen is None else _columns_of(chosen[None], per * bs),
        expand)
    return out[0]


def paged_attention_reference(q, k_pages, v_pages, block_tables, seq_lens,
                              sm_scale=None, k_scales=None,
                              v_scales=None, layer=0, lo=None):
    """The dense masked oracle of the tests: every page of every table
    gathered ([B, P * bs, Hkv, D]), one float32 softmax over the whole
    extent. No serving program calls it."""
    nb, bs = k_pages.shape[1], k_pages.shape[2]
    b, p = block_tables.shape
    h, d = q.shape[1], q.shape[2]
    n_kv = k_pages.shape[-1] // d
    scale = sm_scale if sm_scale is not None else d ** -0.5
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, nb - 1)

    def gathered(arena, scales):
        x = arena[layer, tables].reshape(b, p * bs, n_kv, -1)
        if scales is None:
            return x.astype(jnp.float32)
        return x.astype(jnp.float32) * \
            scales[layer, tables].reshape(b, p * bs, n_kv, 1)

    k, v = gathered(k_pages, k_scales), gathered(v_pages, v_scales)
    qg = (q * scale).astype(jnp.float32).reshape(b, n_kv, h // n_kv, d)
    cols = jnp.arange(p * bs)[None, :]
    seen = cols < seq_lens.reshape(-1, 1)
    if lo is not None:
        seen &= cols >= lo.reshape(-1, 1)
    logits = jnp.einsum('bngd,bknd->bngk', qg, k,
                        precision=jax.lax.Precision.HIGHEST)
    w = jax.nn.softmax(jnp.where(seen[:, None, None], logits, _NEG_INF),
                       axis=-1)
    out = jnp.einsum('bngk,bknd->bngd', w, v,
                     precision=jax.lax.Precision.HIGHEST)
    # a row that sees nothing yields 0, as in the blocked forms
    return out.reshape(b, h, d) * jnp.any(seen, axis=1)[:, None, None]
