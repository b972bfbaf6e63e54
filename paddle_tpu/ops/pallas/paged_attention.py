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

The attention reads the pages its rows hold and no others, in plain
XLA on every backend. Two forms over one inner form
(``_attend_blocks``: whole pages of about BLOCK_COLS columns gathered
through the table at (layer, table) and consumed there under a running
softmax; float32 scores, normaliser and accumulator):
``paged_attention_blocked`` — many tables, one query each (decode
step, spec verify): rows ordered by attended length, blocks of
BLOCK_ROWS rows, each running the column blocks from the one that
holds its smallest lower bound to the one that holds its largest
length; rows that are not live cost no block;
``paged_attention_one_table`` — one table, many queries (every
prefill): the chunk's rows as one group over the blocks the chunk can
see.
The loop bounds come from the step's own inputs (``block_bounds``), in
one compiled program per signature; nothing of the extent [B, P] or
[S, P] is gathered, re-tiled or multiplied (PERF.md section 6, PR 29;
before it the gather covered every page of every table whatever the
rows held). On the TPU the split of H*D into [H, D] is still a
re-tiling of a block's gathered pages when D is under a lane tile
(ROADMAP S3c).

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

import jax
import jax.numpy as jnp

_NEG_INF = -1e9


# The two block sizes, neither swept against a trace: a row block is
# the hardware's sublane tile, a column block about the width the
# one-table form has run at on the chip since PR 28.
BLOCK_ROWS = 8
BLOCK_COLS = 512


def pages_per_block(n_pages, bs, block_cols=BLOCK_COLS):
    """Whole pages a column block: the largest divisor of the table's
    ``n_pages`` that holds at most ``block_cols`` columns, so every
    block is full and block j covers the absolute columns
    [j * pages * bs, (j + 1) * pages * bs)."""
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


def row_blocks(lo, hi, block, n_blocks, xp=jnp):
    """Many tables' rows in blocks of BLOCK_ROWS: (order [B], first [G],
    last [G]). ``order`` puts the longest attended length first and the
    rows that see nothing last, so a block holds rows of like length
    and the blocks that run are a prefix; ``first``/``last`` are
    ``block_bounds`` of the ordered rows, the last block filled up with
    rows that see nothing."""
    order = xp.argsort(-xp.where(hi > lo, hi, 0), stable=True)
    fill = xp.zeros((-len(order) % BLOCK_ROWS,), hi.dtype)
    first, last = block_bounds(xp.concatenate([lo[order], fill]),
                               xp.concatenate([hi[order], fill]),
                               BLOCK_ROWS, block, n_blocks, xp)
    return order, first, last


def pages_covered(lo, hi, n_pages, bs, xp=jnp):
    """Pages one layer of ``paged_attention_blocked`` gathers for
    rows that see columns lo <= j < hi of tables of ``n_pages``: every
    (row block, column block) pair that runs reads BLOCK_ROWS rows of
    ``pages_per_block`` pages."""
    per = pages_per_block(n_pages, bs)
    _, first, last = row_blocks(lo, hi, per * bs, n_pages // per, xp)
    return (last - first + 1).sum() * BLOCK_ROWS * per


def _attend_blocks(q, arenas, layer, tables, lo, hi, first, last, per,
                   latent=None, chosen=None, expand=None):
    """The one inner form: R tables with S queries each
    (decode: R = BLOCK_ROWS, S = 1; a prefill chunk: R = 1, S = bucket).
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

    def block(j, state):
        top, norm, acc = state
        at = jax.lax.dynamic_slice_in_dim(tables, j * per, per, 1)
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
        col = j * bk + jnp.arange(bk)
        seen = (col >= lo[..., None]) & (col < hi[..., None])
        if chosen is not None:
            seen &= chosen(j * bk)
        seen = seen[:, None, None]                         # [R, 1, 1, S, bk]
        each = qg.shape[1] // len(kb)
        scores = jnp.concatenate([
            jnp.einsum(score,
                       qg[:, i * each:(i + 1) * each], x, precision=exact,
                       preferred_element_type=jnp.float32)
            for i, x in enumerate(kb)], axis=1)
        scores = jnp.where(seen, scores, _NEG_INF)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        w = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        norm = keep * norm + jnp.sum(w, axis=-1)
        w = w.astype(vb[0].dtype)
        acc = keep[..., None] * acc + jnp.concatenate([
            jnp.einsum(mix,
                       w[:, i * each:(i + 1) * each], x, precision=exact,
                       preferred_element_type=jnp.float32)
            for i, x in enumerate(vb)], axis=1)
        return new_top, norm, acc

    shape = (r, n_kv, group, s)
    init = (jnp.full(shape, _NEG_INF, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (d_v,), jnp.float32))
    _, norm, acc = jax.lax.fori_loop(first, last + 1, block, init)
    out = acc / jnp.where(norm == 0.0, 1.0, norm)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(r, s, h, d_v)


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
    Nothing of the extent [B, P] is gathered: the rows are ordered by
    attended length (an argsort of [B] ints, undone on the result), go
    in blocks of BLOCK_ROWS, and a row block runs the column blocks
    from the one that holds its smallest ``lo`` to the one that holds
    its largest length and no further (``block_bounds``); row blocks
    past the last row that sees anything run nothing. A row with
    seq_lens <= lo (an empty batch slot: callers pass length 0) costs
    no block and yields 0.

    Columns outside [lo, seq_lens) contribute exactly 0, so the result
    is independent of the garbage content of unowned/partial pages, and
    a row's result does not depend on what else the batch holds
    (``_attend_blocks``).

    Quantized arenas: ``k_scales``/``v_scales`` [L, NB, bs, H] carry
    one fp32 scale per stored (page, slot, head) K/V row; a block's
    pages are dequantized to fp32 through the same table indices
    before the attention math (int8/fp8 only ever live in HBM).
    Grouped heads: where the arena's row holds fewer heads than ``q``
    has (row width = Hkv * D), query head h reads KV head
    h // (H / Hkv). ``lo`` [B] int32 is a lower bound on the columns a
    row sees (a sliding window); None sees every column below
    ``seq_lens``. The latent form (``v_pages`` None, ``latent`` the
    values' width; ``_attend_blocks``) returns [B, H, latent];
    ``chosen`` bool [B, P * bs] narrows each row's columns further."""
    nb, bs = k_pages.shape[1], k_pages.shape[2]
    b, p = block_tables.shape
    h, d = q.shape[1], q.shape[2]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    per = pages_per_block(p, bs, block_cols)
    lo, hi = _seen_from_to(lo, seq_lens)
    order, first, last = row_blocks(lo, hi, per * bs, p // per)
    short = -b % BLOCK_ROWS

    def ordered(x):
        return jnp.concatenate(
            [x[order], jnp.zeros((short,) + x.shape[1:], x.dtype)])

    q_s, lo_s, hi_s = ordered(q * scale), ordered(lo), ordered(hi)
    tables = ordered(jnp.clip(block_tables.astype(jnp.int32), 0, nb - 1))
    arenas = _arenas(k_pages, v_pages, k_scales, v_scales)
    chosen_s = None if chosen is None else ordered(chosen)

    def rows(i, out):
        def cut(x):
            return jax.lax.dynamic_slice_in_dim(x, i * BLOCK_ROWS,
                                                BLOCK_ROWS, 0)
        mine = None if chosen is None else _columns_of(cut(chosen_s)[:, None],
                                                       per * bs)
        got = _attend_blocks(cut(q_s)[:, None], arenas, layer, cut(tables),
                             cut(lo_s)[:, None], cut(hi_s)[:, None],
                             first[i], last[i], per, latent, mine)
        return jax.lax.dynamic_update_slice_in_dim(
            out, got[:, 0], i * BLOCK_ROWS, 0)

    live_blocks = jnp.sum(last >= first)      # a prefix: rows are ordered
    out = jax.lax.fori_loop(
        0, live_blocks, rows,
        jnp.zeros((b + short, h, latent or d), jnp.float32))
    return out[jnp.argsort(order)]


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
