"""The XLA path of paged attention goes in column blocks
(ops/pallas/paged_attention.py): ``paged_attention_blocked`` (many
tables, one query each: decode step, spec verify) and
``paged_attention_one_table`` (one table, many queries: prefill) over
one inner form. On the CPU at a toy geometry whose blocks are small
enough for every edge to be crossed:

- both forms against the dense masked oracle, over the head layouts and
  arena dtypes the serving programs run;
- a row alone and the same row among others give the same bits;
- the list of (row, column block) pairs the many-tables form runs,
  eight an iteration, and the engine's counts of the pages they hold
  and the pages the loop gathers;
- ``chosen`` columns against a dense softmax over them.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.quant import core as qcore

NB, BS, P = 64, 8, 12            # pool, page, table: 96 columns a table
COLS = 32                        # a column block: 4 pages, 3 blocks
N = 20                           # rows (tables) a call
CAP = P * BS

# name -> (query heads, KV heads, head width, arena dtype)
LAYOUTS = {
    'plain_f32_d64': (4, 4, 64, 'float32'),
    'plain_bf16_d16': (4, 4, 16, 'bfloat16'),
    'grouped_bf16_d128': (8, 2, 128, 'bfloat16'),
    # a K/V row of 192: wider than a lane tile and not whole tiles
    'grouped_f32_3x64': (12, 3, 64, 'float32'),
    'int8_scales': (4, 4, 16, 'int8'),
    'fp8_scales': (4, 4, 16, 'float8_e4m3fn'),
}

# name -> attended lengths [N] (0: a row that is not live). Each crosses
# iteration and column-block edges in its own way.
LENGTHS = {
    # every length class in one batch, dead rows in the middle, one row
    # at the table's full capacity, lengths on both sides of the column
    # edges 32 and 64
    'mixed_with_dead_rows': [5, 33, 0, CAP, 31, 32, 0, 0, 64, 65, 1, 17,
                             0, 90, 8, 40, 0, 63, 2, 70],
    # three live rows of 1, 2 and 3 blocks: one iteration, part-filled
    'three_live_rows': [0] * 7 + [50] + [0] * 6 + [3] + [0] * 4 + [CAP],
    # every row full: 20 x 3 pairs
    'all_at_capacity': [CAP] * N,
    # nothing is live
    'all_dead': [0] * N,
    # one row at capacity and nothing else: its own blocks, no more
    'one_of_32_at_capacity': [0] * 11 + [CAP] + [0] * 8,
    # a row of one block beside a row of all three
    'short_beside_full': [0] * 4 + [7] + [0] * 9 + [CAP] + [0] * 5,
    # one block each for two iterations' worth of rows
    'sixteen_of_one_block': [0, 0] + list(range(1, 17)) + [0, 0],
}

# name -> window (0: none). lo = max(len - window, 0): inside the first
# column block for short rows, past it (blocks skipped from the front)
# for long ones.
WINDOWS = {'no_lo': 0, 'lo_inside_first_block': 80, 'lo_past_first_block': 24}


def _case(layout, lengths, window, seed=0):
    h, n_kv, d, dtype = LAYOUTS[layout]
    if dtype == 'float8_e4m3fn' and not qcore.kv_fp8_supported():
        pytest.skip('no fp8 on this install')
    rng = np.random.RandomState(seed)
    hi = np.asarray(LENGTHS[lengths], 'int32')
    lo = np.maximum(hi - window, 0).astype('int32') if window else None
    q = jnp.asarray(rng.randn(N, h, d), jnp.float32)
    kf = jnp.asarray(rng.randn(2, NB, BS, n_kv, d), jnp.float32)
    vf = jnp.asarray(rng.randn(2, NB, BS, n_kv, d), jnp.float32)
    scales = {}
    if dtype in ('int8', 'float8_e4m3fn'):
        kf, scales['k_scales'] = qcore.quantize_rows(kf, dtype)
        vf, scales['v_scales'] = qcore.quantize_rows(vf, dtype)
    k = kf.astype(dtype).reshape(2, NB, BS, n_kv * d)
    v = vf.astype(dtype).reshape(2, NB, BS, n_kv * d)
    # each row its own pages where it has any; the rest "no page"
    tables = np.full((N, P), NB, 'int32')
    for i in range(N):
        owned = -(-int(hi[i]) // BS)
        tables[i, :owned] = rng.permutation(NB)[:owned]
    return q, k, v, jnp.asarray(tables), jnp.asarray(hi), \
        None if lo is None else jnp.asarray(lo), scales


def _tol(layout):
    # bf16 operands round the query and the softmax weights
    return 2e-2 if 'bf16' in layout else 2e-5


@pytest.mark.parametrize('window', sorted(WINDOWS))
@pytest.mark.parametrize('lengths', sorted(LENGTHS))
@pytest.mark.parametrize('layout', sorted(LAYOUTS))
def test_blocked_rows_equal_the_dense_oracle(layout, lengths, window):
    q, k, v, tables, hi, lo, scales = _case(layout, lengths, WINDOWS[window])
    got = pa.paged_attention_blocked(q, k, v, tables, hi, layer=1, lo=lo,
                                     block_cols=COLS, **scales)
    want = pa.paged_attention_reference(q, k, v, tables, hi, layer=1, lo=lo,
                                        **scales)
    assert got.dtype == jnp.float32 and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=_tol(layout), rtol=_tol(layout))
    dead = np.asarray(hi) == 0
    assert not np.asarray(got)[dead].any()      # costs no block, yields 0


@pytest.mark.parametrize('window', sorted(WINDOWS))
@pytest.mark.parametrize('layout', sorted(LAYOUTS))
def test_a_row_alone_and_among_others_gives_the_same_bits(layout, window):
    """Concurrent equals one at a time: whatever the batch holds beside
    it, and wherever the ordering puts it, a row's result is the same
    to the bit (column blocks sit at absolute multiples of their width;
    a block a row sees nothing of leaves its state as it was)."""
    q, k, v, tables, hi, lo, scales = _case(layout, 'mixed_with_dead_rows',
                                            WINDOWS[window])
    among = np.asarray(pa.paged_attention_blocked(
        q, k, v, tables, hi, layer=1, lo=lo, block_cols=COLS, **scales))
    for i in (1, 3, 8, 10, 13):
        only = np.zeros((N,), bool)
        only[i] = True
        alone = np.asarray(pa.paged_attention_blocked(
            q, k, v, jnp.where(only[:, None], tables, NB),
            jnp.where(only, hi, 0), layer=1, lo=lo, block_cols=COLS,
            **scales))
        assert np.array_equal(alone[i], among[i]), i
        assert not alone[~only].any()


@pytest.mark.parametrize('start,rows,window', [
    (0, 16, 0), (27, 16, 0), (60, 16, 24), (CAP - 16, 16, 0), (30, 5, 80)])
@pytest.mark.parametrize('layout', sorted(LAYOUTS))
def test_one_table_rows_equal_the_dense_oracle(layout, start, rows, window):
    """A prefill chunk: 16 consecutive positions of one table from
    ``start`` (the last rows past ``rows`` are the bucket's padding and
    see nothing), quantized arenas included; and the same rows as a
    spec-verify batch (one table repeated, one query each) give what
    the chunk gives."""
    q, k, v, tables, _, _, scales = _case(layout, 'all_at_capacity', 0,
                                          seed=start)
    s = 16
    q, table = q[:s], tables[0]
    hi = np.where(np.arange(s) < rows, start + np.arange(s) + 1, 0)
    lo = np.maximum(hi - window, 0) if window else np.zeros_like(hi)
    hi, lo = jnp.asarray(hi, jnp.int32), jnp.asarray(lo, jnp.int32)
    got = pa.paged_attention_one_table(q, k, v, table, hi, layer=1, lo=lo,
                                       block_cols=COLS, **scales)
    many = jnp.broadcast_to(table, (s, P))
    want = pa.paged_attention_reference(q, k, v, many, hi, layer=1, lo=lo,
                                        **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=_tol(layout), rtol=_tol(layout))
    verify = pa.paged_attention_blocked(q, k, v, many, hi, layer=1, lo=lo,
                                        block_cols=COLS, **scales)
    np.testing.assert_allclose(np.asarray(verify), np.asarray(got),
                               atol=_tol(layout) / 10, rtol=_tol(layout))
    assert not np.asarray(got)[rows:].any()


def _bounds(lengths, window):
    hi = np.asarray(LENGTHS[lengths], 'int32')
    lo = np.maximum(hi - window, 0) if window else np.zeros_like(hi)
    return lo.astype('int32'), hi


@pytest.mark.parametrize('lengths,window,blocks', [
    # 15 live rows: 96, 90, 70 and 65 hold 3 blocks each, 64, 63, 40
    # and 33 two, the seven of 32 or less one: 27 pairs, 4 iterations
    ('mixed_with_dead_rows', 0, (27, 4)),
    # lo = hi - 24: 96 and 90 see block 2 only, 64 and 63 block 1, 70
    # and 65 blocks 1..2, 40 and 33 blocks 0..1, the seven short rows
    # block 0: 19 pairs, 3 iterations
    ('mixed_with_dead_rows', 24, (19, 3)),
    ('three_live_rows', 0, (2 + 1 + 3, 1)),
    ('three_live_rows', 24, (2 + 1 + 1, 1)),
    ('all_at_capacity', 0, (60, 8)),
    ('all_at_capacity', 24, (20, 3)),     # lo = 72: the last block only
    ('all_dead', 0, (0, 0)),
    # one live row of 32 at capacity: its blocks alone
    ('one_of_32_at_capacity', 0, (3, 1)),
    # a 1-block row beside a full-length row costs one pair
    ('short_beside_full', 0, (3 + 1, 1)),
    # 16 rows of one block each: the pairs fill their iterations
    ('sixteen_of_one_block', 0, (16, 2)),
])
def test_pages_covered_counts_the_blocks_that_run(lengths, window, blocks):
    """The engine's ``decode.attn_pages_read`` and ``_held`` come from
    the function that bounds the program's loop, on numpy as on jnp:
    ``blocks`` = (the pairs the live rows hold, the iterations they
    fill)."""
    lo, hi = _bounds(lengths, window)
    per = pa.pages_per_block(P, BS, COLS)
    assert per == 4
    pairs, iterations = blocks
    first, last, ends = pa.row_pairs(lo, hi, per * BS, P // per, np)
    assert int(ends[-1]) == pairs == int((last - first + 1).sum())
    assert -(-pairs // pa.BLOCK_ROWS) == iterations
    # the default width holds the whole toy table: one pair a live row
    live = int((hi > 0).sum())
    for xp in (np, jnp):
        args = (xp.asarray(lo), xp.asarray(hi), P, BS, xp)
        held, read = int(pa.pages_held(*args)), int(pa.pages_covered(*args))
        assert held == live * P
        assert read == -(-live // pa.BLOCK_ROWS) * pa.BLOCK_ROWS * P
        assert held <= read and (held == read) == (live % pa.BLOCK_ROWS == 0)


def test_a_long_row_alone_runs_its_own_blocks_and_no_row_blocks():
    """One live row of 32 at the capacity of a table of 64 column
    blocks: ceil(64 / 8) iterations, whatever the other 31 slots are;
    beside it a row of one block adds one pair, not a row block's run
    to the longest row."""
    n_pages, bs = 1024, 32                       # 64 blocks of 512
    per = pa.pages_per_block(n_pages, bs)
    hi = np.zeros((32,), 'int32')
    hi[11] = n_pages * bs
    lo = np.zeros_like(hi)
    assert int(pa.pages_held(lo, hi, n_pages, bs, np)) == 64 * per
    assert int(pa.pages_covered(lo, hi, n_pages, bs, np)) == 8 * 8 * per
    hi[3] = 17
    assert int(pa.pages_held(lo, hi, n_pages, bs, np)) == 65 * per
    assert int(pa.pages_covered(lo, hi, n_pages, bs, np)) == 9 * 8 * per


@pytest.mark.parametrize('window', sorted(WINDOWS))
@pytest.mark.parametrize('lengths', sorted(LENGTHS))
def test_the_pair_list_is_the_same_on_numpy_and_in_the_program(lengths,
                                                               window):
    """Pair t -> (row, column block), from ``lo``/``hi`` alone: row after
    row, a row's blocks in ascending column order, the rows that see
    nothing stepped over, the fill past the list at row B; on numpy
    (the engine's count) as on jnp (the program's loop)."""
    lo, hi = _bounds(lengths, WINDOWS[window])
    block, n_blocks = COLS, CAP // COLS
    want = [(r, j) for r in range(N) if hi[r] > lo[r]
            for j in range(lo[r] // block, (hi[r] - 1) // block + 1)]
    t = np.arange(N * n_blocks + pa.BLOCK_ROWS)
    got = {}
    for xp in (np, jnp):
        first, last, ends = pa.row_pairs(xp.asarray(lo), xp.asarray(hi),
                                         block, n_blocks, xp)
        row, col = pa.pairs_at(xp.asarray(t), last, ends, xp)
        got[xp] = np.asarray(row), np.asarray(col), np.asarray(first)
        assert int(ends[-1]) == len(want)
    for a, b in zip(got[np], got[jnp]):
        assert np.array_equal(a, b)
    row, col, first = got[np]
    assert list(zip(row[:len(want)], col[:len(want)])) == want
    assert (row[len(want):] == N).all()
    # a pair opens its row at the row's first block
    opens = [j == first[r] for r, j in want]
    assert opens == [i == 0 or want[i - 1][0] != r
                     for i, (r, _) in enumerate(want)]


@pytest.mark.parametrize('window', sorted(WINDOWS))
@pytest.mark.parametrize('lengths', ['mixed_with_dead_rows',
                                     'short_beside_full'])
def test_chosen_columns_equal_a_dense_softmax_over_them(lengths, window):
    """``chosen`` narrows each row's columns within its bounds (a learned
    selection): per-head K/V, float32, against a dense softmax over the
    chosen and seen columns; a row whose choice is empty yields 0."""
    q, k, v, tables, hi, lo, _ = _case('plain_f32_d64', lengths,
                                       WINDOWS[window])
    rng = np.random.RandomState(7)
    chosen = rng.rand(N, CAP) < 0.4
    chosen[5] = False                            # a live row, nothing chosen
    got = np.asarray(pa.paged_attention_blocked(
        q, k, v, tables, hi, layer=1, lo=lo, block_cols=COLS,
        chosen=jnp.asarray(chosen)))
    h, _, d, _ = LAYOUTS['plain_f32_d64']
    cols = np.arange(CAP)
    lo = np.zeros((N,), 'int32') if lo is None else np.asarray(lo)
    clipped = np.clip(np.asarray(tables), 0, NB - 1)
    for r in range(N):
        see = chosen[r] & (cols >= lo[r]) & (cols < int(hi[r]))
        if not see.any():
            assert not got[r].any()
            continue
        keys = np.asarray(k)[1][clipped[r]].reshape(CAP, h, d)[see]
        vals = np.asarray(v)[1][clipped[r]].reshape(CAP, h, d)[see]
        sc = np.einsum('hd,khd->hk', np.asarray(q)[r], keys) * d ** -0.5
        w = np.exp(sc - sc.max(axis=1, keepdims=True))
        want = np.einsum('hk,khd->hd', w / w.sum(axis=1, keepdims=True),
                         vals)
        np.testing.assert_allclose(got[r], want, atol=2e-5, rtol=2e-5)
