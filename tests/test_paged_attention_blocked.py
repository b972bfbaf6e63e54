"""The XLA path of paged attention goes in blocks of rows and columns
(ops/pallas/paged_attention.py): ``paged_attention_blocked`` (many
tables, one query each: decode step, spec verify) and
``paged_attention_one_table`` (one table, many queries: prefill) over
one inner form. On the CPU at a toy geometry whose blocks are small
enough for every edge to be crossed:

- both forms against the dense masked oracle, over the head layouts and
  arena dtypes the serving programs run;
- a row alone and the same row among others give the same bits;
- the loop bounds and the engine's count of the pages they cover.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.quant import core as qcore

NB, BS, P = 64, 8, 12            # pool, page, table: 96 columns a table
COLS = 32                        # a column block: 4 pages, 3 blocks
N = 20                           # 3 row blocks of 8, the last part-filled
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
# row-block and column-block edges in its own way.
LENGTHS = {
    # every length class in one batch, dead rows in the middle, one row
    # at the table's full capacity, lengths on both sides of the column
    # edges 32 and 64
    'mixed_with_dead_rows': [5, 33, 0, CAP, 31, 32, 0, 0, 64, 65, 1, 17,
                             0, 90, 8, 40, 0, 63, 2, 70],
    # fewer live rows than a row block; the other blocks run nothing
    'three_live_rows': [0] * 7 + [50] + [0] * 6 + [3] + [0] * 4 + [CAP],
    # every row full: all 3 x 3 blocks run
    'all_at_capacity': [CAP] * N,
    # nothing is live
    'all_dead': [0] * N,
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


@pytest.mark.parametrize('lengths,window,blocks', [
    # 16 live rows -> 2 row blocks, the longer block to column 96 (3
    # blocks), the shorter (<= 32) one block
    ('mixed_with_dead_rows', 0, 3 + 1),
    # sorted: 96, 90, 70, 65, 64, 63, 40, 33 -> lo 72..9: blocks 0..2;
    # 32, 31, 17, 8, 5, 2, 1 and a dead row -> lo 8..0: block 0
    ('mixed_with_dead_rows', 24, 3 + 1),
    ('three_live_rows', 0, 3),
    ('three_live_rows', 24, 3),
    ('all_at_capacity', 0, 9),
    ('all_at_capacity', 24, 3),       # lo = 72: the last block only
    ('all_dead', 0, 0),
])
def test_pages_covered_counts_the_blocks_that_run(lengths, window, blocks):
    """The engine's ``decode.attn_pages_read`` comes from the function
    that bounds the program's loops, on numpy as on jnp."""
    hi = np.asarray(LENGTHS[lengths], 'int32')
    lo = np.maximum(hi - window, 0) if window else np.zeros_like(hi)
    per = pa.pages_per_block(P, BS, COLS)
    assert per == 4
    order, first, last = pa.row_blocks(lo, hi, per * BS, P // per, np)
    assert sorted(order.tolist()) == list(range(N))
    assert int((last - first + 1).sum()) == blocks
    # the default width holds the whole toy table: one block a live group
    live_groups = -(-int((hi > 0).sum()) // pa.BLOCK_ROWS)
    for xp in (np, jnp):
        assert int(pa.pages_covered(xp.asarray(lo), xp.asarray(hi), P, BS,
                                    xp)) == live_groups * pa.BLOCK_ROWS * P
