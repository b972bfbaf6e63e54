"""The latent decode attention as the TPU lowers it: the kernel of
``ops/pallas/paged_decode_attention.py``, interpreted here on the CPU at a
toy geometry, held to the loop over the pair list that every other
platform runs (``paged_attention_blocked``) and to the dense masked
oracle (``paged_attention_reference``): mixed lengths, a lower bound,
chosen columns, a static and a traced layer, holes in a table, a verify
step's rows that share a table; and a row's result is bitwise its own
whatever else the batch holds. Both forms are reached the way a program
reaches them, through ``paged_attention_blocked``'s choice by platform,
which the tests make for it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa

BS, COLS = 8, 32                 # a page; a column block: 4 pages
P = 240                          # a table: 60 column blocks
NB = 320                         # the pool
H, RANK, WIDTH = 4, 16, 32       # heads; c_kv; the row [c_kv ; k_rope ; 0]
CAP = P * BS

# name -> attended lengths (0: an empty slot). A row of one column, rows
# that end on a block's edge and one past it, empty slots between live
# rows, and short rows beside a row 60 blocks long.
LENGTHS = {
    'mixed': [5, 33, 0, 96, 31, 32, 0, 0, 64, 65, 1, 17, 0, 90],
    'beside_60_blocks': [1, CAP, 0, 40, 32],
    'one_column_alone': [0, 0, 1, 0],
    'nothing_live': [0, 0, 0],
    'one_block_each': list(range(1, 19)),
}
WINDOWS = {'no_lo': 0, 'lo_inside_first_block': 80, 'lo_past_first_block': 24}


@pytest.fixture
def form(monkeypatch):
    """``form('kernel' | 'loop')``: the platform's choice made here, and
    the kernel interpreted."""
    monkeypatch.setenv('PADDLE_TPU_PALLAS_INTERPRET', '1')

    def choose(which):
        monkeypatch.setattr(
            jax.lax, 'platform_dependent',
            lambda *args, tpu, default: (
                tpu if which == 'kernel' else default)(*args))
    return choose


def _case(lengths, window, dtype, seed=0):
    rng = np.random.RandomState(seed)
    hi = np.asarray(lengths, 'int32')
    n = len(hi)
    lo = np.maximum(hi - window, 0).astype('int32') if window else None
    q = jnp.asarray(rng.randn(n, H, WIDTH), jnp.float32)
    rows = rng.randn(2, NB, BS, WIDTH)
    rows[..., 24:] = 0.0                     # the spare columns of a row
    arena = jnp.asarray(rows, dtype)
    # each row its own pages where it has any; the rest "no page"
    tables = np.full((n, P), NB, 'int32')
    free = rng.permutation(NB)
    for i in range(n):
        owned = -(-int(hi[i]) // BS)
        tables[i, :owned], free = free[:owned], free[owned:]
    return q, arena, jnp.asarray(tables), jnp.asarray(hi), \
        None if lo is None else jnp.asarray(lo)


def _attend(q, arena, tables, hi, lo=None, layer=1, chosen=None):
    return np.asarray(pa.paged_attention_blocked(
        q, arena, None, tables, hi, layer=layer, lo=lo, block_cols=COLS,
        latent=RANK, chosen=chosen))


def _oracle(q, arena, tables, hi, lo=None, layer=1):
    """Every head reads the one row: keys the whole row, values its
    first RANK columns."""
    return np.asarray(pa.paged_attention_reference(
        q, arena, arena, tables, hi, layer=layer, lo=lo))[..., :RANK]


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('window', sorted(WINDOWS))
@pytest.mark.parametrize('lengths', sorted(LENGTHS))
def test_the_kernel_equals_the_loop_and_the_oracle(form, lengths, window,
                                                   dtype):
    case = _case(LENGTHS[lengths], WINDOWS[window], dtype)
    form('loop')
    by_loop = _attend(*case)
    form('kernel')
    by_kernel = _attend(*case)
    assert by_kernel.dtype == np.float32
    assert by_kernel.shape == (len(LENGTHS[lengths]), H, RANK)
    # the same arithmetic in the same order: float32 scores and state
    tol = 2e-2 if dtype == 'bfloat16' else 2e-5
    np.testing.assert_allclose(by_kernel, by_loop, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(by_kernel, _oracle(*case), atol=tol, rtol=tol)
    dead = np.asarray(case[3]) == 0
    assert not by_kernel[dead].any()         # holds no pair, yields 0


@pytest.mark.parametrize('window', sorted(WINDOWS))
@pytest.mark.parametrize('lengths', ['mixed', 'beside_60_blocks'])
def test_chosen_columns_contribute_and_no_others(form, lengths, window):
    q, arena, tables, hi, lo = _case(LENGTHS[lengths], WINDOWS[window],
                                     'float32')
    n = len(LENGTHS[lengths])
    chosen = np.random.RandomState(7).rand(n, CAP) < 0.4
    chosen[1] = False                        # a live row, nothing chosen
    chosen[3, :COLS] = False                 # a whole block of a row left out
    form('loop')
    by_loop = _attend(q, arena, tables, hi, lo, chosen=jnp.asarray(chosen))
    form('kernel')
    by_kernel = _attend(q, arena, tables, hi, lo, chosen=jnp.asarray(chosen))
    np.testing.assert_allclose(by_kernel, by_loop, atol=1e-6, rtol=1e-6)
    assert not by_kernel[1].any()
    # against a dense softmax over the chosen and seen columns
    cols = np.arange(CAP)
    lo = np.zeros((n,), 'int32') if lo is None else np.asarray(lo)
    clipped = np.clip(np.asarray(tables), 0, NB - 1)
    for r in range(n):
        see = chosen[r] & (cols >= lo[r]) & (cols < int(hi[r]))
        if not see.any():
            assert not by_kernel[r].any()
            continue
        rows = np.asarray(arena)[1][clipped[r]].reshape(CAP, WIDTH)[see]
        sc = np.asarray(q)[r] @ rows.T * WIDTH ** -0.5
        w = np.exp(sc - sc.max(axis=1, keepdims=True))
        want = (w / w.sum(axis=1, keepdims=True)) @ rows[:, :RANK]
        np.testing.assert_allclose(by_kernel[r], want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('window', sorted(WINDOWS))
def test_a_row_alone_and_among_others_gives_the_same_bits(form, window):
    """Concurrent equals one at a time: a row's state is its own from
    its first pair to its last, whoever's pairs come before and after."""
    form('kernel')
    q, arena, tables, hi, lo = _case(LENGTHS['mixed'], WINDOWS[window],
                                     'bfloat16')
    among = _attend(q, arena, tables, hi, lo)
    for i in (1, 3, 8, 10, 13):
        only = np.zeros((len(hi),), bool)
        only[i] = True
        alone = _attend(q, arena, jnp.where(only[:, None], tables, NB),
                        jnp.where(only, hi, 0), lo)
        assert np.array_equal(alone[i], among[i]), i
        assert not alone[~only].any()


def test_a_static_and_a_traced_layer_are_one_kernel(form, monkeypatch):
    """A lead layer hands ``layer`` as a Python int, a scanned one as a
    traced scalar: the same bits, and the kernel's body traced once for
    both (the layer is an operand)."""
    from paddle_tpu.ops.pallas import paged_decode_attention as kernel
    form('kernel')
    q, arena, tables, hi, _ = _case(LENGTHS['mixed'], 0, 'bfloat16')
    traces = []
    body = kernel._kernel
    monkeypatch.setattr(kernel, '_kernel', lambda *refs, **static: (
        traces.append(static), body(*refs, **static))[1])
    kernel._pair_attention.clear_cache()
    static = [_attend(q, arena, tables, hi, layer=at) for at in (0, 1)]
    traced = jax.jit(lambda at: pa.paged_attention_blocked(
        q, arena, None, tables, hi, layer=at, block_cols=COLS,
        latent=RANK))
    for at in (0, 1):
        assert np.array_equal(np.asarray(traced(jnp.int32(at))), static[at])
    kernel._pair_attention.clear_cache()
    assert not np.array_equal(static[0], static[1])
    assert len(traces) == 1


def test_holes_in_a_table_below_lo_change_nothing(form):
    """Pages given back behind a window point past the pool: blocks
    wholly below ``lo`` are never read, and in the block that holds
    ``lo`` a hole is read clipped to a real page and contributes 0."""
    form('kernel')
    q, arena, tables, hi, lo = _case(LENGTHS['mixed'], 24, 'bfloat16')
    whole = _attend(q, arena, tables, hi, lo)
    holes = np.asarray(tables).copy()
    for r in range(len(hi)):
        holes[r, :int(lo[r]) // BS] = NB
    assert (holes != np.asarray(tables)).any()
    assert np.array_equal(_attend(q, arena, jnp.asarray(holes), hi, lo),
                          whole)


def test_a_verify_steps_rows_share_a_table(form):
    """Spec verify: K1 rows a sequence through the one table, row j at
    its own length. Each equals the decode row of that length."""
    form('kernel')
    k1 = 3
    _, arena, tables, hi, _ = _case([29, 0, 62, 95], 0, 'bfloat16')
    q = jnp.asarray(np.random.RandomState(5).randn(len(hi) * k1, H, WIDTH),
                    jnp.float32)
    lens = jnp.where(hi[:, None] > 0, hi[:, None] + jnp.arange(k1),
                     0).reshape(-1)
    tables = jnp.repeat(tables, k1, axis=0)
    # the sequences own the pages their longest row reaches
    own = np.asarray(tables).copy()
    own[3 * k1:, 95 // BS] = NB - 1
    own[2 * k1:3 * k1, 62 // BS:64 // BS + 1] = [NB - 2, NB - 3]
    tables = jnp.asarray(own)
    together = _attend(q, arena, tables, lens)
    np.testing.assert_allclose(together, _oracle(q, arena, tables, lens),
                               atol=2e-2, rtol=2e-2)
    for r in (0, 1, 2, 7, 11):
        only = np.arange(len(lens)) == r
        alone = _attend(q, arena, tables, jnp.where(only, lens, 0))
        assert np.array_equal(alone[r], together[r]), r
    form('loop')
    np.testing.assert_allclose(_attend(q, arena, tables, lens), together,
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize('platform,block', [
    ('tpu', 'latent_moe'), ('cpu', 'latent_moe'), ('tpu', 'transformer')])
def test_the_engine_counts_a_steps_pairs_under_the_form_that_runs_them(
        monkeypatch, platform, block):
    """``decode.attn_pairs{form}``: the two labels add up to the pairs
    the live rows hold, summed over the layers, all of them under the
    form the static rule gives the engine's platform and cache
    (``pairs_form``); with the kernel the pages read are the pages
    held."""
    from paddle_tpu import observe
    from paddle_tpu.core.place import CPUPlace
    from paddle_tpu.serving.decode import DecodeEngine, LMSpec
    from paddle_tpu.serving.decode.scheduler import Sequence
    if block == 'latent_moe':
        spec = LMSpec(
            vocab_size=64, n_layer=3, d_model=32, d_inner=24, block=block,
            layer_types=['full_attention'] * 3,
            latent={'full_attention': dict(
                n_head=4, q_rank=16, kv_rank=12, d_nope=8, d_rope=8, d_v=8,
                rope_theta=100.0)},
            index_topk=0, n_experts=4, experts_per_token=2,
            n_shared_experts=1)
    else:
        spec = LMSpec(vocab_size=64, n_layer=3, n_head=2, d_key=8,
                      d_value=8, d_model=16, d_inner=32)
    rule = pa.pairs_form
    monkeypatch.setattr(pa, 'pairs_form',
                        lambda _, *rest: rule(platform, *rest))
    observe.reset()
    observe.enable()
    try:
        # tables of 48 pages of 32: three column blocks of 16 pages
        eng = DecodeEngine(spec, max_batch=6, block_size=32, num_blocks=64,
                           pages_per_seq=48, place=CPUPlace())
        batch = []
        for i, length in enumerate((5, 600, 1500, 512)):
            seq = Sequence(i + 1, [7] * length, 4, 0.0, i, None)
            seq.cache_len = length
            batch.append(seq)
        eng._step_feeds(batch, 1)
        counters = observe.snapshot()['counters']
        eng.shutdown(drain=False)
    finally:
        observe.disable()
        observe.reset()
    pairs = 3 * (1 + 2 + 3 + 2)          # a row sees its new token too
    kernel = platform == 'tpu' and block == 'latent_moe'
    assert counters['decode.attn_pairs{form=kernel}'] == \
        (pairs if kernel else 0)
    assert counters['decode.attn_pairs{form=loop}'] == \
        (0 if kernel else pairs)
    assert counters['decode.attn_pages_held'] == pairs * 16
    assert counters['decode.attn_pages_read'] == \
        (pairs if kernel else 3 * 8) * 16
