"""The latent_moe block (LMSpec block='latent_moe': dots3_note) against
its plain reference, at a tiny size on the CPU in float32: a leading
dense full layer, then (full, sliding, sliding, sliding); 4 heads over a
rank-12 latent in the full layers and 2 over a rank-20 one in the
sliding ones; an indexer of 3 heads that keeps 8 positions; window 5;
8 experts of which 4 are held, 3 per token, one shared. Every sequence
runs past the 8 selected positions and the window.

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU; they differ in the order of their sums (in a decode
step and a short chunk the block folds the key up-projection into the
query and applies the value up-projection to the weighted sum of
latents, in a chunk of more rows it expands keys and values a column
block at a time under a running softmax; the reference expands them
head by head over the whole sequence), which at these widths gives
differences of a few 1e-6 on logits of order 1. 5e-5 leaves a margin,
and is two orders and more under what bfloat16 state, a dropped gate, a
dropped rescale or an unapplied selection gives (checked below by
breaking each)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.reference import dots3_note as ref
from paddle_tpu.ops import latent_moe_ops as lmo
from paddle_tpu.ops import moe_held_ops as moe
from paddle_tpu.ops import paged_decode_ops as pdo
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
import block_harness
from block_harness import Driver
from util import (as_held, cell_spec, heads_of_held, platform_forms,
                  weights_round_trip)

TOL = 5e-5
BS, PAGES, NB = 4, 12, 40            # 48 positions a sequence
F, S = lm.FULL, lm.SLIDING


def _spec(**over):
    kw = dict(
        vocab_size=64, n_layer=5, d_model=32, d_inner=24,
        block='latent_moe', layer_types=[F, F, S, S, S], sliding_window=5,
        latent={F: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8,
                        d_rope=4, d_v=8, rope_theta=8e7),
                S: dict(n_head=2, q_rank=16, kv_rank=20, d_nope=12,
                        d_rope=4, d_v=8, rope_theta=5e4)},
        dense_layers=1, d_inner_dense=40, index_n_heads=3,
        index_head_dim=8, index_topk=8, n_experts=8, experts_held=4,
        first_expert=2, experts_per_token=3, n_shared_experts=1)
    kw.update(over)
    return LMSpec(**kw)


SPEC = _spec()
WEIGHTS = random_weights(SPEC, seed=5)


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, pages=PAGES)


def _reference_logits(tokens, **lowered):
    return DRIVER.reference_logits(ref, tokens, **lowered)


# ------------------------------------------------------ spec and caches
def test_layer_plan_of_the_published_list():
    """1 leading dense full layer, 11 periods of (full, sliding x 3) and
    one full layer over: the loop is written for the 46, of which the
    cut runs the first five."""
    types = [F] + [F, S, S, S] * 11 + [F]
    spec = _spec(n_layer=46, layer_types=types)
    assert spec.layer_plan() == ((F,), (F, S, S, S), 11, (F,))
    assert SPEC.layer_plan() == ((F,), (F, S, S, S), 1, ())
    assert len(spec.layers_of(F)) == 13 and len(spec.layers_of(S)) == 33
    kinds = {k.name: k for k in spec.cache_kinds()}
    assert kinds['lm_latent_full'].layers == spec.layers_of(F)
    assert kinds['lm_latent_sliding'].layers == spec.layers_of(S)


def test_one_place_for_a_tokens_cache_bytes():
    """Three arenas under one table: a token costs the full layers'
    latent row and index key and the sliding layers' latent row, and
    every function of a token's bytes reads the same list."""
    kinds = SPEC.cache_kinds()
    assert [(k.name, k.slot, k.layers, k.width) for k in kinds] == [
        ('lm_latent_full', 'LatentFull', (0, 1), 16),
        ('lm_index_full', 'IndexFull', (0, 1), 8),
        ('lm_latent_sliding', 'LatentSliding', (2, 3, 4), 24)]
    per_token = 2 * 16 + 2 * 8 + 3 * 24
    assert lm.kv_bytes_per_token(SPEC) == per_token * 4
    assert lm.kv_bytes_per_token(SPEC, 'bfloat16') == per_token * 2
    assert lm.kv_bytes_per_kind(SPEC, 'bfloat16') == {
        'lm_latent_full': 64, 'lm_index_full': 32, 'lm_latent_sliding': 144}
    assert lm.kv_page_bytes(SPEC, BS) == per_token * 4 * BS
    assert lm.arena_bytes(SPEC, NB, BS) == per_token * 4 * BS * NB
    assert lm.num_blocks_for_budget(
        lm.arena_bytes(SPEC, NB, BS), SPEC, BS) == NB
    # the published widths: 9,344 B a token in bfloat16 over the 5 layers,
    # stored in whole lane tiles (576 -> 640, 1,088 -> 1,152): 9,984 B
    big = _spec(latent={
        F: dict(n_head=128, q_rank=1024, kv_rank=512, d_nope=128, d_rope=64,
                d_v=128, rope_theta=8e7),
        S: dict(n_head=64, q_rank=1024, kv_rank=1024, d_nope=192, d_rope=64,
                d_v=128, rope_theta=5e4)}, index_head_dim=128)
    assert sum(len(k.layers) * k.width * 2 for k in big.cache_kinds()) \
        == 9344
    assert [k.stored for k in big.cache_kinds()] == [640, 128, 1152]
    assert lm.kv_bytes_per_token(big, 'bfloat16') == 9984
    # the blocks of K and V rows declare theirs through the same function
    old = LMSpec(vocab_size=8, n_layer=3, n_head=4, d_key=8, d_value=8)
    assert [(k.name, k.layers, k.width) for k in old.cache_kinds()] == [
        ('lm_kcache', (0, 1, 2), 32), ('lm_vcache', (0, 1, 2), 32)]


@pytest.mark.parametrize('bad', [
    dict(latent={F: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8,
                         d_rope=4, d_v=8, rope_theta=8e7)}),
    dict(index_n_heads=0), dict(dense_layers=6), dict(d_inner_dense=0)])
def test_spec_refuses_what_it_cannot_run(bad):
    with pytest.raises(ValueError):
        _spec(**bad)


# -------------------------------------------------------- the selection
@pytest.mark.parametrize('rows,cols,k', [(5, 40, 8), (3, 64, 1), (4, 8, 8),
                                         (2, 33, 32)])
def test_selection_is_the_dense_top_k(rows, cols, k):
    """The k largest of each row, found by counting: the set lax.top_k
    indexes, with -inf rows' tails, fewer than k candidates, and ties
    (taken from the left, as top_k takes them)."""
    rng = np.random.RandomState(rows * cols + k)
    scores = rng.randn(rows, cols).astype('float32')
    scores[0, cols // 2:] = -np.inf                # fewer candidates
    scores[1, :] = np.round(scores[1, :])          # many ties
    scores[-1, 3:9] = scores[-1, 3]                # ties at the boundary?
    got = np.asarray(lmo.select_topk(jnp.asarray(scores), k))
    if cols <= k:
        assert got.all()
        return
    _, at = jax.lax.top_k(jnp.asarray(scores), k)
    want = np.zeros((rows, cols), bool)
    want[np.arange(rows)[:, None], np.asarray(at)] = True
    assert np.array_equal(got, want)
    assert (got.sum(axis=1) == k).all()


# name -> (columns, k): column blocks of whole lane tiles, 9 and 16 to a
# block; columns that are no lane tile, in one block
SELECTIONS = {'two_blocks_of_9_lane_tiles': (2304, 300),
              'five_blocks_of_16': (10240, 2048),
              'no_lane_tile': (200, 24)}


def _rows_under_lengths(columns, k, seed):
    """(scores [24, columns], lens [24]): a tile of rows of length 0,
    under k, exactly k, one over, with ties at the k-th value, with a
    -inf tail inside its length, the whole extent, and many ties; a
    tile none of whose rows is live; a tile of live rows none of which
    holds more than k."""
    rng = np.random.RandomState(seed)
    scores = rng.randn(24, columns).astype('float32')
    long = max(2 * k + k // 2, min(columns, 3 * k))
    lens = np.zeros(24, 'int32')
    lens[:8] = [0, k // 3, k, k + 1, long, long, columns, columns - 7]
    scores[4, 0:long:2] = 0.25            # the k-th value is one of these
    assert np.sort(scores[4, :long])[-k] == 0.25
    scores[5, k // 2:] = -np.inf          # fewer than k finite candidates
    scores[7] = np.round(scores[7])
    lens[16:] = rng.randint(0, k + 1, 8)
    lens[16] = k
    # what lies at and past a row's length is the score buffer's -inf
    scores[np.arange(columns)[None, :] >= lens[:, None]] = -np.inf
    return scores, lens


@pytest.mark.parametrize('form', ['dense', 'kernel'])
@pytest.mark.parametrize('case', sorted(SELECTIONS))
def test_selection_under_lengths_is_the_top_k_of_what_a_row_holds(
        monkeypatch, form, case):
    """The choice of rows of every kind of length in one batch, through
    the dense form and through the kernel's row tiles: of a row that
    holds more than k positions the set lax.top_k indexes among them
    (ties to the lower column, -inf a value like another), of any other
    row every position it holds, and nothing at or past a length; and
    the two forms return the same k-th key and the same last tie."""
    from paddle_tpu.ops.pallas import selection_kth
    columns, k = SELECTIONS[case]
    scores, lens = _rows_under_lengths(columns, k, seed=columns)
    platform_forms(monkeypatch, 'tpu' if form == 'kernel' else 'default')
    got = np.asarray(lmo.select_topk(jnp.asarray(scores), k,
                                     jnp.asarray(lens)))
    want = np.zeros_like(got)
    for r, held in enumerate(lens):
        if held <= k:
            want[r, :held] = True
        else:
            _, at = jax.lax.top_k(jnp.asarray(scores[r, :held]), k)
            want[r, np.asarray(at)] = True
    assert np.array_equal(got, want)
    assert np.array_equal(got.sum(axis=1), np.minimum(lens, k))
    if form == 'kernel':
        dense = lmo.kth_and_cut_dense(jnp.asarray(scores),
                                      jnp.asarray(lens), k)
        kernel = selection_kth.kth_and_cut(jnp.asarray(scores),
                                           jnp.asarray(lens), k=k)
        for a, b in zip(kernel, dense):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    # the counting runs over the first tile's blocks alone
    block = selection_kth.block_columns(columns)
    assert selection_kth.columns_counted(lens, k, columns, np) == \
        8 * -(-columns // block) * block


def _scores_of_every_table(q, w, arena, layer, tables, lens, per):
    """The many-table scores as they were made before the pair list:
    column block after column block to the longest length, every
    table's keys gathered for each and every row scored against its
    own."""
    n, bk = q.shape[0], per * arena.shape[2]
    tables = jnp.clip(tables, 0, arena.shape[1] - 1)

    def block(j, out):
        at = jax.lax.dynamic_slice_in_dim(tables, j * per, per, -1)
        dots = jnp.einsum('nhd,nkd->nhk', q,
                          arena[layer, at].reshape(n, bk, -1),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        got = jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)
        return jax.lax.dynamic_update_slice(out, got, (0, j * bk))
    out = jax.lax.fori_loop(
        0, (jnp.max(lens) + bk - 1) // bk, block,
        jnp.full((n, tables.shape[1] * arena.shape[2]), -jnp.inf))
    return jnp.where(jnp.arange(out.shape[1])[None, :] < lens[:, None],
                     out, -jnp.inf)


@pytest.mark.parametrize('lens', [
    [0, 48, 0, 0, 3, 0, 17, 8, 0, 9],          # dead rows between live ones
    [1, 0, 47],                                # very different lengths
    [0, 0, 0, 0],                              # nothing live
    [12, 11, 13, 24, 25, 36, 5, 16, 40, 48, 2, 1, 30],   # two iterations
])
@pytest.mark.parametrize('per', [1, 3])
def test_index_scores_of_many_tables_run_the_live_rows_pairs(lens, per):
    """Many tables through the (row, column block) pair list: a live
    row's scores are the dense form's over its own columns, whatever
    rows lie between and however long its neighbours are, and -inf
    stands at and past every length and all along a dead row, whose
    table names no page."""
    rng = np.random.RandomState(len(lens))
    heads, width, n = 3, 8, len(lens)
    arena = jnp.asarray(rng.randn(2, NB, BS, width), jnp.float32)
    q = jnp.asarray(rng.randn(n, heads, width), jnp.float32)
    w = jnp.asarray(rng.randn(n, heads), jnp.float32)
    tables = np.full((n, PAGES), NB, np.int32)
    for i, held in enumerate(lens):
        need = -(-held // BS)
        tables[i, :need] = rng.permutation(NB)[:need]
    lens = jnp.asarray(lens, jnp.int32)
    got = np.asarray(lmo.index_scores(q, w, arena, 1, jnp.asarray(tables),
                                      lens, per))
    want = np.asarray(_scores_of_every_table(
        q, w, arena, 1, jnp.asarray(tables), lens, per))
    assert np.array_equal(got, want)


def test_index_scores_of_both_table_forms_are_the_reference():
    """The indexer's scores over cached keys, through many tables (one
    query each) and through one table (many queries): the reference's
    dense I(t, s) with -inf at and past each row's length."""
    rng = np.random.RandomState(9)
    heads, width, n = 3, 8, 6
    arena = jnp.asarray(rng.randn(2, NB, BS, width), jnp.float32)
    q = jnp.asarray(rng.randn(n, heads, width), jnp.float32)
    w = jnp.asarray(rng.randn(n, heads), jnp.float32)
    table = jnp.asarray(rng.permutation(NB)[:PAGES], jnp.int32)
    keys = np.asarray(arena)[1][np.asarray(table)].reshape(-1, width)
    first = 17
    lens = first + 1 + jnp.arange(n, dtype=jnp.int32)
    want = np.asarray(ref.index_scores(q, w, jnp.asarray(keys), first,
                                       'float32'))
    one = lmo.index_scores(q, w, arena, 1, table, lens, 2)
    many = lmo.index_scores(q, w, arena, 1, jnp.tile(table, (n, 1)), lens, 3)
    for got in (one, many):
        got = np.asarray(got)
        assert np.array_equal(np.isinf(got), np.isinf(want))
        np.testing.assert_allclose(got[~np.isinf(got)],
                                   want[~np.isinf(want)], atol=1e-5)


# ------------------------------------------------- the router's bias
def test_router_bias_chooses_and_does_not_weigh():
    """``noaux_tc``: the bias is added for the choosing only; the
    weights are the chosen experts' own sigmoids, normalised."""
    x = jnp.asarray([[1.0, 0.0]])
    router = jnp.asarray([[0.0, 1.0, -1.0, 2.0], [0.0, 0.0, 0.0, 0.0]])
    bias = jnp.asarray([0.0, 0.0, 5.0, -5.0])
    chosen, weight = moe.route_sigmoid_topk(x, router, 2, bias=bias)
    assert chosen.tolist() == [[2, 1]]          # without it: [3, 1]
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    want = np.asarray([sig(-1.0), sig(1.0)])
    np.testing.assert_allclose(np.asarray(weight)[0], want / want.sum(),
                               rtol=1e-6)
    got = ref.route(x, router, bias, 2)
    assert np.array_equal(np.asarray(got[0]), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(weight),
                               rtol=1e-6)
    # and the drawn bias changes choices at these widths
    rng = np.random.RandomState(0)
    n = jnp.asarray(rng.randn(64, SPEC.d_model), jnp.float32)
    with_b, _ = moe.route_sigmoid_topk(
        n, WEIGHTS['lm_moe_router.w'][0], 3, bias=WEIGHTS['lm_moe_router.b'][0])
    without, _ = moe.route_sigmoid_topk(n, WEIGHTS['lm_moe_router.w'][0], 3)
    assert not np.array_equal(np.sort(np.asarray(with_b), 1),
                              np.sort(np.asarray(without), 1))


# ----------------------------------------------------- shares add up
def test_shares_add_up_to_the_uncut_layer():
    """The eight shares of a routed layer (8 experts, one a share), the
    shared expert counted once, are the uncut layer's FFN: in the
    reference, and between the block's product and the reference.
    Attention, indexer and router are replicated: a share's are the
    uncut model's own arrays."""
    n, _, arch, uncut, _, cut = block_harness.shares_of_one_expert_add_up(
        ref, _spec, 2, TOL)
    assert np.abs(np.asarray(ref.experts(n, cut(0), 2, arch, (0, 1)))
                  - uncut).max() > 1e-2


# ------------------------------------- prefill in chunks, then decode
@pytest.mark.parametrize('prompt_len,chunk', [(13, 8), (21, 16), (6, 8),
                                              (30, 30)])
def test_chunked_prefill_then_decode_matches_full_forward(prompt_len,
                                                          chunk):
    """A sequence that passes index_topk (8) and the window (5): its
    prompt prefilled in chunks (or in one) through the three arenas,
    then decoded a token at a time, row by row against the reference's
    one full forward."""
    for _, stats in block_harness.chunked_prefill_then_decode(
            DRIVER, ref, prompt_len, chunk, 12, TOL):
        assert np.asarray(stats).shape == (4, 4)     # the routed layers


def test_the_published_order_with_periods_and_a_remainder():
    """Ten layers: one leading dense, two whole periods in the scan and
    a full layer over, each kind's stacks and arenas indexed at its own
    count: logits against the reference."""
    types = [F] + [F, S, S, S] * 2 + [F]
    spec = _spec(n_layer=10, layer_types=types)
    assert spec.layer_plan() == ((F,), (F, S, S, S), 2, (F,))
    # a driver of its own: another spec
    deep = Driver(spec, random_weights(spec, seed=2), BS, NB, pages=PAGES)
    rng = np.random.RandomState(2)
    tokens = rng.randint(0, spec.vocab_size, 22)
    want = deep.reference_logits(ref, tokens)
    table = jnp.arange(PAGES, dtype=jnp.int32)
    got, arenas, stats = deep.prefill_chunk(deep.arenas(), table,
                                            tokens[:16], 0)
    np.testing.assert_allclose(np.asarray(got), want[:16], atol=TOL)
    assert np.asarray(stats).shape == (9, 4)
    got, arenas, _ = deep.prefill_chunk(arenas, table, tokens[16:], 16)
    np.testing.assert_allclose(np.asarray(got), want[16:], atol=TOL)


@pytest.mark.parametrize('lengths', [[3, 9, 17, 30, 0], [5, 0, 20]])
def test_decode_batch_of_mixed_lengths_matches_reference(lengths):
    """Sequences of lengths on both sides of index_topk and the
    window in one decode batch, an empty slot (0) after them or between
    two of them: every live row's logits are the reference's for that
    sequence, and the statistics count the live rows of the four routed
    layers only."""
    rng = np.random.RandomState(7)
    seqs = [rng.randint(0, SPEC.vocab_size, n + 1) if n else None
            for n in lengths]
    _, stats = block_harness.decode_batch_of_mixed_lengths(
        DRIVER, ref, seqs, DRIVER.packed_tables(rng.permutation(NB), seqs),
        TOL)
    live = sum(s is not None for s in seqs)
    stats = np.asarray(stats)
    assert stats.shape == (4, 4)
    assert (stats[:, 0] <= 3 * live).all()
    assert (stats[:, 1] <= live).all()
    assert (stats[:, 2] <= SPEC.experts_held).all()


def test_a_row_alone_is_the_row_in_a_full_batch_bit_for_bit():
    """One sequence's decode step with every other slot empty, and the
    same sequence at another slot among three others: its logits bit
    for bit, its cache rows too."""
    rng = np.random.RandomState(11)
    lengths = [26, 9, 14, 31]
    seqs = [rng.randint(0, SPEC.vocab_size, n + 1) for n in lengths]
    arenas = DRIVER.arenas()
    tables = DRIVER.packed_tables(rng.permutation(NB), seqs)
    for seq, table in zip(seqs, tables):
        _, arenas, _ = DRIVER.prefill_chunk(arenas, table, seq[:-1], 0)
    among, _, _ = DRIVER.decode(arenas, tables, [s[-1] for s in seqs],
                                lengths)
    for slot in (0, 3):
        alone_tables = np.full((4, PAGES), NB, np.int32)
        alone_tables[1] = tables[slot]
        tokens = np.zeros(4, np.int32)
        tokens[1] = seqs[slot][-1]
        lens = np.zeros(4, np.int32)
        lens[1] = lengths[slot]
        alone, _, _ = DRIVER.decode(arenas, alone_tables, tokens, lens)
        assert np.array_equal(np.asarray(alone)[1], np.asarray(among)[slot])


def test_padded_chunk_rows_write_nothing():
    """A chunk padded to its bucket: the rows past ``length`` leave all
    three arenas as they were, and the real rows' logits do not move."""
    block_harness.padded_chunk_rows_write_nothing(DRIVER, TOL)


# --------------------------------- the absorbed against the expanded form
@pytest.mark.parametrize('pad,lens', [
    (0, [1, 7, 19, 33, 48, 0]),
    # the row stored wider than it is (whole lane tiles on the chip),
    # what lies past it never read into a score; 17 pairs of 8 columns
    # in rows of 1 to 6, so rows straddle the iterations of 8
    (8, [48, 0, 3, 30, 17, 24]),
])
def test_absorbed_attention_is_the_expanded_attention(pad, lens):
    """The latent form of the paged attention (keys the cached row,
    values its first r columns, the key up-projection folded into the
    query and the value up-projection applied to the sum) against keys
    and values expanded from the same rows, head by head, under a dense
    masked softmax with a chosen subset of columns."""
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_attention_blocked
    rng = np.random.RandomState(5)
    heads, rank, d_nope, d_rope, d_v, rows = 4, 12, 8, 4, 8, 6
    arena = jnp.asarray(rng.randn(2, NB, BS, rank + d_rope + pad),
                        jnp.float32)
    w_bk = rng.randn(heads, d_nope, rank).astype('float32')
    w_bv = rng.randn(heads, rank, d_v).astype('float32')
    q_nope = rng.randn(rows, heads, d_nope).astype('float32')
    q_rope = rng.randn(rows, heads, d_rope).astype('float32')
    tables = np.stack([rng.permutation(NB)[:PAGES] for _ in range(rows)])
    lens = np.asarray(lens, np.int32)
    chosen = rng.rand(rows, PAGES * BS) < 0.6
    chosen[np.arange(rows), np.maximum(lens - 1, 0)] = True
    q_row = np.concatenate([np.einsum('nhd,hdr->nhr', q_nope, w_bk),
                            q_rope, np.zeros((rows, heads, pad), 'f')], -1)
    scale = (d_nope + d_rope) ** -0.5
    mixed = paged_attention_blocked(
        jnp.asarray(q_row), arena, None, jnp.asarray(tables, jnp.int32),
        jnp.asarray(lens), sm_scale=scale, layer=1, latent=rank,
        chosen=jnp.asarray(chosen), block_cols=8)
    got = np.einsum('nhr,hrv->nhv', np.asarray(mixed), w_bv)
    for r in range(rows):
        cached = np.asarray(arena)[1][tables[r]].reshape(
            -1, rank + d_rope + pad)
        see = chosen[r] & (np.arange(PAGES * BS) < lens[r])
        if not see.any():
            assert not got[r].any()
            continue
        c_kv, k_rope = cached[see, :rank], cached[see, rank:rank + d_rope]
        for h in range(heads):
            keys, values = c_kv @ w_bk[h].T, c_kv @ w_bv[h]
            sc = (keys @ q_nope[r, h] + k_rope @ q_rope[r, h]) * scale
            p = np.exp(sc - sc.max())
            np.testing.assert_allclose(got[r, h], (p / p.sum()) @ values,
                                       atol=2e-5)


# ------------------------------------------------ what the tolerance is for
@pytest.mark.parametrize('broken', ['state', 'gate', 'rescale', 'select'])
def test_the_tolerance_catches_what_it_is_for(broken):
    """bfloat16 for the residual stream, scores, softmax and logits; a
    dropped headwise gate; a dropped rescale of the latents; a full
    layer that attends to all it holds: each moves the logits by far
    more than the tolerance the sound block is held to."""
    lowered = {'state': dict(state_dtype='bfloat16'),
               'gate': dict(gate=False), 'rescale': dict(rescale=False),
               'select': dict(select=False)}[broken]
    rng = np.random.RandomState(4)
    tokens = rng.randint(0, SPEC.vocab_size, 30)
    sound = _reference_logits(tokens)
    moved = np.abs(_reference_logits(tokens, **lowered) - sound)
    assert moved.max() > 100 * TOL
    if broken == 'select':
        # the rows below index_topk are untouched: the selection is all
        assert moved[:SPEC.index_topk].max() < TOL
        assert moved[SPEC.index_topk:].max() > 100 * TOL


@pytest.mark.parametrize('broken', ['window', 'index_topk', 'first_expert',
                                    'lora_rescale'])
def test_the_tolerance_catches_a_wrong_block(broken):
    """And the block itself, broken: a window one key short, a
    selection one position short, the wrong experts held, no rescale."""
    over = {'window': dict(sliding_window=4), 'index_topk': dict(index_topk=7),
            'first_expert': dict(first_expert=3),
            'lora_rescale': dict(lora_rescale=False)}[broken]
    rng = np.random.RandomState(2)
    tokens = rng.randint(0, SPEC.vocab_size, 20)
    # a driver of its own: the broken spec over the sound weights
    wrong = Driver(_spec(**over), WEIGHTS, BS, NB, pages=PAGES)
    got, _, _ = wrong.prefill_chunk(
        wrong.arenas(), jnp.arange(PAGES, dtype=jnp.int32), tokens, 0)
    assert np.abs(np.asarray(got) - _reference_logits(tokens)).max() > 1e-3


# ------------------------------------------------------------ the engine
def _engine(spec=SPEC, **kw):
    kw.setdefault('max_batch', 4)
    kw.setdefault('block_size', BS)
    kw.setdefault('num_blocks', 64)
    kw.setdefault('pages_per_seq', PAGES)
    kw.setdefault('prefill_chunk', 8)
    kw.setdefault('min_prompt_bucket', 4)
    kw.setdefault('weights', WEIGHTS)
    kw.setdefault('place', fluid.CPUPlace())
    return DecodeEngine(spec, **kw)


def _requests(n=6, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, SPEC.vocab_size,
                         int(rng.randint(9, 34))).tolist(),
             int(rng.randint(3, 12))) for _ in range(n)]


@pytest.fixture(scope='module')
def engine():
    eng = _engine()
    eng.warmup()
    eng.start()
    yield eng
    eng.shutdown(drain=False)


def test_engine_serves_the_reference_tokens_batched_and_alone(engine):
    """Through DecodeEngine's normal path (scheduler, pool, chunked
    prefill, the one decode signature): every request's greedy tokens
    are the reference's own choices, and the same served concurrently
    and one at a time."""
    requests = _requests()
    streams = [engine.submit(p, max_new_tokens=n) for p, n in requests]
    together = [s.result(300) for s in streams]
    arch, held = ref.arch_of(SPEC), ref.held_of(SPEC)
    for (prompt, n), tokens in zip(requests, together):
        assert len(tokens) == n
        gaps, _ = ref.token_gaps(WEIGHTS, arch, held, prompt, tokens, 8)
        assert max(gaps) <= TOL
    alone = [engine.generate(p, max_new_tokens=n, timeout=300)
             for p, n in requests[:3]]
    assert alone == together[:3]


def test_engine_counts_the_selection_and_the_cache_by_kind(engine):
    """The counters the benchmark reads: positions the full layers'
    rows hold and those the selection keeps, rows past index_topk, the
    bytes a step's attention must read by kind, the bytes a token costs
    by kind, and the router statistics of the four routed layers."""
    from paddle_tpu import observe
    prompt = list(range(20))
    observe.enable()
    try:
        before = observe.snapshot()
        engine.generate(prompt, max_new_tokens=4, timeout=300)
        after = observe.snapshot()
    finally:
        observe.disable()
        observe.reset()

    def grown(name, kind=None):
        key = name if kind is None else '%s{kind=%s}' % (name, kind)

        def total(snap):
            return sum(v for k, v in snap['counters'].items()
                       if k == key or k.startswith(name + '{') and not kind)
        return total(after) - total(before)

    # three decode steps at lengths 20, 21, 22 (+1: the new token), two
    # full layers; the selection keeps 8 of them
    seen = sum(n + 1 for n in (20, 21, 22))
    assert grown('decode.sparse_positions_seen') == 2 * seen
    assert grown('decode.sparse_positions_selected') == 2 * 3 * 8
    assert grown('decode.sparse_rows') == 3
    assert grown('decode.sparse_rows_live') == 3
    item = 4
    assert grown('decode.cache_bytes_read', kind='lm_latent_full') == \
        2 * 3 * 8 * 16 * item
    assert grown('decode.cache_bytes_read', kind='lm_index_full') == \
        2 * seen * 8 * item
    assert grown('decode.cache_bytes_read', kind='lm_latent_sliding') == \
        3 * 3 * 5 * 24 * item
    assert grown('decode.moe_layer_steps') == 3 * 4
    assert engine.kv_bytes_per_token == lm.kv_bytes_per_token(SPEC)


@pytest.mark.parametrize('kw,error', [
    (dict(prefix_cache=True), NotImplementedError),
    (dict(spec_k=2), NotImplementedError),
    (dict(kv_dtype='int8'), NotImplementedError)])
def test_engine_refuses_what_has_no_test_for_this_block(kw, error):
    with pytest.raises(error):
        _engine(**kw)


def test_handoff_is_refused_for_a_cache_that_is_not_per_head_rows(engine):
    from paddle_tpu.serving import handoff
    with pytest.raises(handoff.CacheKindError):
        engine.read_pages([0])
    with pytest.raises(handoff.CacheKindError):
        engine.write_pages([0], {})
    with pytest.raises(handoff.CacheKindError):
        handoff._geometry_header(engine)


def test_programs_write_every_arena_in_place():
    """The decode step and a prefill chunk as the executor jits them,
    over a pool far larger than a block of the attention's gathers: no
    instruction of the compiled program materialises a layer of any of
    the three arenas."""
    eng = _engine(num_blocks=2048)
    try:
        block_harness.programs_write_arenas_in_place(eng)
    finally:
        eng.shutdown(drain=False)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_weights_go_in_and_come_out_in_the_declared_layout(dtype):
    """``q_b`` of both kinds and the indexer's ``idx_q`` are held ``[n,
    out, q_rank]`` (model.HeldTransposed) and loaded, exported and
    handed out on the device as declared, ``[n, q_rank, out]``, bit for
    bit; every other parameter is the array the programs read
    (util.weights_round_trip)."""
    weights_round_trip(
        _spec(dtype=dtype), WEIGHTS,
        {'lm_full_q_b.w', 'lm_swa_q_b.w', 'lm_full_idx_q.w'},
        max_batch=2, block_size=BS, num_blocks=NB, pages_per_seq=PAGES)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_a_product_with_a_held_matrix_is_the_declared_product(dtype):
    """``_mm_t`` over a matrix held ``[out, in]`` is ``_mm`` over the
    declared ``[in, out]``: rows at the weight's dtype, the same
    contraction, accumulated in float32 (to the order of the sum)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(7, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 48) / 4, dtype)
    got, want = pdo._mm_t(x, w.T), pdo._mm(x, w)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------- the heads taken from the stack as it is shaped
LATENT_CELLS = ['dots3_note.long_ctx_steady', 'kimi_k2_6.doc_qa_sessions',
                'longcat_flash_chat.chat_decode_heavy',
                'glm_5_2.long_ctx_long_answers']


def _sliced_then_split(c_q, stack, i, heads):
    """``_heads_at`` as the block wrote it before: the layer sliced out
    of the stack as it is held, the product's output split into heads."""
    return pdo._mm_t(c_q, lmo._at(stack, i)).reshape(c_q.shape[0], heads, -1)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('cell', LATENT_CELLS)
def test_heads_out_of_the_shaped_stack_are_the_split_product(
        cell, dtype, monkeypatch):
    """The four latent configurations at their rehearsal sizes, weights
    in float32 and in bfloat16. ``_heads_at`` shapes a stack held ``[n,
    heads x d, q_rank]`` to its heads before it takes the layer (so that
    the v5e reads the slice where it lies: tests/test_v5e_compile.py);
    that is ``_mm_t`` over the layer's slice with its output split into
    heads: the same operands at the same precision, equal to the order
    of the sum (this CPU sums a product over ``[heads, d, q_rank]`` and
    one over ``[heads x d, q_rank]`` in whatever order suits each: the
    indexer's three heads differ in the last bit, the others in none),
    for every marked stack of the configuration (``q_b`` of each kind,
    the indexer's ``idx_q``), every layer, the index an int or a traced
    scalar. And an engine whose block is written the one way serves the
    tokens of an engine whose block is written the other, through a
    prompt's chunks and its decode steps, and leaves the same arenas
    (float32 weights: this CPU has no bfloat16 product for a whole
    program, written either way)."""
    spec, sizes = cell_spec(cell, rehearsal=True, dtype=dtype)
    weights = random_weights(spec, seed=55)
    held = as_held(spec, {k: jnp.asarray(v, dtype)
                          for k, v in weights.items()})
    marked = sorted(lm.held_transposed(spec))
    assert marked
    rng = np.random.RandomState(5)
    forms = [jax.jit(f, static_argnums=3)
             for f in (lmo._heads_at, _sliced_then_split)]
    for name in marked:
        stack, heads = held[name], heads_of_held(spec, name)
        c_q = jnp.asarray(rng.randn(5, stack.shape[-1]), jnp.float32)
        for i in range(stack.shape[0]):
            want = forms[1](c_q, stack, i, heads)
            for index in (i, jnp.int32(i)):
                got = forms[0](c_q, stack, index, heads)
                assert got.dtype == want.dtype == jnp.float32
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    if dtype != 'float32':
        return
    prompt = rng.randint(0, spec.vocab_size,
                         sizes['prefill_chunk'] + 5).tolist()
    # the cell's rehearsal spec in the least engine that still runs the
    # prompt in chunks and four steps: one row, one sequence's pages,
    # chunks of the least bucket (one prefill program and the step)
    least = dict(sizes, max_batch=1, num_blocks=sizes['pages_per_seq'],
                 prefill_chunk=sizes['min_prompt_bucket'])
    served = []
    for form in (lmo._heads_at, _sliced_then_split):
        monkeypatch.setattr(lmo, '_heads_at', form)
        eng = DecodeEngine(spec, weights=weights, place=fluid.CPUPlace(),
                           **least)
        try:
            eng.start()
            tokens = eng.generate(prompt, max_new_tokens=4, timeout=300)
            with eng._arena_mu:
                arenas = [np.asarray(eng._scope.get(name))
                          for name in eng._progs.arena_names]
        finally:
            eng.shutdown(drain=False)
        served.append((tokens, arenas))
    (tokens, arenas), (theirs, their_arenas) = served
    assert len(tokens) == 4 and tokens == theirs
    assert any(a.any() for a in arenas)
    for a, b in zip(arenas, their_arenas):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
