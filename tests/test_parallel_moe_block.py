"""The parallel_moe block (LMSpec block='parallel_moe': cohere2_moe)
against its plain reference, at a tiny size on the CPU in float32:
window 8, 4 query heads over 2 KV heads, 8 experts of which 4 are held,
3 per token, 2 shared, three sliding layers and a full one.

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU; they differ in the order of their sums (the block
sums the experts inside one product and pads its attention to the page
table's extent, the reference loops), which at these widths gives
differences of a few 1e-6 on logits of order 1. 2e-5 leaves a margin of
about five and is three orders under what a wrong mask, a missing
rotation or a misrouted expert gives (1e-2 and more, checked below by
breaking each)."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.reference import command_a_plus as ref
from paddle_tpu.ops import moe_held_ops as moe
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode.model import arena_bytes, kv_bytes_per_token
import block_harness
from block_harness import Driver

TOL = 2e-5
BS, PAGES, NB = 4, 10, 24            # 40 positions a sequence


def _spec(**over):
    kw = dict(vocab_size=64, n_layer=4, n_head=4, n_kv_head=2, d_key=8,
              d_value=8, d_model=16, d_inner=24, block='parallel_moe',
              layer_types=['sliding_attention'] * 3 + ['full_attention'],
              sliding_window=8, rope_theta=50000.0, n_experts=8,
              experts_held=4, first_expert=2, experts_per_token=3,
              n_shared_experts=2)
    kw.update(over)
    return LMSpec(**kw)


SPEC = _spec()
WEIGHTS = random_weights(SPEC, seed=5)


_arch, _held = ref.arch_of, ref.held_of


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, pages=PAGES)


def _reference_logits(tokens):
    return DRIVER.reference_logits(ref, tokens)


# ------------------------------------------------------------- the router
def test_router_hand_worked_case():
    """Scores are sigmoids of the row's products, the k largest are
    kept, and the weights are normalised over all that were kept."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
    router = jnp.asarray([[0.0, 1.0, -1.0, 2.0],
                          [1.0, 0.0, 0.5, -0.5]])
    chosen, weight = moe.route_sigmoid_topk(x, router, 2)
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    # row 0 scores experts by (0, 1, -1, 2): keeps 3 and 1
    # row 1 scores them by (2, 0, 1, -1): keeps 0 and 2
    assert chosen.tolist() == [[3, 1], [0, 2]]
    want = [[sig(2.0), sig(1.0)], [sig(2.0), sig(1.0)]]
    want = np.asarray(want) / np.sum(want, axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weight), want, rtol=1e-6)
    # the experts held are 1 and 2: row 0 gives 1 its weight, row 1
    # gives 2 its weight; normalisation was over the absent ones too
    gate, hit = moe.held_gates(chosen, weight, 1, 2)
    np.testing.assert_allclose(
        np.asarray(gate), [[want[0, 1], 0.0], [0.0, want[1, 1]]], rtol=1e-6)
    assert hit.tolist() == [[True, False], [False, True]]
    stats = moe.load_stats(hit, jnp.asarray([True, True]))
    assert stats.tolist() == [2, 1, 2, 2]
    assert moe.load_stats(hit, jnp.asarray([True, False])).tolist() == \
        [1, 1, 1, 1]


def test_reference_router_is_the_same_rule():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(9, 16), jnp.float32)
    router = jnp.asarray(rng.randn(16, 8), jnp.float32)
    got = moe.route_sigmoid_topk(x, router, 3)
    want = ref.route(x, router, 3)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-6)


# ----------------------------------------------------- shares add up
def test_shares_add_up_to_the_uncut_layer():
    """What every share of the experts gives, the shared experts counted
    once, is the whole layer's expert sum: in the reference, and between
    the block's product and the reference."""
    whole = _spec(experts_held=8, first_expert=0)
    w = random_weights(whole, seed=11)
    rng = np.random.RandomState(1)
    n = jnp.asarray(rng.randn(7, whole.d_model), jnp.float32)
    arch = _arch(whole)
    layer = 1
    uncut = np.asarray(ref.experts(n, w, layer, arch, (0, 8)))

    def cut(first, count):
        out = dict(w)
        for part in ('gate', 'up', 'down'):
            name = 'lm_stack_exp_%s.w' % part
            out[name] = w[name][:, first:first + count]
        return out

    n_shared = whole.n_shared_experts
    shared = sum(np.asarray(ref.expert(
        n, w['lm_stack_shr_gate.w'][layer, j],
        w['lm_stack_shr_up.w'][layer, j],
        w['lm_stack_shr_down.w'][layer, j])) for j in range(n_shared)) \
        / n_shared
    from_reference = shared.copy()
    from_block = shared.copy()
    for first in (0, 4):
        share = cut(first, 4)
        from_reference += np.asarray(
            ref.experts(n, share, layer, arch, (first, 4))) - shared
        chosen, weight = moe.route_sigmoid_topk(
            n, share['lm_stack_router.w'][layer], whole.experts_per_token)
        gate, _ = moe.held_gates(chosen, weight, first, 4)
        from_block += np.asarray(moe.gated_experts(
            n, gate, *(jnp.asarray(share['lm_stack_exp_%s.w' % p][layer])
                       for p in ('gate', 'up', 'down'))))
    np.testing.assert_allclose(from_reference, uncut, atol=TOL)
    np.testing.assert_allclose(from_block, uncut, atol=TOL)
    # and a share alone is not the layer
    assert np.abs(np.asarray(ref.experts(n, cut(0, 4), layer, arch,
                                         (0, 4))) - uncut).max() > 1e-2


# ------------------------------------- the routed product, by its oracle
E_HELD, FIRST, TOP_K, N_EXPERTS = 4, 2, 3, 8
# two layers of four held experts, 16 wide with 24 inside
_EXPERTS = tuple(
    (np.random.RandomState(3 + i).randn(2, E_HELD, a, b) * a ** -0.5)
    .astype('float32') for i, (a, b) in enumerate(((16, 24), (16, 24),
                                                    (24, 16))))


def _routing(case, n, rng):
    """(chosen [n, TOP_K] distinct experts of the 8, weight, valid)."""
    held = np.arange(FIRST, FIRST + E_HELD)
    away = np.setdiff1d(np.arange(N_EXPERTS), held)
    pool = {'worst': held, 'none': away}.get(case, np.arange(N_EXPERTS))
    chosen = np.stack([rng.permutation(pool)[:TOP_K] for _ in range(n)])
    weight = rng.rand(n, TOP_K).astype('float32') + 0.1
    weight /= weight.sum(axis=1, keepdims=True)
    valid = rng.rand(n) < 0.6 if case == 'dead' else np.ones(n, bool)
    return (jnp.asarray(chosen, jnp.int32), jnp.asarray(weight),
            jnp.asarray(valid))


def _routed(x, chosen, weight, valid, layer=1):
    gate, hit = moe.held_gates(chosen, weight, FIRST, E_HELD)
    out = moe.routed_experts(x, gate, hit, valid, min(TOP_K, E_HELD),
                             *(jnp.asarray(w) for w in _EXPERTS),
                             layer=jnp.int32(layer))
    return out, gate, hit


@pytest.mark.parametrize('rows', [32, 512])
@pytest.mark.parametrize('case', ['random', 'worst', 'none', 'dead'])
def test_grouped_product_is_the_masked_product(case, rows):
    """Rows grouped by the expert they chose (512: tiles of 128 of one
    expert; 32: all rows under each touched expert's gate column) give
    what the gate-masked product over every held expert gives, for
    random routings, for every row choosing as many held experts as it
    can, for none choosing any, and with rows that are not live, whose
    choices add nothing and touch nothing."""
    rng = np.random.RandomState(rows + len(case))
    x = jnp.asarray(rng.randn(rows, 16), jnp.float32)
    chosen, weight, valid = _routing(case, rows, rng)
    out, gate, hit = _routed(x, chosen, weight, valid)
    want = moe.gated_experts(
        x, jnp.where(valid[:, None], gate, 0.0),
        *(jnp.asarray(w[1]) for w in _EXPERTS))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL, rtol=0)
    live = np.asarray(hit) & np.asarray(valid)[:, None]
    stats = moe.load_stats(hit, valid).tolist()
    assert stats[0] == live.sum() and stats[2] == live.any(axis=0).sum()
    if case == 'worst':
        assert stats[0] == rows * min(TOP_K, E_HELD)
    if case == 'none':
        assert stats == [0, 0, 0, 0] and not np.asarray(out).any()
    if case == 'dead':
        dead = ~np.asarray(valid)
        assert dead.any() and np.asarray(hit)[dead].any()
        assert not np.asarray(out)[dead].any()


@pytest.mark.parametrize('rows', [32, 512])
def test_a_row_alone_is_the_row_among_others_bit_for_bit(rows):
    """The decode shape and a chunk shape: a row with every other row
    dead, and the same row at the same slot among 31 (511) live others
    that land it in another place of another tile, bit for bit."""
    rng = np.random.RandomState(rows)
    x = jnp.asarray(rng.randn(rows, 16), jnp.float32)
    chosen, weight, _ = _routing('random', rows, rng)
    # the row chooses two held experts and one that is away
    slot = rows // 2 + 3
    chosen = chosen.at[slot].set(jnp.asarray([FIRST + 3, 0, FIRST + 1]))
    only = jnp.arange(rows) == slot
    alone, _, _ = _routed(x, chosen, weight, only)
    among, _, hit = _routed(x, chosen, weight, jnp.ones((rows,), bool))
    assert np.asarray(hit)[:slot, [1, 3]].any()    # rows ahead of it
    assert np.asarray(alone)[slot].any()
    assert np.array_equal(np.asarray(alone)[slot], np.asarray(among)[slot])
    assert not np.asarray(alone)[~np.asarray(only)].any()


def test_row_tiles_hand_worked_case():
    """512 rows over the four held experts, 0, 1, 128 and 300 live rows
    on them: 0 + 1 + 1 + 3 tiles of 128; a dead row's choice makes none;
    the first 32 rows alone (one on expert 1, 22 on expert 2) are one
    tile for each of the two."""
    hit = np.zeros((512, E_HELD), bool)
    hit[5, 1] = True
    hit[10:138, 2] = True
    hit[200:500, 3] = True
    valid = np.ones(512, bool)
    assert moe.load_stats(jnp.asarray(hit), jnp.asarray(valid)).tolist() \
        == [429, 300, 3, 5]
    valid[5] = False            # expert 1 untouched
    valid[499] = False          # 299 rows are still three tiles
    valid[137] = False          # 127 rows are still one
    assert moe.load_stats(jnp.asarray(hit), jnp.asarray(valid)).tolist() \
        == [426, 299, 2, 4]
    hit[138, 2] = True
    valid[137] = True           # 129 rows are two
    assert moe.load_stats(jnp.asarray(hit), jnp.asarray(valid)).tolist() \
        == [428, 299, 2, 5]
    assert moe.load_stats(jnp.asarray(hit[:32]),
                          jnp.ones((32,), bool)).tolist() == [23, 22, 2, 2]


# ------------------------------------------------- attention, alone
@pytest.mark.parametrize('start,window,block_cols', [
    (0, 0, 8), (17, 0, 8), (17, 6, 8), (26, 9, 12), (5, 3, 512)])
def test_one_table_attention_in_blocks_is_the_dense_softmax(start, window,
                                                            block_cols):
    """8 consecutive rows of one sequence from ``start``, grouped heads,
    a window or none: the blocked running softmax over the blocks that
    hold lo..hi gives what a dense masked softmax over the whole table
    gives, and a column outside a row's bounds has no say (the pages
    past it hold garbage)."""
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_attention_one_table
    rng = np.random.RandomState(start + window)
    heads, kv_heads, d, rows = 4, 2, 8, 8
    table = jnp.asarray(rng.permutation(NB)[:PAGES], jnp.int32)
    k_arena = jnp.asarray(rng.randn(2, NB, BS, kv_heads * d), jnp.float32)
    v_arena = jnp.asarray(rng.randn(2, NB, BS, kv_heads * d), jnp.float32)
    q = jnp.asarray(rng.randn(rows, heads, d), jnp.float32)
    pos = start + np.arange(rows)
    hi = pos + 1
    lo = np.maximum(hi - window, 0) if window else np.zeros_like(pos)
    got = paged_attention_one_table(
        q, k_arena, v_arena, table, jnp.asarray(hi, jnp.int32), layer=1,
        lo=jnp.asarray(lo, jnp.int32), block_cols=block_cols)
    k = np.asarray(k_arena)[1][np.asarray(table)].reshape(-1, kv_heads, d)
    v = np.asarray(v_arena)[1][np.asarray(table)].reshape(-1, kv_heads, d)
    want = np.zeros((rows, heads, d), 'float32')
    for r in range(rows):
        for h in range(heads):
            n = h // (heads // kv_heads)
            sc = k[lo[r]:hi[r], n] @ np.asarray(q)[r, h] * d ** -0.5
            w = np.exp(sc - sc.max())
            want[r, h] = (w / w.sum()) @ v[lo[r]:hi[r], n]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)


# ------------------------------------- prefill in chunks, then decode
@pytest.mark.parametrize('prompt_len,chunk', [(13, 8), (21, 16), (6, 8)])
def test_chunked_prefill_then_decode_matches_full_forward(prompt_len,
                                                          chunk):
    """A sequence that crosses the window (8): its prompt prefilled in
    chunks through the arenas, then decoded a token at a time, row by
    row against the reference's one full forward."""
    block_harness.chunked_prefill_then_decode(DRIVER, ref, prompt_len, chunk,
                                              12, TOL)


def test_kv_rows_wider_than_a_lane_tile_keep_their_width():
    """12 query heads over 3 KV heads of 64: a K (or V) row is 192
    wide, more than a lane tile and not whole tiles. The arena is as
    wide as the row (only a row all heads share is stored in whole
    tiles: the attention reads the KV heads off the width, and 256
    would read as 4 heads), and the logits are the reference's."""
    spec = _spec(n_layer=2, n_head=12, n_kv_head=3, d_key=64, d_value=64,
                 layer_types=['sliding_attention', 'full_attention'])
    assert [(k.width, k.stored, k.shared) for k in spec.cache_kinds()] \
        == [(192, 192, False)] * 2
    assert kv_bytes_per_token(spec, 'bfloat16') == 2 * 2 * 192 * 2
    # a driver of its own: another spec
    wide = Driver(spec, random_weights(spec, seed=7), BS, NB, pages=PAGES)
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, spec.vocab_size, 17)
    want = wide.reference_logits(ref, tokens)
    table = jnp.asarray(rng.permutation(NB)[:PAGES], jnp.int32)
    got, arenas, _ = wide.prefill_chunk(wide.arenas(), table, tokens[:16], 0)
    np.testing.assert_allclose(np.asarray(got), want[:16], atol=TOL)
    got, _, _ = wide.decode(arenas, table[None, :], tokens[16:], [16])
    np.testing.assert_allclose(np.asarray(got)[0], want[16], atol=TOL)


def test_padded_chunk_rows_write_nothing():
    """A chunk padded to its bucket: the rows past ``length`` leave the
    arenas as they were, and the real rows' logits do not move."""
    block_harness.padded_chunk_rows_write_nothing(DRIVER, TOL)


def test_decode_batch_of_mixed_lengths_matches_reference():
    """Four sequences of lengths on both sides of the window in one
    decode batch, an empty slot among them: every row's logits are the
    reference's for that sequence, and the router statistics count the
    live rows only."""
    rng = np.random.RandomState(7)
    lengths = [3, 9, 17, 30]
    seqs = [rng.randint(0, SPEC.vocab_size, n + 1) for n in lengths]
    _, stats = block_harness.decode_batch_of_mixed_lengths(
        DRIVER, ref, seqs + [None],
        DRIVER.packed_tables(rng.permutation(NB), seqs + [None]), TOL)
    stats = np.asarray(stats)
    assert stats.shape == (SPEC.n_layer, 4)
    # 5 rows are one tile an expert: the loop runs once for each touched
    assert (stats[:, 3] == stats[:, 2]).all()
    # 4 live rows x 3 choices a layer bound the local ones; the busiest
    # expert holds at most every live row; at most 4 experts are held
    assert (stats[:, 0] <= 12).all() and (stats[:, 1] <= 4).all()
    assert (stats[:, 2] <= SPEC.experts_held).all()
    assert (stats[:, 1] <= stats[:, 0]).all()
    # by the reference's router: the choices of the live rows that fall
    # on experts 2..5, layer 0 (its input is the embedding's norm)
    x = ref.layer_norm(
        jnp.asarray(WEIGHTS['lm_emb'])[jnp.asarray([s[-1] for s in seqs])],
        WEIGHTS['lm_stack_ln.w'][0], SPEC.norm_eps)
    chosen, _ = ref.route(x, WEIGHTS['lm_stack_router.w'][0], 3)
    chosen = np.asarray(chosen)
    assert stats[0, 0] == int(((chosen >= 2) & (chosen < 6)).sum())


@pytest.mark.parametrize('broken', ['window', 'rotary', 'first_expert'])
def test_the_tolerance_catches_a_wrong_layer(broken):
    """What the tolerance is for: a window one key short, a full layer
    rotated, the wrong experts held, each moves logits by far more."""
    over = {'window': dict(sliding_window=7),
            'rotary': dict(layer_types=['sliding_attention'] * 4),
            'first_expert': dict(first_expert=3)}[broken]
    rng = np.random.RandomState(2)
    tokens = rng.randint(0, SPEC.vocab_size, 20)
    # a driver of its own: the broken spec over the sound weights
    wrong = Driver(_spec(**over), WEIGHTS, BS, NB, pages=PAGES)
    got, _, _ = wrong.prefill_chunk(
        wrong.arenas(), jnp.arange(PAGES, dtype=jnp.int32), tokens, 0)
    assert np.abs(np.asarray(got) - _reference_logits(tokens)).max() > 1e-2


def test_the_tolerance_catches_a_narrower_state():
    """The configuration states float32 for the residual stream, the
    router, the softmax and the logits. Served tokens cannot tell
    (on the chip the reference with those in bfloat16 lies as close to
    itself as the sound engine does: PERF.md section 6), so that half
    of the stated precision is held here, by logits: in bfloat16 they
    move by a hundred times the tolerance and more."""
    rng = np.random.RandomState(4)
    tokens = rng.randint(0, SPEC.vocab_size, 20)
    narrow = DRIVER.reference_logits(ref, tokens, state_dtype='bfloat16')
    moved = np.abs(narrow - _reference_logits(tokens))
    assert moved.max() > 100 * TOL


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
                         int(rng.randint(2, 27))).tolist(),
             int(rng.randint(3, 12))) for _ in range(n)]


@pytest.fixture(scope='module')
def engine():
    """One engine of the default arguments, warmed and started, for the
    tests that read what their own requests add to the counters."""
    eng = _engine()
    assert eng.prompt_buckets == [4, 8]
    eng.warmup()
    eng.start()
    yield eng
    eng.shutdown(drain=False)


def test_engine_batched_equals_one_at_a_time_and_the_reference(engine):
    """Through DecodeEngine (scheduler, pool, executor, chunked prefill
    above 8 tokens): the tokens of six requests served together are the
    tokens of each served alone, no signature compiles after warmup, the
    pool drains, and every served token is the reference's choice up to
    a logit gap of TOL."""
    from paddle_tpu import observe
    requests = _requests()
    assert max(len(p) for p, _ in requests) > 16    # three chunks
    assert max(len(p) + n for p, n in requests) > SPEC.sliding_window
    alone = []
    try:
        for prompt, n in requests:
            alone.append(engine.generate(prompt, max_new_tokens=n,
                                         timeout=120))
        observe.enable()
        before = observe.snapshot()
        streams = [engine.submit(p, max_new_tokens=n) for p, n in requests]
        together = [s.result(120) for s in streams]
        assert engine.drain(timeout=60)
        after = observe.snapshot()
    finally:
        observe.disable()
        observe.reset()
    assert together == alone
    assert engine.free_pages() == 64

    def grown(name):
        return sum(v for k, v in after['counters'].items()
                   if k.startswith(name)) - \
            sum(v for k, v in before['counters'].items()
                if k.startswith(name))
    assert grown('executor.cache_miss_total') == 0
    chunks = sum(-(-len(p) // 8) for p, _ in requests)
    assert grown('decode.prefill_chunks') == chunks
    assert grown('decode.prefills_total') == len(requests)
    # a prefill's whole time lies under its largest program's bucket: the
    # top one wherever it was chunked, never the short last chunk's
    for rung in engine.prompt_buckets:
        want = sum(1 for p, _ in requests
                   if engine._bucket(min(len(p), 8)) == rung)
        key = 'decode.prefill_seconds{bucket=%d}' % rung
        got = after['histograms'].get(key, {}).get('count', 0) - \
            before['histograms'].get(key, {}).get('count', 0)
        assert got == want, (rung, got, want)
    assert grown('decode.moe_assignments') == \
        grown('decode.step_rows') * 3 * SPEC.n_layer
    assert 0 < grown('decode.moe_local_assignments') < \
        grown('decode.moe_assignments')
    assert grown('decode.step_window_rows') > 0
    for (prompt, _), answer in zip(requests, together):
        gaps, _ = ref.token_gaps(WEIGHTS, _arch(SPEC), _held(SPEC),
                                 prompt, answer, 8)
        assert max(gaps) <= TOL


def test_engine_counts_the_row_tiles_its_programs_ran(engine):
    """A prefill in chunks and the decode steps behind it feed both
    counters: a tile for every (layer, expert some live row chose) in
    each program, against one for every expert held (all programs here
    are under 128 rows); the decode steps' share is what the touched
    experts counter says."""
    from paddle_tpu import observe
    observe.enable()
    try:
        before = observe.snapshot()
        engine.generate(list(range(1, 20)), max_new_tokens=1, timeout=120)
        prefill = observe.snapshot()
        engine.generate(list(range(3, 9)), max_new_tokens=5, timeout=120)
        after = observe.snapshot()
    finally:
        observe.disable()
        observe.reset()

    def grown(name, a, b):
        return b['counters'].get(name, 0) - a['counters'].get(name, 0)
    per_program = SPEC.n_layer * SPEC.experts_held
    # 19 tokens in chunks of 8 are three programs, and no decode step:
    # the two counters the benchmark's HBM shares take ``touched`` from
    # are the decode steps' alone, and a prefill leaves them as they were
    assert grown('decode.prefill_chunks', before, prefill) == 3
    assert grown('decode.moe_layer_steps', before, prefill) == 0
    assert grown('decode.moe_experts_touched', before, prefill) == 0
    assert grown('decode.moe_row_tiles_dense', before, prefill) == \
        3 * per_program
    assert 0 < grown('decode.moe_row_tiles_run', before, prefill) <= \
        3 * per_program
    # one chunk and four decode steps
    steps = grown('decode.steps_total', prefill, after)
    assert steps == 4
    assert grown('decode.moe_row_tiles_dense', prefill, after) == \
        (1 + steps) * per_program
    run = grown('decode.moe_row_tiles_run', prefill, after)
    touched = grown('decode.moe_experts_touched', prefill, after)
    assert 0 < touched <= run <= touched + per_program
    # one live row a step chooses at most 3 experts a layer
    assert touched <= steps * SPEC.n_layer * 3
    # what those steps' attention had to read of K (and of V), from
    # what the kinds say a layer reads (CacheKind.reads): the row holds
    # 7, 8, 9, 10 positions with its new token's, three layers see the
    # last 8 of them and the fourth all, 2 KV heads x 8 wide, float32
    positions = 3 * (7 + 8 + 8 + 8) + (7 + 8 + 9 + 10)
    for kind in ('lm_kcache', 'lm_vcache'):
        assert grown('decode.cache_bytes_read{kind=%s}' % kind,
                     prefill, after) == positions * 2 * 8 * 4


def test_engine_keeps_declared_dtypes_and_device_arrays():
    spec = _spec(dtype='bfloat16')
    eng = _engine(spec, weights=None, kv_dtype='bfloat16')
    held = eng.device_weights()
    assert str(held['lm_stack_exp_gate.w'].dtype) == 'bfloat16'
    assert str(held['lm_stack_ln.w'].dtype) == 'float32'
    assert str(held['lm_emb'].dtype) == 'bfloat16'
    # a device array of the right dtype is taken as it is
    mine = jnp.ones(held['lm_stack_router.w'].shape, jnp.bfloat16)
    eng.load_weights({'lm_stack_router.w': mine})
    assert eng.device_weights()['lm_stack_router.w'] is mine
    # a host array is cast to the declared dtype, not to float32
    eng.load_weights({'lm_emb': np.zeros(held['lm_emb'].shape, 'float32')})
    assert str(eng.device_weights()['lm_emb'].dtype) == 'bfloat16'
    with pytest.raises(ValueError, match='unknown param'):
        eng.load_weights({'lm_out_proj.w': np.zeros((2, 2))})
    # bf16 end to end: a request runs
    eng.start()
    try:
        assert len(eng.generate([1, 2, 3, 4, 5, 6, 7, 8, 9],
                                max_new_tokens=4, timeout=120)) == 4
    finally:
        eng.shutdown()


@pytest.mark.parametrize('kw,what', [
    (dict(prefix_cache=True), 'prefix cache'),
    (dict(spec_k=2), 'speculation'),
    (dict(kv_dtype='int8'), 'unquantized')])
def test_what_the_block_does_not_run_raises(kw, what):
    with pytest.raises(NotImplementedError, match=what):
        _engine(**kw)


def test_spec_refuses_what_it_cannot_describe():
    with pytest.raises(ValueError, match='KV heads'):
        _spec(n_kv_head=3)
    with pytest.raises(ValueError, match='layer_types'):
        _spec(layer_types=['full_attention'])
    with pytest.raises(ValueError, match='experts'):
        _spec(first_expert=6)
    with pytest.raises(ValueError, match='one KV head'):
        LMSpec(vocab_size=8, n_head=4, n_kv_head=2)
    assert _spec().windows() == [8, 8, 8, 0]
    assert _spec().rotary() == [True, True, True, False]


def test_kv_bytes_count_kv_heads(engine):
    # 4 layers x 2 KV heads x (8 + 8) x 4 B, not the 4 query heads
    assert kv_bytes_per_token(SPEC) == 4 * 2 * 16 * 4
    assert kv_bytes_per_token(SPEC, 'bfloat16') == 4 * 2 * 16 * 2
    assert arena_bytes(SPEC, 10, 4) == 4 * 2 * 16 * 4 * 40
    dense = LMSpec(vocab_size=8, n_layer=2, n_head=2, d_key=8, d_value=8)
    assert kv_bytes_per_token(dense) == 2 * 2 * 16 * 4
    assert engine.kv_geometry()['n_kv_head'] == 2


def test_long_prefix_of_the_dense_block_prefills_in_chunks():
    """The post-LN block takes the same chunked feed: a prompt above the
    top bucket gives the tokens of an engine whose bucket holds it."""
    spec = LMSpec(vocab_size=60, n_layer=2, n_head=2, d_key=8, d_value=8,
                  d_model=16, d_inner=32)
    weights = random_weights(spec, seed=3)
    prompt = list(np.random.RandomState(4).randint(0, 60, 21))
    out = []
    for kw in (dict(max_prompt_len=32), dict(max_prompt_len=32,
                                             prefill_chunk=8)):
        eng = DecodeEngine(spec, max_batch=2, block_size=4, num_blocks=32,
                           pages_per_seq=10, weights=weights,
                           place=fluid.CPUPlace(), **kw)
        eng.start()
        try:
            out.append(eng.generate(prompt, max_new_tokens=6, timeout=120))
        finally:
            eng.shutdown()
    assert out[0] == out[1]
