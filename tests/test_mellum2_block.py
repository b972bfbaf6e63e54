"""The gqa_moe block (mellum2_12b: grouped per-head attention, sliding
layers whose pool keeps a window's pages beside full layers whose pool
keeps every page, a position table per layer kind, softmax-routed
experts, no shared expert) against its plain reference, at a tiny size
on the CPU in float32: two periods of (sliding x3, full), 4 query heads
over 2 KV heads of 8, window 8, 8 experts, 3 per token; YaRN by 4 over
16 original positions on the full layers, so every sequence here runs
past the original length and several windows deep.

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU; they differ in the order of their sums (the block
attends column block by column block under a running softmax and sums a
row's experts in tiles; the reference takes one softmax over the whole
sequence and loops over the experts), which at these widths gives
differences of a few 1e-6 on logits of order 1. 5e-5 leaves a margin and
is two orders and more under what a plain table on the full layers, a
window left out or a sigmoid router gives (checked below by breaking
each)."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import observe
from paddle_tpu.models.reference import mellum2_12b as ref
from paddle_tpu.ops import gqa_moe_ops as gmo
from paddle_tpu.ops import moe_held_ops as moe
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
from paddle_tpu.serving.decode.kv_pool import BlockTable, KVPool
from paddle_tpu.serving.decode.scheduler import Scheduler, Sequence
from block_harness import Driver, tokens as _tokens

TOL = 5e-5
BS, PAGES = 4, 24                    # 96 positions a sequence
NB = {'': 64, 'sliding': 40}
WINDOW, CHUNK = 8, 16
S, F = lm.SLIDING, lm.FULL
ROPE = {F: dict(rope_type='yarn', rope_theta=100.0, factor=4,
                original_max_position_embeddings=16, beta_fast=4,
                beta_slow=1, attention_factor=0.1 * np.log(4) + 1),
        S: dict(rope_type='default', rope_theta=100.0)}
PUBLISHED = dict(rope_type='yarn', rope_theta=500000, factor=16,
                 original_max_position_embeddings=8192, beta_fast=32,
                 beta_slow=1, attention_factor=1.2772588722239782)


@pytest.fixture(autouse=True)
def _clean_observe():
    """Counters start at zero in every test; a module-scoped fixture
    that turns observe on keeps its own snapshot."""
    yield
    observe.disable()
    observe.reset()


def _spec(**over):
    kw = dict(
        vocab_size=64, n_layer=8, n_head=4, n_kv_head=2, d_key=8, d_value=8,
        d_model=32, d_inner=24, block='gqa_moe', layer_types=[S, S, S, F] * 2,
        sliding_window=WINDOW, n_experts=8, experts_per_token=3,
        norm_eps=1e-6, rope_parameters=ROPE)
    kw.update(over)
    return LMSpec(**kw)


SPEC = _spec()
WEIGHTS = random_weights(SPEC, seed=11)


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, pages=PAGES)


def _reference_logits(tokens, **lowered):
    return DRIVER.reference_logits(ref, tokens, **lowered)


def _pools(chunk=CHUNK, sliding=NB['sliding']):
    return [KVPool(NB[''], BS),
            KVPool(sliding, BS, kind='sliding', keep=WINDOW, ahead=chunk)]


def _rows(pools, tables):
    """The block tables as the programs take them (engine._table_rows)."""
    out = []
    for pool, table in zip(pools, tables):
        row = np.full((PAGES,), pool.num_blocks, 'int32')
        ids = table.block_ids[table.freed:]
        row[table.freed:table.freed + len(ids)] = ids
        out.append(row)
    return out


def _grow(pools, tables, need, first_query):
    for pool, table in zip(pools, tables):
        pool.trim(table, first_query)
        assert pool.grow(table, need)


def _serve(tokens, prompt, chunk=CHUNK, pools=None, between=None):
    """``tokens`` through the block as the engine serves a sequence:
    the first ``prompt`` in chunks of ``chunk``, the rest one decode step
    each, the sliding pool trimmed behind the window before every
    program. Logits of every position, the pools, the tables, the
    arenas. ``between(arenas, pools, tables)`` runs after the prefill."""
    arenas = DRIVER.arenas()
    pools = pools or _pools(chunk)
    tables = [BlockTable(), BlockTable()]
    out = []
    for a in range(0, prompt, chunk):
        piece = tokens[a:min(a + chunk, prompt)]
        _grow(pools, tables, a + len(piece), a)
        padded = np.zeros((chunk,), np.int32)
        padded[:len(piece)] = piece
        # the padded tail is written nowhere and seen by no kept row
        logits, arenas, _ = DRIVER.prefill_chunk(
            arenas, _rows(pools, tables), padded, a, length=len(piece))
        out.append(np.asarray(logits)[:len(piece)])
    if between is not None:
        arenas = between(arenas, pools, tables) or arenas
    for p in range(prompt, len(tokens)):
        _grow(pools, tables, p + 1, p)
        logits, arenas, _ = DRIVER.decode(
            arenas, [r[None] for r in _rows(pools, tables)], [tokens[p]],
            [p])
        out.append(np.asarray(logits))
    return np.concatenate(out), pools, tables, arenas


# ------------------------------------------------------ spec and pools
def test_each_layer_kind_keeps_its_own_arenas_under_its_own_pool():
    kinds = SPEC.cache_kinds()
    assert [(k.name, k.slot, k.layers, k.width, k.pool, k.keeps)
            for k in kinds] == [
        ('lm_kcache_full', 'KCacheFull', (3, 7), 16, '', 0),
        ('lm_vcache_full', 'VCacheFull', (3, 7), 16, '', 0),
        ('lm_kcache_sliding', 'KCacheSliding', (0, 1, 2, 4, 5, 6), 16,
         'sliding', WINDOW),
        ('lm_vcache_sliding', 'VCacheSliding', (0, 1, 2, 4, 5, 6), 16,
         'sliding', WINDOW)]
    full, sliding = SPEC.page_pools()
    assert (full.name, full.keeps, full.feed, full.slot) == ('', 0, '', '')
    assert (sliding.name, sliding.keeps, sliding.feed, sliding.slot) == \
        ('sliding', WINDOW, '_sliding', 'Sliding')
    assert [k.name for k in sliding.kinds] == ['lm_kcache_sliding',
                                               'lm_vcache_sliding']
    assert SPEC.per_head_cache() and not SPEC.shares_frozen_pages()
    assert SPEC.layer_plan() == ((), (S, S, S, F), 2, ())
    assert SPEC.windows() == [WINDOW] * 3 + [0] + [WINDOW] * 3 + [0]
    assert all(SPEC.rotary())
    # the published geometry: 2,048 B a token a layer, 4,096 B in the
    # full pool and 12,288 B in the sliding one
    big = _spec(n_head=32, n_kv_head=4, d_key=128, d_value=128,
                sliding_window=1024, dtype='bfloat16')
    per_kind = lm.kv_bytes_per_kind(big, 'bfloat16')
    assert [per_kind[k.name] for k in big.cache_kinds()] == \
        [2048, 2048, 6144, 6144]
    assert lm.arena_bytes(big, {'': 33280, 'sliding': 1600}, 32,
                          'bfloat16') == \
        33280 * 32 * 4096 + 1600 * 32 * 12288


@pytest.mark.parametrize('spec', [
    LMSpec(vocab_size=32),
    LMSpec(vocab_size=32, n_layer=4, n_head=4, n_kv_head=2, d_key=8,
           d_value=8, block='parallel_moe', layer_types=[S, S, S, F],
           sliding_window=8, n_experts=4, experts_per_token=2,
           n_shared_experts=1),
    LMSpec(vocab_size=32, n_layer=2, block='latent_moe',
           layer_types=[F, S], sliding_window=5, n_experts=4,
           experts_per_token=2, n_shared_experts=1, index_topk=4,
           index_n_heads=2, index_head_dim=8,
           latent={k: dict(n_head=2, q_rank=8, kv_rank=8, d_nope=4,
                           d_rope=4, d_v=4, rope_theta=100.0)
                   for k in (F, S)}),
], ids=['post_ln', 'parallel_moe', 'latent_moe'])
def test_the_other_blocks_keep_one_pool_that_keeps_every_page(spec):
    """No existing kind took a lifetime: their arenas, their one table
    and their feeds are the parent's."""
    (pool,) = spec.page_pools()
    assert (pool.name, pool.keeps, pool.feed) == ('', 0, '')
    assert pool.kinds == spec.cache_kinds()
    assert all(k.pool == '' and k.keeps == 0 for k in spec.cache_kinds())
    progs = lm.build_lm_programs(spec, 2, 4, 8, 4)
    feeds = {v.name for v in progs.decode.global_block().vars.values()
             if getattr(v, 'is_data', False)}
    assert feeds == {'dec_tokens', 'dec_lens', 'dec_tables', 'dec_temps',
                     'dec_seeds'}


@pytest.mark.parametrize('over,what', [
    (dict(n_shared_experts=1), 'shared'),
    (dict(rope_parameters={F: ROPE[F]}), 'rope_parameters'),
    (dict(rope_parameters={F: dict(rope_type='linear', rope_theta=1e4),
                           S: ROPE[S]}), 'rope_type'),
    (dict(n_kv_head=3), 'KV heads'),
])
def test_a_spec_the_block_cannot_build_is_refused(over, what):
    with pytest.raises(ValueError, match=what):
        _spec(**over)


def test_the_prefix_cache_speculation_and_the_handoff_are_refused():
    with pytest.raises(NotImplementedError, match='prefix cache'):
        DecodeEngine(SPEC, prefix_cache=True)
    with pytest.raises(NotImplementedError):
        DecodeEngine(SPEC, spec_k=2)
    eng = DecodeEngine(SPEC, max_batch=2, block_size=BS, num_blocks=16,
                       pages_per_seq=8)
    from paddle_tpu.serving.handoff import CacheKindError
    with pytest.raises(CacheKindError, match='one block table'):
        eng.kv_geometry()
    with pytest.raises(ValueError, match='pool_blocks'):
        DecodeEngine(SPEC, pool_blocks={'window': 4})
    eng.shutdown(drain=False)


# ------------------------------------------------------------ positions
def test_the_position_tables_are_the_closed_form_and_the_references():
    """YaRN at the published section: pairs 0-18 keep their frequency,
    pairs 35-63 are stretched 16 times, a linear blend between; the
    sliding layers turn by the plain powers; the latent block reads the
    same function."""
    assert lm.yarn_range(128, 500000, PUBLISHED) == (18, 35)
    assert ref.yarn_range(128, 500000.0, PUBLISHED) == (18, 35)
    freq = lm.yarn_frequencies(128, 500000, PUBLISHED)
    plain = 500000.0 ** (-np.arange(64) * 2 / 128.0)
    np.testing.assert_allclose(freq[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(freq[35:], plain[35:] / 16, rtol=1e-12)
    mid = 26
    r = (mid - 18) / 17.0
    np.testing.assert_allclose(
        freq[mid], plain[mid] * (1 - r) + plain[mid] / 16 * r, rtol=1e-12)
    np.testing.assert_allclose(
        freq, ref.pair_frequencies(128, PUBLISHED)[0], rtol=1e-12)
    big = _spec(d_key=128, d_value=128, rope_parameters={
        F: PUBLISHED, S: dict(rope_type='default', rope_theta=500000)})
    tables = big.rope_tables()
    np.testing.assert_allclose(tables[F][0], freq, rtol=1e-12)
    assert tables[F][1] == pytest.approx(0.1 * np.log(16) + 1)
    np.testing.assert_allclose(tables[S][0], plain, rtol=1e-12)
    assert tables[S][1] == 1.0
    attrs = lm._block_attrs(big, 32)
    assert attrs['full_softmax_mult'] == pytest.approx(1.2772588722239782
                                                       ** 2)
    assert attrs['sliding_softmax_mult'] == 1.0
    assert attrs['pools'] == ['', 'Sliding']
    # one function for both blocks
    shape = lm.LatentShape(4, 16, 12, 8, 64, 8, 50000.0, dict(
        type='yarn', factor=64, beta_fast=32, beta_slow=1,
        original_max_position_embeddings=4096))
    np.testing.assert_array_equal(
        shape.rope_frequencies(),
        lm.yarn_frequencies(64, 50000.0, shape.rope_scaling))


def test_rope_half_turns_the_two_halves_as_the_reference_does():
    x = np.random.RandomState(1).randn(5, 3, 8).astype('float32')
    pos = jnp.asarray([0, 3, 17, 40, 95], jnp.int32)
    freq, factor = ref.pair_frequencies(8, ROPE[F])
    got = gmo.rope_half_at(jnp.asarray(x), pos,
                           jnp.asarray(freq, jnp.float32)) * factor
    want = ref.rotate_halves(jnp.asarray(x), pos,
                             jnp.asarray(freq, jnp.float32),
                             jnp.float32(factor))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# --------------------------------------------------------------- router
def test_the_softmax_router_is_the_references():
    x = jnp.asarray(np.random.RandomState(2).randn(40, 32), jnp.float32)
    router = jnp.asarray(WEIGHTS['lm_stack_router.w'][1])
    chosen, weight = moe.route_softmax_topk(x, router, 3)
    want_chosen, want_weight = ref.route(x, router, 3)
    np.testing.assert_array_equal(np.asarray(chosen),
                                  np.asarray(want_chosen))
    np.testing.assert_allclose(np.asarray(weight), np.asarray(want_weight),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 1.0, atol=1e-6)
    # normalised over the chosen: the softmax over their logits alone
    logits = np.asarray(x) @ np.asarray(router)
    top = np.take_along_axis(logits, np.asarray(chosen), 1)
    np.testing.assert_allclose(
        np.asarray(weight),
        np.exp(top) / np.exp(top).sum(-1, keepdims=True), atol=1e-6)


@pytest.mark.parametrize('shares', [
    [(0, 8)], [(0, 3), (3, 5)], [(0, 2), (2, 2), (4, 4)],
    [(i, 1) for i in range(8)]])
def test_the_shares_of_the_routed_sum_add_up_to_the_whole_layer(shares):
    """Every chip of an expert-parallel group routes over all experts
    and adds what its own give: the shares over ``first_expert`` /
    ``experts_held`` sum to the layer's whole routed sum, in the block
    and in the reference."""
    tokens = _tokens(24, 5)
    whole = _reference_logits(tokens)
    x = np.asarray(ref.rms_norm(
        jnp.asarray(WEIGHTS['lm_emb'])[tokens],
        jnp.asarray(WEIGHTS['lm_stack_ln2.w'][0]), 1e-6))
    w = {k: jnp.asarray(v) for k, v in WEIGHTS.items()}
    total = np.asarray(ref.experts(jnp.asarray(x), w, 0, ref.arch_of(SPEC),
                                   (0, 8)))
    def held(first, count):
        """The weights a chip that holds ``count`` from ``first`` has."""
        return dict(w, **{name: w[name][:, first:first + count]
                          for name in w if '_exp_' in name})
    parts_ref = sum(np.asarray(ref.experts(
        jnp.asarray(x), held(*share), 0, ref.arch_of(SPEC), share))
        for share in shares)
    np.testing.assert_allclose(parts_ref, total, atol=2e-6)
    chosen, weight = moe.route_softmax_topk(
        jnp.asarray(x), w['lm_stack_router.w'][0], 3)
    parts = 0
    for first, count in shares:
        gate, hit = moe.held_gates(chosen, weight, first, count)
        mine = held(first, count)
        parts = parts + np.asarray(moe.routed_experts(
            jnp.asarray(x), gate, hit, jnp.ones((24,), bool),
            min(3, count), mine['lm_stack_exp_gate.w'],
            mine['lm_stack_exp_up.w'], mine['lm_stack_exp_down.w'], 0))
    np.testing.assert_allclose(parts, total, atol=2e-6)
    assert np.isfinite(whole).all()


# ------------------------------------------------- against the reference
@pytest.mark.parametrize('length,prompt,chunk', [
    (60, 44, 16), (72, 50, 8), (40, 1, 16), (90, 90, 16)])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        length, prompt, chunk):
    """A sequence several windows deep (the window is 8) and past
    YaRN's original 16 positions, prefilled in chunks and decoded
    through both pools with the sliding one trimmed before every
    program, against the reference's whole-sequence forward."""
    tokens = _tokens(length, length)
    got, pools, tables, _ = _serve(tokens, prompt, chunk)
    np.testing.assert_allclose(got, _reference_logits(tokens), atol=TOL)
    # the sliding pool holds a window's pages, the full one every page
    assert tables[0].freed == 0 and len(tables[0]) == -(-length // BS)
    held = len(tables[1].block_ids) - tables[1].freed
    assert tables[1].freed > 0 and held <= pools[1].span_pages(length)
    # trimmed behind the last program's first query: the last decode
    # step's row, or the first row of the prompt's last chunk
    last = length - 1 if length > prompt else (prompt - 1) // chunk * chunk
    assert tables[1].freed == (last + 1 - WINDOW) // BS


@pytest.mark.parametrize('lowered,what', [
    (dict(yarn=False), 'a plain table on the full layers'),
    (dict(windowed=False), 'no window'),
])
def test_the_tolerance_catches_a_wrong_layer(lowered, what):
    tokens = _tokens(60, 60)
    got, _, _, _ = _serve(tokens, 44)
    wrong = _reference_logits(tokens, **lowered)
    assert np.abs(got - wrong).max() > 100 * TOL, what


def test_the_tolerance_catches_a_sigmoid_router():
    x = jnp.asarray(np.random.RandomState(3).randn(16, 32), jnp.float32)
    router = jnp.asarray(WEIGHTS['lm_stack_router.w'][0])
    _, soft = moe.route_softmax_topk(x, router, 3)
    _, sig = moe.route_sigmoid_topk(x, router, 3)
    assert np.abs(np.asarray(soft) - np.asarray(sig)).max() > 100 * TOL


def test_a_decode_batch_of_mixed_depths_matches_the_reference():
    """Three sequences of unlike depth in one decode step, each through
    its own tables, and an empty slot beside them."""
    lengths = (13, 37, 58)
    seqs = [_tokens(n + 1, 100 + n) for n in lengths]
    arenas, pools = DRIVER.arenas(), _pools()
    tables = []
    for tokens, n in zip(seqs, lengths):
        mine = [BlockTable(), BlockTable()]
        for a in range(0, n, CHUNK):
            piece = tokens[a:min(a + CHUNK, n)]
            _grow(pools, mine, a + len(piece), a)
            padded = np.zeros((CHUNK,), np.int32)
            padded[:len(piece)] = piece
            _, arenas, _ = DRIVER.prefill_chunk(
                arenas, _rows(pools, mine), padded, a, length=len(piece))
        _grow(pools, mine, n + 1, n)
        tables.append(mine)
    rows = [np.stack([_rows(pools, t)[i] for t in tables]
                     + [np.full((PAGES,), pools[i].num_blocks, 'int32')])
            for i in range(2)]
    logits, _, stats = DRIVER.decode(
        arenas, rows, [int(s[-1]) for s in seqs] + [0], list(lengths) + [0])
    for i, tokens in enumerate(seqs):
        np.testing.assert_allclose(np.asarray(logits)[i],
                                   _reference_logits(tokens)[-1], atol=TOL)
    # every live row chose 3 of the 8 experts, all held here
    assert np.asarray(stats).shape == (8, 4)
    assert (np.asarray(stats)[:, 0] == 9).all()


# ------------------------------------------------------- freed pages
def test_a_freed_page_taken_by_another_sequence_leaves_the_logits_as_they_were():
    """The pages a sequence gave back behind its window are taken and
    overwritten by another sequence; the first one's later logits are
    bit for bit what they are when nobody touches those pages: a column
    block may still gather a given-back entry (clipped to a real page,
    finite garbage), and masks it to exactly 0."""
    tokens = _tokens(70, 9)
    quiet, _, _, _ = _serve(tokens, 44)

    def overwrite(arenas, pools, tables):
        freed = set(range(pools[1].num_blocks)) - set(
            tables[1].block_ids[tables[1].freed:])
        assert tables[1].freed > 0
        ids = pools[1].alloc(pools[1].free_blocks())    # every free page
        assert set(ids) <= freed
        arenas = list(arenas)
        for a in (2, 3):                # the sliding kinds' K and V
            arenas[a] = arenas[a].at[:, jnp.asarray(ids)].set(1e4)
        pools[1].free(ids)
        return tuple(arenas)
    loud, _, _, _ = _serve(tokens, 44, between=overwrite)
    np.testing.assert_array_equal(loud, quiet)
    assert np.isfinite(loud).all()


def test_a_given_back_entry_lies_below_every_row_s_lower_bound():
    """What the trim gives back before a program is below the lower
    bound of that program's first query in whole pages, and the column
    blocks wholly below it are not in the attention's loops."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    pool = KVPool(64, BS, kind='sliding', keep=WINDOW, ahead=CHUNK)
    table = BlockTable()
    for query in range(0, 90, 7):
        pool.trim(table, query)
        assert pool.grow(table, query + 1)
        lo = max(query + 1 - WINDOW, 0)
        assert table.freed * BS <= lo < (table.freed + 1) * BS
        first, last = pa.block_bounds(
            np.asarray([lo]), np.asarray([query + 1]), 1, 2 * BS,
            PAGES // 2, np)
        assert first[0] * 2 >= table.freed - 1 and last[0] >= first[0]


# --------------------------------------------------------- pool, scheduler
@pytest.mark.parametrize('query,freed', [
    (0, 0), (6, 0), (7, 0), (10, 0), (11, 1), (14, 1), (15, 2), (31, 6),
    (95, 22)])
def test_trim_gives_back_the_pages_wholly_behind_the_window(query, freed):
    observe.enable()
    pool = KVPool(32, BS, kind='sliding', keep=WINDOW, ahead=CHUNK)
    table = BlockTable()
    assert pool.grow(table, 96)
    assert pool.trim(table, query) == freed
    assert table.freed == freed and table.block_ids[:freed] == [None] * freed
    assert pool.used_blocks() == 24 - freed
    assert pool.trim(table, query) == 0             # nothing twice
    assert observe.get_counter('decode.kv_pages_freed_behind_window_total',
                               kind='sliding') == freed
    assert observe.get_counter('decode.kv_pages_allocated_total',
                               kind='sliding') == 24
    assert observe.get_gauge('decode.kv_pages_used', kind='sliding') == \
        24 - freed
    pool.release(table)
    assert pool.used_blocks() == 0 and table.freed == 0 and not table.block_ids


def test_a_pool_without_a_lifetime_trims_nothing_and_publishes_bare():
    observe.enable()
    pool = KVPool(8, BS)
    table = BlockTable()
    assert pool.grow(table, 30) and pool.trim(table, 29) == 0
    assert table.freed == 0 and pool.span_pages(30) == 8
    assert observe.get_gauge('decode.kv_blocks_total') == 8
    assert observe.get_counter('decode.kv_pages_allocated_total') == 8
    assert observe.get_counter(
        'decode.kv_pages_freed_behind_window_total') == 0
    windowed = KVPool(64, BS, kind='sliding', keep=WINDOW, ahead=CHUNK)
    assert windowed.span_pages(5) == 2
    assert windowed.span_pages(1000) == (WINDOW + CHUNK) // BS + 2
    trimmed = BlockTable()
    windowed.grow(trimmed, 40)
    windowed.trim(trimmed, 39)
    with pytest.raises(ValueError, match='trimmed'):
        windowed.fork(trimmed)


def _seq(rid, prompt_len, max_new=4):
    return Sequence(rid, list(range(1, prompt_len + 1)), max_new, 0.0, 1,
                    None)


def test_admission_takes_pages_of_every_pool_or_of_none():
    pools = [KVPool(12, BS), KVPool(8, BS, kind='sliding', keep=WINDOW,
                                    ahead=CHUNK)]
    sched = Scheduler(pools, max_batch=4)
    a, b, c = _seq('a', 30), _seq('b', 9), _seq('c', 9)
    for seq in (a, b, c):
        sched.add(seq)
        assert len(seq.tables) == 2 and seq.table is seq.tables[0]
    # a: 8 pages of the full pool, its span of 8 of the sliding one
    assert sched.admittable() and sched.pop_admittable() is a
    assert [len(t) for t in a.tables] == [8, 8]
    # b: the full pool has its 3 pages, the sliding pool none: b waits
    # and keeps nothing
    assert not sched.admittable() and sched.pop_admittable() is None
    assert [p.used_blocks() for p in pools] == [8, 8]
    assert [len(t) for t in b.tables] == [0, 0]
    # a's decode write at 30 first gives back what lies behind position
    # 30's window: five pages
    a.cache_len = 30
    assert sched.ensure_growth(a, need_tokens=31)
    assert a.tables[1].freed == 5 and pools[1].used_blocks() == 3
    # b's three; c's would be a ninth page of the sliding pool
    assert sched.pop_admittable() is b and sched.pop_admittable() is None
    sched.finish(b, 'max_tokens')
    assert sched.pop_admittable() is c
    for seq in (a, c):
        sched.finish(seq, 'max_tokens')
    assert [p.used_blocks() for p in pools] == [0, 0]


def test_growth_preempts_for_the_pool_that_ran_out():
    observe.enable()
    pools = [KVPool(16, BS), KVPool(5, BS, kind='sliding', keep=WINDOW,
                                    ahead=4)]
    sched = Scheduler(pools, max_batch=2)
    a, b = _seq('a', 7), _seq('b', 7)
    for seq in (a, b):
        sched.add(seq)
    assert sched.pop_admittable() is a and sched.pop_admittable() is b
    assert pools[1].used_blocks() == 4
    a.cache_len = 7
    assert sched.ensure_growth(a, need_tokens=9)        # the last page
    assert sched.ensure_growth(a, need_tokens=13)       # b pays
    assert b.state == 'waiting' and [len(t) for t in b.tables] == [0, 0]
    assert observe.get_counter('decode.preemptions_total') == 1


# ------------------------------------------------------------ the engine
def _engine(**over):
    kw = dict(max_batch=4, block_size=BS, num_blocks=NB[''],
              pages_per_seq=PAGES, prefill_chunk=CHUNK, min_prompt_bucket=8,
              weights=WEIGHTS, pool_blocks={'sliding': NB['sliding']})
    kw.update(over)
    return DecodeEngine(SPEC, **kw)


def _reference_tokens(prompt, answer):
    lg = _reference_logits(list(prompt) + list(answer))
    return lg[len(prompt) - 1:len(prompt) + len(answer) - 1].argmax(1) \
        .tolist()


@pytest.fixture(scope='module')
def roomy():
    """One roomy engine, built once, warmed and started."""
    eng = _engine()
    eng.warmup()
    eng.start()
    yield eng
    eng.shutdown()


@pytest.fixture(scope='module')
def served(roomy):
    """Six requests of unlike depth through the roomy engine, submitted
    together: (prompts, answers, the engine's counters after them)."""
    observe.enable()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 64, n).tolist() for n in (5, 23, 41, 60, 33,
                                                        70)]
    streams = [roomy.submit(p, max_new_tokens=18) for p in prompts]
    answers = [s.result(timeout=600) for s in streams]
    assert roomy.drain(timeout=60)
    counters = dict(observe.snapshot()['counters'])
    pools = [(p.used_blocks(), p.num_blocks) for p in roomy.pools]
    return prompts, answers, counters, pools, roomy.warmup_signatures


@pytest.mark.parametrize('i', range(6))
def test_the_engine_serves_the_references_tokens(served, i):
    prompts, answers, _, _, _ = served
    assert answers[i] == _reference_tokens(prompts[i], answers[i])


def test_pages_of_every_kind_return_to_zero_after_release(served):
    _, _, counters, pools, signatures = served
    assert pools == [(0, NB['']), (0, NB['sliding'])]
    assert signatures == 3                  # buckets 8 and 16, the step
    freed = counters['decode.kv_pages_freed_behind_window_total'
                     '{kind=sliding}']
    allocated = counters['decode.kv_pages_allocated_total{kind=sliding}']
    assert 0 < freed < allocated
    assert 'decode.kv_pages_freed_behind_window_total{kind=full}' \
        not in counters
    # the routed layers' counters are fed by the new block
    assert counters['decode.moe_assignments'] == \
        counters['decode.moe_local_assignments'] > 0
    assert counters['decode.step_window_rows'] > 0
    assert counters['decode.cache_bytes_read{kind=lm_kcache_sliding}'] > 0


def test_a_sequence_holds_a_window_of_the_sliding_pool_whatever_its_length():
    observe.enable()
    eng = _engine(max_batch=1)
    eng.warmup()
    peak = 0

    def handed():
        return observe.get_counter('decode.kv_pages_allocated_total',
                                   kind='full')
    before = handed()
    eng.start()
    stream = eng.submit(_tokens(70, 3).tolist(), max_new_tokens=20)
    for _ in stream:
        peak = max(peak, eng.pools[1].used_blocks())
    # the full pool gives nothing back before the end, so its peak is the
    # pages it handed the one sequence (a reader of the stream misses the
    # last page where the worker, a step ahead, has let them all go)
    assert handed() - before == -(-90 // BS)
    assert 0 < peak <= WINDOW // BS + 2
    assert eng.pools[1].span_pages(90) == (WINDOW + CHUNK) // BS + 2
    eng.shutdown()


def test_a_preempted_sequence_reprefills_bit_exact_with_per_kind_tables(
        roomy):
    """A full pool too small for all three sequences' pages at once: the
    youngest is preempted, its tables of both pools released, and its
    re-prefill (whose sliding layers only ever see a window) continues
    its stream as the roomy engine serves it."""
    observe.enable()
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 64, n).tolist() for n in (30, 26, 22)]
    want = [roomy.generate(p, max_new_tokens=24, timeout=600)
            for p in prompts]
    before = observe.get_counter('decode.preemptions_total')
    tight = _engine(num_blocks=30)
    tight.start()
    streams = [tight.submit(p, max_new_tokens=24) for p in prompts]
    got = [s.result(timeout=600) for s in streams]
    assert observe.get_counter('decode.preemptions_total') > before
    assert got == want
    assert [p.used_blocks() for p in tight.pools] == [0, 0]
    tight.shutdown()


def test_the_programs_take_a_table_a_pool_and_keep_one_signature(roomy):
    eng = roomy
    feeds = {v.name for v in eng._progs.decode.global_block().vars.values()
             if getattr(v, 'is_data', False)}
    assert feeds == {'dec_tokens', 'dec_lens', 'dec_tables',
                     'dec_tables_sliding', 'dec_temps', 'dec_seeds'}
    assert eng._progs.arena_names == (
        'lm_kcache_full', 'lm_vcache_full', 'lm_kcache_sliding',
        'lm_vcache_sliding')
    shapes = {n: tuple(eng._scope.get(n).shape)
              for n in eng._progs.arena_names}
    assert shapes['lm_kcache_full'] == (2, NB[''], BS, 16)
    assert shapes['lm_vcache_sliding'] == (6, NB['sliding'], BS, 16)
    with pytest.raises(ValueError, match='KV pages'):
        _engine(num_blocks=4).submit([1] * 30, max_new_tokens=4)
    # a sequence longer than the sliding pool is fine: it holds a span
    small = _engine(pool_blocks={'sliding': 8})
    small.start()
    assert len(small.generate([1] * 60, max_new_tokens=8,
                              timeout=600)) == 8
    small.shutdown()
