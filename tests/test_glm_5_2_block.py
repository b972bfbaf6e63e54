"""The latent_moe block under a carried selection (LMSpec
block='latent_moe' with ``indexer_types``: glm_5_2's IndexShare) against
its plain reference, at a tiny size on the CPU in float32: five layers of
one attention shape (4 heads over a rank-12 latent, nope 6, rope 4, v 8:
values wider than the unrotated keys, as published), the first dense and
scoring, then (shared, shared, shared, full); an indexer of 3 heads of 8
in the two scoring layers, rotated in interleaved pairs, that keeps 8
positions; 8 experts of which 4 are held, 3 per token, times 2.5, one
shared; no gate, no rescale. Every sequence runs past the 8 selected
positions, so a carried selection differs from "all".

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU; they differ in the order of their sums (absorbed
against expanded attention, column blocks under a running softmax
against one dense softmax, the routed product's row tiles against a
loop over experts), which at these widths gives differences of a few
1e-6 on logits of order 1. 5e-5 leaves a margin and is two orders and
more under what a dropped carry, an unapplied selection, the indexer's
other rotary form or bfloat16 state gives (checked below by breaking
each)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.reference import glm_5_2 as ref
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
import block_harness
from block_harness import Driver
from util import platform_forms, weights_round_trip

TOL = 5e-5
BS, PAGES, NB = 4, 12, 40            # 48 positions a sequence
F, S, C = lm.FULL, lm.SLIDING, lm.CARRIED
SHAPE = dict(n_head=4, q_rank=16, kv_rank=12, d_nope=6, d_rope=4, d_v=8,
             rope_theta=8e6)
# the published list: layers 0-2 score, then (shared x 3, full) x 18 and
# three shared over
PUBLISHED = ['full'] * 3 + ['shared', 'shared', 'shared', 'full'] * 18 + \
    ['shared'] * 3


def _spec(**over):
    kw = dict(
        vocab_size=64, n_layer=5, d_model=32, d_inner=24,
        block='latent_moe', latent={F: SHAPE}, dense_layers=1,
        d_inner_dense=40, index_n_heads=3, index_head_dim=8, index_topk=8,
        indexer_types=PUBLISHED[2:7], index_rope_interleave=True,
        n_experts=8, experts_held=4, first_expert=2, experts_per_token=3,
        n_shared_experts=1, routed_scale=2.5, lora_rescale=False,
        attn_gate=False)
    kw.update(over)
    return LMSpec(**kw)


SPEC = _spec()
WEIGHTS = random_weights(SPEC, seed=54)


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, pages=PAGES)


def _reference_logits(tokens, **lowered):
    return DRIVER.reference_logits(ref, tokens, **lowered)


# ------------------------------------------- (d) the plan, (c) the caches
def test_layer_plan_of_the_published_list_and_of_the_cut():
    """The published 78 layers: three leading dense layers that score,
    18 periods of (carried x 3, scoring) and three carried layers over;
    the cut runs layers 2-6: the last leading dense layer and the whole
    period that follows it. The period is found among layers of one
    attention shape by their indexer kinds."""
    spec = _spec(n_layer=78, dense_layers=3, indexer_types=PUBLISHED)
    assert spec.layer_types == (F,) * 78
    assert spec.layer_plan() == ((F, F, F), (C, C, C, F), 18, (C, C, C))
    assert len(spec.scoring_layers()) == 21
    assert spec.scoring_layers()[:5] == (0, 1, 2, 6, 10)
    assert SPEC.layer_plan() == ((F,), (C, C, C, F), 1, ())
    assert SPEC.scoring_layers() == (0, 4)
    assert SPEC.plan_kinds() == (F, C, C, C, F)
    kinds = {k.name: k for k in spec.cache_kinds()}
    assert kinds['lm_latent_full'].layers == tuple(range(78))
    assert kinds['lm_index_full'].layers == spec.scoring_layers()
    # a layer's place in each stack, as the block reads it off the plan
    block = DRIVER.block()
    assert block.carries and block._places == (
        [0, 1, 2, 3, 4], [0, 1, 1, 1, 1])


def test_the_index_arena_holds_the_scoring_layers_only():
    """Two arenas under one table: every layer keeps a latent row, the
    two scoring layers an index key beside it; every function of a
    token's bytes reads the same list."""
    kinds = SPEC.cache_kinds()
    assert [(k.name, k.slot, k.layers, k.width, k.reads) for k in kinds] == [
        ('lm_latent_full', 'LatentFull', (0, 1, 2, 3, 4), 16, (8,) * 5),
        ('lm_index_full', 'IndexFull', (0, 4), 8, (0, 0))]
    per_token = 5 * 16 + 2 * 8
    assert lm.kv_bytes_per_token(SPEC) == per_token * 4
    assert lm.kv_bytes_per_kind(SPEC, 'bfloat16') == {
        'lm_latent_full': 160, 'lm_index_full': 32}
    assert lm.arena_bytes(SPEC, NB, BS) == per_token * 4 * BS * NB
    assert [a.shape for a in DRIVER.arenas()] == [(5, NB, BS, 16),
                                                  (2, NB, BS, 8)]
    # the published widths: 5 x 576 + 2 x 128 values a token, the latent
    # row stored in whole lane tiles (576 -> 640): 6,912 B in bfloat16
    big = _spec(latent={F: dict(n_head=64, q_rank=2048, kv_rank=512,
                                d_nope=192, d_rope=64, d_v=256,
                                rope_theta=8e6)}, index_head_dim=128)
    assert [k.stored for k in big.cache_kinds()] == [640, 128]
    assert lm.kv_bytes_per_token(big, 'bfloat16') == 6912
    # the shared layers hold no indexer weights
    shapes = lm.block_param_shapes(SPEC)
    for name, (shape, _, _) in shapes.items():
        if name.startswith('lm_full_idx_'):
            assert shape[0] == 2, name
        elif name.startswith('lm_full_'):
            assert shape[0] == 5, name


@pytest.mark.parametrize('bad', [
    dict(indexer_types=['shared', 'full', 'full', 'full', 'full']),
    dict(indexer_types=['full', 'shared']),
    dict(indexer_types=['full', 'shared', 'shared', 'shared', 'window']),
    dict(index_topk=0, index_n_heads=0)])
def test_spec_refuses_what_it_cannot_run(bad):
    with pytest.raises(ValueError):
        _spec(**bad)


# --------------- (e) a spec without indexer kinds is what it always was
DOTS = dict(
    vocab_size=64, n_layer=5, d_model=32, d_inner=24, block='latent_moe',
    layer_types=[F, F, S, S, S], sliding_window=5,
    latent={F: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8, d_rope=4,
                    d_v=8, rope_theta=8e7),
            S: dict(n_head=2, q_rank=16, kv_rank=20, d_nope=12, d_rope=4,
                    d_v=8, rope_theta=5e4)},
    dense_layers=1, d_inner_dense=40, index_n_heads=3, index_head_dim=8,
    index_topk=8, n_experts=8, experts_held=4, first_expert=2,
    experts_per_token=3, n_shared_experts=1)
KIMI = dict(
    vocab_size=64, n_layer=3, d_model=32, d_inner=24, block='latent_moe',
    latent={F: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8, d_rope=4,
                    d_v=8, rope_theta=5e4)},
    dense_layers=1, d_inner_dense=40, n_experts=8, experts_held=4,
    experts_per_token=3, n_shared_experts=1, lora_rescale=False,
    attn_gate=False, routed_scale=2.0)


@pytest.mark.parametrize('kw', [DOTS, KIMI], ids=['dots3_note', 'kimi_k2_6'])
def test_a_spec_without_indexer_kinds_is_unchanged(kw):
    """dots3_note's and kimi_k2_6's form of the spec: every full layer
    under ``index_topk`` scores for itself, the plan's kinds are
    ``layer_types``, the ops get no new attribute, the indexer's stacks
    and the index arena are the full layers', and the block carries
    nothing. Giving every layer as 'full' lowers to the same program,
    text for text: the carry exists only where a layer shares."""
    spec = LMSpec(**kw)
    assert spec.indexer_types == () and not spec.index_rope_interleave
    assert spec.plan_kinds() is spec.layer_types
    full = spec.layers_of(F)
    assert spec.scoring_layers() == (full if spec.index_topk else ())
    attrs = lm._block_attrs(spec, BS)
    assert 'index_rope_interleave' not in attrs
    assert set(attrs['lead'] + attrs['period'] + attrs['tail']) <= {F, S}
    shapes = lm.block_param_shapes(spec)
    kinds = {k.name: k for k in spec.cache_kinds()}
    if spec.index_topk:
        assert kinds['lm_index_full'].layers == full
        assert shapes['lm_full_idx_q.w'][0][0] == len(full)
    else:
        assert 'lm_index_full' not in kinds
        assert 'lm_full_idx_q.w' not in shapes
    block = Driver(spec, random_weights(spec, seed=1), BS, NB).block()
    assert not block.carries and block._places is None
    if not spec.index_topk:
        return
    texts = []
    for types in (None, ['full'] * spec.n_layer):
        eng = DecodeEngine(LMSpec(**dict(kw, indexer_types=types)),
                           max_batch=2, block_size=BS, num_blocks=NB,
                           pages_per_seq=PAGES, prefill_chunk=8,
                           min_prompt_bucket=8, place=fluid.CPUPlace())
        try:
            texts.append([eng.trace_program(which).lower().as_text()
                          for which in ('decode', 8)])
        finally:
            eng.shutdown(drain=False)
    assert texts[0] == texts[1]


# ---------------------------------------------------- the indexer's form
def test_the_indexer_rotates_in_the_form_the_spec_states():
    """Interleaved pairs here, half-split pairs without the option (as
    dots3_note): index keys as cached, against the reference's."""
    rng = np.random.RandomState(3)
    tokens = rng.randint(0, SPEC.vocab_size, 9)
    table = jnp.arange(PAGES, dtype=jnp.int32)
    _, arenas, _ = DRIVER.prefill_chunk(DRIVER.arenas(), table, tokens, 0)
    x = jnp.take(jnp.asarray(WEIGHTS['lm_emb']), jnp.asarray(tokens), axis=0)
    n = ref.rms_norm(x, WEIGHTS['lm_stack_ln1.w'][0], SPEC.norm_eps)
    want = ref.sequence_keys(n, 0, WEIGHTS, 0, ref.arch_of(SPEC))[2]
    got = np.asarray(arenas[1])[0].reshape(NB * BS, -1)[:9]
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)
    # a driver of its own: the spec in the other form
    other = Driver(_spec(index_rope_interleave=False), WEIGHTS, BS, NB)
    _, arenas, _ = other.prefill_chunk(other.arenas(), table, tokens, 0)
    moved = np.asarray(arenas[1])[0].reshape(NB * BS, -1)[:9] - got
    assert np.abs(moved[1:, :4]).max() > 1e-2 and not moved[:, 4:].any()


# ----------------------------------------------------- (b) shares add up
def test_shares_add_up_to_the_uncut_layer():
    """The eight shares of a routed layer (8 experts, one a share), the
    shared expert counted once, are the uncut layer's FFN times the
    routed scale: in the reference, and between the block's product and
    the reference. Attention, indexer and router are replicated: a
    share's are the uncut model's own arrays."""
    n, w, arch, uncut, _, cut = block_harness.shares_of_one_expert_add_up(
        ref, _spec, 2, TOL, scale=SPEC.routed_scale)
    assert np.abs(np.asarray(ref.experts(n, cut(0), 2, arch, (0, 1)))
                  - uncut).max() > 1e-2
    # the chosen experts' weights sum to the routed scale
    _, weight = ref.route(n, w['lm_moe_router.w'][2],
                          w['lm_moe_router.b'][2], 3, 2.5)
    np.testing.assert_allclose(np.asarray(weight).sum(1), 2.5, rtol=1e-6)


# --------------------------------- (a) prefill in chunks, then decode
@pytest.mark.parametrize('prompt_len,chunk', [(13, 8), (21, 16), (6, 8),
                                              (30, 30)])
def test_chunked_prefill_then_decode_matches_full_forward(prompt_len,
                                                          chunk):
    """A sequence that passes index_topk (8): its prompt prefilled in
    chunks (or in one) through the two arenas, then decoded a token at a
    time, row by row against the reference's one full forward. Past
    position 8 the three shared layers attend over what layer 0 chose."""
    for _, stats in block_harness.chunked_prefill_then_decode(
            DRIVER, ref, prompt_len, chunk, 12, TOL):
        assert np.asarray(stats).shape == (4, 4)     # the routed layers


def test_the_published_order_with_leading_layers_periods_and_a_remainder():
    """Thirteen layers: three leading dense layers that score, two whole
    periods in the scan (the selection a scan carry from one to the
    next) and two carried layers over, the attention's stacks indexed
    over all layers and the indexer's over the five that score: logits
    against the reference."""
    types = ['full'] * 3 + ['shared', 'shared', 'shared', 'full'] * 2 + \
        ['shared'] * 2
    spec = _spec(n_layer=13, dense_layers=3, indexer_types=types)
    assert spec.layer_plan() == ((F, F, F), (C, C, C, F), 2, (C, C))
    assert spec.scoring_layers() == (0, 1, 2, 6, 10)
    # a driver of its own: another spec
    deep = Driver(spec, random_weights(spec, seed=2), BS, NB)
    rng = np.random.RandomState(2)
    tokens = rng.randint(0, spec.vocab_size, 22)
    want = deep.reference_logits(ref, tokens)
    table = jnp.arange(PAGES, dtype=jnp.int32)
    got, arenas, stats = deep.prefill_chunk(deep.arenas(), table,
                                            tokens[:16], 0)
    np.testing.assert_allclose(np.asarray(got), want[:16], atol=TOL)
    assert np.asarray(stats).shape == (10, 4)
    got, arenas, _ = deep.prefill_chunk(arenas, table, tokens[16:], 16)
    np.testing.assert_allclose(np.asarray(got), want[16:], atol=TOL)


def test_a_period_without_leading_layers_carries_from_its_own_first():
    """No dense layer: the scan is the first segment, and its first
    layer scores (full, shared) x 2."""
    spec = _spec(n_layer=4, dense_layers=0,
                 indexer_types=['full', 'shared'] * 2)
    assert spec.layer_plan() == ((), (F, C), 2, ())
    # a driver of its own: another spec
    bare = Driver(spec, random_weights(spec, seed=3), BS, NB)
    tokens = np.random.RandomState(5).randint(0, spec.vocab_size, 20)
    got, _, _ = bare.prefill_chunk(
        bare.arenas(), jnp.arange(PAGES, dtype=jnp.int32), tokens, 0)
    np.testing.assert_allclose(np.asarray(got),
                               bare.reference_logits(ref, tokens), atol=TOL)


@pytest.mark.parametrize('lengths,forms', [
    ([3, 9, 17, 30, 0], 'default'), ([5, 0, 20], 'default'),
    ([5, 0, 20], 'tpu')])
def test_decode_batch_of_mixed_lengths_matches_reference(
        monkeypatch, lengths, forms):
    """Sequences of lengths on both sides of index_topk in one decode
    batch, an empty slot (0) after them or between two of them: every
    live row's logits are the reference's for that sequence; also in
    the forms a TPU's programs take (the selection's, the attention's
    and the routed product's kernels, interpreted here)."""
    platform_forms(monkeypatch, forms)
    # the forms a TPU's programs take are traced by a driver of their
    # own: the module's holds the programs traced in this platform's
    driver = DRIVER if forms == 'default' else \
        Driver(SPEC, WEIGHTS, BS, NB, pages=PAGES)
    rng = np.random.RandomState(7)
    seqs = [rng.randint(0, SPEC.vocab_size, n + 1) if n else None
            for n in lengths]
    _, stats = block_harness.decode_batch_of_mixed_lengths(
        driver, ref, seqs, driver.packed_tables(rng.permutation(NB), seqs),
        TOL)
    assert np.asarray(stats).shape == (4, 4)


# ------------------------------------- (f) what the tolerance is for
@pytest.mark.parametrize('broken', ['carry', 'select', 'state'])
def test_the_tolerance_catches_what_it_is_for(broken):
    """The planted faults, in the reference: the shared layers scoring
    for themselves with the nearest scoring layer's weights (no carry);
    every layer attending to all it holds; bfloat16 for the residual
    stream, scores, softmax and logits. Each moves the logits by far
    more than the tolerance the sound block is held to, and only past
    index_topk where the fault is in the selection."""
    lowered = {'carry': dict(carry=False), 'select': dict(select=False),
               'state': dict(state_dtype='bfloat16')}[broken]
    rng = np.random.RandomState(4)
    tokens = rng.randint(0, SPEC.vocab_size, 30)
    sound = _reference_logits(tokens)
    moved = np.abs(_reference_logits(tokens, **lowered) - sound)
    assert moved.max() > 100 * TOL
    if broken != 'state':
        # the rows below index_topk are untouched: the selection is all
        assert moved[:SPEC.index_topk].max() < TOL
        assert moved[SPEC.index_topk:].max() > 100 * TOL


@pytest.mark.parametrize('broken', ['no_carry', 'all_positions',
                                    'half_split', 'routed_scale'])
def test_the_tolerance_catches_a_wrong_block(broken):
    """And the block itself, broken: every layer scoring with an indexer
    of its own (the selection not carried), no selection in the shared
    layers' place (a block that attends over all positions there cannot
    be built from the spec, so the reference's ``select`` off stands in:
    the sound block is far from it), the indexer's other rotary form,
    the routed scale dropped."""
    rng = np.random.RandomState(2)
    tokens = rng.randint(0, SPEC.vocab_size, 24)
    table = jnp.arange(PAGES, dtype=jnp.int32)
    if broken == 'all_positions':
        got, _, _ = DRIVER.prefill_chunk(DRIVER.arenas(), table, tokens, 0)
        want = _reference_logits(tokens, select=False)
    else:
        over = {'no_carry': dict(indexer_types=['full'] * 5),
                'half_split': dict(index_rope_interleave=False),
                'routed_scale': dict(routed_scale=1.0)}[broken]
        spec = _spec(**over)
        weights = WEIGHTS
        if broken == 'no_carry':
            # the indexer of the nearest scoring layer below, in every
            # layer: what a program that dropped the carry would hold
            weights = dict(WEIGHTS)
            for name in WEIGHTS:
                if name.startswith('lm_full_idx_'):
                    weights[name] = WEIGHTS[name][[0, 0, 0, 0, 1]]
        # a driver of its own: the broken spec
        wrong = Driver(spec, weights, BS, NB)
        got, _, _ = wrong.prefill_chunk(wrong.arenas(), table, tokens, 0)
        want = _reference_logits(tokens)
        if broken == 'no_carry':
            # and that is the reference with its carry off, to rounding
            np.testing.assert_allclose(
                np.asarray(got), _reference_logits(tokens, carry=False),
                atol=TOL)
    assert np.abs(np.asarray(got) - want)[SPEC.index_topk:].max() > 1e-3


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


@pytest.fixture(scope='module')
def engine():
    eng = _engine()
    eng.warmup()
    eng.start()
    yield eng
    eng.shutdown(drain=False)


def test_engine_serves_the_reference_tokens_batched_and_alone(engine):
    """Through DecodeEngine's normal path (scheduler, pool, one block
    table, chunked prefill, the one decode signature): every request's
    greedy tokens are the reference's own choices, and the same served
    concurrently and one at a time."""
    rng = np.random.RandomState(0)
    requests = [(rng.randint(0, SPEC.vocab_size,
                             int(rng.randint(9, 34))).tolist(),
                 int(rng.randint(3, 12))) for _ in range(6)]
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


def test_engine_counts_scored_and_attended_layers_apart(engine):
    """The counters the benchmark reads: the positions attended under a
    selection over all five layers, the positions scored and the index
    keys read over the two scoring layers, and a step's attention
    layer-calls split into scored and carried."""
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

    def grown(name, **labels):
        key = name + ('{%s}' % ','.join(
            '%s=%s' % kv for kv in sorted(labels.items())) if labels else '')
        return after['counters'].get(key, 0) - before['counters'].get(key, 0)

    # three decode steps at lengths 20, 21, 22 (+1: the new token)
    seen = sum(n + 1 for n in (20, 21, 22))
    assert grown('decode.sparse_positions_seen') == 5 * seen
    assert grown('decode.sparse_positions_selected') == 5 * 3 * 8
    assert grown('decode.index_positions_scored') == 2 * seen
    assert grown('decode.selection_layer_calls', how='scored') == 3 * 2
    assert grown('decode.selection_layer_calls', how='carried') == 3 * 3
    item = 4
    assert grown('decode.cache_bytes_read', kind='lm_latent_full') == \
        5 * 3 * 8 * 16 * item
    assert grown('decode.cache_bytes_read', kind='lm_index_full') == \
        2 * seen * 8 * item
    assert grown('decode.moe_layer_steps') == 3 * 4
    assert engine.kv_bytes_per_token == lm.kv_bytes_per_token(SPEC)


def test_a_rehearsed_step_gathers_the_blocks_its_live_rows_hold():
    """The benchmark cell's engine at its rehearsal geometry (pages of
    8, tables of 8 pages: one column block of 64 positions; 8 kept; 4
    slots), the prompt bound widened to the table: a prompt of 62
    tokens and one decode step, whose one live row holds 63 of its
    block's 64 positions (a sequence may not fill its table). The step
    gathers that row's one block in each scoring layer, where every
    table to the longest length was four tables' blocks; fed two rows
    that fill their blocks with an empty slot between, the step's
    counters read scored over gathered 1.0. Its counting reaches the
    live row's tile: on a TPU by the kernel's bounds, and in the dense
    form that runs here over every column the four tables address. The
    prompt's chunks gather up to their own last block, and a tile of
    rows at or under the 8 kept counts nothing."""
    from paddle_tpu import observe
    from util import cell_spec
    spec, geometry = cell_spec('glm_5_2.long_ctx_long_answers',
                               rehearsal=True)
    assert (geometry['block_size'], geometry['pages_per_seq'],
            geometry['max_batch'], spec.index_topk) == (8, 8, 4, 8)
    engine = DecodeEngine(spec, weights=random_weights(spec, seed=57),
                          place=fluid.CPUPlace(),
                          **dict(geometry, max_prompt_len=62))
    engine.warmup()
    engine.start()
    scoring, columns = 2, 64
    names = ('decode.index_positions', 'decode.selection_columns')

    def grown(run):
        before = observe.snapshot()['counters']
        run()
        after = observe.snapshot()['counters']
        return {key: after[key] - before.get(key, 0) for key in after
                if key.startswith(names)}

    observe.enable()
    try:
        counted = {}
        for by_kernel in (False, True):
            engine._selects_by_kernel = by_kernel
            counted[by_kernel] = grown(lambda: engine.generate(
                list(range(62)), max_new_tokens=2, timeout=300))
        whole = grown(lambda: (
            engine._count_cache_reads(np.asarray([64, 64])),
            engine._count_selection_reach(np.asarray([64, 0, 64, 0]),
                                          'decode')))
    finally:
        observe.disable()
        observe.reset()
        engine.shutdown(drain=False)
    assert whole['decode.index_positions_scored'] == \
        whole['decode.index_positions_gathered{kind=decode}'] == \
        scoring * 2 * columns
    for got in counted.values():
        assert got['decode.index_positions_scored'] == scoring * 63
        assert got['decode.index_positions_gathered{kind=decode}'] == \
            scoring * columns
        # chunks of 16 rows at 0, 16 and 32 and one of 14: a block each
        assert got['decode.index_positions_gathered{kind=prefill}'] == \
            scoring * 4 * columns
        assert got['decode.selection_columns_addressed{kind=decode}'] == \
            scoring * 4 * columns
        assert got['decode.selection_columns_addressed{kind=prefill}'] == \
            scoring * 4 * 16 * columns
    # the dense form counts over all it addresses; the kernel over the
    # tiles that hold a row past the 8 kept: the step's one tile of 4
    # rows and each chunk's one tile of 16 (a first chunk of 8 rows or
    # fewer would count nothing)
    dense, kernel = counted[False], counted[True]
    for kind in ('decode', 'prefill'):
        assert dense['decode.selection_columns_counted{kind=%s}' % kind] == \
            dense['decode.selection_columns_addressed{kind=%s}' % kind]
    assert kernel['decode.selection_columns_counted{kind=decode}'] == \
        scoring * 4 * columns
    assert kernel['decode.selection_columns_counted{kind=prefill}'] == \
        scoring * 4 * 16 * columns
    from paddle_tpu.ops.pallas.selection_kth import columns_counted
    assert columns_counted(np.arange(1, 9), 8, columns, np) == 0
    assert columns_counted(np.arange(1, 17), 8, columns, np) == 16 * columns


@pytest.mark.parametrize('kw,error', [
    (dict(prefix_cache=True), NotImplementedError),
    (dict(spec_k=2), NotImplementedError),
    (dict(kv_dtype='int8'), NotImplementedError)])
def test_engine_refuses_what_it_refuses_for_every_selected_cache(kw, error):
    """By the spec's properties, not by name: a kind that reads a
    selection shares no frozen pages, and no block but 'post_ln' has a
    test for speculation or quantized arenas."""
    assert not SPEC.shares_frozen_pages()
    with pytest.raises(error):
        _engine(**kw)


def test_handoff_is_refused_for_a_cache_that_is_not_per_head_rows(engine):
    from paddle_tpu.serving import handoff
    with pytest.raises(handoff.CacheKindError):
        engine.read_pages([0])
    with pytest.raises(handoff.CacheKindError):
        handoff._geometry_header(engine)


def test_programs_write_every_arena_in_place_and_keep_the_selection():
    """The decode step and a prefill chunk as the executor jits them,
    over a pool far larger than a block of the attention's gathers: no
    instruction of the compiled program materialises a layer of either
    arena, and the selection is no output: it never leaves the
    device's program."""
    eng = _engine(num_blocks=2048)
    try:
        for traced in block_harness.programs_write_arenas_in_place(eng):
            outs = jax.tree_util.tree_leaves(traced.out_info)
            assert not any(o.dtype == jnp.bool_ for o in outs)
    finally:
        eng.shutdown(drain=False)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_weights_go_in_and_come_out_in_the_declared_layout(dtype):
    weights_round_trip(
        _spec(dtype=dtype), WEIGHTS, {'lm_full_q_b.w', 'lm_full_idx_q.w'},
        max_batch=2, block_size=BS, num_blocks=NB, pages_per_seq=PAGES)
