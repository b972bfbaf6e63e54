"""The latent_moe block as kimi_k2_6 runs it (dense latent attention,
YaRN positions, a scaled routed sum, no gate, no rescale, the prefix
cache over latent pages) against its plain reference, at a tiny size on
the CPU in float32: a leading dense layer and three routed ones, 4 heads
over a rank-12 latent, 8 experts of which 3 are held, 3 per token, one
shared, ``routed_scale`` 2.827; YaRN by 4 over 16 original positions, so
every sequence here runs past the original length.

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU; they differ in the order of their sums (in a decode
step and a short chunk the block folds the key up-projection into the
query and applies the value up-projection to the weighted sum of
latents, in a chunk of more rows it expands keys and values a column
block at a time under a running softmax; the reference expands them
head by head over the whole sequence), which at these widths gives
differences of a few 1e-6 on logits of order 1. 5e-5 leaves a margin
and is two orders and more under what plain rope, a softmax scale
without m^2, an unscaled routed sum or a suffix at positions counted
from 0 gives (checked below by breaking each)."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.models.reference import kimi_k2_6 as ref
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
import block_harness
from block_harness import Driver
from util import weights_round_trip

TOL = 5e-5
BS, PAGES, NB = 4, 16, 64            # 64 positions a sequence
F = lm.FULL
YARN = dict(type='yarn', factor=4, original_max_position_embeddings=16,
            beta_fast=4, beta_slow=1, mscale=1, mscale_all_dim=1)
PUBLISHED = dict(type='yarn', factor=64, beta_fast=32, beta_slow=1,
                 mscale=1, mscale_all_dim=1,
                 original_max_position_embeddings=4096)


def _spec(**over):
    kw = dict(
        vocab_size=64, n_layer=4, d_model=32, d_inner=24,
        block='latent_moe', layer_types=[F] * 4,
        latent={F: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8,
                        d_rope=8, d_v=8, rope_theta=100.0,
                        rope_scaling=YARN)},
        dense_layers=1, d_inner_dense=40, index_topk=0, n_experts=8,
        experts_held=3, first_expert=2, experts_per_token=3,
        n_shared_experts=1, lora_rescale=False, attn_gate=False,
        routed_scale=2.827)
    kw.update(over)
    return LMSpec(**kw)


SPEC = _spec()
WEIGHTS = random_weights(SPEC, seed=7)


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, pages=PAGES)


def _reference_logits(tokens, **lowered):
    return DRIVER.reference_logits(ref, tokens, **lowered)


# --------------------------------------------------- spec and positions
def test_a_dense_full_layer_keeps_one_kind_and_no_indexer():
    (kind,) = SPEC.cache_kinds()
    assert (kind.name, kind.slot, kind.layers, kind.width, kind.reads,
            kind.shared) == ('lm_latent_full', 'LatentFull', (0, 1, 2, 3),
                             20, (0, 0, 0, 0), True)
    names = set(lm.block_param_shapes(SPEC))
    assert not {n for n in names if 'idx' in n or 'gate.w' in n
                and 'full' in n}
    assert DRIVER.block().arena_slots == ('LatentFull',)
    # the published widths: 6 layers x 576 values, stored 640
    big = _spec(n_layer=6, layer_types=[F] * 6, latent={F: dict(
        n_head=64, q_rank=1536, kv_rank=512, d_nope=128, d_rope=64,
        d_v=128, rope_theta=50000, rope_scaling=PUBLISHED)})
    assert lm.kv_bytes_per_token(big, 'bfloat16') == 7680
    assert sum(len(k.layers) * k.width * 2 for k in big.cache_kinds()) \
        == 6912
    assert SPEC.shares_frozen_pages()
    assert not _spec(index_topk=8, index_n_heads=2, index_head_dim=8
                     ).shares_frozen_pages()


def test_yarn_table_and_m_are_the_closed_form_at_the_published_numbers():
    shape = lm.LatentShape(64, 1536, 512, 128, 64, 128, 50000, PUBLISHED)
    assert shape.yarn_range() == (8, 20)
    assert ref.yarn_range(64, 50000.0, PUBLISHED) == (8, 20)
    # c(32) = 8.91 and c(1) = 19.17
    c = [64 * np.log(4096 / (2 * np.pi * b)) / (2 * np.log(50000))
         for b in (32, 1)]
    np.testing.assert_allclose(c, [8.91, 19.17], atol=0.01)
    freq = shape.rope_frequencies()
    plain = 50000.0 ** (-np.arange(32) * 2 / 64.0)
    np.testing.assert_allclose(freq[:9], plain[:9], rtol=1e-12)
    np.testing.assert_allclose(freq[20:], plain[20:] / 64, rtol=1e-12)
    mid = 14
    r = (mid - 8) / 12.0
    np.testing.assert_allclose(
        freq[mid], plain[mid] * (1 - r) + plain[mid] / 64 * r, rtol=1e-12)
    np.testing.assert_allclose(freq, ref.pair_frequencies(
        64, 50000.0, PUBLISHED), rtol=1e-12)
    m = 0.1 * np.log(64) + 1
    np.testing.assert_allclose(m, 1.4159, atol=1e-4)
    np.testing.assert_allclose(shape.softmax_multiplier(), 2.0047, atol=1e-4)
    np.testing.assert_allclose(shape.softmax_multiplier(), m * m, rtol=1e-12)
    # what the programs are given
    attrs = lm._block_attrs(_spec(latent={F: dict(vars(shape))}), BS)
    np.testing.assert_allclose(attrs['full_rope_freq'], freq, rtol=1e-12)
    assert attrs['full_softmax_mult'] == shape.softmax_multiplier()
    # a kind without scaling gives the programs the plain powers
    unscaled = lm._block_attrs(_spec(latent={F: dict(
        vars(shape), rope_scaling=None)}), BS)
    np.testing.assert_allclose(unscaled['full_rope_freq'], plain, rtol=1e-12)
    assert unscaled['full_softmax_mult'] == 1.0


def test_mscale_that_would_scale_cos_and_sin_is_refused():
    shape = lm.LatentShape(4, 16, 12, 8, 8, 8, 100.0,
                           dict(YARN, mscale=0.7))
    with pytest.raises(ValueError):
        shape.softmax_multiplier()
    with pytest.raises(ValueError):
        lm.LatentShape(4, 16, 12, 8, 8, 8, 100.0, dict(YARN, type='linear'))


# ------------------------------------- prefill in chunks, then decode
@pytest.mark.parametrize('prompt_len,chunk', [(13, 16), (29, 8), (40, 16),
                                              (21, 5)])
def test_chunked_prefill_then_decode_matches_full_forward(prompt_len,
                                                          chunk):
    """A prompt in one chunk, in several, and one longer than the YaRN
    original length (16) at this scale, prefilled through the one arena
    and decoded a token at a time, row by row against the reference's
    one full forward."""
    for _, stats in block_harness.chunked_prefill_then_decode(
            DRIVER, ref, prompt_len, chunk, 10, TOL):
        assert np.asarray(stats).shape == (3, 4)     # the routed layers


def test_a_suffix_after_shared_pages_is_the_whole_prompts_logits():
    """Two sequences with one head: the second maps the first's frozen
    pages (the same page ids at the head of its table) and prefills only
    its own suffix from that offset. Its logits are those of the prompt
    prefilled whole, and the reference's."""
    rng = np.random.RandomState(3)
    head = rng.randint(0, SPEC.vocab_size, 24)            # 6 whole pages
    first = np.concatenate([head, rng.randint(0, SPEC.vocab_size, 7)])
    second = np.concatenate([head, rng.randint(0, SPEC.vocab_size, 9)])
    arenas = DRIVER.arenas()
    pages = rng.permutation(NB)
    table_a = jnp.asarray(pages[:PAGES], jnp.int32)
    _, arenas, _ = DRIVER.prefill_chunk(arenas, table_a, first, 0)
    # the head's six pages shared, the rest its own
    table_b = jnp.asarray(np.concatenate(
        [pages[:6], pages[PAGES:2 * PAGES - 6]]), jnp.int32)
    got, arenas, _ = DRIVER.prefill_chunk(arenas, table_b, second[24:], 24)
    want = _reference_logits(second)
    np.testing.assert_allclose(np.asarray(got), want[24:], atol=TOL)
    whole, _, _ = DRIVER.prefill_chunk(
        DRIVER.arenas(), jnp.asarray(pages[2 * PAGES:3 * PAGES], jnp.int32),
        second, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole)[24:],
                               atol=TOL)
    # and the first sequence still decodes as the reference says
    nxt = int(np.argmax(_reference_logits(first)[-1]))
    step, _, _ = DRIVER.decode(arenas, table_a[None, :], [nxt], [len(first)])
    np.testing.assert_allclose(
        np.asarray(step)[0],
        _reference_logits(np.concatenate([first, [nxt]]))[-1], atol=TOL)


def test_decode_batch_of_mixed_lengths_matches_reference():
    rng = np.random.RandomState(9)
    pages = rng.permutation(NB)
    seqs = [rng.randint(0, SPEC.vocab_size, n + 1) for n in (5, 18, 33)]
    block_harness.decode_batch_of_mixed_lengths(
        DRIVER, ref, seqs, pages[:3 * PAGES].reshape(3, PAGES), TOL)


@pytest.mark.parametrize('broken', ['yarn', 'softmax_mscale',
                                    'scale_routed', 'offset_from',
                                    'state_dtype'])
def test_the_tolerance_catches_what_it_is_for(broken):
    """The reference with one thing lowered is far outside the tolerance
    the block is held to: plain rope, the softmax scale without m^2, the
    routed sum unscaled, a suffix's positions counted from 0 after a
    shared head of 24, bfloat16 state."""
    tokens = np.random.RandomState(4).randint(0, SPEC.vocab_size, 40)
    lowered = {'offset_from': 24, 'state_dtype': 'bfloat16'}.get(
        broken, False)
    diff = np.abs(_reference_logits(tokens, **{broken: lowered})
                  - _reference_logits(tokens))
    assert diff[30:].max() > 100 * TOL


def test_shares_add_up_to_the_uncut_layer():
    """The eight shares of a routed layer (8 experts, one a share), the
    shared expert counted once and the routed sum scaled by 2.827, are
    the uncut layer's FFN: in the reference, and between the block's
    product and the reference. Attention and router are replicated: a
    share's are the uncut model's own arrays."""
    n, w, arch, uncut, shared, _ = block_harness.shares_of_one_expert_add_up(
        ref, _spec, 1, TOL, scale=SPEC.routed_scale)
    # the scale is on the routed sum alone
    unscaled = np.asarray(ref.experts(
        n, w, 1, dict(arch, scale_routed=False), (0, 8)))
    np.testing.assert_allclose(uncut - shared,
                               (unscaled - shared) * 2.827, atol=TOL)


# ------------------------------------------------------------ the engine
def _engine(**over):
    kw = dict(max_batch=4, block_size=BS, num_blocks=NB,
              pages_per_seq=PAGES, max_prompt_len=48, prefill_chunk=16,
              min_prompt_bucket=8, weights=WEIGHTS, prefix_cache=True)
    kw.update(over)
    return DecodeEngine(SPEC, **kw)


@pytest.fixture(scope='module')
def engine():
    eng = _engine()
    eng.warmup()
    eng.start()
    yield eng
    eng.shutdown(drain=False)


def _sessions(seed=0, docs=2, asks=3):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(docs):
        doc = rng.randint(0, SPEC.vocab_size, 30).tolist()
        for _ in range(asks):
            out.append((doc + rng.randint(0, SPEC.vocab_size, 5).tolist(),
                        int(rng.randint(3, 9))))
    return out


def test_engine_serves_shared_pages_with_a_step_in_flight(engine):
    """Through DecodeEngine's normal path with the prefix cache on: the
    first ask of a document prefills it in chunks and publishes its
    pages; later asks arrive while earlier ones decode (a step is in
    flight whenever nothing is admittable), map the shared pages and
    prefill their suffix. Every request's greedy tokens are the
    reference's own choices, and the same as served by an engine
    without the prefix cache, one at a time."""
    from paddle_tpu import observe
    requests = _sessions()
    observe.enable()
    try:
        before = observe.snapshot()['counters']
        streams = []
        for prompt, n in requests:
            streams.append(engine.submit(prompt, max_new_tokens=n))
        together = [s.result(300) for s in streams]
        after = observe.snapshot()['counters']
    finally:
        observe.disable()
        observe.reset()
    arch, held = ref.arch_of(SPEC), ref.held_of(SPEC)
    for (prompt, n), tokens in zip(requests, together):
        assert len(tokens) == n
        gaps, _ = ref.token_gaps(WEIGHTS, arch, held, prompt, tokens, 8)
        assert max(gaps) <= TOL

    def grown(name):
        return sum(v for k, v in after.items() if k.split('{')[0] == name) \
            - sum(v for k, v in before.items() if k.split('{')[0] == name)
    # 2 documents x 3 asks: the first of each misses, the others map the
    # document's 7 whole pages (28 of its 30 tokens)
    assert grown('decode.prefix_tokens_reused_total') == 4 * 28
    assert grown('decode.prompt_tokens_total') == 6 * 35
    assert grown('decode.steps_ahead_total') > 0
    assert grown('decode.prefix_pages_published_total') >= 2 * 7
    assert engine.prefix_cache.evictions == 0
    plain = _engine(prefix_cache=False)
    try:
        plain.warmup()
        plain.start()
        alone = [plain.generate(p, max_new_tokens=n, timeout=300)
                 for p, n in requests]
    finally:
        plain.shutdown(drain=False)
    assert alone == together


def test_engine_counts_every_cached_position_and_the_chunks_pairs(engine):
    """The counters the benchmark reads: with no selection a decode
    step's attention reads every cached position of the one kind (and
    ``decode.sparse_*`` is not fed), a prefill counts the (query, key)
    pairs of its chunks over the four layers, and the prefill's span
    carries the cached span."""
    from paddle_tpu import observe
    prompt = list(range(1, 21))
    observe.enable()
    try:
        before = observe.snapshot()['counters']
        engine.generate(prompt, max_new_tokens=4, timeout=300)
        middle = observe.snapshot()['counters']
        engine.generate(prompt + [7, 8, 9], max_new_tokens=1, timeout=300)
        after = observe.snapshot()['counters']
    finally:
        observe.disable()
        observe.reset()

    def grown(name, a, b):
        return sum(v for k, v in b.items() if k.split('{')[0] == name) - \
            sum(v for k, v in a.items() if k.split('{')[0] == name)
    seen = sum(n + 1 for n in (20, 21, 22))
    assert grown('decode.cache_bytes_read', before, middle) == \
        4 * seen * 20 * 4
    assert [k for k in middle if k.startswith('decode.cache_bytes_read')] \
        == ['decode.cache_bytes_read{kind=lm_latent_full}']
    assert not [k for k in after if k.startswith('decode.sparse_')]
    assert grown('decode.prefill_attn_pairs', before, middle) == \
        4 * 20 * 21 // 2
    # the second prompt maps 5 whole pages (20 tokens) and prefills 3
    assert grown('decode.prefix_tokens_reused_total', middle, after) == 20
    assert grown('decode.prefill_attn_pairs', middle, after) == \
        4 * (21 + 22 + 23)


def test_each_chunks_span_carries_its_own_pairs(engine):
    """A prefill of several chunks (``prefill_chunk`` 16): every
    ``decode.prefill.chunk`` span has the pairs of its own positions,
    which add up to the ``decode.prefill.run`` span's; a prefill of one
    chunk has the run's span alone."""
    from paddle_tpu import observe
    observe.enable()
    try:
        engine.prefix_cache.clear()
        engine.generate(list(range(30, 70)), max_new_tokens=1, timeout=300)
        engine.generate(list(range(80, 90)), max_new_tokens=1, timeout=300)
        events = [ev for ev in observe.spans().events()
                  if ev['name'].startswith('decode.prefill.')]
    finally:
        observe.disable()
        observe.reset()
    runs = [ev['args'] for ev in events if ev['name'] == 'decode.prefill.run']
    chunks = [ev['args'] for ev in events
              if ev['name'] == 'decode.prefill.chunk']
    assert [r['chunks'] for r in runs] == [3, 1]
    assert [c['start'] for c in chunks] == [0, 16, 32]
    assert [c['attn_pairs'] for c in chunks] == [
        4 * sum(range(a + 1, b + 1)) for a, b in ((0, 16), (16, 32),
                                                  (32, 40))]
    assert runs[0]['attn_pairs'] == sum(c['attn_pairs'] for c in chunks) \
        == 4 * 40 * 41 // 2
    assert runs[1]['attn_pairs'] == 4 * 10 * 11 // 2


@pytest.mark.parametrize('spec,kw', [
    (SPEC, dict(spec_k=2)), (SPEC, dict(kv_dtype='int8')),
    (_spec(index_topk=8, index_n_heads=2, index_head_dim=8),
     dict(prefix_cache=True)),
    (_spec(layer_types=[F, F, lm.SLIDING, F], sliding_window=5, latent={
        F: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8, d_rope=8,
                d_v=8, rope_theta=100.0),
        lm.SLIDING: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8,
                         d_rope=8, d_v=8, rope_theta=100.0)}),
     dict(prefix_cache=True))])
def test_engine_refuses_what_has_no_test_for_this_block(spec, kw):
    """Speculation and quantized arenas for the block, and the prefix
    cache for a spec with a selected or a windowed kind."""
    with pytest.raises(NotImplementedError):
        DecodeEngine(spec, **dict(dict(
            max_batch=2, block_size=BS, num_blocks=NB, pages_per_seq=PAGES),
            **kw))


def test_programs_write_the_arena_in_place():
    eng = _engine(num_blocks=2048)
    try:
        block_harness.programs_write_arenas_in_place(eng)
    finally:
        eng.shutdown(drain=False)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_weights_go_in_and_come_out_in_the_declared_layout(dtype):
    """``q_b`` of the one kind is held ``[n, out, q_rank]``
    (model.HeldTransposed) and loaded, exported and handed out on the
    device as declared, ``[n, q_rank, out]``, bit for bit; every other
    parameter is the array the programs read (util.weights_round_trip)."""
    weights_round_trip(
        _spec(dtype=dtype), WEIGHTS, {'lm_full_q_b.w'},
        max_batch=2, block_size=BS, num_blocks=NB, pages_per_seq=PAGES)
