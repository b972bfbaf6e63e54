"""The shortcut_moe block (longcat_flash: two latent attentions and two
dense FFNs a layer, one shortcut-connected expert branch, a softmax
router a third of whose outputs are identity experts) against its plain
reference, at a tiny size on the CPU in float32: three layers (six
sublayers, six cache layers), 4 heads over a rank-12 latent with the
latents rescaled, 16 real experts of which 4 are held beside 8 identity
experts, 6 a token, ``routed_scale`` 6.

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU; they differ in the order of their sums (the block
folds the key up-projection into the query in a decode step and a short
chunk and expands keys and values a column block at a time under a
running softmax in a longer one; the reference expands them head by head
over the whole sequence), which at these widths gives differences of a
few 1e-6 on logits of order 1. 5e-5, as tests/test_kimi_k2_6_block.py
has it, leaves a margin and is two orders and more under what the
shortcut placed after the first FFN, normalised weights, a dropped
factor 6, a dropped identity term, a second sublayer that reads the
first's rows or bfloat16 state gives (checked below by breaking
each)."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.models.reference import longcat_flash_chat as ref
from paddle_tpu.ops import moe_held_ops as moe
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
import block_harness
from block_harness import Driver
from util import weights_round_trip

TOL = 5e-5
BS, PAGES, NB = 4, 16, 64            # 64 positions a sequence
F = lm.FULL
PUBLISHED = dict(n_head=64, q_rank=1536, kv_rank=512, d_nope=128,
                 d_rope=64, d_v=128, rope_theta=1e7)


def _spec(**over):
    kw = dict(
        vocab_size=64, n_layer=3, d_model=32, d_inner=24,
        block='shortcut_moe', layer_types=[F] * 3,
        latent={F: dict(n_head=4, q_rank=16, kv_rank=12, d_nope=8,
                        d_rope=8, d_v=8, rope_theta=100.0)},
        d_inner_dense=40, n_experts=16, zero_experts=8, experts_held=4,
        first_expert=4, experts_per_token=6, lora_rescale=True,
        attn_gate=False, routed_scale=6.0)
    kw.update(over)
    return LMSpec(**kw)


SPEC = _spec()
WEIGHTS = random_weights(SPEC, seed=49)


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, pages=PAGES)


def _reference_logits(tokens, **lowered):
    return DRIVER.reference_logits(ref, tokens, **lowered)


# ------------------------------------------------------ spec and table
def test_a_token_keeps_two_cache_layers_a_layer_of_the_one_kind():
    (kind,) = SPEC.cache_kinds()
    assert (kind.name, kind.slot, kind.layers, kind.width, kind.reads,
            kind.shared) == ('lm_latent_full', 'LatentFull',
                             (0, 1, 2, 3, 4, 5), 20, (0,) * 6, True)
    assert SPEC.sublayers == 2 and SPEC.cache_layers_of(F) == kind.layers
    assert SPEC.layer_plan() == ((), (F,), 3, ())
    assert DRIVER.block().arena_slots == ('LatentFull',)
    # the published widths: 4 layers x 2 sublayers x 576 values, stored
    # 640, bfloat16
    big = _spec(n_layer=4, layer_types=[F] * 4, latent={F: PUBLISHED},
                d_model=6144, d_inner=2048, d_inner_dense=12288)
    assert lm.kv_bytes_per_token(big, 'bfloat16') == 8 * 640 * 2 == 10240
    assert sum(len(k.layers) * k.width * 2 for k in big.cache_kinds()) \
        == 8 * 576 * 2
    assert lm.arena_bytes(big, 8192, 32, 'bfloat16') == 8192 * 32 * 10240
    assert not SPEC.shares_frozen_pages() and not SPEC.per_head_cache()
    # one cache layer a layer everywhere else
    kimi = LMSpec(vocab_size=64, n_layer=2, d_model=32, d_inner=24,
                  block='latent_moe', latent={F: SPEC.latent[F].__dict__},
                  n_experts=4, experts_per_token=2, n_shared_experts=1,
                  index_topk=0)
    assert kimi.sublayers == 1
    assert kimi.cache_kinds()[0].layers == kimi.layers_of(F) == (0, 1)


def test_the_parameter_table_stacks_sublayers_and_has_no_shared_expert():
    table = lm.block_param_shapes(SPEC)
    assert not [n for n in table if 'shr' in n or 'gate.w' in n
                and 'full' in n or 'idx' in n]
    sub = SPEC.n_layer * 2
    for name in ('lm_stack_ln1.w', 'lm_stack_ln2.w', 'lm_full_q_a.w',
                 'lm_full_kv_a.w', 'lm_full_kv_bk.w', 'lm_full_o.w',
                 'lm_dense_gate.w', 'lm_dense_up.w', 'lm_dense_down.w'):
        assert table[name][0][0] == sub, name
    for name in ('lm_moe_router.w', 'lm_moe_router.b', 'lm_moe_exp_gate.w',
                 'lm_moe_exp_up.w', 'lm_moe_exp_down.w'):
        assert table[name][0][0] == SPEC.n_layer, name
    # the router keeps its width, real and identity experts together
    assert table['lm_moe_router.w'][0] == [3, 32, 24]
    assert table['lm_moe_exp_gate.w'][0] == [3, 4, 32, 24]
    # the latents are rescaled: what reads one counts the hidden width
    assert table['lm_full_q_b.w'][1] == table['lm_full_kv_bv.w'][1] == 32
    held = lm.block_param_shapes(_spec(experts_held=1, first_expert=3))
    assert {k for k in table if table[k][0] != held[k][0]} == {
        'lm_moe_exp_gate.w', 'lm_moe_exp_up.w', 'lm_moe_exp_down.w'}


@pytest.mark.parametrize('over', [
    dict(n_shared_experts=1), dict(zero_experts=0), dict(attn_gate=True),
    dict(index_topk=4, index_n_heads=2, index_head_dim=8),
    dict(dense_layers=1), dict(d_inner_dense=0),
    dict(experts_per_token=25), dict(first_expert=14)])
def test_the_spec_refuses_what_the_block_is_not(over):
    with pytest.raises(ValueError):
        _spec(**over)


def test_identity_experts_are_refused_for_every_other_block():
    with pytest.raises(ValueError):
        LMSpec(vocab_size=64, n_layer=2, d_model=32, d_inner=24,
               block='latent_moe', latent={F: SPEC.latent[F].__dict__},
               n_experts=4, experts_per_token=2, n_shared_experts=1,
               index_topk=0, zero_experts=2)


# ------------------------------------- prefill in chunks, then decode
@pytest.mark.parametrize('prompt_len,chunk', [(13, 16), (29, 8), (40, 16),
                                              (21, 5)])
def test_chunked_prefill_then_decode_matches_full_forward(prompt_len,
                                                          chunk):
    """A prompt in one chunk, in several (each after the first from an
    offset, and chunks of 5 that start inside a page), prefilled through
    the one arena's six cache layers and decoded a token at a time, row
    by row against the reference's one full forward."""
    for rows, stats in block_harness.chunked_prefill_then_decode(
            DRIVER, ref, prompt_len, chunk, 10, TOL):
        # the routed layers; 4 of load and the rows by 0 .. 6 real experts
        assert np.asarray(stats).shape == (3, 4 + 7)
        assert (np.asarray(stats)[:, 4:].sum(axis=1) == rows).all()


def test_decode_batch_of_mixed_lengths_matches_reference():
    rng = np.random.RandomState(9)
    pages = rng.permutation(NB)
    seqs = [rng.randint(0, SPEC.vocab_size, n + 1) for n in (5, 18, 33)]
    # a fourth row that holds no sequence rides along
    tables = np.concatenate([pages[:3 * PAGES].reshape(3, PAGES),
                             np.full((1, PAGES), NB)])
    _, stats = block_harness.decode_batch_of_mixed_lengths(
        DRIVER, ref, seqs + [None], tables, TOL)
    # three live rows in every layer's count
    assert (np.asarray(stats)[:, 4:].sum(axis=1) == 3).all()


@pytest.mark.parametrize('broken,lowered', [
    ('shortcut_last', False), ('raw_weights', False),
    ('scale_routed', False), ('identity', False), ('own_rows', False),
    ('state_dtype', 'bfloat16')])
def test_the_tolerance_catches_what_it_is_for(broken, lowered):
    """The reference with one thing changed is far outside the tolerance
    the block is held to: the shortcut added after the first FFN (so the
    second sublayer sees it), the chosen weights normalised, the factor
    6 dropped, the identity term dropped, the second sublayer reading
    the first's cache rows, bfloat16 state. So a block that computed any
    of these fails the tests above."""
    tokens = np.random.RandomState(4).randint(0, SPEC.vocab_size, 40)
    diff = np.abs(_reference_logits(tokens, **{broken: lowered})
                  - _reference_logits(tokens))
    assert diff[30:].max() > 100 * TOL


# ------------------------------------------------------------ the router
def _plain_route(x, router, bias, k, scale):
    """float64 numpy: softmax, the k largest of p + e by a stable sort
    (ties to the lower index), the chosen p times the scale."""
    z = x.astype(np.float64) @ router.astype(np.float64)
    p = np.exp(z - z.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    chosen = np.argsort(-(p.astype(np.float32) + bias.astype(np.float32)),
                        axis=1, kind='stable')[:, :k]
    return chosen, np.take_along_axis(p, chosen, axis=1) * scale


def test_router_is_a_plain_topk_of_softmax_plus_bias_unnormalised():
    rng = np.random.RandomState(2)
    x = rng.randn(33, 32).astype('float32')
    router = (rng.randn(32, 24) * 0.4).astype('float32')
    bias = (rng.randn(24) * 0.02).astype('float32')
    # ties: outputs 3 and 17 (a real and an identity expert) and 5 and 6
    # score alike in every row and carry the same bias
    router[:, 17] = router[:, 3]
    router[:, 6] = router[:, 5]
    bias[17], bias[6] = bias[3], bias[5]
    chosen, weight = moe.route_softmax_topk(x, router, 6, bias=bias,
                                            scale=6.0)
    want_chosen, want_weight = _plain_route(x, router, bias, 6, 6.0)
    np.testing.assert_array_equal(np.asarray(chosen), want_chosen)
    np.testing.assert_allclose(np.asarray(weight), want_weight, rtol=1e-5)
    # a tie goes to the lower index wherever only one of a pair fits
    picked = np.asarray(chosen)
    assert not ((picked == 17).any(1) & ~(picked == 3).any(1)).any()
    assert not ((picked == 6).any(1) & ~(picked == 5).any(1)).any()
    # the bias chooses and does not weigh; the weights are not
    # normalised: they sum to 6 x the chosen mass, under 6
    unbiased, _ = moe.route_softmax_topk(x, router, 6, scale=6.0)
    assert (np.asarray(unbiased) != picked).any()
    assert (np.asarray(weight).sum(1) < 6.0).all()
    assert np.ptp(np.asarray(weight).sum(1)) > 0.1
    # mellum's form is as it was: normalised over the chosen, no bias
    same, normed = moe.route_softmax_topk(x, router, 6)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(unbiased))
    np.testing.assert_allclose(np.asarray(normed).sum(1), 1.0, rtol=1e-6)


def test_a_row_whose_choices_are_all_identity_runs_no_expert():
    """Rows pushed onto identity experts alone by the bias: the branch
    is the row times the sum of its weights, no assignment is local, no
    expert is touched and no row tile runs."""
    rng = np.random.RandomState(5)
    spec = _spec(experts_held=16, first_expert=0)
    w = random_weights(spec, seed=3)
    w['lm_moe_router.b'] = np.where(np.arange(24) >= 16, 1.0, 0.0)[
        None, :].repeat(3, 0).astype('float32')
    block = Driver(spec, w, BS, NB).block()
    n = jnp.asarray(rng.randn(5, 32), jnp.float32)
    m, stats = block._routed(n, 1, None)
    chosen, weight = moe.route_softmax_topk(
        n, w['lm_moe_router.w'][1], 6, bias=w['lm_moe_router.b'][1],
        scale=6.0)
    assert (np.asarray(chosen) >= 16).all()
    np.testing.assert_allclose(
        np.asarray(m), np.asarray(weight).sum(1)[:, None] * np.asarray(n),
        rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(m), np.asarray(ref.experts(n, w, 1, ref.arch_of(spec),
                                              (0, 16))), atol=TOL)
    # local, busiest, touched, tiles; then the rows by real experts: all
    # five chose none
    np.testing.assert_array_equal(
        np.asarray(stats), [0, 0, 0, 0, 5, 0, 0, 0, 0, 0, 0])


def test_shares_add_up_to_the_uncut_branch():
    """The four shares of a layer's expert branch (16 real experts, 4 a
    share), with the identity term, which every chip computes alike for
    its own rows, counted once, are the uncut branch ``MoE(n0)``: in the
    reference, and between the block's own functions and the reference.
    The router is replicated: a share's is the uncut model's array."""
    whole = _spec(experts_held=16, first_expert=0)
    w = random_weights(whole, seed=11)
    n = jnp.asarray(np.random.RandomState(1).randn(7, whole.d_model),
                    jnp.float32)
    arch = ref.arch_of(whole)
    layer = 1
    uncut = np.asarray(ref.experts(n, w, layer, arch, (0, 16)))

    def cut(first):
        out = dict(w)
        for part in ('gate', 'up', 'down'):
            name = 'lm_moe_exp_%s.w' % part
            out[name] = w[name][:, first:first + 4]
        return out

    chosen, weight = moe.route_softmax_topk(
        n, w['lm_moe_router.w'][layer], whole.experts_per_token,
        bias=w['lm_moe_router.b'][layer], scale=whole.routed_scale)
    identity = np.asarray(moe.identity_weight(chosen, weight, 16))[:, None] \
        * np.asarray(n)
    assert np.abs(identity).max() > 0.1          # the term is not nothing
    from_reference, from_block = identity.copy(), identity.copy()
    for first in range(0, 16, 4):
        share = cut(first)
        # a share of the reference carries the identity term too: once
        from_reference += np.asarray(
            ref.experts(n, share, layer, arch, (first, 4))) - identity
        gate, _ = moe.held_gates(chosen, weight, first, 4)
        from_block += np.asarray(moe.gated_experts(
            n, gate, *(jnp.asarray(share['lm_moe_exp_%s.w' % p][layer])
                       for p in ('gate', 'up', 'down'))))
        # and the block's own branch on that share is the reference's
        spec = _spec(experts_held=4, first_expert=first)
        mine, _ = Driver(spec, share, BS, NB).block()._routed(
            n, layer, None)
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(ref.experts(
                n, share, layer, arch, (first, 4))), atol=TOL)
    np.testing.assert_allclose(from_reference, uncut, atol=TOL)
    np.testing.assert_allclose(from_block, uncut, atol=TOL)
    # without the identity term the uncut branch is the real experts'
    bare = np.asarray(ref.experts(n, w, layer, dict(arch, identity=False),
                                  (0, 16)))
    np.testing.assert_allclose(uncut - bare, identity, atol=TOL)


# ------------------------------------------------------------ the engine
def _engine(**over):
    kw = dict(max_batch=4, block_size=BS, num_blocks=NB,
              pages_per_seq=PAGES, max_prompt_len=48, prefill_chunk=16,
              min_prompt_bucket=8, weights=WEIGHTS)
    kw.update(over)
    return DecodeEngine(SPEC, **kw)


@pytest.fixture(scope='module')
def engine():
    eng = _engine()
    eng.warmup()
    eng.start()
    yield eng
    eng.shutdown(drain=False)


def test_engine_serves_the_references_tokens_and_counts_the_routing(engine):
    """Through DecodeEngine's normal path: prompts prefilled in chunks of
    16 and decoded together. Every request's greedy tokens are the
    reference's own choices; the counters the benchmark reads add up."""
    from paddle_tpu import observe
    rng = np.random.RandomState(0)
    requests = [(rng.randint(0, SPEC.vocab_size, n).tolist(), m)
                for n, m in ((35, 6), (9, 8), (20, 5), (41, 7))]
    observe.enable()
    try:
        before = observe.snapshot()
        streams = [engine.submit(p, max_new_tokens=m) for p, m in requests]
        together = [s.result(300) for s in streams]
        after = observe.snapshot()
    finally:
        observe.disable()
        observe.reset()
    arch, held = ref.arch_of(SPEC), ref.held_of(SPEC)
    for (prompt, m), tokens in zip(requests, together):
        assert len(tokens) == m
        gaps, _ = ref.token_gaps(WEIGHTS, arch, held, prompt, tokens, 8)
        assert max(gaps) <= TOL

    def grown(name):
        a, b = before['counters'], after['counters']
        return sum(v for k, v in b.items() if k.split('{')[0] == name) - \
            sum(v for k, v in a.items() if k.split('{')[0] == name)
    total = grown('decode.moe_assignments')
    zero = grown('decode.moe_zero_assignments')
    real = grown('decode.moe_real_assignments')
    # a decode step's rows x 6 choices x 3 layers: each real or identity
    assert total > 0 and total % (6 * 3) == 0
    assert zero + real == total and zero > 0 and real > 0
    # held real experts are among the real choices
    assert 0 < grown('decode.moe_local_assignments') <= real
    hist = after['histograms']['decode.moe_real_experts_per_token']
    assert hist['count'] == total // 6
    np.testing.assert_allclose(hist['sum'], real)
    assert 0 <= hist['min'] and hist['max'] <= 6
    # both sublayers' rows of every live sequence, at the row's own width
    assert [k for k in after['counters']
            if k.startswith('decode.cache_bytes_read')] == [
                'decode.cache_bytes_read{kind=lm_latent_full}']
    steps = grown('decode.moe_layer_steps') // 3
    assert grown('decode.cache_bytes_read') % (6 * 20 * 4) == 0
    assert grown('decode.cache_bytes_read') >= steps * 6 * 20 * 4 * 10
    # a prefill's pairs over the six attentions
    assert grown('decode.prefill_attn_pairs') == 6 * sum(
        n * (n + 1) // 2 for n in (35, 9, 20, 41))
    # the same one at a time
    alone = [engine.generate(p, max_new_tokens=m, timeout=300)
             for p, m in requests]
    assert alone == together


@pytest.mark.parametrize('kw', [dict(prefix_cache=True), dict(spec_k=2),
                                dict(kv_dtype='int8')])
def test_engine_refuses_what_has_no_test_for_this_block(kw):
    with pytest.raises(NotImplementedError):
        DecodeEngine(SPEC, **dict(dict(
            max_batch=2, block_size=BS, num_blocks=NB, pages_per_seq=PAGES),
            **kw))


def test_the_page_handoff_is_refused(engine):
    from paddle_tpu.serving.handoff import CacheKindError
    with pytest.raises(CacheKindError):
        engine.kv_geometry()


def test_programs_write_the_arena_in_place():
    eng = _engine(num_blocks=2048)
    try:
        block_harness.programs_write_arenas_in_place(eng)
    finally:
        eng.shutdown(drain=False)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_weights_go_in_and_come_out_in_the_declared_layout(dtype):
    """``q_b`` of the sublayers' stack is held ``[n, out, q_rank]``
    (model.HeldTransposed) and loaded, exported and handed out on the
    device as declared, ``[n, q_rank, out]``, bit for bit; every other
    parameter is the array the programs read (util.weights_round_trip)."""
    weights_round_trip(
        _spec(dtype=dtype), WEIGHTS, {'lm_full_q_b.w'},
        max_batch=2, block_size=BS, num_blocks=NB, pages_per_seq=PAGES)
