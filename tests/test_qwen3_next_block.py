"""The delta_hybrid block (qwen3_next: three linear-attention layers
under the gated delta rule to each gated full-attention layer, routed
experts beside a gated shared expert in every layer) against its plain
reference, at a tiny size on the CPU in float32: two periods ``L L L F``,
4 value heads of 8 over 2 key heads of 8, scan chunks of 8, 4 query
heads over 2 KV heads of 16 of which 4 columns are rotated, 4 of 8
experts of 24 held, top-3, a shared expert of 24.

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU and differ in the order of their sums (the chunked
form with its triangular inverse and blockwise attention against a
token-by-token recurrence and one softmax; a tile list against a loop
over the experts). What this block adds to that is the gated norm over
a head's 8 values of ``o = S^T q``, a sum whose terms cancel: where a
head's ``mean(o^2)`` is at or under the norm's eps the norm multiplies
the sum's rounding by up to ``eps^-1/2``. At the published eps 1e-6 a
tenth of the (row, head) pairs of this tiny model lie there (mean
squares of 1e-6 to 1e-9 against 4e-4 typical), the two sides drift by
up to 2e-3 over the 8 layers, and the reference itself moves as far
when its weights are perturbed by one part in 1e7: no tolerance can
tell a wrong layer from that. So the tiny model's eps is 1e-3 (every
norm's; the others' inputs have a mean square near 1 and do not feel
it), under which 16 draws of weights and tokens read 1e-5 to 6e-5.
1e-4 leaves a margin and is an order and more under what a piece left
out or lowered gives (checked below by breaking each). Whether a
program copies an arena is a property of the chip's compiler:
tests/test_v5e_compile.py reads it off the programs compiled for a
described v5e at the published geometry."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observe
from paddle_tpu.models.reference import qwen3_next as ref
from paddle_tpu.ops import delta_hybrid_ops as dho
from paddle_tpu.ops import gated_delta_ops as gdo
from paddle_tpu.ops import moe_held_ops as moe
from paddle_tpu.ops import paged_decode_ops as pdo
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
import block_harness
from block_harness import BS, NB, PAGES, SLOTS, Driver, tokens as _tokens

TOL = 1e-4
CHUNK = 16                               # the engine's prefill chunk
L, F = lm.LINEAR, lm.FULL
PATTERN = [L, L, L, F] * 2


@pytest.fixture(autouse=True)
def _clean_observe():
    yield
    observe.disable()
    observe.reset()


def _spec(**over):
    kw = dict(
        vocab_size=64, n_layer=8, n_head=4, n_kv_head=2, d_key=16,
        d_value=16, d_model=32, d_inner=24, block='delta_hybrid',
        layer_types=PATTERN, ssm_heads=4, ssm_head_dim=8, ssm_state=8,
        ssm_groups=2, ssm_conv=4, ssm_chunk=8, n_experts=8, experts_held=4,
        first_expert=2, experts_per_token=3, n_shared_experts=1,
        d_inner_shared=24, norm_eps=1e-3, rope_theta=1e7, rotary_dim=4)
    kw.update(over)
    return LMSpec(**kw)


def _weights(spec, seed):
    """``random_weights`` with the decays spread as the published
    initialiser spreads them (A uniform in (0, 16), dt_bias ones): drawn
    near zero every head would forget alike."""
    w = random_weights(spec, seed=seed)
    rng = np.random.RandomState(seed + 1)
    shape = w['lm_gdn_a_log'].shape
    w['lm_gdn_a_log'] = np.log(rng.uniform(0.05, 16.0, shape)).astype('f')
    w['lm_gdn_dt.b'] = np.ones(shape, 'f')
    return w


SPEC = _spec()
WEIGHTS = _weights(SPEC, 61)


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, slots=SLOTS, pages=PAGES)


def _reference_logits(tokens, **lowered):
    return DRIVER.reference_logits(ref, tokens, **lowered)


# ------------------------------------------------------------- the spec
def test_the_state_is_a_matrix_a_head_beside_pages_of_the_full_layers():
    """K and V pages for the two full-attention layers; for the six
    linear-attention layers a state ``[value heads, key width, value
    width]`` and the convolution's three kept rows over ``[q; k; v]``, in
    the pool of slots; zero-centred gains start at zero and the gated
    norm's at one; a query and a gate a head; a gate on the shared
    expert; the prefix cache and speculation refused for the state."""
    kinds = {k.name: k for k in SPEC.cache_kinds()}
    assert sorted(kinds) == ['lm_kcache', 'lm_ssm_conv', 'lm_ssm_state',
                             'lm_vcache']
    assert kinds['lm_kcache'].layers == (3, 7)
    assert kinds['lm_kcache'].width == 2 * 16
    assert kinds['lm_ssm_state'].layers == (0, 1, 2, 4, 5, 6)
    assert kinds['lm_ssm_state'].per_seq == (4, 8, 8)
    assert kinds['lm_ssm_state'].dtype == 'float32'
    assert kinds['lm_ssm_conv'].per_seq == (3 * (2 * 2 * 8 + 4 * 8),)
    assert [p.per_sequence for p in SPEC.page_pools()] == [False, True]
    assert SPEC.layer_plan() == ((), (L, L, L, F), 2, ())
    assert SPEC.attn_windows() == [0, 0]
    table = lm.block_param_shapes(SPEC)
    assert table['lm_gdn_in.w'][0] == [6, 32, 16 + 16 + 32 + 32]
    assert table['lm_gdn_ba.w'][0] == [6, 32, 8]
    assert table['lm_gdn_conv.w'][0] == [6, 4, 64]
    assert table['lm_attn_q.w'][0] == table['lm_attn_gate.w'][0] \
        == [2, 64, 32]
    assert table['lm_moe_shr_sg.w'][0] == [8, 32]
    assert table['lm_moe_router.w'][0] == [8, 32, 8]
    zero_centred = [n for n, (_, fan, _) in table.items() if fan == 0]
    assert sorted(zero_centred) == [
        'lm_attn_k_ln.w', 'lm_attn_q_ln.w', 'lm_final_ln.w',
        'lm_gdn_a_log', 'lm_gdn_dt.b', 'lm_stack_ln1.w', 'lm_stack_ln2.w']
    assert table['lm_gdn_norm.w'][1] is None
    assert SPEC.keeps_state()
    for what in ('prefix_cache', 'speculation'):
        assert 'state' in SPEC.refusal(what)
        assert 'state-space' not in SPEC.refusal(what)
    with pytest.raises(NotImplementedError, match='cannot be rewound'):
        lm.build_lm_programs(SPEC, 4, BS, NB, PAGES, spec_k=2)
    with pytest.raises(NotImplementedError, match='recurrent state'):
        DecodeEngine(SPEC, max_batch=SLOTS, block_size=BS, num_blocks=NB,
                     pages_per_seq=PAGES, prefix_cache=True)


@pytest.mark.parametrize('over,what', [
    (dict(ssm_groups=3), 'value heads that do not divide by the key heads'),
    (dict(n_shared_experts=0), 'no shared expert'),
    (dict(d_inner_shared=0), 'a shared expert of no width'),
    (dict(experts_held=8), 'more experts held than there are from 2 on'),
    (dict(rotary_dim=5), 'an odd count of rotated columns'),
    (dict(rotary_dim=32), 'more rotated columns than a head has'),
    (dict(layer_types=[L, 'mamba'] * 4), 'a kind of another block'),
    (dict(ssm_chunk=0), 'scan chunks of no rows'),
])
def test_a_spec_the_block_cannot_build_is_refused(over, what):
    with pytest.raises(ValueError, match='LMSpec'):
        _spec(**over)


# -------------------------------------------------------- the delta rule
def _rule_inputs(rows, heads=4, key_heads=2, k=8, v=8, seed=0, pad=0):
    """q, k [rows, key heads, K] normalised, v, g <= 0 and beta in (0,
    1), the last ``pad`` rows padded (g = beta = 0)."""
    rng = np.random.RandomState(seed)
    q, key = (rng.randn(rows, key_heads, k).astype('f') for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * k ** -0.5
    key = key / np.linalg.norm(key, axis=-1, keepdims=True)
    val = rng.randn(rows, heads, v).astype('f')
    g = -np.exp(rng.randn(rows, heads) - 1.0).astype('f')
    beta = (1.0 / (1.0 + np.exp(-rng.randn(rows, heads)))).astype('f')
    if pad:
        g[-pad:], beta[-pad:] = 0.0, 0.0
    return q, key, val, g, beta


def _token_by_token(q, k, v, g, beta, first=None):
    per = v.shape[1] // q.shape[1]
    return ref.recurrence(
        jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1), v,
        jnp.exp(g), beta, 'float32', first)


_scan = jax.jit(gdo.delta_chunk_scan, static_argnums=(9,))


@pytest.mark.parametrize('rows,chunk,pad', [
    (8, 8, 0),            # one scan chunk
    (32, 8, 0),           # four, the state carried across their edges
    (16, 64, 0),          # fewer rows than a scan chunk
    (64, 16, 0),
    (32, 8, 5),           # padded rows behind the ones that count
    (4, 8, 3),            # one live row
])
def test_the_chunked_form_is_the_token_by_token_recurrence(rows, chunk,
                                                           pad):
    """The WY form with its triangular inverse against the plain scan
    over tokens, and the slot holds the state the live rows end in; a
    padded row moves nothing."""
    q, k, v, g, beta = _rule_inputs(rows, seed=rows + chunk, pad=pad)
    live = rows - pad
    want, last = _token_by_token(q[:live], k[:live], v[:live], g[:live],
                                 beta[:live])
    state = jnp.zeros((2, 3, 4, 8, 8), jnp.float32)
    got, state = _scan(state, 1, 2, q, k, v, g, beta, True, chunk)
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[1, 2]), np.asarray(last),
                               atol=2e-5)
    assert not np.asarray(state[0]).any()


def test_a_chunk_that_is_not_the_first_starts_from_its_slot():
    """Two prefill chunks of one sequence: the second is seeded from the
    slot the first left (``fresh`` False) and a fresh one ignores what
    the slot's last owner left."""
    q, k, v, g, beta = _rule_inputs(48, seed=3)
    want, last = _token_by_token(q, k, v, g, beta)
    dirty = jnp.full((1, 2, 4, 8, 8), 7.0, jnp.float32)
    first, state = _scan(dirty, 0, 1, q[:16], k[:16], v[:16], g[:16],
                         beta[:16], True, 8)
    rest, state = _scan(state, 0, 1, q[16:], k[16:], v[16:], g[16:],
                        beta[16:], False, 8)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(first), np.asarray(rest)]),
        np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[0, 1]), np.asarray(last),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(state[0, 0]), 7.0)


def test_the_inverse_of_a_unit_lower_matrix_comes_from_products():
    rng = np.random.RandomState(2)
    for size in (1, 2, 8, 13, 64):
        a = np.tril(rng.randn(3, size, size), -1).astype('f') * 0.3
        got = np.asarray(gdo._unit_lower_inverse(jnp.asarray(a)))
        want = np.linalg.inv(np.eye(size) + a.astype('float64'))
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


@pytest.fixture
def in_a_kernel(monkeypatch):
    """Both kernels' TPU forms on this platform, interpreted, in the
    place of the loops every platform but the TPU lowers
    (tests/test_nemotron_3_super_block.py steers its own the same way).
    A slot goes in two tiles of whole heads."""
    from paddle_tpu.ops.pallas import ssm_state_update as kernel
    monkeypatch.setenv('PADDLE_TPU_PALLAS_INTERPRET', '1')
    monkeypatch.setattr(kernel, 'TILE_BYTES', 2 * 8 * 8 * 4)
    interpreted = lambda *args: kernel.delta_state_update(*args)
    monkeypatch.setattr(gdo, 'delta_state_update', interpreted)
    monkeypatch.setattr(gdo, '_delta_row_by_row', interpreted)
    for form in ('_in_place', '_grouped'):
        product = getattr(moe, form + '_by_kernel')
        for name in ('_by_loop', '_by_kernel'):
            monkeypatch.setattr(
                moe, form + name, lambda *args, _k=product: _k(*args))


# live rows, the slot a row (the spare is 9), value heads, key heads, K, V
UPDATES = {
    'two tiles of two heads': ([1, 1, 1, 0, 0, 0], [3, 8, 0, 4, 1, 5],
                               4, 2, 8, 8),
    'a row that is not valid, slots in one group': (
        [1, 0, 1, 1, 0, 0], [3, 9, 2, 1, 9, 9], 4, 4, 8, 8),
    'one tile, key and value widths unlike': (
        [1, 1, 0, 0], [8, 2, 5, 9], 2, 1, 16, 8),
    'no live row': ([0, 0, 0, 0], [9, 9, 9, 9], 4, 2, 8, 8),
}


def _updated(live, slots, heads, key_heads, n_key, width):
    rows, cols = len(live), 2 * key_heads * n_key + heads * width
    q, k, v, g, beta = _rule_inputs(rows, heads, key_heads, n_key, width,
                                    seed=5)
    on = np.asarray(live, 'f')[:, None]
    g, beta = g * on, beta * on
    rng = np.random.RandomState(6)
    state = rng.randn(2, 10, heads, n_key, width).astype('f')
    conv = rng.randn(2, 10, 3 * cols).astype('f')
    window = rng.randn(rows, 4, cols).astype('f')
    out = jax.jit(lambda *args: gdo.delta_decode_update(*args))(
        jnp.asarray(state), jnp.asarray(conv), 1,
        jnp.asarray(slots, jnp.int32), jnp.asarray(live, bool), q, k, v, g,
        beta, jnp.asarray(window))
    return [np.asarray(x) for x in out], (state, conv, window, q, k, v, g,
                                          beta)


@pytest.mark.parametrize('case', sorted(UPDATES))
def test_the_decode_update_steps_each_row_s_own_slot(case, request):
    """The row loop against the recurrence in float32, and the kernel
    (interpreted) against the row loop bit for bit: the state, the kept
    rows and what the step reads out."""
    live, slots = UPDATES[case][:2]
    (o, new, kept), (state, conv, window, q, k, v, g, beta) = _updated(
        *UPDATES[case])
    for i in [i for i, on in enumerate(live) if on and slots[i] != 9]:
        want, last = _token_by_token(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], g[i:i + 1], beta[i:i + 1],
            jnp.asarray(state[1, slots[i]]))
        np.testing.assert_allclose(new[1, slots[i]], np.asarray(last),
                                   atol=1e-5)
        np.testing.assert_allclose(o[i], np.asarray(want)[0], atol=1e-5)
        np.testing.assert_array_equal(kept[1, slots[i]],
                                      window[i, 1:].reshape(-1))
    touched = {slots[i] for i, on in enumerate(live) if on} | {9}
    for slot in set(range(10)) - touched:
        np.testing.assert_array_equal(new[1, slot], state[1, slot])
        np.testing.assert_array_equal(kept[1, slot], conv[1, slot])
    np.testing.assert_array_equal(new[0], state[0])
    upper = max([i + 1 for i, on in enumerate(live) if on] or [0])
    assert not o[upper:].any()
    request.getfixturevalue('in_a_kernel')
    (o_k, new_k, kept_k), _ = _updated(*UPDATES[case])
    np.testing.assert_array_equal(new_k, new)
    np.testing.assert_array_equal(kept_k, kept)
    np.testing.assert_array_equal(o_k, o)


# ------------------------------------------------- the attention's pieces
def test_a_part_of_a_head_is_rotated_and_the_rest_is_not():
    """``rope_part_at`` over the whole head with two rolls against the
    reference's slices and join: the first 4 of 16 columns in half-split
    pairs (i, i + 2), the other 12 untouched."""
    rng = np.random.RandomState(0)
    x = rng.randn(6, 3, 16).astype('f')
    pos = jnp.arange(5, 11, dtype=jnp.int32)
    inv = jnp.asarray(1e7 ** (-np.arange(2) * 2 / 4.0), jnp.float32)
    got = np.asarray(dho.rope_part_at(jnp.asarray(x), pos, inv))
    want = np.asarray(ref.rotated(jnp.asarray(x), jnp.float32(5), 1e7, 4))
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    assert np.abs(got[..., :4] - x[..., :4]).max() > 0.1


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their routed partial sums and the
    gated shared expert counted once are the uncut reference's whole
    expert layer (layer 5 of the eight)."""
    rng = np.random.RandomState(7)
    n = jnp.asarray(rng.randn(24, 32).astype('f'))
    names = ('lm_moe_exp_gate.w', 'lm_moe_exp_up.w', 'lm_moe_exp_down.w')
    # all eight experts' matrices; a share holds two of them
    full = {name: rng.randn(8, 8, *WEIGHTS[name].shape[2:]).astype('f')
            * WEIGHTS[name].shape[2] ** -0.5 for name in names}
    arch = ref.arch_of(SPEC)
    w = {k: jnp.asarray(v) for k, v in dict(WEIGHTS, **full).items()}
    uncut = np.asarray(ref.experts(n, w, 5, arch, (0, 8)))
    shared = np.asarray(ref.experts(n, w, 5, arch, (0, 0)))
    assert np.abs(uncut - shared).max() > 0.1
    step = pdo._Step(None, None, None, None, None, ())
    total = shared
    for first in (0, 2, 4, 6):
        share = {name: v[:, first:first + 2] for name, v in full.items()}
        block = Driver(
            _spec(first_expert=first, experts_held=2),
            dict(WEIGHTS, **share), BS, NB).block(
                BlockTablesState=jnp.zeros((24,), jnp.int32))
        layer = {slot: stack[5] for slot, stack in block.w[None].items()}
        out, stats = block._experts(n, step, layer, 5)
        assert stats.shape == (4,)
        # what every chip computes alike is counted once
        total = total + np.asarray(out) - shared
    np.testing.assert_allclose(total, uncut, atol=2e-5)


# ------------------------------------------- the block against the reference
def test_a_whole_prompt_prefill_matches_the_full_forward():
    tokens = _tokens(16, 1)
    got, _, stats = DRIVER.prefill(DRIVER.arenas(), DRIVER.table(0, 16),
                                   tokens, [16], slot=1)
    np.testing.assert_allclose(got, _reference_logits(tokens), atol=TOL)
    # a row of statistics a layer: 16 rows x 3 choices, of which those
    # on experts 2..5 are local
    stats = np.asarray(stats)
    assert stats.shape == (8, 4) and (stats[:, 0] <= 48).all() \
        and (stats[:, 0] > 0).all() and (stats[:, 2] <= 4).all()


@pytest.mark.parametrize('pieces', [
    [16, 16, 5],          # a last chunk shorter than its bucket (8)
    [12, 9, 3],           # chunk boundaries inside a scan chunk of 8
    [1, 1, 1, 16, 3],     # first chunks of under three rows
    [13],                 # padded: 3 rows of a bucket of 16 are not live
])
def test_prefill_in_chunks_matches_the_full_forward(pieces):
    tokens = _tokens(sum(pieces), 2)
    got, _, _ = DRIVER.prefill(DRIVER.arenas(), DRIVER.table(3, len(tokens)),
                               tokens, pieces, slot=2)
    np.testing.assert_allclose(got, _reference_logits(tokens), atol=TOL)


@pytest.mark.parametrize('form', ['the loops', 'the kernels'])
def test_prefill_then_decode_through_the_cache_matches_the_full_forward(
        form, request):
    """Three sequences of unlike depth, each prefilled in chunks into
    its own slot and pages, then decoded together, the rows changing
    places between steps; with the state update and the routed product
    in either form."""
    stepper = None
    if form == 'the kernels':
        request.getfixturevalue('in_a_kernel')
        # a driver of its own: the step traced again, with them
        stepper = Driver(SPEC, WEIGHTS, BS, NB, slots=SLOTS, pages=PAGES)
    block_harness.prefill_then_decode_through_the_cache(
        DRIVER, ref, 8, CHUNK, TOL, stepper)


@pytest.mark.parametrize('lowered,what', [
    (dict(l2norm=False), 'the l2 norm of q and k'),
    (dict(beta=False), 'the write strength'),
    (dict(decay=False), 'the decay'),
    (dict(gate_after_norm=False), 'the gate before the norm'),
    (dict(qk_norm=False), 'the per-head norms of q and k'),
    (dict(rotary_dim=16), 'the whole head rotated'),
    (dict(attn_gate=False), 'the output gate'),
    (dict(shared_gate=False), 'the shared expert\'s gate'),
    (dict(norm_topk=False), 'weights not normalised over the chosen'),
    (dict(state_dtype='bfloat16'), 'a state and a router in bfloat16'),
])
def test_the_tolerance_catches_a_piece_left_out_or_lowered(lowered, what):
    tokens = _tokens(40, 8)
    sound = _reference_logits(tokens)
    wrong = _reference_logits(tokens, **lowered)
    assert np.abs(sound - wrong).max() > 10 * TOL, what


def test_the_reference_in_blocks_of_tokens_is_the_reference_whole(
        monkeypatch):
    """The reference runs a linear-attention layer some tokens at a time
    with the state and the convolution's last inputs carried, and an
    attention layer some query rows at a time: blocks of 5 tokens, which
    cut inside a convolution's reach, give what one block gives."""
    tokens = _tokens(37, 9)
    whole = _reference_logits(tokens)
    monkeypatch.setattr(ref, 'TIME_BLOCK', 5)
    monkeypatch.setattr(ref, 'ATTN_ROWS', 7)
    np.testing.assert_allclose(_reference_logits(tokens), whole, atol=1e-5)


# ------------------------------------------------------------ the engine
PROMPTS = [_tokens(n, 40 + n).tolist() for n in (21, 7, 34)]
ANSWERS = (9, 12, 6)


def _engine(**over):
    kw = dict(max_batch=SLOTS, block_size=BS, num_blocks=NB,
              pages_per_seq=PAGES, prefill_chunk=CHUNK, min_prompt_bucket=4,
              weights=WEIGHTS)
    kw.update(over)
    return DecodeEngine(SPEC, **kw)


def _is_the_references_choice(prompt, answer):
    gaps, _ = ref.token_gaps(
        {k: jnp.asarray(v) for k, v in WEIGHTS.items()}, ref.arch_of(SPEC),
        ref.held_of(SPEC), prompt, answer, 8)
    return max(gaps) <= TOL


@pytest.fixture(scope='module')
def served():
    """The prompts served together by one engine with the series on, and
    the slot of the first served again after its release."""
    observe.reset()
    observe.enable()
    eng = _engine()
    try:
        eng.start()
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(PROMPTS, ANSWERS)]
        together = [s.result(timeout=300) for s in streams]
        assert eng.drain(timeout=60)
        counters = observe.snapshot()['counters']
        pools = [(p.used_blocks(), p.num_blocks) for p in eng.pools]
        # every slot has had an owner now: the next one starts in a slot
        # that holds what its last owner left
        for p, n in zip(PROMPTS, ANSWERS):
            eng.generate(p[::-1], max_new_tokens=n, timeout=300)
        again = eng.generate(PROMPTS[0], max_new_tokens=ANSWERS[0],
                             timeout=300)
    finally:
        eng.shutdown(drain=False)
        observe.disable()
        observe.reset()
    return together, again, counters, pools


@pytest.mark.parametrize('i', range(len(PROMPTS)))
def test_the_engine_serves_the_references_tokens(served, i):
    assert len(served[0][i]) == ANSWERS[i]
    assert _is_the_references_choice(PROMPTS[i], served[0][i])


def test_a_slot_reused_after_release_starts_from_a_zero_state(served):
    together, again, _, pools = served
    assert again == together[0]
    assert [used for used, _ in pools] == [0, 0]


def test_the_series_count_the_block_s_rows(served):
    """The shared series this block feeds: live rows x the six layers
    that keep a state a decode step, the tokens through the chunked form
    and its scan chunks, the experts' assignments and tiles."""
    def total(name):
        return sum(v for k, v in served[2].items()
                   if k == name or k.startswith(name + '{'))
    prompt_rows, steps = sum(map(len, PROMPTS)), total('decode.step_rows')
    assert total('decode.step_state_rows_total') == 6 * steps
    assert total('decode.prefill_scan_rows_total') == 6 * prompt_rows
    assert total('decode.prefill_scan_chunks_total') > 0
    assert total('decode.state_resets_total') == len(PROMPTS)
    assert total('decode.moe_latent_rows_total') == 0
    assert total('decode.moe_layer_steps') > 0
    assert 0 < total('decode.moe_local_assignments') \
        < total('decode.moe_assignments')
    assert 0 < total('decode.moe_row_tiles_run') \
        <= total('decode.moe_row_tiles_dense')
