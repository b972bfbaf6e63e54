"""The ssm_hybrid block in its one-sublayer form (nemotron_3_super: a
layer is a Mamba-2 mixer in state groups, a position-free attention or a
LatentMoE expert layer alone) against its plain reference, at a tiny
size on the CPU in float32: the period ``*EMEMEMEMEM``, 8 heads of 8 in
2 groups over a state of 8, scan chunks of 8, 4 query heads over 2 KV
heads of 8, 4 of 8 two-matrix relu^2 experts of 24 held inside a latent
of 16, top-3, a shared expert of 40.

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU and differ in the order of their sums (chunked scan
and blockwise attention against a token-by-token recurrence and one
softmax; a tile list against a loop over the experts), a few 1e-6 on
logits of order 1. 5e-5 leaves a margin and is an order and more under
what a norm over the whole inner width, a dropped shared expert, skip
term or gate, or a state in bfloat16 gives (checked below by breaking
each). Whether a program copies an arena is a property of the chip's
compiler: tests/test_v5e_compile.py reads it off the three programs
compiled for a described v5e at the published geometry
(``serving/decode/hlo_check.py``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observe
from paddle_tpu.models.reference import nemotron_3_super as ref
from paddle_tpu.ops import moe_held_ops as moe
from paddle_tpu.ops import paged_decode_ops as pdo
from paddle_tpu.ops import ssm_ops
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
import block_harness
from block_harness import BS, NB, PAGES, SLOTS, Driver, tokens as _tokens

TOL = 5e-5
CHUNK = 16                               # the engine's prefill chunk
M, A, E = lm.MAMBA, lm.ATTENTION, lm.MOE
PATTERN = [A] + [E, M] * 5


@pytest.fixture(autouse=True)
def _clean_observe():
    yield
    observe.disable()
    observe.reset()


def _spec(**over):
    kw = dict(
        vocab_size=64, n_layer=11, n_head=4, n_kv_head=2, d_key=8, d_value=8,
        d_model=32, d_inner=24, block='ssm_hybrid', layer_types=PATTERN,
        ssm_heads=8, ssm_head_dim=8, ssm_state=8, ssm_conv=4, ssm_chunk=8,
        ssm_groups=2, mixer_only=True, tie_embeddings=False, n_experts=8,
        experts_held=4, first_expert=2, experts_per_token=3,
        n_shared_experts=1, routed_scale=5.0, moe_latent=16,
        d_inner_shared=40, norm_eps=1e-5)
    kw.update(over)
    return LMSpec(**kw)


SPEC = _spec()
WEIGHTS = random_weights(SPEC, seed=58)


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, slots=SLOTS, pages=PAGES)


def _reference_logits(tokens, **lowered):
    return DRIVER.reference_logits(ref, tokens, **lowered)


# ------------------------------------------------------------- the spec
def test_a_layer_is_one_sublayer_and_an_expert_layer_owns_no_cache():
    """K and V pages for the one attention layer, state and convolution
    slots for the five Mamba-2 layers, nothing for the expert layers;
    one norm a layer and no MLP in the table; stacks a kind; a head of
    its own; the convolution over x and every group's B and C."""
    kinds = {k.name: k for k in SPEC.cache_kinds()}
    assert sorted(kinds) == ['lm_kcache', 'lm_ssm_conv', 'lm_ssm_state',
                             'lm_vcache']
    assert kinds['lm_kcache'].layers == (0,)
    assert kinds['lm_ssm_state'].layers == (2, 4, 6, 8, 10)
    assert kinds['lm_ssm_state'].per_seq == (8, 64)
    assert kinds['lm_ssm_conv'].per_seq == (3 * (64 + 2 * 2 * 8),)
    assert SPEC.layer_plan() == ((), tuple(PATTERN), 1, ())
    table = lm.block_param_shapes(SPEC)
    assert not [n for n in table if 'mlp' in n or 'ln2' in n]
    assert table['lm_head.w'][0] == [64, 32]
    assert table['lm_stack_ln1.w'][0] == [11, 32]
    assert table['lm_attn_q.w'][0] == [1, 32, 32]
    assert table['lm_mamba_in.w'][0] == [5, 32, 64 + 96 + 8]
    assert table['lm_moe_router.w'][0] == [5, 32, 8]
    assert table['lm_moe_exp_up.w'][0] == [5, 4, 16, 24]
    assert table['lm_moe_exp_down.w'][0] == [5, 4, 24, 16]
    assert table['lm_moe_shr_up.w'][0] == [5, 32, 40]
    assert table['lm_moe_lat_out.w'][0] == [5, 16, 32]
    for what in ('prefix_cache', 'speculation'):
        assert 'state' in SPEC.refusal(what)
    with pytest.raises(NotImplementedError, match='cannot be rewound'):
        lm.build_lm_programs(SPEC, 4, BS, NB, PAGES, spec_k=2)


@pytest.mark.parametrize('over,what', [
    (dict(mixer_only=False), 'an expert layer behind a dense MLP'),
    (dict(ssm_groups=3), 'heads that do not divide into the groups'),
    (dict(moe_latent=0), 'experts without their latent'),
    (dict(experts_held=8), 'more experts held than there are from 2 on'),
    (dict(n_shared_experts=0), 'no shared expert'),
    (dict(layer_types=[A] + [M] * 10, n_experts=8),
     'experts and no layer to hold them'),
])
def test_a_spec_the_block_cannot_build_is_refused(over, what):
    with pytest.raises(ValueError, match='LMSpec'):
        _spec(**over)


# ------------------------------------------------------- the state groups
def _scan_inputs(rows, groups, seed=0, tied=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, 8, 8).astype('f')
    b, c = (rng.randn(rows, 1 if tied else groups, 8).astype('f')
            for _ in range(2))
    if tied:
        b, c = (np.repeat(v, groups, axis=1) for v in (b, c))
    dt = np.log1p(np.exp(rng.randn(rows, 8))).astype('f')
    a = -np.exp(rng.randn(8) * 0.5).astype('f')
    return x, b, c, dt, a


_scan = jax.jit(ssm_ops.ssm_chunk_scan, static_argnums=(9, 10))


@pytest.mark.parametrize('groups', [2, 8])
@pytest.mark.parametrize('rows,chunk', [(8, 8), (32, 8), (16, 256)])
def test_the_chunked_scan_in_groups_is_the_recurrence(groups, rows, chunk):
    """Head h reads the B and C of group h // (8 / groups): the chunked
    form against the token-by-token recurrence, and the slot holds the
    state it ends in, state-major."""
    x, b, c, dt, a = _scan_inputs(rows, groups)
    want = np.asarray(ref.recurrence(x, b, c, dt, a, 'float32')[0])
    state = jnp.zeros((2, 3, 8, 64), jnp.float32)
    got, state = _scan(state, 1, 2, x, b, c, dt, a, True, chunk,
                       jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    s = np.zeros((8, 8, 8), 'f')
    for t in range(rows):
        s = np.exp(dt[t] * a)[:, None, None] * s + \
            (dt[t][:, None] * x[t])[:, :, None] * \
            np.repeat(b[t], 8 // groups, axis=0)[:, None, :]
    np.testing.assert_allclose(np.asarray(state[1, 2]),
                               s.reshape(64, 8).T, atol=2e-5)
    assert not np.asarray(state[0]).any()


@pytest.mark.parametrize('groups', [2, 8])
def test_groups_tied_to_one_another_are_the_one_group_form(groups):
    """With every group's B and C the same, the grouped form gives what
    the one-group form gives on that one B and C: the scan and the
    decode update alike."""
    x, b, c, dt, a = _scan_inputs(24, groups, seed=4, tied=True)
    zeros = jnp.zeros((1, 2, 8, 64), jnp.float32)
    one, s_one = _scan(zeros, 0, 1, x, b[:, 0], c[:, 0], dt, a, True, 8,
                       jnp.float32)
    many, s_many = _scan(zeros, 0, 1, x, b, c, dt, a, True, 8, jnp.float32)
    np.testing.assert_allclose(np.asarray(many), np.asarray(one), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_many), np.asarray(s_one),
                               atol=1e-5)
    conv = jnp.zeros((1, 2, 3 * 80), jnp.float32)
    window = jnp.zeros((4, 4, 80), jnp.float32)
    step = jax.jit(lambda b, c: ssm_ops.ssm_decode_update(
        s_one, conv, 0, jnp.asarray([1, 1, 1, 1]), jnp.asarray(
            [True, False, False, False]), x[:4], b, c, dt[:4], a, window))
    for got, want in zip(step(b[:4], c[:4]), step(b[:4, 0], c[:4, 0])):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.fixture
def in_a_kernel(monkeypatch):
    """Both kernels' TPU forms on this platform, interpreted, in the
    place of the loops every platform but the TPU lowers
    (tests/test_granite_block.py and tests/test_moe_routed_kernel.py
    steer theirs the same way). A slot goes in two row tiles."""
    from paddle_tpu.ops.pallas import ssm_state_update as kernel
    monkeypatch.setenv('PADDLE_TPU_PALLAS_INTERPRET', '1')
    monkeypatch.setattr(kernel, 'TILE_BYTES', 8 * 64 * 4)
    interpreted = lambda *args: kernel.state_update(*args)
    monkeypatch.setattr(ssm_ops, 'state_update', interpreted)
    monkeypatch.setattr(ssm_ops, '_update_row_by_row', interpreted)
    for form in ('_in_place', '_grouped'):
        # functions of their own (jax keeps a branch's trace by the
        # function, and the chip's form was traced uninterpreted)
        product = getattr(moe, form + '_by_kernel')
        for name in ('_by_loop', '_by_kernel'):
            monkeypatch.setattr(
                moe, form + name, lambda *args, _k=product: _k(*args))


# live rows, the slot a row (the spare is 9), heads x width, state, groups
UPDATES = {
    'two groups': ([1, 1, 1, 0, 0, 0], [3, 8, 0, 4, 1, 5], 4, 16, 16, 2),
    'eight groups, a row that is not valid': (
        [1, 0, 1, 1, 0, 0], [3, 9, 8, 4, 9, 9], 8, 16, 16, 8),
    'one layer at the published widths': (
        [1, 1, 1, 0], [8, 2, 5, 9], 128, 64, 128, 8),
}


def _updated(live, slots, heads, width, n_state, groups):
    rows, cols = len(live), heads * width + 2 * groups * n_state
    rng = np.random.RandomState(5)
    x = rng.randn(rows, heads, width).astype('f')
    b, c = (rng.randn(rows, groups, n_state).astype('f') for _ in range(2))
    dt = np.log1p(np.exp(rng.randn(rows, heads))).astype('f') * \
        np.asarray(live, 'f')[:, None]
    a = -np.exp(rng.randn(heads) * 0.5).astype('f')
    state = rng.randn(2, 10, n_state, heads * width).astype('f')
    conv = rng.randn(2, 10, 3 * cols).astype('f')
    window = rng.randn(rows, 4, cols).astype('f')
    out = jax.jit(lambda *args: ssm_ops.ssm_decode_update(*args))(
        jnp.asarray(state), jnp.asarray(conv), 1,
        jnp.asarray(slots, jnp.int32), jnp.asarray(live, bool), x, b, c, dt,
        a, jnp.asarray(window))
    return [np.asarray(v) for v in out], (state, x, b, c, dt, a)


@pytest.mark.parametrize('case', sorted(UPDATES))
def test_the_decode_update_in_groups_steps_each_row_s_own_slot(
        case, request):
    """The row loop against the recurrence in float32, and the kernel
    (interpreted) against the row loop: the state and the kept rows bit
    for bit, ``y`` to the order of its sum."""
    live, slots, heads, width, n_state, groups = UPDATES[case]
    (y, new, kept), (state, x, b, c, dt, a) = _updated(*UPDATES[case])
    per = heads // groups
    for i in [i for i, on in enumerate(live) if on and slots[i] != 9]:
        s = state[1, slots[i]].T.reshape(heads, width, n_state)
        s = np.exp(dt[i] * a)[:, None, None] * s + \
            (dt[i][:, None] * x[i])[:, :, None] * \
            np.repeat(b[i], per, axis=0)[:, None, :]
        np.testing.assert_allclose(
            new[1, slots[i]], s.reshape(heads * width, n_state).T, atol=1e-5)
        np.testing.assert_allclose(
            y[i], (s * np.repeat(c[i], per, axis=0)[:, None, :]).sum(-1),
            atol=1e-5 * n_state ** 0.5)
    np.testing.assert_array_equal(new[0], state[0])
    request.getfixturevalue('in_a_kernel')
    (y_k, new_k, kept_k), _ = _updated(*UPDATES[case])
    np.testing.assert_array_equal(new_k, new)
    np.testing.assert_array_equal(kept_k, kept)
    np.testing.assert_allclose(y_k, y, atol=1e-5 * n_state ** 0.5)


# ------------------------------------------- the experts of two matrices
def _routed(rows, held, d, f, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, d).astype('f')
    up = (rng.randn(2, held, d, f) * d ** -0.5).astype('f')
    down = (rng.randn(2, held, f, d) * f ** -0.5).astype('f')
    chosen = np.stack([rng.permutation(held + 3)[:3] for _ in range(rows)])
    weight = rng.rand(rows, 3).astype('f')
    valid = rng.rand(rows) < 0.8
    gate, hit = moe.held_gates(jnp.asarray(chosen), jnp.asarray(weight), 1,
                               held)
    return x, gate, hit, jnp.asarray(valid), up, down


@pytest.mark.parametrize('rows', [13, 160])
def test_the_two_matrix_expert_goes_through_the_tile_list(rows, request):
    """``routed_experts`` without a gate matrix, a decode batch (in
    place) and a chunk (grouped): the loop against ``sum_e gate_e
    relu(x W1_e)^2 W2_e`` written out, and the kernel (interpreted)
    against the loop, to the order of a row's sum."""
    x, gate, hit, valid, up, down = _routed(rows, 5, 32, 128)
    run = lambda: np.asarray(jax.jit(
        lambda x, gate, hit, valid, up, down: moe.routed_experts(
            x, gate, hit, valid, 3, None, up, down, layer=1))(
                x, gate, hit, valid, up, down))
    by_loop = run()
    g = np.where(np.asarray(hit) & np.asarray(valid)[:, None],
                 np.asarray(gate), 0.0)
    want = sum(g[:, e:e + 1] * (np.square(np.maximum(x @ up[1, e], 0.0))
                                @ down[1, e]) for e in range(5))
    np.testing.assert_allclose(by_loop, want, atol=2e-5)
    request.getfixturevalue('in_a_kernel')
    np.testing.assert_allclose(run(), by_loop, atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their routed partial sums, each
    projected out of the latent, and the shared expert counted once are
    the uncut reference's whole expert layer (layer 3 of the five)."""
    rng = np.random.RandomState(7)
    n = jnp.asarray(rng.randn(24, 32).astype('f'))
    # all eight experts' matrices; a share holds two of them
    full = {name: rng.randn(5, 8, *WEIGHTS[name].shape[2:]).astype('f')
            * WEIGHTS[name].shape[2] ** -0.5
            for name in ('lm_moe_exp_up.w', 'lm_moe_exp_down.w')}
    arch = ref.arch_of(SPEC)
    w = {k: jnp.asarray(v) for k, v in dict(WEIGHTS, **full).items()}
    uncut = np.asarray(ref.experts(n, w, 3, arch, (0, 8)))
    shared = np.asarray(ref.experts(n, w, 3, arch, (0, 0)))
    assert np.abs(uncut - shared).max() > 0.1
    step = pdo._Step(None, None, None, None, None, ())
    total = shared
    for first in (0, 2, 4, 6):
        share = {name: v[:, first:first + 2] for name, v in full.items()}
        block = Driver(
            _spec(first_expert=first, experts_held=2),
            dict(WEIGHTS, **share), BS, NB).block(
                BlockTablesState=jnp.zeros((24,), jnp.int32))
        layer = {slot: stack[3] for slot, stack in block.w[E].items()}
        out, stats = block._experts(n, step, layer, 3)
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
    # a row of statistics an expert layer: 16 rows x 3 choices, of which
    # those on experts 2..5 are local
    stats = np.asarray(stats)
    assert stats.shape == (5, 4) and (stats[:, 0] <= 48).all() \
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
    (dict(group_norm=False), 'a norm over the whole inner width'),
    (dict(shared=False), 'the shared expert'),
    (dict(d_skip=False), 'the skip term'),
    (dict(gate=False), 'the gate'),
    (dict(state_dtype='bfloat16'), 'a state and a router in bfloat16'),
])
def test_the_tolerance_catches_a_wrong_layer(lowered, what):
    tokens = _tokens(40, 8)
    sound = _reference_logits(tokens)
    wrong = _reference_logits(tokens, **lowered)
    assert np.abs(sound - wrong).max() > 10 * TOL, what


def test_the_reference_in_blocks_of_tokens_is_the_reference_whole(
        monkeypatch):
    """The reference runs a Mamba-2 layer some tokens at a time with the
    state and the convolution's last inputs carried (what lets 18k
    tokens fit beside the model on the chip): blocks of 5 tokens, which
    cut inside a convolution's reach, give what one block gives."""
    tokens = _tokens(37, 9)
    whole = _reference_logits(tokens)
    monkeypatch.setattr(ref, 'TIME_BLOCK', 5)
    np.testing.assert_allclose(_reference_logits(tokens), whole, atol=2e-6)


def test_one_group_and_an_mlp_are_the_defaults():
    """The forms are off by default: a spec that names none of them has
    granite's table, one group and a tied head."""
    plain = LMSpec(vocab_size=64, n_layer=2, block='ssm_hybrid',
                   layer_types=[M, A], ssm_heads=4, ssm_head_dim=16,
                   ssm_state=8)
    assert (plain.ssm_groups, plain.mixer_only, plain.tie_embeddings) \
        == (1, False, True)
    table = lm.block_param_shapes(plain)
    assert 'lm_head.w' not in table and 'lm_stack_mlp_up.w' in table
    assert plain.ssm_conv_width == 64 + 2 * 8


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


def test_a_slot_reused_after_release_starts_from_zeros(served):
    together, again, _, pools = served
    assert again == together[0]
    assert [used for used, _ in pools] == [0, 0]


def test_the_series_count_the_block_s_rows(served):
    """The shared series this block feeds, and the one it adds: rows x
    expert layers that took the projection into the latent and out."""
    def total(name):
        return sum(v for k, v in served[2].items()
                   if k == name or k.startswith(name + '{'))
    prompt_rows, steps = sum(map(len, PROMPTS)), total('decode.step_rows')
    assert total('decode.moe_latent_rows_total') == 5 * (prompt_rows + steps)
    assert total('decode.step_state_rows_total') == 5 * steps
    assert total('decode.moe_layer_steps') > 0
    assert 0 < total('decode.moe_local_assignments') \
        < total('decode.moe_assignments')
    assert 0 < total('decode.moe_row_tiles_run') \
        <= total('decode.moe_row_tiles_dense')
    assert total('decode.prefill_scan_chunks_total') > 0
