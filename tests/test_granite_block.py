"""The ssm_hybrid block (granite_4_0_h_micro: Mamba-2 layers whose state
is one slot a sequence beside position-free attention layers in the
paged cache, a dense gated MLP in every layer) against its plain
reference, at a tiny size on the CPU in float32: two periods of (mamba,
mamba, attention, mamba), 4 heads of 16 over a state of 8, scan chunks of
8, 4 query heads over 2 KV heads of 8.

The comparisons are of logits, not tokens. Tolerance: both sides are
float32 on the CPU; they differ in the order of their sums (the block
scans in chunks and attends column block by column block under a running
softmax; the reference steps the recurrence token by token and takes one
softmax over the whole sequence), which at these widths gives differences
of a few 1e-6 on logits of order 1. 5e-5 leaves a margin and is an order
and more under what a dropped skip term, time-step bias or gate, or a
state rounded to bfloat16, gives (checked below by breaking each)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observe
from paddle_tpu.models.reference import granite_4_0_h_micro as ref
from paddle_tpu.ops import ssm_ops
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
from paddle_tpu.serving.decode.kv_pool import KVPool
from paddle_tpu.serving.decode.scheduler import Scheduler, Sequence
import block_harness
from block_harness import BS, NB, PAGES, SLOTS, Driver, tokens as _tokens

TOL = 5e-5
CHUNK = 16                               # the engine's prefill chunk
M, A = lm.MAMBA, lm.ATTENTION


@pytest.fixture(autouse=True)
def _clean_observe():
    yield
    observe.disable()
    observe.reset()


def _spec(**over):
    kw = dict(
        vocab_size=64, n_layer=8, n_head=4, n_kv_head=2, d_key=8, d_value=8,
        d_model=32, d_inner=48, block='ssm_hybrid',
        layer_types=[M, M, A, M] * 2, ssm_heads=4, ssm_head_dim=16,
        ssm_state=8, ssm_conv=4, ssm_chunk=8, embed_scale=12.0,
        residual_scale=0.22, attn_scale=0.125, logit_scale=0.125,
        norm_eps=1e-5)
    kw.update(over)
    return LMSpec(**kw)


SPEC = _spec()
WEIGHTS = random_weights(SPEC, seed=11)


DRIVER = Driver(SPEC, WEIGHTS, BS, NB, slots=SLOTS, pages=PAGES)


def _reference_logits(tokens, **lowered):
    return DRIVER.reference_logits(ref, tokens, **lowered)


# ------------------------------------------------------- the cache's terms
def test_the_state_is_a_cache_kind_with_a_size_a_sequence():
    kinds = {k.name: k for k in SPEC.cache_kinds()}
    assert sorted(kinds) == ['lm_kcache', 'lm_ssm_conv', 'lm_ssm_state',
                             'lm_vcache']
    assert kinds['lm_kcache'].layers == (2, 6) and not kinds[
        'lm_kcache'].per_seq
    state, conv = kinds['lm_ssm_state'], kinds['lm_ssm_conv']
    assert state.layers == conv.layers == (0, 1, 3, 4, 5, 7)
    assert state.per_seq == (8, 64) and state.dtype == 'float32'
    assert conv.per_seq == (3 * (64 + 16),) and conv.dtype == 'float32'
    assert state.reads == () and state.keeps == 0
    pools = SPEC.page_pools()
    assert [(p.name, p.per_sequence, p.keeps) for p in pools] == [
        ('', False, 0), ('state', True, 0)]
    assert [p.table_width(PAGES) for p in pools] == [PAGES, 1]
    assert SPEC.layer_plan() == ((), (M, M, A, M), 2, ())
    assert not SPEC.shares_frozen_pages() and not SPEC.per_head_cache()
    assert SPEC.keeps_state() and not _other().keeps_state()


def _other():
    return LMSpec(vocab_size=64, n_layer=2)


def test_the_bytes_count_the_state_without_a_case_for_the_block():
    # K and V: 2 layers x 16 columns x 4 B a token; a slot: 6 layers x
    # (8 x 64 + 3 x 80) x 4 B
    assert lm.kv_bytes_per_kind(SPEC) == {
        'lm_kcache': 128, 'lm_vcache': 128, 'lm_ssm_state': 0,
        'lm_ssm_conv': 0}
    assert lm.kv_bytes_per_token(SPEC) == 256
    unit = lm.unit_bytes_per_kind(SPEC, BS)
    assert unit == {'lm_kcache': 512, 'lm_vcache': 512,
                    'lm_ssm_state': 6 * 8 * 64 * 4,
                    'lm_ssm_conv': 6 * 3 * 80 * 4}
    pages = {'': NB, 'state': SLOTS}
    assert lm.pages_by_pool(SPEC, pages) == pages
    # the arenas have the spare slot beside the pool's
    assert lm.arena_bytes(SPEC, pages, BS) == 2 * 512 * NB + (
        unit['lm_ssm_state'] + unit['lm_ssm_conv']) * (SLOTS + 1)
    # the state is float32 whatever K and V are kept at
    half = lm.unit_bytes_per_kind(_spec(dtype='bfloat16'), BS, 'bfloat16')
    assert half['lm_ssm_state'] == unit['lm_ssm_state']
    assert half['lm_ssm_conv'] * 2 == unit['lm_ssm_conv']
    assert half['lm_kcache'] * 2 == unit['lm_kcache']


def test_a_pool_of_whole_states_gives_a_sequence_one_slot():
    observe.enable()
    pool = KVPool(3, BS, kind='state', whole=True)
    assert [pool.blocks_for(n) for n in (0, 1, 5, 9000)] == [0, 1, 1, 1]
    assert pool.span_pages(9000) == 1
    sched = Scheduler([KVPool(64, BS), pool], max_batch=4)
    seqs = [Sequence(i, list(range(1, 10 + i)), 4, 0.0, 1, None)
            for i in range(4)]
    for seq in seqs:
        sched.add(seq)
    taken = [sched.pop_admittable() for _ in range(3)]
    assert taken == seqs[:3]
    assert [len(s.tables[1]) for s in seqs] == [1, 1, 1, 0]
    assert sorted(s.tables[1].block_ids[0] for s in taken) == [0, 1, 2]
    # a batch row is free and pages are, a slot is not: the fourth waits
    assert not sched.admittable() and sched.pop_admittable() is None
    assert observe.get_gauge('decode.state_slots_used') == 3
    assert observe.get_gauge('decode.state_slots_total') == 3
    # growth never asks the state pool for more
    seqs[0].cache_len = 9
    assert sched.ensure_growth(seqs[0], need_tokens=40)
    assert len(seqs[0].tables[1]) == 1 and pool.used_blocks() == 3
    # a preemption gives the slot back, a finish too
    sched.preempt(seqs[2])
    assert pool.used_blocks() == 2 and len(seqs[2].tables[1]) == 0
    assert sched.pop_admittable() is seqs[2]
    for seq in seqs[:3]:
        sched.finish(seq, 'max_tokens')
    assert pool.used_blocks() == 0
    assert observe.get_gauge('decode.state_slots_used') == 0


@pytest.mark.parametrize('over,what', [
    (dict(layer_types=[M, 'full_attention'] * 4), 'layer_types'),
    (dict(ssm_state=0), 'mamba layers'),
    (dict(n_experts=4, experts_per_token=2), 'no experts'),
    (dict(n_kv_head=3), 'query heads'),
])
def test_a_spec_the_block_cannot_build_is_refused(over, what):
    with pytest.raises(ValueError, match=what):
        _spec(**over)


def test_the_prefix_cache_and_speculation_are_refused_with_their_reasons():
    with pytest.raises(NotImplementedError,
                       match='no page of it to map from a page boundary'):
        DecodeEngine(SPEC, prefix_cache=True)
    with pytest.raises(NotImplementedError,
                       match='cannot be rewound past a rejected draft'):
        DecodeEngine(SPEC, spec_k=2)
    # the other blocks keep the reason they had
    with pytest.raises(NotImplementedError, match='no test against'):
        lm.build_lm_programs(
            LMSpec(vocab_size=64, n_layer=2, block='parallel_moe',
                   n_experts=4, experts_per_token=2, n_shared_experts=1),
            2, BS, 8, 4, spec_k=2)
    eng = DecodeEngine(SPEC, max_batch=2, block_size=BS, num_blocks=8,
                       pages_per_seq=4)
    from paddle_tpu.serving.handoff import CacheKindError
    with pytest.raises(CacheKindError):
        eng.kv_geometry()
    eng.shutdown(drain=False)


# ------------------------------------------------------------ the two ops
def _scan_inputs(rows, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, 4, 16).astype('f')
    b, c = rng.randn(rows, 8).astype('f'), rng.randn(rows, 8).astype('f')
    dt = np.log1p(np.exp(rng.randn(rows, 4))).astype('f')
    a = -np.exp(rng.randn(4) * 0.5).astype('f')
    return x, b, c, dt, a


@pytest.mark.parametrize('rows,chunk', [(8, 8), (32, 8), (16, 256)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(rows, chunk):
    x, b, c, dt, a = _scan_inputs(rows)
    want = np.asarray(ref.recurrence(x, b, c, dt, a, 'float32'))
    state = jnp.zeros((2, 3, 8, 64), jnp.float32)
    got, state = jax.jit(ssm_ops.ssm_chunk_scan, static_argnums=(9, 10))(
        state, 1, 2, x, b, c, dt, a, True, chunk, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # the slot holds the state the recurrence ends in, state-major
    s = np.zeros((4, 16, 8), 'f')
    for t in range(rows):
        s = np.exp(dt[t] * a)[:, None, None] * s + \
            (dt[t][:, None] * x[t])[:, :, None] * b[t][None, None, :]
    np.testing.assert_allclose(np.asarray(state[1, 2]),
                               s.reshape(64, 8).T, atol=2e-5)
    assert not np.asarray(state[0]).any() and not np.asarray(
        state[1, :2]).any()


def test_a_scan_seeded_from_its_slot_goes_on_where_the_last_one_ended():
    x, b, c, dt, a = _scan_inputs(24, seed=3)
    want = np.asarray(ref.recurrence(x, b, c, dt, a, 'float32'))
    scan = jax.jit(ssm_ops.ssm_chunk_scan, static_argnums=(9, 10))
    state = jnp.full((1, 2, 8, 64), 7.0)      # the last owner's
    first, state = scan(state, 0, 1, x[:16], b[:16], c[:16], dt[:16], a,
                        True, 8, jnp.float32)
    # a padded tail (dt = 0) leaves the state alone
    pad = np.zeros((8, 4), 'f')
    rest, state = scan(state, 0, 1, np.concatenate([x[16:], x[:8]]),
                       np.concatenate([b[16:], b[:8]]),
                       np.concatenate([c[16:], c[:8]]),
                       np.concatenate([dt[16:], pad]), a, False, 8,
                       jnp.float32)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(first), np.asarray(rest)[:8]]), want,
        atol=2e-5)
    again, _ = scan(state, 0, 1, x[:8] * 0, b[:8], c[:8] * 0 + 1, pad, a,
                    False, 8, jnp.float32)
    whole, _ = scan(jnp.zeros_like(state), 0, 1, x, b, c, dt, a, True, 8,
                    jnp.float32)
    assert np.isfinite(np.asarray(again)).all() and whole.shape == (24, 4,
                                                                   16)
    assert (np.asarray(state[0, 0]) == 7.0).all()


@pytest.fixture
def in_a_kernel(monkeypatch):
    """The decode update's TPU form on this platform: the kernel of
    ``ops/pallas/ssm_state_update.py``, interpreted, in the place of the
    row loop that every platform but the TPU lowers (the choice is by
    the platform a program is lowered for, so a test on the CPU steers
    it here). A slot goes in two row tiles at the small widths."""
    from paddle_tpu.ops.pallas import ssm_state_update as kernel
    monkeypatch.setenv('PADDLE_TPU_PALLAS_INTERPRET', '1')
    monkeypatch.setattr(kernel, 'TILE_BYTES', 8 * 64 * 4)
    # a function of its own for both branches: jax keeps a branch's trace
    # by the function, and an interpreted kernel must not be found again
    # under the name a program for the chip is traced by
    interpreted = lambda *args: kernel.state_update(*args)
    monkeypatch.setattr(ssm_ops, 'state_update', interpreted)
    monkeypatch.setattr(ssm_ops, '_update_row_by_row', interpreted)


SMALL = dict(heads=4, width=16, n_state=16, cols=80, slots=9)
# 64 heads of 64 over a state of 128 and a convolution over 4,352
# columns: one layer at the published widths, a slot in four tiles
PUBLISHED = dict(heads=64, width=64, n_state=128, cols=4352, slots=9)
UPDATES = {
    # live rows, the slot a row (the spare is ``slots``), the widths
    'one live row': ([1, 0, 0, 0, 0, 0], [3, 0, 8, 4, 1, 5], SMALL),
    'a live prefix and rows past it': (
        [1, 1, 1, 0, 0, 0], [3, 8, 0, 4, 1, 5], SMALL),
    'every row live': ([1] * 6, [3, 0, 8, 4, 1, 5], SMALL),
    'a row that is not valid inside the prefix': (
        [1, 0, 1, 1, 0, 0], [3, 9, 8, 4, 9, 9], SMALL),
    'no live row': ([0] * 6, [3, 0, 8, 4, 1, 5], SMALL),
    'one layer at the published widths': (
        [1, 1, 1, 0], [8, 2, 5, 9], PUBLISHED),
}


def _update_inputs(live, heads, width, n_state, cols, slots):
    rows = len(live)
    rng = np.random.RandomState(5)
    x = rng.randn(rows, heads, width).astype('f')
    b = rng.randn(rows, n_state).astype('f')
    c = rng.randn(rows, n_state).astype('f')
    # a row that is not live takes a step of dt = 0
    dt = np.log1p(np.exp(rng.randn(rows, heads))).astype('f') * \
        np.asarray(live, 'f')[:, None]
    a = -np.exp(rng.randn(heads) * 0.5).astype('f')
    state = rng.randn(2, slots + 1, n_state, heads * width).astype('f')
    conv = rng.randn(2, slots + 1, 3 * cols).astype('f')
    window = rng.randn(rows, 4, cols).astype('f')
    return state, conv, x, b, c, dt, a, window


def _updated(live, slots, sizes):
    state, conv, x, b, c, dt, a, window = _update_inputs(live, **sizes)
    # a jit of its own: the form is chosen as the program is traced
    y, new, kept = jax.jit(lambda *args: ssm_ops.ssm_decode_update(*args))(
        jnp.asarray(state), jnp.asarray(conv), 1,
        jnp.asarray(slots, jnp.int32), jnp.asarray(live, bool), x, b, c, dt,
        a, jnp.asarray(window))
    return (np.asarray(y), np.asarray(new), np.asarray(kept)), \
        (state, conv, x, b, c, dt, a, window)


@pytest.mark.parametrize('case', sorted(UPDATES))
@pytest.mark.parametrize('form', ['the row loop', 'the kernel'])
def test_the_decode_update_steps_each_live_row_s_own_slot(form, case,
                                                          request):
    """Both forms of the decode update against the recurrence in
    float32, and the kernel against the row loop: the state, the kept
    rows and ``y`` of every row up to the last live one; every other
    slot, the spare and the other layer bit for bit as they were."""
    live, slots, sizes = UPDATES[case]
    by_loop, (state, conv, x, b, c, dt, a, window) = _updated(
        live, slots, sizes)
    y, new, kept = by_loop
    if form == 'the kernel':
        request.getfixturevalue('in_a_kernel')
        (y, new, kept), _ = _updated(live, slots, sizes)
    heads, width, n_state = sizes['heads'], sizes['width'], sizes['n_state']
    upper = max([i + 1 for i, on in enumerate(live) if on] or [0])
    spare = sizes['slots']
    for i in range(upper):
        if slots[i] == spare:
            continue            # whatever the rows without a slot left
        s = state[1, slots[i]].T.reshape(heads, width, n_state)
        s = np.exp(dt[i] * a)[:, None, None] * s + \
            (dt[i][:, None] * x[i])[:, :, None] * b[i][None, None, :]
        np.testing.assert_allclose(
            new[1, slots[i]], s.reshape(heads * width, n_state).T, atol=1e-5)
        np.testing.assert_allclose(y[i], (s * c[i][None, None, :]).sum(-1),
                                   atol=1e-5 * n_state ** 0.5)
        np.testing.assert_array_equal(kept[1, slots[i]],
                                      window[i, 1:].reshape(-1))
    assert not y[upper:].any()
    # nothing else moved: the other layer, the slots of no row up to the
    # last live one, and the spare unless such a row points at it
    still = [j for j in range(spare + 1) if j not in slots[:upper]]
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[1, still], state[1, still])
    np.testing.assert_array_equal(kept[0], conv[0])
    np.testing.assert_array_equal(kept[1, still], conv[1, still])
    # the kernel's arithmetic is the loop's: the state's expression has
    # one order, the read-out's sum is free to take another
    np.testing.assert_array_equal(new, by_loop[1])
    np.testing.assert_array_equal(kept, by_loop[2])
    np.testing.assert_allclose(y, by_loop[0], atol=1e-5 * n_state ** 0.5)


# ------------------------------------------- the block against the reference
def test_a_whole_prompt_prefill_matches_the_full_forward():
    tokens = _tokens(16, 1)
    got, _, _ = DRIVER.prefill(DRIVER.arenas(), DRIVER.table(0, 16), tokens,
                               [16], slot=1)
    np.testing.assert_allclose(got, _reference_logits(tokens), atol=TOL)


@pytest.mark.parametrize('pieces', [
    [16, 16, 5],          # a last chunk shorter than its bucket (8)
    [16, 2],              # one of fewer rows than the convolution keeps
    [1, 1, 1, 16, 3],     # first chunks of under three rows
    [13],                 # padded: 3 rows of a bucket of 16 are not live
])
def test_prefill_in_chunks_matches_the_full_forward(pieces):
    tokens = _tokens(sum(pieces), 2)
    got, _, _ = DRIVER.prefill(DRIVER.arenas(), DRIVER.table(3, len(tokens)),
                               tokens, pieces, slot=2)
    np.testing.assert_allclose(got, _reference_logits(tokens), atol=TOL)


@pytest.mark.parametrize('form', ['the row loop', 'the kernel'])
def test_prefill_then_decode_through_the_cache_matches_the_full_forward(
        form, request):
    """Three sequences of unlike depth, each prefilled in chunks into
    its own slot and pages, then decoded together; between the steps the
    rows change places (the engine compacts its batch every step: a row
    index is no home for state, the slot is). With the state update in
    either form."""
    stepper = None
    if form == 'the kernel':
        request.getfixturevalue('in_a_kernel')
        # a driver of its own: the step traced again, with the kernel
        stepper = Driver(SPEC, WEIGHTS, BS, NB, slots=SLOTS, pages=PAGES)
    block_harness.prefill_then_decode_through_the_cache(
        DRIVER, ref, 12, CHUNK, TOL, stepper)


@pytest.mark.parametrize('lowered,what', [
    (dict(d_skip=False), 'the skip term'),
    (dict(dt_bias=False), 'the time-step bias'),
    (dict(gate=False), 'the gate'),
    (dict(state_dtype='bfloat16'), 'a state in bfloat16'),
])
def test_the_tolerance_catches_a_wrong_layer(lowered, what):
    tokens = _tokens(40, 8)
    sound = _reference_logits(tokens)
    wrong = _reference_logits(tokens, **lowered)
    assert np.abs(sound - wrong).max() > 10 * TOL, what


# Whether a program copies an arena is a property of the chip's compiler
# (the CPU's drops the barrier that keeps a slot's slice a value of its
# own, ops/ssm_ops.py::_slot_of, and copies the arena twice a row):
# tests/test_v5e_compile.py::test_the_state_arenas_are_written_where_they_lie
# reads it off the programs compiled for a described v5e.


# ------------------------------------------------------------ the engine
def _engine(**over):
    kw = dict(max_batch=SLOTS, block_size=BS, num_blocks=NB,
              pages_per_seq=PAGES, prefill_chunk=CHUNK, min_prompt_bucket=4,
              weights=WEIGHTS)
    kw.update(over)
    return DecodeEngine(SPEC, **kw)


def _reference_tokens(prompt, answer):
    lg = _reference_logits(list(prompt) + list(answer))
    return lg[len(prompt) - 1:len(prompt) + len(answer) - 1].argmax(1) \
        .tolist()


PROMPTS = [_tokens(n, 20 + n).tolist() for n in (5, 23, 41, 60, 33, 2, 18)]
ANSWERS = (18, 6, 25, 9, 30, 12, 3)


@pytest.fixture(scope='module')
def alone():
    """Each request through an engine of its own batch row, one at a
    time, sampled at temperature 1 (a stream depends on its seed and
    positions alone): what every other way of serving them must give."""
    eng = _engine(max_batch=1)
    eng.start()
    out = [eng.generate(p, max_new_tokens=n, temperature=1.0, seed=i,
                        timeout=600)
           for i, (p, n) in enumerate(zip(PROMPTS, ANSWERS))]
    eng.shutdown()
    return out


@pytest.fixture(scope='module')
def served():
    """Seven requests through an engine of four rows, submitted
    together: rows join as others leave, the batch is compacted every
    step, slots are reused. (greedy answers, sampled answers, counters,
    slots and pages used at the end, signatures)."""
    observe.enable()
    eng = _engine()
    eng.warmup()
    eng.start()
    greedy = [s.result(timeout=600) for s in [
        eng.submit(p, max_new_tokens=n) for p, n in zip(PROMPTS, ANSWERS)]]
    sampled = [s.result(timeout=600) for s in [
        eng.submit(p, max_new_tokens=n, temperature=1.0, seed=i)
        for i, (p, n) in enumerate(zip(PROMPTS, ANSWERS))]]
    counters = dict(observe.snapshot()['counters'])
    used = [(p.used_blocks(), p.num_blocks) for p in eng.pools]
    signatures = eng.warmup_signatures
    eng.shutdown()
    observe.disable()
    observe.reset()
    return greedy, sampled, counters, used, signatures


@pytest.mark.parametrize('i', range(len(PROMPTS)))
def test_the_engine_serves_the_references_tokens(served, i):
    assert served[0][i] == _reference_tokens(PROMPTS[i], served[0][i])


def test_concurrent_is_one_at_a_time(served, alone):
    assert served[1] == alone
    assert len({tuple(a) for a in alone}) == len(alone)


def test_slots_and_pages_return_and_the_series_count_them(served):
    _, _, counters, used, signatures = served
    assert used == [(0, NB), (0, SLOTS)]
    assert signatures == 4                  # buckets 4, 8, 16 and the step
    # a first chunk a request; 6 state layers a live row a step
    assert counters['decode.state_resets_total'] == 2 * len(PROMPTS)
    assert counters['decode.step_state_rows_total'] == \
        6 * counters['decode.step_rows']
    assert counters['decode.prefill_scan_chunks_total'] >= \
        6 * counters['decode.prefill_chunks']
    assert 'decode.state_recomputed_tokens_total' not in counters
    assert counters['decode.kv_pages_allocated_total{kind=state}'] == \
        2 * len(PROMPTS)
    # the attention's counters see the two attention layers alone
    assert counters['decode.cache_bytes_read{kind=lm_kcache}'] > 0
    assert counters.get('decode.cache_bytes_read{kind=lm_ssm_state}', 0) == 0
    assert counters['decode.attn_pages_reachable'] % (2 * PAGES) == 0


def test_a_reused_slot_gives_what_a_fresh_engine_gives(alone):
    """One slot, so every request takes the one its predecessor left
    full of state: a sequence's first chunk starts from zeros."""
    eng = _engine(max_batch=1)
    eng.start()
    for i in (3, 1, 5):
        assert eng.pools[1].used_blocks() == 0
        assert eng.generate(PROMPTS[i], max_new_tokens=ANSWERS[i],
                            temperature=1.0, seed=i, timeout=600) == alone[i]
    # the slot was left full: the arena is not zeros
    assert np.abs(np.asarray(eng._scope.get('lm_ssm_state'))[:, 0]).max() > 0
    eng.shutdown()


def test_a_preempted_sequence_resumes_to_identical_tokens(alone):
    """Pages to admit all three and not to finish them: the youngest is
    preempted,
    its slot and pages released, its prefix prefilled again from a zero
    state, and its stream goes on as if nothing had happened."""
    observe.enable()
    tight = _engine(num_blocks=38, max_batch=3)
    tight.start()
    picks = (2, 3, 4)
    streams = [tight.submit(PROMPTS[i], max_new_tokens=ANSWERS[i],
                            temperature=1.0, seed=i) for i in picks]
    got = [s.result(timeout=600) for s in streams]
    assert observe.get_counter('decode.preemptions_total') > 0
    assert observe.get_counter('decode.state_recomputed_tokens_total') > 0
    assert got == [alone[i] for i in picks]
    assert [p.used_blocks() for p in tight.pools] == [0, 0]
    tight.shutdown()


def test_a_step_kept_in_flight_sees_the_state_the_step_before_wrote(alone):
    """With nothing waiting the worker enqueues step n + 1 before it
    fetches step n; the arenas chain through the donated scope, so the
    tokens are the synchronous ones."""
    observe.enable()
    eng = _engine(max_batch=2)
    eng.warmup()
    eng.start()
    picks = (0, 4)
    streams = [eng.submit(PROMPTS[i], max_new_tokens=ANSWERS[i],
                          temperature=1.0, seed=i) for i in picks]
    assert [s.result(timeout=600) for s in streams] == \
        [alone[i] for i in picks]
    assert observe.get_counter('decode.steps_ahead_total') > 0
    eng.shutdown()


def test_the_programs_take_a_slot_a_row_and_keep_one_signature():
    eng = _engine()
    feeds = {v.name: tuple(v.shape) for v in
             eng._progs.decode.global_block().vars.values()
             if getattr(v, 'is_data', False)}
    assert sorted(feeds) == ['dec_lens', 'dec_seeds', 'dec_tables',
                             'dec_tables_state', 'dec_temps', 'dec_tokens']
    assert feeds['dec_tables_state'][-1] == 1
    assert eng._progs.arena_names == ('lm_kcache', 'lm_vcache',
                                      'lm_ssm_state', 'lm_ssm_conv')
    shapes = {n: tuple(eng._scope.get(n).shape)
              for n in eng._progs.arena_names}
    assert shapes == {'lm_kcache': (2, NB, BS, 16),
                      'lm_vcache': (2, NB, BS, 16),
                      'lm_ssm_state': (6, SLOTS + 1, 8, 64),
                      'lm_ssm_conv': (6, SLOTS + 1, 240)}
    assert str(eng._scope.get('lm_ssm_state').dtype) == 'float32'
    # the rows past the batch and every warm-up feed point at the spare
    args = eng._warm_args('decode')
    assert args[-1].shape == (SLOTS, 1) and (args[-1] == SLOTS).all()
    assert eng._warm_args(8)[-1].tolist() == [[SLOTS]]
    # a state pool as wide as the batch unless told otherwise
    assert eng.pools[1].num_blocks == SLOTS and eng.pools[1].whole
    wide = _engine(pool_blocks={'state': 7})
    assert wide.pools[1].num_blocks == 7
    assert wide._scope.get('lm_ssm_state').shape[1] == 8
    wide.shutdown(drain=False)
    eng.shutdown(drain=False)
