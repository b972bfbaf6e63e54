"""The form of the latent attention follows the rows that share a table
(ops/latent_moe_ops.py, ops/pallas/paged_attention.py): a prefill chunk
of many rows expands each block of latent rows it reads to per-head keys
and values, a decode step and a short chunk run absorbed. On the CPU at
toy widths:

- the expanded form against the absorbed form and against a dense
  float32 oracle that expands every cached row, over the three ways the
  serving configurations bound what a row sees, three places of a chunk
  in its sequence and both arena dtypes;
- a block no column of which a row sees leaves that row's result as it
  was, to the bit;
- the rule (``LatentShape.expands``) at the published widths, the
  engine's count of the chunks it sends to the expanded form against
  what the prefill programs hold, and the block's logits under either
  form;
- the per-head branch (tbig_lm, command_a_plus) traces to the program it
  traced to before the latent form could expand;
- the benchmark's metric of the count resolves and reads.
"""

import collections
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import latent_moe_ops as lmo
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode import model as lm
from block_harness import Driver

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NB, BS, P = 48, 8, 16            # pool, page, table: 128 columns a table
COLS = 32                        # a column block: 4 pages, 4 blocks
S = 32                           # the chunk's bucket
CAP = P * BS
YARN = dict(type='yarn', factor=4, original_max_position_embeddings=16,
            beta_fast=4, beta_slow=1, mscale=1, mscale_all_dim=1)

# name -> (heads, rank, d_nope, d_rope, d_v, rope scaling, window, kept):
# what bounds a row's columns beside its own position
KINDS = {
    # kimi_k2_6: every position at or below the row's own, YaRN positions
    'dense_yarn': (4, 12, 8, 8, 8, YARN, 0, 0),
    # dots3_note's full layers: the columns a selection kept (``chosen``)
    'full_under_chosen': (4, 12, 8, 4, 8, None, 0, 8),
    # dots3_note's sliding layers: the last ``window`` columns (``lo``)
    'sliding_under_lo': (2, 20, 12, 4, 8, None, 5, 0),
}
# name -> (first position, live rows of the bucket's S)
PLACES = {
    'at_offset_0': (0, S),
    'after_cached_shared_pages': (40, S),
    'last_padded_chunk': (72, 11),
}
SPARE = 4                        # a stored row's columns past [c_kv ; k_rope]


def _turned(x, pos, freq):
    """Interleaved pairs of x [N, D] turned by pos * freq."""
    angle = np.asarray(pos, np.float64)[:, None] * freq[None, :]
    pairs = x.reshape(x.shape[0], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    cos, sin = np.cos(angle), np.sin(angle)
    return np.stack([even * cos - odd * sin, even * sin + odd * cos],
                    -1).reshape(x.shape)


def _case(kind, place, dtype, seed=0):
    """A table whose every column holds a latent row, and a chunk of S
    queries at ``place``: (query [S, H, d_nope + d_rope], arena, table,
    lo, hi, chosen, W_UK, W_UV, softmax scale, rank)."""
    h, r, d_nope, d_rope, d_v, scaling, window, kept = KINDS[kind]
    start, live = PLACES[place]
    shape = lm.LatentShape(h, 16, r, d_nope, d_rope, d_v, 100.0, scaling)
    freq = shape.rope_frequencies()
    rng = np.random.RandomState(seed)
    # cached rows as the block writes them: position j's k_rope turned by j
    table = rng.permutation(NB)[:P]
    rows = np.zeros((2, NB, BS, r + d_rope + SPARE))
    at = np.arange(CAP)
    rows[1, table[at // BS], at % BS, :r] = rng.randn(CAP, r)
    rows[1, table[at // BS], at % BS, r:r + d_rope] = _turned(
        rng.randn(CAP, d_rope), at, freq)
    pos = start + np.arange(S)
    q = rng.randn(S, h, d_nope + d_rope)
    q[..., d_nope:] = _turned(
        q[..., d_nope:].reshape(S * h, d_rope), np.repeat(pos, h),
        freq).reshape(S, h, d_rope)
    hi = np.where(np.arange(S) < live, pos + 1, 0)
    lo = np.maximum(hi - window, 0) if window else np.zeros_like(hi)
    chosen = None
    if kept:
        # any ``kept`` of the columns a row may see, its own among them
        score = rng.rand(S, CAP)
        score[np.arange(S), pos] = 2.0
        score[at[None, :] > pos[:, None]] = -1.0
        chosen = score >= np.sort(score, 1)[:, -kept][:, None]
    w_uk = rng.randn(h, d_nope, r) * r ** -0.5
    w_uv = rng.randn(h, r, d_v) * r ** -0.5
    scale = (d_nope + d_rope) ** -0.5 * shape.softmax_multiplier()
    return (jnp.asarray(q, jnp.float32), jnp.asarray(rows, dtype),
            jnp.asarray(table, jnp.int32), jnp.asarray(lo, jnp.int32),
            jnp.asarray(hi, jnp.int32),
            None if chosen is None else jnp.asarray(chosen),
            jnp.asarray(w_uk, dtype), jnp.asarray(w_uv, dtype), scale, r)


def _expanded(case, **kw):
    q, arena, table, lo, hi, chosen, w_uk, w_uv, scale, r = case
    return pa.paged_attention_one_table(
        q, arena, None, table, hi, sm_scale=scale, layer=1, lo=lo,
        block_cols=COLS, latent=r, chosen=chosen, expand=(w_uk, w_uv), **kw)


def _absorbed(case):
    """As ``LatentMoEBlock._attention`` runs it: the key up-projection
    folded into the query, the value up-projection applied to the sum."""
    q, arena, table, lo, hi, chosen, w_uk, w_uv, scale, r = case
    d_nope = w_uk.shape[1]
    exact = jax.lax.Precision.HIGHEST
    # the two products outside the attention in float32 (the CPU has no
    # batched bfloat16 product that accumulates in float32)
    q_abs = jnp.einsum('nhd,hdr->nhr', q[..., :d_nope],
                       w_uk.astype(jnp.float32), precision=exact)
    q_row = jnp.concatenate(
        [q_abs, q[..., d_nope:], jnp.zeros(q.shape[:2] + (SPARE,))], -1)
    mixed = pa.paged_attention_one_table(
        q_row, arena, None, table, hi, sm_scale=scale, layer=1, lo=lo,
        block_cols=COLS, latent=r, chosen=chosen)
    return jnp.einsum('nhr,hrv->nhv', mixed, w_uv.astype(jnp.float32),
                      precision=exact)


def _oracle(case):
    """Every cached row of the table expanded to a key and a value a
    head, one float64 softmax over the whole extent."""
    q, arena, table, lo, hi, chosen, w_uk, w_uv, scale, r = (
        None if x is None else np.asarray(x, np.float64)
        if hasattr(x, 'dtype') and x.dtype != bool else np.asarray(x)
        for x in case)
    d_nope = w_uk.shape[1]
    d_rope = q.shape[-1] - d_nope
    rows = arena[1][table.astype(int)].reshape(CAP, -1)
    keys = np.concatenate(
        [np.einsum('kc,hdc->hkd', rows[:, :r], w_uk),
         np.broadcast_to(rows[None, :, r:r + d_rope],
                         (q.shape[1], CAP, d_rope))], -1)
    values = np.einsum('kc,hcv->hkv', rows[:, :r], w_uv)
    cols = np.arange(CAP)[None, :]
    seen = (cols >= lo[:, None]) & (cols < hi[:, None])
    if chosen is not None:
        seen &= chosen
    scores = np.einsum('shd,hkd->hsk', q, keys) * scale
    scores = np.where(seen[None], scores, -np.inf)
    top = np.where(seen.any(1), scores.max(-1), 0.0)
    w = np.where(seen[None], np.exp(scores - top[..., None]), 0.0)
    w = w / np.where(seen.any(1), w.sum(-1), 1.0)[..., None]
    return np.einsum('hsk,hkv->shv', w, values)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('place', sorted(PLACES))
@pytest.mark.parametrize('kind', sorted(KINDS))
def test_expanded_rows_equal_the_absorbed_form_and_the_dense_oracle(
        kind, place, dtype):
    """Float32 arenas: the three differ in the order of their sums only.
    bfloat16: the expanded form rounds each key and value it makes, the
    absorbed form the folded query and the weighted sum of latents; both
    stay within what bfloat16 operands give the per-head forms
    (tests/test_paged_attention_blocked.py)."""
    case = _case(kind, place, dtype, seed=len(kind) + len(place))
    got, folded, want = (np.asarray(_expanded(case)),
                         np.asarray(_absorbed(case)), _oracle(case))
    h, _, _, _, d_v = KINDS[kind][:5]
    assert got.dtype == np.float32 and got.shape == (S, h, d_v)
    tol = 2e-5 if dtype == 'float32' else 3e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    np.testing.assert_allclose(got, folded, atol=tol, rtol=tol)
    live = PLACES[place][1]
    assert not got[live:].any()         # the bucket's padding yields 0
    assert np.abs(want[:live]).max() > 0.1


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('kind', sorted(KINDS))
def test_a_block_a_row_sees_nothing_of_leaves_its_state_to_the_bit(
        kind, dtype):
    """The chunk's 32 rows at positions 40..71 cross from column block 1
    into block 2. With the rows past the edge taken out, block 2 is not
    run at all; with them in, it runs over the first 24 rows too and they
    see no column of it. Their results are the same bits. (Under a
    window of 5 the loop also starts later for the late rows alone.)"""
    q, arena, table, lo, hi, chosen, w_uk, w_uv, scale, r = \
        _case(kind, 'after_cached_shared_pages', dtype)
    among = np.asarray(_expanded(
        (q, arena, table, lo, hi, chosen, w_uk, w_uv, scale, r)))
    early = np.arange(S) < 24           # positions 40..63: block 1
    for only in (early, ~early):
        alone = np.asarray(_expanded(
            (q, arena, table, lo, jnp.where(only, hi, 0), chosen, w_uk, w_uv,
             scale, r)))
        assert np.array_equal(alone[only], among[only])
        assert not alone[~only].any()


# --------------------------------------------------------------- the rule
@pytest.mark.parametrize('shape,least', [
    # kimi_k2_6, and dots3_note's full layers: rank 512 over 128 + 128
    ((64, 1536, 512, 128, 64, 128), 171),
    ((128, 1536, 512, 128, 64, 128), 171),
    # dots3_note's sliding layers: rank 1,024 over 192 + 128
    ((64, 1536, 1024, 192, 64, 128), 190),
])
def test_the_rule_at_the_published_widths(shape, least):
    kind = lm.LatentShape(*shape, rope_theta=1e4)
    assert not kind.expands(least - 1) and kind.expands(least)
    assert [kind.expands(b) for b in (1, 8, 64, 128, 256, 512)] == \
        [False, False, False, False, True, True]
    # the function under the method, as the lowering calls it
    assert lm.latent_expands(kind.kv_rank, kind.d_nope, kind.d_v, least)
    # a latent no wider than what it expands to never pays
    assert not lm.LatentShape(4, 8, 8, 8, 4, 8, 1e4).expands(10 ** 6)


def _latent(kind):
    """A kind of KINDS as LMSpec takes a latent attention."""
    h, r, d_nope, d_rope, d_v, scaling, _, _ = KINDS[kind]
    return dict(n_head=h, q_rank=16, kv_rank=r, d_nope=d_nope,
                d_rope=d_rope, d_v=d_v, rope_scaling=scaling)


_Toy = collections.namedtuple('_Toy', 'spec weights ref bs pages nb tol')


@functools.lru_cache(maxsize=None)
def _kimi():
    """kimi_k2_6 at the size of tests/test_kimi_k2_6_block.py: four
    full layers of the dense kind under YaRN, the first one dense."""
    from paddle_tpu.models.reference import kimi_k2_6 as ref
    spec = LMSpec(
        vocab_size=64, n_layer=4, d_model=32, d_inner=24,
        block='latent_moe', layer_types=[lm.FULL] * 4,
        latent={lm.FULL: dict(_latent('dense_yarn'), rope_theta=100.0)},
        dense_layers=1, d_inner_dense=40, index_topk=0, n_experts=8,
        experts_held=3, first_expert=2, experts_per_token=3,
        n_shared_experts=1, lora_rescale=False, attn_gate=False,
        routed_scale=2.827)
    return _Toy(spec, random_weights(spec, seed=7), ref, 4, 16, 64, 5e-5)


@functools.lru_cache(maxsize=None)
def _dots3():
    """dots3_note at the size of tests/test_latent_moe_block.py: a dense
    full layer, then (full, sliding x 3); an indexer that keeps 8
    positions, window 5."""
    from paddle_tpu.models.reference import dots3_note as ref
    spec = LMSpec(
        vocab_size=64, n_layer=5, d_model=32, d_inner=24,
        block='latent_moe', sliding_window=KINDS['sliding_under_lo'][6],
        layer_types=[lm.FULL] * 2 + [lm.SLIDING] * 3,
        latent={lm.FULL: dict(_latent('full_under_chosen'), rope_theta=8e7),
                lm.SLIDING: dict(_latent('sliding_under_lo'),
                                 rope_theta=5e4)},
        dense_layers=1, d_inner_dense=40, index_n_heads=3,
        index_head_dim=8, index_topk=KINDS['full_under_chosen'][7],
        n_experts=8, experts_held=4, first_expert=2, experts_per_token=3,
        n_shared_experts=1)
    return _Toy(spec, random_weights(spec, seed=5), ref, 4, 12, 40, 5e-5)


@pytest.mark.parametrize('which,cached', [
    (_kimi, 0), (_kimi, 12), (_dots3, 0), (_dots3, 12)])
def test_block_logits_are_the_references_under_either_form(
        monkeypatch, which, cached):
    """A chunk of 32 rows through the whole block (kimi_k2_6's dense
    layers under YaRN; dots3_note's full layers under their selection
    and sliding ones under their window), after ``cached`` positions of
    an earlier chunk: expanded as the rule has it (the toy widths expand
    from 25 and 21 rows), and with the rule held to the absorbed form,
    both against the plain reference."""
    t = which()
    rng = np.random.RandomState(cached)
    tokens = rng.randint(0, t.spec.vocab_size, cached + 32)
    table = jnp.asarray(rng.permutation(t.nb)[:t.pages], jnp.int32)
    assert all(a.expands(32) and not a.expands(cached)
               for a in t.spec.latent.values())
    want = Driver(t.spec, t.weights, t.bs, t.nb).reference_logits(
        t.ref, tokens)[cached:]

    def chunk():
        # a driver of its own a form: a program reads the rule as it is
        # traced
        driver = Driver(t.spec, t.weights, t.bs, t.nb)
        arenas = driver.arenas()
        if cached:
            _, arenas, _ = driver.prefill_chunk(arenas, table,
                                                tokens[:cached], 0)
        logits, _, _ = driver.prefill_chunk(arenas, table, tokens[cached:],
                                            cached)
        return np.asarray(logits)

    expanded = chunk()
    monkeypatch.setattr(lmo, 'latent_expands', lambda *a: False)
    absorbed = chunk()
    np.testing.assert_allclose(expanded, want, atol=t.tol, rtol=t.tol)
    np.testing.assert_allclose(absorbed, want, atol=t.tol, rtol=t.tol)
    assert not np.array_equal(expanded, absorbed)   # two forms did run


def test_engine_counts_the_chunks_the_lowering_expands():
    """Buckets 8, 16 and 32 at the toy widths (expanded from 25 rows):
    a prompt of 45 tokens is a chunk of 32 and one of 13 (bucket 16).
    The counter follows ``LatentShape.expands``, and so do the programs:
    a bucket's prefill program holds the expanded form's accumulator
    ([1, 1, H, S, d_v]) or the absorbed form's ([1, 1, H, S, rank])."""
    from paddle_tpu import observe
    kimi = _kimi()
    shape = kimi.spec.latent[lm.FULL]
    eng = DecodeEngine(
        kimi.spec, max_batch=4, block_size=kimi.bs, num_blocks=kimi.nb,
        pages_per_seq=kimi.pages, max_prompt_len=48, prefill_chunk=32,
        min_prompt_bucket=8, weights=kimi.weights, prefix_cache=False)
    try:
        assert eng.prompt_buckets == [8, 16, 32]
        for bucket in eng.prompt_buckets:
            text = str(eng.trace_program(bucket).jaxpr)
            accs = {form: 'f32[1,1,%d,%d,%d]' % (shape.n_head, bucket, width)
                    in text for form, width in
                    (('expanded', shape.d_v), ('absorbed', shape.kv_rank))}
            assert accs == {'expanded': shape.expands(bucket),
                            'absorbed': not shape.expands(bucket)}, bucket
        # a decode step has one query a table: absorbed
        assert 'f32[8,1,%d,1,%d]' % (shape.n_head, shape.kv_rank) in \
            str(eng.trace_program('decode').jaxpr)
        eng.warmup()
        eng.start()
        # whatever an earlier file of this worker left in the registry
        observe.reset()
        observe.enable()
        try:
            eng.generate(list(range(1, 46)), max_new_tokens=2, timeout=300)
            eng.generate(list(range(1, 12)), max_new_tokens=2, timeout=300)
            counters = observe.snapshot()['counters']
        finally:
            observe.disable()
            observe.reset()
    finally:
        eng.shutdown(drain=False)
    assert counters['decode.prefill_chunks'] == 3
    assert counters['decode.prefill_chunks_expanded'] == 1


# ------------------------------------------------- the per-head branch
def _attend_blocks_before(q, arenas, layer, tables, lo, hi, first, last, per,
                          latent=None, chosen=None):
    """``_attend_blocks`` as it stood before the latent form could expand
    (PR 36), comments and docstring apart: what the per-head
    configurations' programs were traced from."""
    _NEG_INF = pa._NEG_INF
    k_pages = arenas[0]
    v_pages = k_pages if latent else arenas[1]
    r, s, h, d = q.shape
    bs = k_pages.shape[2]
    n_kv = k_pages.shape[-1] // d
    d_v = latent or d
    bk = per * bs
    quantized = len(arenas) == 4
    group = h // n_kv
    qg = jnp.transpose(
        q.astype(jnp.float32 if quantized else k_pages.dtype).reshape(
            r, s, n_kv, group, d), (0, 2, 3, 1, 4))
    exact = jax.lax.Precision.HIGHEST if qg.dtype == jnp.float32 else None
    by_head = d % 128 == 0 and \
        h * s * 4 < n_kv * d * jnp.dtype(k_pages.dtype).itemsize

    def pages(arena, at):
        return arena[layer, at].reshape(r, bk, -1)

    def heads(x, width):
        if by_head:
            return [x[:, :, n * width:(n + 1) * width][:, :, None]
                    for n in range(n_kv)]
        return [x.reshape(r, bk, n_kv, width)]

    def block(j, state):
        top, norm, acc = state
        at = jax.lax.dynamic_slice_in_dim(tables, j * per, per, 1)
        kb = heads(pages(k_pages, at), d)
        vb = [x[..., :latent] for x in kb] if latent \
            else heads(pages(v_pages, at), d)
        if quantized:
            kb = [x.astype(jnp.float32) * sc for x, sc in
                  zip(kb, heads(pages(arenas[2], at), 1))]
            vb = [x.astype(jnp.float32) * sc for x, sc in
                  zip(vb, heads(pages(arenas[3], at), 1))]
        col = j * bk + jnp.arange(bk)
        seen = (col >= lo[..., None]) & (col < hi[..., None])
        if chosen is not None:
            seen &= chosen(j * bk)
        seen = seen[:, None, None]
        each = qg.shape[1] // len(kb)
        scores = jnp.concatenate([
            jnp.einsum('rngsd,rknd->rngsk',
                       qg[:, i * each:(i + 1) * each], x, precision=exact,
                       preferred_element_type=jnp.float32)
            for i, x in enumerate(kb)], axis=1)
        scores = jnp.where(seen, scores, _NEG_INF)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        w = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        norm = keep * norm + jnp.sum(w, axis=-1)
        w = w.astype(vb[0].dtype)
        acc = keep[..., None] * acc + jnp.concatenate([
            jnp.einsum('rngsk,rknd->rngsd',
                       w[:, i * each:(i + 1) * each], x, precision=exact,
                       preferred_element_type=jnp.float32)
            for i, x in enumerate(vb)], axis=1)
        return new_top, norm, acc

    shape = (r, n_kv, group, s)
    init = (jnp.full(shape, _NEG_INF, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (d_v,), jnp.float32))
    _, norm, acc = jax.lax.fori_loop(first, last + 1, block, init)
    out = acc / jnp.where(norm == 0.0, 1.0, norm)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(r, s, h, d_v)


# name -> (query heads, KV heads, head width, arena dtype, page, pages a
# table): the two per-head serving configurations' own
PER_HEAD = {
    'tbig_lm': (16, 16, 64, 'float32', 32, 24),
    'command_a_plus': (128, 8, 128, 'bfloat16', 32, 8),
    # the absorbed latent form: the decode step of both latent
    # configurations and their short chunks
    'latent_absorbed': (64, 1, 640, 'bfloat16', 32, 8),
}


@pytest.mark.parametrize('rows', ['prefill_chunk', 'decode_step'])
@pytest.mark.parametrize('config', sorted(PER_HEAD))
def test_the_branches_that_do_not_expand_trace_as_before(
        monkeypatch, config, rows):
    h, n_kv, d, dtype, bs, p = PER_HEAD[config]
    latent = dict(latent=512) if n_kv == 1 else {}
    arena = jax.ShapeDtypeStruct((2, 64, bs, n_kv * d), dtype)
    arenas = (arena, None) if latent else (arena, arena)
    if rows == 'prefill_chunk':
        def attend(q, k, v, table, lens, lo):
            return pa.paged_attention_one_table(
                q, k, v, table, lens, layer=1, lo=lo, **latent)
        args = (jax.ShapeDtypeStruct((64, h, d), 'float32'),) + arenas + (
            jax.ShapeDtypeStruct((p,), 'int32'),
            jax.ShapeDtypeStruct((64,), 'int32'),
            jax.ShapeDtypeStruct((64,), 'int32'))
    else:
        def attend(q, k, v, tables, lens, lo):
            return pa.paged_attention_blocked(
                q, k, v, tables, lens, layer=1, lo=lo, **latent)
        args = (jax.ShapeDtypeStruct((32, h, d), 'float32'),) + arenas + (
            jax.ShapeDtypeStruct((32, p), 'int32'),
            jax.ShapeDtypeStruct((32,), 'int32'),
            jax.ShapeDtypeStruct((32,), 'int32'))
    now = str(jax.make_jaxpr(attend)(*args))

    def before(*a):
        assert a[11:] in ((), (None,))          # no caller here expands
        return _attend_blocks_before(*a[:11])
    monkeypatch.setattr(pa, '_attend_blocks', before)
    assert str(jax.make_jaxpr(attend)(*args)) == now
    assert 'dot_general' in now


# ------------------------------------------------- the benchmark's metric
def test_the_share_of_expanded_chunks_resolves_and_reads():
    """``serve.mla_prefill_expanded_chunk_share`` is data only: an entry
    of BENCHMARK.json for the kimi cell and a file naming the reader
    ``registry_ratio`` over the engine's two counters. On a program
    without the counter (the parent) it reads 0, not nothing."""
    from benchmark import manifest
    name, cell = ('serve.mla_prefill_expanded_chunk_share',
                  'kimi_k2_6.doc_qa_sessions')
    bench = manifest.load(ROOT)
    assert manifest.problems(bench) == []
    # found by name, its cell by membership: where the entry stands and
    # which other cells a later PR lists are not this test's to hold
    # (until PR 43 it pinned index 84 and the list itself, which kept 23
    # copies of shared entries in the manifest: PERF.md section 7)
    (entry,) = [m for m in bench['per_layer'] if m['name'] == name]
    assert cell in entry.pop('workloads')
    assert entry == dict(
        name=name, unit='%', better='higher', source='program_counter',
        layer='op lowerings', moves='ttft_mean_ms')
    (metric,) = [m for m in manifest.resolve(bench, cell)['per_layer']
                 if m['entry']['name'] == name]
    assert os.path.basename(metric['reader']) == 'registry_ratio.py'
    assert metric['spec']['args'] == dict(
        counter='decode.prefill_chunks_expanded',
        per='decode.prefill_chunks', scale=100)
    with open(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                           name + '.json')) as f:
        assert json.load(f) == metric['spec']
    read = manifest.load_module(metric['reader']).read

    def registry(chunks, expanded=None):
        counters = {'decode.prefill_chunks': chunks}
        if expanded is not None:
            counters['decode.prefill_chunks_expanded'] = expanded
        return {'counters': counters}
    got = read(metric['spec']['args'], dict(
        registry_before=registry(40, 30), registry_after=registry(440, 390)))
    assert got == 90.0
    assert read(metric['spec']['args'], dict(
        registry_before=registry(40), registry_after=registry(440))) == 0.0
    assert read(metric['spec']['args'], dict(
        registry_before=registry(40, 30),
        registry_after=registry(40, 30))) is None
