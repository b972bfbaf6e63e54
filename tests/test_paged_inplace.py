"""The paged ops write the KV arenas in place (ops/paged_decode_ops.py).

Two halves, each over the three ops and an unquantized and a quantized
arena, at a toy geometry on the CPU:

- structure: in each op's lowered computation the arenas travel as
  loop-carried state only (no loop scans one in or stacks one out), no
  scatter takes one as its operand, and the compiled executable
  aliases every arena input to an output;
- contents: after one call the arenas differ from what went in at
  exactly the rows a plain numpy write of the same tokens changes,
  the three drop cases (row not live, position past the table's
  capacity, table entry >= NB) included.

Plus the HLO reader the chip's smoke run and tests/test_v5e_compile.py
use (serving/decode/hlo_check.py) on two canned modules.
"""

import re

import numpy as np
import pytest

from paddle_tpu.serving.decode import DecodeEngine, LMSpec, random_weights
from paddle_tpu.serving.decode.hlo_check import arena_sized_instructions

SPEC = LMSpec(vocab_size=64, n_layer=2, n_head=2, d_key=8, d_value=8,
              d_model=16, d_inner=32)
NB, BS, P, MB, K = 12, 8, 4, 4, 2     # pool, page, table, batch, spec_k
BUCKET = 16
OPS = ('paged_decode_step', 'paged_prefill', 'paged_spec_verify')
WHICH = {'paged_decode_step': 'decode', 'paged_prefill': BUCKET,
         'paged_spec_verify': 'verify'}


@pytest.fixture(scope='module', params=['float32', 'int8'])
def engine(request):
    eng = DecodeEngine(SPEC, max_batch=MB, block_size=BS, num_blocks=NB,
                       pages_per_seq=P, max_prompt_len=BUCKET, spec_k=K,
                       kv_dtype=request.param,
                       weights=random_weights(SPEC, seed=11))
    yield eng
    eng.shutdown(drain=False)


# ------------------------------------------------------------ structure
def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, 'jaxpr', sub)
                if hasattr(inner, 'eqns'):
                    for e in _eqns(inner):
                        yield e


def _is_arena(aval, arenas):
    """An arena, or one layer cut out of one."""
    shape = tuple(getattr(aval, 'shape', ()))
    return any(shape == a or shape == a[1:] for a in arenas)


@pytest.mark.parametrize('op', OPS)
def test_arenas_are_carried_not_scanned_and_never_scattered(engine, op):
    arenas = {tuple(engine._scope.get(n).shape)
              for n in engine._progs.arena_names}
    traced = engine.trace_program(WHICH[op])
    loops = carried = 0
    for eqn in _eqns(traced.jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == 'scan':
            loops += 1
            nc, nk = eqn.params['num_consts'], eqn.params['num_carry']
            scanned = [v.aval for v in eqn.invars[nc + nk:]] + \
                [v.aval for v in eqn.outvars[nk:]]
            assert not [a for a in scanned if _is_arena(a, arenas)], \
                '%s: a loop scans an arena in or stacks one out' % op
            carried += sum(_is_arena(v.aval, arenas)
                           for v in eqn.invars[nc:nc + nk])
        elif name.startswith('scatter'):
            assert not _is_arena(eqn.invars[0].aval, arenas), \
                '%s: %s takes an arena as its operand' % (op, name)
    assert loops and carried >= len(arenas), \
        '%s: the layer loop does not carry the arenas' % op


@pytest.mark.parametrize('op', OPS)
def test_compiled_program_aliases_every_arena(engine, op):
    text = engine.trace_program(WHICH[op]).lower().compile().as_text()
    header = text.split('\n', 1)[0]
    aliased = {int(p) for p in re.findall(
        r'\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)', header)}
    for name in engine._progs.arena_names:
        param = re.search(
            r'parameter\((\d+)\)[^\n]*scope_vals\[\\?\'%s\\?\'\]' % name,
            text)
        assert param, 'no parameter for %s' % name
        assert int(param.group(1)) in aliased, \
            '%s: %s is not aliased to an output' % (op, name)


# ------------------------------------------------------------- contents
def _ln(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + 1e-5) * w + b


def _quantize(rows):
    """quant.core.quantize_rows for int8, in numpy: [N, H, D] ->
    (int8 [N, H, D], fp32 scales [N, H])."""
    s = np.maximum(np.abs(rows).max(-1), 1e-12) / np.float32(127.0)
    q = np.clip(np.round(rows / s[..., None]), -127, 127).astype('int8')
    return q, s.astype('float32')


def _numpy_write(eng, arenas, tokens, pos, tables, live):
    """The plain reference: the same forward, row by row, writing each
    live row's K/V (and scales) at (layer, table[pos // bs], pos % bs)
    unless the position is past the table or the entry is not a page.
    Returns the arenas after, and which (layer, page, slot) it wrote."""
    w = {n: np.asarray(eng._scope.get(n)) for n in eng._progs.param_names}
    h_, dk = SPEC.n_head, SPEC.d_key
    out = {n: a.copy() for n, a in arenas.items()}
    wrote = np.zeros((SPEC.n_layer, NB, BS), bool)
    quant = 'lm_kscale' in arenas
    x = w['lm_emb'][tokens] * np.float32(SPEC.d_model ** 0.5) + \
        w['lm_pos_enc'][np.clip(pos, 0, P * BS - 1)]
    for layer in range(SPEC.n_layer):
        def lw(slot):
            return w['lm_stack_%s' % slot][layer]
        k_new = (x @ lw('slf_k.w')).reshape(-1, h_, dk)
        v_new = (x @ lw('slf_v.w')).reshape(-1, h_, dk)
        for n in range(len(tokens)):
            page = tables[n][min(pos[n] // BS, P - 1)]
            if not (live[n] and pos[n] < P * BS and 0 <= page < NB):
                continue
            at = (layer, page, pos[n] % BS)
            wrote[at] = True
            if quant:
                kq, ks = _quantize(k_new[n:n + 1])
                vq, vs = _quantize(v_new[n:n + 1])
                out['lm_kcache'][at], out['lm_kscale'][at] = \
                    kq.reshape(-1), ks[0]
                out['lm_vcache'][at], out['lm_vscale'][at] = \
                    vq.reshape(-1), vs[0]
            else:
                out['lm_kcache'][at] = k_new[n].reshape(-1)
                out['lm_vcache'][at] = v_new[n].reshape(-1)
        q = (x @ lw('slf_q.w')).reshape(-1, h_, dk) * dk ** -0.5
        attn = np.zeros((len(tokens), h_ * dk), 'float32')
        for n in range(len(tokens)):
            pages = np.clip(tables[n], 0, NB - 1)

            def rows(cache, scale):
                r = out[cache][layer, pages].reshape(P * BS, h_, dk) \
                    .astype('float32')
                if quant:
                    r = r * out[scale][layer, pages] \
                        .reshape(P * BS, h_, 1)
                return r
            k, v = rows('lm_kcache', 'lm_kscale'), \
                rows('lm_vcache', 'lm_vscale')
            logit = np.einsum('hd,khd->hk', q[n], k)
            logit[:, pos[n] + 1:] = -1e9
            p_ = np.exp(logit - logit.max(-1, keepdims=True))
            p_ /= p_.sum(-1, keepdims=True)
            attn[n] = np.einsum('hk,khd->hd', p_, v).reshape(-1)
        x = _ln(x + attn @ lw('slf_o.w'), lw('ln1.w'), lw('ln1.b'))
        ffn = np.maximum(x @ lw('ffn_1.w') + lw('ffn_1.b'), 0) \
            @ lw('ffn_2.w') + lw('ffn_2.b')
        x = _ln(x + ffn, lw('ln2.w'), lw('ln2.b'))
    return out, wrote


def _cases(op):
    """(tokens, pos, tables, live, call) per op: ``call(engine)`` runs
    the op on the engine; the first four are what it should write."""
    none = np.full((P,), NB, 'int32')
    rng = np.random.RandomState(3)
    if op == 'paged_prefill':
        # (cached, length, table): a cold prompt with a padded tail; a
        # suffix that starts mid-page, skips a table entry >= NB and
        # fills the bucket; a suffix that runs past the table
        for cached, length, table in (
                (0, 11, [5, 2, NB, NB]),
                (5, BUCKET, [7, NB, 1, 9]),
                (P * BS - 4, 9, [0, 3, 6, 10])):
            ids = rng.randint(0, SPEC.vocab_size, BUCKET)
            pos = cached + np.arange(BUCKET)
            table = np.asarray(table, 'int32')
            yield (ids, pos, np.tile(table, (BUCKET, 1)),
                   np.arange(BUCKET) < length,
                   lambda e, i=ids, n=length, c=cached, t=table:
                   e._run_prefill(i[None].astype('int64'), n, c,
                                  t[None], 0.0, 0))
        return
    k1 = 1 if op == 'paged_decode_step' else K + 1
    # slots: mid-page; an empty slot (every entry >= NB); one at the
    # table's end (the decode row is past it, the verify rows run off
    # it after the first); one that starts or crosses into a new page
    lens = np.asarray([5, 3, P * BS - (k1 > 1), BS - (k1 > 1)], 'int32')
    tables = np.stack([np.asarray(t, 'int32') for t in
                       ([4, 8, NB, NB], none, [0, 1, 2, 3],
                        [11, 6, NB, NB])])
    tokens = rng.randint(0, SPEC.vocab_size, (MB, k1))
    pos = (lens[:, None] + np.arange(k1)[None]).reshape(-1)
    zeros = np.zeros((MB,), 'float32')
    if k1 == 1:
        def call(e):
            np.asarray(e._dispatch_decode(
                tokens[:, 0].astype('int64'), lens, tables, zeros,
                zeros.astype('int32')))
    else:
        def call(e):
            np.asarray(e._dispatch_verify(
                tokens.astype('int64'), lens, tables, zeros,
                zeros.astype('int32')))
    yield (tokens.reshape(-1), pos, np.repeat(tables, k1, axis=0),
           np.ones(pos.shape, bool), call)


@pytest.mark.parametrize('op', OPS)
def test_one_call_writes_exactly_the_live_rows(engine, op):
    import jax.numpy as jnp
    names = engine._progs.arena_names
    rng = np.random.RandomState(5)
    for tokens, pos, tables, live, call in _cases(op):
        before = {}
        for n in names:
            a = engine._scope.get(n)
            fill = rng.randint(-90, 90, a.shape) if a.dtype == jnp.int8 \
                else rng.rand(*a.shape) + 0.5
            before[n] = np.asarray(fill).astype(a.dtype)
            engine._scope.set(n, jnp.asarray(before[n]))
        call(engine)
        want, wrote = _numpy_write(engine, before, tokens, pos, tables,
                                   live)
        assert wrote.any() and not wrote.all()
        for n in names:
            got = np.asarray(engine._scope.get(n))
            changed = (got != before[n]).any(-1)
            np.testing.assert_array_equal(
                changed, wrote, err_msg='%s %s: rows written' % (op, n))
            if got.dtype == np.int8:
                # a tie in round() may land one step apart
                assert np.abs(got.astype('int32')
                              - want[n].astype('int32')).max() <= 1, n
            else:
                np.testing.assert_allclose(got, want[n], rtol=2e-4,
                                           atol=2e-5, err_msg=n)


# ------------------------------------------------- the HLO reader alone
_CLEAN = '''HloModule jit_decode_step, is_scheduled=true

%fused_gather (p0: f32[2,8,4,16], p1: s32[3,2]) -> f32[6,4,16] {
  %p0 = f32[2,8,4,16]{3,2,1,0:T(8,128)} parameter(0)
  %p1 = s32[3,2]{1,0} parameter(1)
  %gather.1 = f32[3,2,4,16]{3,2,1,0:T(8,128)} gather(%p0, %p1), offset_dims={2,3}
  ROOT %reshape.1 = f32[6,4,16]{2,1,0:T(8,128)} reshape(%gather.1)
}

%fused_dus (p0.1: f32[2,8,4,16], p1.1: f32[1,1,1,16], p2.1: s32[]) -> f32[2,8,4,16] {
  %p0.1 = f32[2,8,4,16]{3,2,1,0:T(8,128)} parameter(0)
  %p1.1 = f32[1,1,1,16]{3,2,1,0} parameter(1)
  %p2.1 = s32[] parameter(2)
  ROOT %dus.1 = f32[2,8,4,16]{3,2,1,0:T(8,128)} dynamic-update-slice(%p0.1, %p1.1, %p2.1, %p2.1, %p2.1, %p2.1)
}

%body (arg: (s32[], f32[2,8,4,16])) -> (s32[], f32[2,8,4,16]) {
  %arg = (s32[], f32[2,8,4,16]{3,2,1,0:T(8,128)}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %arena = f32[2,8,4,16]{3,2,1,0:T(8,128)} get-tuple-element(%arg), index=1
  %row = f32[1,1,1,16]{3,2,1,0} broadcast(%i), dimensions={}
  %fusion.2 = f32[2,8,4,16]{3,2,1,0:T(8,128)} fusion(%arena, %row, %i), kind=kLoop, calls=%fused_dus
  %tables = s32[3,2]{1,0} broadcast(%i), dimensions={}
  %fusion.3 = f32[6,4,16]{2,1,0:T(8,128)} fusion(%fusion.2, %tables), kind=kCustom, calls=%fused_gather
  %reshape.9 = f32[3,8,2,8]{3,2,1,0:T(8,128)} reshape(%fusion.3)
  %copy.7 = f32[1,1,1,16]{3,2,1,0} copy(%row)
  ROOT %tuple.1 = (s32[], f32[2,8,4,16]{3,2,1,0:T(8,128)}) tuple(%i, %fusion.2)
}

ENTRY %main (a: f32[2,8,4,16]) -> f32[2,8,4,16] {
  %a = f32[2,8,4,16]{3,2,1,0:T(8,128)} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[2,8,4,16]{3,2,1,0:T(8,128)}) tuple(%zero, %a)
  %while.1 = (s32[], f32[2,8,4,16]{3,2,1,0:T(8,128)}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[2,8,4,16]{3,2,1,0:T(8,128)} get-tuple-element(%while.1), index=1
}
'''

# the parent's shape of the same loop: a layer sliced out, re-laid for
# a scatter, re-laid back, and the gathered pages transposed
_COPIES = '''HloModule jit_decode_step, is_scheduled=true

%fused_slice (p0: f32[2,8,2,4,8], p1: s32[]) -> f32[1,8,2,4,8] {
  %p0 = f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)} parameter(0)
  %p1 = s32[] parameter(1)
  ROOT %ds = f32[1,8,2,4,8]{1,4,3,2,0:T(8,128)} dynamic-slice(%p0, %p1, %p1, %p1, %p1, %p1), dynamic_slice_sizes={1,8,2,4,8}
}

%fused_scatter (p0.1: f32[64,8], p1.1: f32[3,8], p2.1: s32[3,1]) -> f32[64,8] {
  %p0.1 = f32[64,8]{0,1:T(8,128)} parameter(0)
  %p1.1 = f32[3,8]{1,0} parameter(1)
  %p2.1 = s32[3,1]{1,0} parameter(2)
  ROOT %scatter.1 = f32[64,8]{0,1:T(8,128)} scatter(%p0.1, %p2.1, %p1.1), to_apply=%assign
}

%body (arg: (s32[], f32[2,8,2,4,8])) -> (s32[], f32[2,8,2,4,8]) {
  %arg = (s32[], f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %arena = f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)} get-tuple-element(%arg), index=1
  %fusion.26 = f32[1,8,2,4,8]{1,4,3,2,0:T(8,128)} fusion(%arena, %i), kind=kLoop, calls=%fused_slice
  %copy.116 = f32[1,8,2,4,8]{4,3,2,1,0:T(8,128)} copy(%fusion.26)
  %bitcast.1 = f32[64,8]{1,0:T(8,128)} bitcast(%copy.116)
  %copy.117 = f32[64,8]{0,1:T(8,128)} copy(%bitcast.1)
  %rows = f32[3,8]{1,0} broadcast(%i), dimensions={}
  %idx = s32[3,1]{1,0} broadcast(%i), dimensions={}
  %fusion.153 = f32[64,8]{0,1:T(8,128)} fusion(%copy.117, %rows, %idx), kind=kLoop, calls=%fused_scatter
  %copy.121 = f32[64,8]{1,0:T(8,128)} copy(%fusion.153)
  %small = f32[3,8]{0,1} copy(%rows)
  ROOT %tuple.1 = (s32[], f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)}) tuple(%i, %arena)
}

ENTRY %main (a: f32[2,8,2,4,8]) -> f32[2,8,2,4,8] {
  %a = f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)}) tuple(%zero, %a)
  %while.1 = (s32[], f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)}) while(%init), condition=%cond, body=%body
  %gte = f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)} get-tuple-element(%while.1), index=1
  ROOT %copy.164 = f32[2,8,2,4,8]{1,4,3,2,0:T(8,128)} copy(%gte)
}
'''


# a kernel that takes the arena whole: its first result is its fourth
# operand's buffer, its second a buffer of its own
_KERNEL = '''HloModule jit_decode_step, is_scheduled=true

ENTRY %main (a: f32[2,8,4,16], n: s32[1]) -> f32[2,8,4,16] {
  %a = f32[2,8,4,16]{3,2,1,0:T(8,128)} parameter(0)
  %n = s32[1]{0} parameter(1)
  %rows = f32[3,1,64]{2,1,0:T(1,128)} broadcast(%n), dimensions={}
  %update.1 = (f32[2,8,4,16]{3,2,1,0:T(8,128)}, f32[3,1,64]{2,1,0:T(1,128)S(1)}) custom-call(%n, %n, %rows, %a), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}, s32[1]{0}, f32[3,1,64]{2,1,0}, f32[2,8,4,16]{3,2,1,0}}, output_to_operand_aliasing={{0}: (3, {})}, backend_config={"custom_call_config": {"body": "TUzvUg", "needs_layout_passes": true}}
  %relaid.1 = (f32[2,8,4,16]{3,2,1,0:T(8,128)}, f32[8,64]{1,0}) custom-call(%n, %a), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{1}: (0, {})}
  %whole.1 = f32[2,8,4,16]{3,2,1,0:T(8,128)} custom-call(%update.1), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{}: (0, {})}
  ROOT %out = f32[2,8,4,16]{3,2,1,0:T(8,128)} get-tuple-element(%update.1), index=0
}
'''


@pytest.mark.parametrize('text,layer,gathers,want', [
    (_CLEAN, 8 * 4 * 16, False, []),
    (_COPIES, 8 * 4 * 16, False,
     ['fusion.26', 'copy.116', 'copy.117', 'fusion.153', 'copy.121',
      'copy.164']),
    # at the extent of what the gather yields (3 tables x 2 pages): what
    # consumes a gather's result is not exempt, and with gathers=True
    # neither is the gather; the in-place update and the loop still are
    (_CLEAN, 6 * 4 * 16, False, ['reshape.9']),
    (_CLEAN, 6 * 4 * 16, True, ['fusion.3', 'reshape.9']),
    # a kernel's result that is its operand's buffer moves nothing; one
    # of that size that is a buffer of its own does
    (_KERNEL, 8 * 4 * 16, False, ['relaid.1']),
    (_KERNEL, 3 * 64, False, ['rows', 'update.1', 'relaid.1']),
], ids=['in_place', 'parent_shape', 'gather_consumer', 'whole_table',
        'aliased_kernel', 'kernel_own_result'])
def test_hlo_reader_counts_arena_sized_instructions(text, layer, gathers,
                                                    want):
    # ``layer``: one layer's arena elements in both modules, or the
    # elements of the tables' whole extent
    found = arena_sized_instructions(text, layer, gathers=gathers)
    assert [i.name for i in found] == want
    assert all(i.elements >= layer for i in found)
