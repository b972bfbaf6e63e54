"""Program builder for the decode engine's decoder-only LM.

Builds the three Programs the engine drives through one Executor +
Scope, all sharing one parameter namespace (prefix ``lm_``):

- **startup** — initializes the stacked GPT-block weights
  (models.transformer._stacked_layer_params layout, ENC_SLOTS — causal
  self-attention + FFN + 2 LNs per layer), token embedding, sinusoid
  position table, output projection, and the two zeroed KV page arenas
  ``[L, NB, bs, H*d]``. Arenas are persistable scope state: every
  prefill/decode run reads them from scope and writes them back
  through executor donation — in-place HBM updates, the same
  whole-program-state contract the trainer uses for params.
- **prefill** — the ``paged_prefill`` op over feeds
  (ids [1, S], len, cached-prefix length, block table row, temp,
  seed). S varies by prompt bucket; each bucket is one compile-cache
  key, enumerated by ``DecodeEngine.warmup()``. ``pf_cached`` carries
  the prefix-cache hit length (0 on a miss) — a traced feed, so cache
  hits of any depth share the bucket's one signature.
- **decode** — the ``paged_decode_step`` op over fixed [max_batch]
  feeds: ONE signature for the engine's whole lifetime.
- **verify** (only when ``spec_k > 0``) — the ``paged_spec_verify``
  op over fixed [max_batch, spec_k+1] feeds: speculative-decoding
  verification as one more lifetime-fixed signature (k is a static
  attr, never a shape the scheduler can vary).

A scope trained elsewhere can be served by passing its weights to
``DecodeEngine(weights=...)`` — names here are stable and listed in
``DecodePrograms.param_names``.
"""

import collections

import numpy as np

from ... import layers
from ...core.program import Program, program_guard
from ...initializer import Constant, Normal, NumpyArrayInitializer
from ...layers.helper import LayerHelper
from ...models.transformer import (_stacked_layer_params,
                                   position_encoding_table)
from ...ops.transformer_ops import _slot_to_input
from ...param_attr import ParamAttr

__all__ = ['LMSpec', 'DecodePrograms', 'build_lm_programs']


class LMSpec(object):
    """Decoder-only LM hyperparameters (GPT block: causal self-attn +
    FFN, pre-LN-free residual+LN layout shared with the NMT encoder)."""

    def __init__(self, vocab_size, n_layer=2, n_head=2, d_key=16,
                 d_value=16, d_model=32, d_inner=64):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        self.d_key = int(d_key)
        self.d_value = int(d_value)
        self.d_model = int(d_model)
        self.d_inner = int(d_inner)


DecodePrograms = collections.namedtuple(
    'DecodePrograms',
    ['startup', 'prefill', 'decode', 'verify', 'prefill_fetch',
     'decode_fetch', 'verify_fetch', 'param_names', 'arena_names',
     'capacity', 'kv_dtype'])


def kv_bytes_per_token(spec, kv_dtype='float32'):
    """HBM bytes one cached token costs across all layers: the K/V
    rows at the arena dtype plus (for quantized arenas) the per-token
    per-head fp32 scale pair. This is the number the ISSUE's capacity
    claim rides on: int8 at d_head=128 is ~3.9x less than fp32."""
    from ...quant.core import kv_itemsize, kv_quantized
    item = kv_itemsize(kv_dtype)
    b = spec.n_layer * spec.n_head * (spec.d_key + spec.d_value) * item
    if kv_quantized(kv_dtype):
        b += spec.n_layer * spec.n_head * 2 * 4   # k + v scale rows
    return b


def arena_bytes(spec, num_blocks, block_size, kv_dtype='float32'):
    """Total bytes of the K/V (+ scale) arenas."""
    return kv_bytes_per_token(spec, kv_dtype) * int(num_blocks) * \
        int(block_size)


def kv_page_bytes(spec, block_size, kv_dtype='float32'):
    """Wire bytes one FULL page costs in a KV handoff packet
    (serving/handoff.py): the page's K/V rows at the arena dtype plus,
    for quantized arenas, its per-row fp32 scales. The 3-4x shrink the
    disaggregated fleet claims at ``kv_dtype='int8'`` is exactly this
    number's ratio to the fp32 one — quantized pages ship their scale
    sideband, never a dequantized copy."""
    return kv_bytes_per_token(spec, kv_dtype) * int(block_size)


def num_blocks_for_budget(budget_bytes, spec, block_size,
                          kv_dtype='float32'):
    """Pages an arena byte budget buys at ``kv_dtype`` — how bench.py
    sizes the equal-bytes capacity ablation."""
    page = kv_bytes_per_token(spec, kv_dtype) * int(block_size)
    return max(1, int(budget_bytes) // page)


def _lm_params(spec, capacity):
    """Declare the shared parameter set in the CURRENT program (and its
    init ops in the current startup, first declaration wins)."""
    stacked = _stacked_layer_params(
        'lm_stack', spec.n_layer, spec.n_head, spec.d_key, spec.d_value,
        spec.d_model, spec.d_inner, decoder=False)
    emb = layers.create_parameter(
        shape=[spec.vocab_size, spec.d_model], dtype='float32',
        name='lm_emb',
        attr=ParamAttr(name='lm_emb',
                       initializer=Normal(0., spec.d_model ** -0.5)))
    pos = layers.create_parameter(
        shape=[capacity, spec.d_model], dtype='float32',
        name='lm_pos_enc',
        attr=ParamAttr(name='lm_pos_enc',
                       initializer=NumpyArrayInitializer(
                           position_encoding_table(capacity,
                                                   spec.d_model)),
                       trainable=False))
    wout = layers.create_parameter(
        shape=[spec.d_model, spec.vocab_size], dtype='float32',
        name='lm_out_proj.w', attr=ParamAttr(name='lm_out_proj.w'))
    return stacked, emb, pos, wout


def _arenas(spec, num_blocks, block_size, kv_dtype='float32'):
    """K/V page arenas ``[L, NB, bs, H*d]`` at ``kv_dtype``: token-major
    inside a page, heads and head width merged into one lane-dense
    minor axis, which is what lets the paged ops write a row in place
    (ops/paged_decode_ops.py). Axes 0 and 1 are layer and page for
    every arena — all that read_pages/write_pages and the handoff
    index by. Quantized dtypes (int8 / fp8) additionally get
    per-(page, slot, head) fp32 scale arenas ``[L, NB, bs, H]`` — one
    scale per written K/V row, so a page's stored bits are a pure
    function of the tokens written into it (the bit-consistency
    invariant) and prefix-cache sharing carries the scales for free
    (same physical page index)."""
    from ...quant.core import kv_quantized
    shapes = {
        'lm_kcache': [spec.n_layer, num_blocks, block_size,
                      spec.n_head * spec.d_key],
        'lm_vcache': [spec.n_layer, num_blocks, block_size,
                      spec.n_head * spec.d_value],
    }
    out = {}
    for name, shape in shapes.items():
        out[name] = layers.create_parameter(
            shape=shape, dtype=kv_dtype, name=name,
            attr=ParamAttr(name=name, initializer=Constant(0.0),
                           trainable=False))
    ks = vs = None
    if kv_quantized(kv_dtype):
        sshape = [spec.n_layer, num_blocks, block_size, spec.n_head]
        ks, vs = [layers.create_parameter(
            shape=sshape, dtype='float32', name=name,
            attr=ParamAttr(name=name, initializer=Constant(1.0),
                           trainable=False))
            for name in ('lm_kscale', 'lm_vscale')]
    return out['lm_kcache'], out['lm_vcache'], ks, vs


def _common_inputs(stacked, emb, pos, wout, kc, vc, ks=None, vs=None):
    inputs = {'Emb': [emb], 'PosEnc': [pos], 'OutProj': [wout],
              'KCache': [kc], 'VCache': [vc]}
    if ks is not None:
        inputs['KScale'] = [ks]
        inputs['VScale'] = [vs]
    for slot, param in stacked.items():
        inputs[_slot_to_input(slot)] = [param]
    return inputs


def _arena_outputs(kc, vc, ks=None, vs=None):
    outputs = {'KCacheOut': [kc], 'VCacheOut': [vc]}
    if ks is not None:
        outputs['KScaleOut'] = [ks]
        outputs['VScaleOut'] = [vs]
    return outputs


def build_lm_programs(spec, max_batch, block_size, num_blocks,
                      pages_per_seq, spec_k=0, kv_dtype='float32'):
    """Returns DecodePrograms. ``capacity`` (= pages_per_seq *
    block_size) bounds prompt_len + max_new_tokens per sequence.
    ``spec_k > 0`` additionally builds the speculative-decoding
    verify Program ([max_batch, spec_k+1], one fixed signature).
    ``kv_dtype`` (fp32 default / bf16 / int8 / fp8) sets the arena
    storage dtype; quantized arenas carry fp32 scale arenas alongside
    and dequantize inside the shared paged-attention path, so every
    feed signature is unchanged — the zero-recompile contract holds at
    any dtype."""
    from ...quant.core import resolve_kv_dtype
    kv_dtype = resolve_kv_dtype(kv_dtype)
    capacity = int(pages_per_seq) * int(block_size)
    spec_k = int(spec_k)
    startup = Program()
    prefill_prog = Program()
    decode_prog = Program()

    with program_guard(prefill_prog, startup):
        stacked, emb, pos, wout = _lm_params(spec, capacity)
        kc, vc, ks, vs = _arenas(spec, num_blocks, block_size, kv_dtype)
        ids = layers.data(name='pf_ids', shape=[-1], dtype='int64')
        length = layers.data(name='pf_len', shape=[], dtype='int32')
        cached = layers.data(name='pf_cached', shape=[], dtype='int32')
        table = layers.data(name='pf_table', shape=[pages_per_seq],
                            dtype='int32')
        temp = layers.data(name='pf_temp', shape=[], dtype='float32')
        seed = layers.data(name='pf_seed', shape=[], dtype='int32')
        helper = LayerHelper('paged_prefill', name='paged_prefill')
        nxt = helper.create_variable_for_type_inference('int64')
        nxt.shape = (1,)
        inputs = _common_inputs(stacked, emb, pos, wout, kc, vc, ks, vs)
        inputs.update({'Ids': [ids], 'Len': [length], 'Cached': [cached],
                       'BlockTable': [table], 'Temp': [temp],
                       'Seed': [seed]})
        outputs = dict(_arena_outputs(kc, vc, ks, vs),
                       NextToken=[nxt])
        helper.append_op(type='paged_prefill', inputs=inputs,
                         outputs=outputs,
                         attrs={'n_head': spec.n_head,
                                'block_size': int(block_size)})
        prefill_fetch = nxt.name

    with program_guard(decode_prog, startup):
        stacked, emb, pos, wout = _lm_params(spec, capacity)
        kc, vc, ks, vs = _arenas(spec, num_blocks, block_size, kv_dtype)
        tokens = layers.data(name='dec_tokens', shape=[], dtype='int64')
        lens = layers.data(name='dec_lens', shape=[], dtype='int32')
        tables = layers.data(name='dec_tables', shape=[pages_per_seq],
                             dtype='int32')
        temps = layers.data(name='dec_temps', shape=[], dtype='float32')
        seeds = layers.data(name='dec_seeds', shape=[], dtype='int32')
        helper = LayerHelper('paged_decode_step', name='paged_decode_step')
        nxt = helper.create_variable_for_type_inference('int64')
        nxt.shape = (max_batch,)
        inputs = _common_inputs(stacked, emb, pos, wout, kc, vc, ks, vs)
        inputs.update({'Tokens': [tokens], 'SeqLens': [lens],
                       'BlockTables': [tables], 'Temps': [temps],
                       'Seeds': [seeds]})
        outputs = dict(_arena_outputs(kc, vc, ks, vs),
                       NextTokens=[nxt])
        helper.append_op(type='paged_decode_step', inputs=inputs,
                         outputs=outputs,
                         attrs={'n_head': spec.n_head,
                                'block_size': int(block_size)})
        decode_fetch = nxt.name

    verify_prog, verify_fetch = None, None
    if spec_k > 0:
        verify_prog = Program()
        with program_guard(verify_prog, startup):
            stacked, emb, pos, wout = _lm_params(spec, capacity)
            kc, vc, ks, vs = _arenas(spec, num_blocks, block_size,
                                     kv_dtype)
            tokens = layers.data(name='sv_tokens', shape=[spec_k + 1],
                                 dtype='int64')
            lens = layers.data(name='sv_lens', shape=[], dtype='int32')
            tables = layers.data(name='sv_tables', shape=[pages_per_seq],
                                 dtype='int32')
            temps = layers.data(name='sv_temps', shape=[],
                                dtype='float32')
            seeds = layers.data(name='sv_seeds', shape=[], dtype='int32')
            helper = LayerHelper('paged_spec_verify',
                                 name='paged_spec_verify')
            nxt = helper.create_variable_for_type_inference('int64')
            nxt.shape = (max_batch, spec_k + 1)
            inputs = _common_inputs(stacked, emb, pos, wout, kc, vc,
                                    ks, vs)
            inputs.update({'Tokens': [tokens], 'SeqLens': [lens],
                           'BlockTables': [tables], 'Temps': [temps],
                           'Seeds': [seeds]})
            outputs = dict(_arena_outputs(kc, vc, ks, vs),
                           NextTokens=[nxt])
            helper.append_op(type='paged_spec_verify', inputs=inputs,
                             outputs=outputs,
                             attrs={'n_head': spec.n_head,
                                    'block_size': int(block_size),
                                    'k': spec_k})
            verify_fetch = nxt.name

    param_names = sorted(
        {'lm_emb', 'lm_pos_enc', 'lm_out_proj.w'} |
        {p.name for p in stacked.values()})
    arena_names = ('lm_kcache', 'lm_vcache')
    if ks is not None:
        arena_names += ('lm_kscale', 'lm_vscale')
    return DecodePrograms(
        startup=startup, prefill=prefill_prog, decode=decode_prog,
        verify=verify_prog,
        prefill_fetch=prefill_fetch, decode_fetch=decode_fetch,
        verify_fetch=verify_fetch,
        param_names=param_names,
        arena_names=arena_names,
        capacity=capacity, kv_dtype=kv_dtype)


def random_weights(spec, seed=0):
    """Deterministic numpy weight set matching build_lm_programs'
    parameter names — handy for tests that need two engines to share
    identical weights."""
    rng = np.random.RandomState(seed)
    d, dk, dv = spec.d_model, spec.d_key, spec.d_value
    h, L = spec.n_head, spec.n_layer

    def mat(*shape):
        fan = shape[-2] if len(shape) >= 2 else shape[-1]
        return (rng.randn(*shape) * (1.0 / np.sqrt(fan))) \
            .astype('float32')

    w = {
        'lm_emb': (rng.randn(spec.vocab_size, d) * d ** -0.5)
        .astype('float32'),
        'lm_out_proj.w': mat(d, spec.vocab_size),
        'lm_stack_slf_q.w': mat(L, d, dk * h),
        'lm_stack_slf_k.w': mat(L, d, dk * h),
        'lm_stack_slf_v.w': mat(L, d, dv * h),
        'lm_stack_slf_o.w': mat(L, dv * h, d),
        'lm_stack_ffn_1.w': mat(L, d, spec.d_inner),
        'lm_stack_ffn_1.b': np.zeros((L, spec.d_inner), 'float32'),
        'lm_stack_ffn_2.w': mat(L, spec.d_inner, d),
        'lm_stack_ffn_2.b': np.zeros((L, d), 'float32'),
        'lm_stack_ln1.w': np.ones((L, d), 'float32'),
        'lm_stack_ln1.b': np.zeros((L, d), 'float32'),
        'lm_stack_ln2.w': np.ones((L, d), 'float32'),
        'lm_stack_ln2.b': np.zeros((L, d), 'float32'),
    }
    return w
