"""Program builder for the decode engine's decoder-only LM.

Builds the three Programs the engine drives through one Executor +
Scope, all sharing one parameter namespace (prefix ``lm_``):

- **startup** — initializes the block's weights (``LMSpec.block``:
  'post_ln' — models.transformer._stacked_layer_params layout,
  ENC_SLOTS, causal self-attention + FFN + 2 LNs per layer, token
  embedding, sinusoid position table, output projection; 'parallel_moe'
  — ``moe_param_shapes``, at ``LMSpec.dtype``) and the two zeroed KV
  page arenas ``[L, NB, bs, Hkv*d]``. Arenas are persistable scope state: every
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


SLIDING, FULL = 'sliding_attention', 'full_attention'


class LMSpec(object):
    """Decoder-only LM hyperparameters: a family of two blocks.

    ``block='post_ln'`` (the default; every argument after ``d_inner``
    unused): the 2017 decoder block — embedding scaled by sqrt(d_model)
    plus a sinusoid position table, causal self-attention and a ReLU FFN
    each followed by residual add and LayerNorm, an output table of its
    own, float32.

    ``block='parallel_moe'`` (cohere2_moe): one bias-free LayerNorm
    (``norm_eps``) feeding attention and the expert FFN side by side,
    ``y = x + attn + experts``; ``n_head`` query heads over
    ``n_kv_head`` KV heads of ``d_key`` (= ``d_value``); per layer a
    kind from ``layer_types`` — ``sliding_attention`` rotates q and k
    (interleaved pairs, ``rope_theta``) and sees the last
    ``sliding_window`` keys, its own included, ``full_attention``
    carries no position and sees all; a gated SiLU FFN of width
    ``d_inner`` in every expert; a sigmoid router over ``n_experts``
    that keeps ``experts_per_token`` and normalises over them, of which
    this engine holds ``experts_held`` starting at ``first_expert`` (one
    chip's share of an expert-parallel deployment: the rest of the sum
    is left out) plus the mean of ``n_shared_experts`` shared ones; a
    tied, unscaled embedding (``vocab_size`` rows: a slice is a smaller
    vocabulary) behind a final LayerNorm, logits times ``logit_scale``.
    ``dtype`` is what the matrices are kept and multiplied at
    (float32 / bfloat16); the residual stream, the norms' statistics,
    the router, the softmax and the logits are float32."""

    def __init__(self, vocab_size, n_layer=2, n_head=2, d_key=16,
                 d_value=16, d_model=32, d_inner=64, block='post_ln',
                 n_kv_head=None, layer_types=None, sliding_window=0,
                 rope_theta=10000.0, n_experts=0, experts_held=None,
                 first_expert=0, experts_per_token=0, n_shared_experts=0,
                 norm_eps=1e-5, logit_scale=1.0, dtype='float32'):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        self.d_key = int(d_key)
        self.d_value = int(d_value)
        self.d_model = int(d_model)
        self.d_inner = int(d_inner)
        self.block = str(block)
        self.n_kv_head = int(n_kv_head) if n_kv_head else self.n_head
        self.layer_types = tuple(layer_types) if layer_types else \
            (FULL,) * self.n_layer
        self.sliding_window = int(sliding_window)
        self.rope_theta = float(rope_theta)
        self.n_experts = int(n_experts)
        self.experts_held = self.n_experts if experts_held is None \
            else int(experts_held)
        self.first_expert = int(first_expert)
        self.experts_per_token = int(experts_per_token)
        self.n_shared_experts = int(n_shared_experts)
        self.norm_eps = float(norm_eps)
        self.logit_scale = float(logit_scale)
        self.dtype = str(dtype)
        if self.block == 'post_ln':
            if self.n_kv_head != self.n_head:
                raise ValueError("LMSpec: block='post_ln' has one KV head "
                                 "per query head")
            return
        if self.block != 'parallel_moe':
            raise ValueError('LMSpec: unknown block %r (post_ln, '
                             'parallel_moe)' % self.block)
        if self.n_head % self.n_kv_head or self.d_key != self.d_value:
            raise ValueError('LMSpec: %d query heads over %d KV heads of '
                             '%d/%d' % (self.n_head, self.n_kv_head,
                                        self.d_key, self.d_value))
        if len(self.layer_types) != self.n_layer or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError('LMSpec: layer_types %r for %d layers'
                             % (self.layer_types, self.n_layer))
        if SLIDING in self.layer_types and self.sliding_window < 1:
            raise ValueError('LMSpec: sliding layers need a window')
        if not (0 < self.experts_per_token <= self.n_experts and
                0 < self.experts_held and self.n_shared_experts > 0 and
                self.first_expert + self.experts_held <= self.n_experts):
            raise ValueError(
                'LMSpec: experts %d..%d of %d, %d per token, %d shared'
                % (self.first_expert,
                   self.first_expert + self.experts_held - 1,
                   self.n_experts, self.experts_per_token,
                   self.n_shared_experts))

    def windows(self):
        """Per layer, the keys a query sees (0: all of them)."""
        return [self.sliding_window if t == SLIDING else 0
                for t in self.layer_types]

    def rotary(self):
        """Per layer, whether q and k are rotated."""
        return [t == SLIDING for t in self.layer_types]


DecodePrograms = collections.namedtuple(
    'DecodePrograms',
    ['startup', 'prefill', 'decode', 'verify', 'prefill_fetch',
     'decode_fetch', 'verify_fetch', 'param_names', 'arena_names',
     'capacity', 'kv_dtype', 'stats_fetch', 'prefill_stats_fetch'])


def kv_bytes_per_token(spec, kv_dtype='float32'):
    """HBM bytes one cached token costs across all layers: the K/V
    rows at the arena dtype plus (for quantized arenas) the per-token
    per-head fp32 scale pair. This is the number the ISSUE's capacity
    claim rides on: int8 at d_head=128 is ~3.9x less than fp32."""
    from ...quant.core import kv_itemsize, kv_quantized
    item = kv_itemsize(kv_dtype)
    b = spec.n_layer * spec.n_kv_head * (spec.d_key + spec.d_value) * item
    if kv_quantized(kv_dtype):
        b += spec.n_layer * spec.n_kv_head * 2 * 4   # k + v scale rows
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
    init ops in the current startup, first declaration wins): the op's
    weight inputs by slot, stacked ones under their slot names."""
    if spec.block == 'parallel_moe':
        return _moe_params(spec)
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
    inputs = {'Emb': [emb], 'PosEnc': [pos], 'OutProj': [wout]}
    for slot, param in stacked.items():
        inputs[_slot_to_input(slot)] = [param]
    return inputs


def moe_param_shapes(spec):
    """{name: (shape, fan-in or None for a norm's gain, op input slot)}
    of the parallel_moe block's weights: matrices are kept at
    ``spec.dtype`` and drawn N(0, 1/fan-in); gains are float32 ones."""
    L, d, f = spec.n_layer, spec.d_model, spec.d_inner
    q, kv = spec.n_head * spec.d_key, spec.n_kv_head * spec.d_key
    e, sh = spec.experts_held, spec.n_shared_experts
    return collections.OrderedDict([
        ('lm_emb', ([spec.vocab_size, d], d, 'Emb')),
        ('lm_final_ln.w', ([d], None, 'FinalLN')),
        ('lm_stack_ln.w', ([L, d], None, 'LnW')),
        ('lm_stack_slf_q.w', ([L, d, q], d, 'SlfQ')),
        ('lm_stack_slf_k.w', ([L, d, kv], d, 'SlfK')),
        ('lm_stack_slf_v.w', ([L, d, kv], d, 'SlfV')),
        ('lm_stack_slf_o.w', ([L, q, d], q, 'SlfO')),
        ('lm_stack_router.w', ([L, d, spec.n_experts], d, 'Router')),
        ('lm_stack_exp_gate.w', ([L, e, d, f], d, 'ExpGate')),
        ('lm_stack_exp_up.w', ([L, e, d, f], d, 'ExpUp')),
        ('lm_stack_exp_down.w', ([L, e, f, d], f, 'ExpDown')),
        ('lm_stack_shr_gate.w', ([L, sh, d, f], d, 'ShrGate')),
        ('lm_stack_shr_up.w', ([L, sh, d, f], d, 'ShrUp')),
        ('lm_stack_shr_down.w', ([L, sh, f, d], f, 'ShrDown')),
    ])


def _moe_params(spec):
    inputs = {}
    for name, (shape, fan_in, slot) in moe_param_shapes(spec).items():
        init = Constant(1.0) if fan_in is None else \
            Normal(0., fan_in ** -0.5)
        inputs[slot] = [layers.create_parameter(
            shape=shape, dtype='float32' if fan_in is None else spec.dtype,
            name=name, attr=ParamAttr(name=name, initializer=init))]
    return inputs


def _block_attrs(spec, block_size):
    attrs = {'n_head': spec.n_head, 'block_size': int(block_size)}
    if spec.block == 'parallel_moe':
        attrs.update({
            'block': spec.block, 'windows': spec.windows(),
            'rotary': [int(r) for r in spec.rotary()],
            'rope_theta': spec.rope_theta, 'norm_eps': spec.norm_eps,
            'top_k': spec.experts_per_token,
            'first_expert': spec.first_expert,
            'logit_scale': spec.logit_scale})
    return attrs


def _arenas(spec, num_blocks, block_size, kv_dtype='float32'):
    """K/V page arenas ``[L, NB, bs, Hkv*d]`` at ``kv_dtype``: token-major
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
                      spec.n_kv_head * spec.d_key],
        'lm_vcache': [spec.n_layer, num_blocks, block_size,
                      spec.n_kv_head * spec.d_value],
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


def _common_inputs(params, kc, vc, ks=None, vs=None):
    inputs = dict(params, KCache=[kc], VCache=[vc])
    if ks is not None:
        inputs['KScale'] = [ks]
        inputs['VScale'] = [vs]
    return inputs


def _arena_outputs(kc, vc, ks=None, vs=None):
    outputs = {'KCacheOut': [kc], 'VCacheOut': [vc]}
    if ks is not None:
        outputs['KScaleOut'] = [ks]
        outputs['VScaleOut'] = [vs]
    return outputs


def _moe_stats_output(helper, spec, outputs):
    """Give a ``parallel_moe`` program its MoeStats output (per layer:
    choices that landed on an expert held here, rows on the busiest of
    them, experts any row chose, row tiles the routed product ran) and
    return its name; None for a block that routes nothing."""
    if spec.block != 'parallel_moe':
        return None
    stats = helper.create_variable_for_type_inference('int32')
    stats.shape = (spec.n_layer, 4)
    outputs['MoeStats'] = [stats]
    return stats.name


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
    from ...quant.core import kv_quantized, resolve_kv_dtype
    kv_dtype = resolve_kv_dtype(kv_dtype)
    capacity = int(pages_per_seq) * int(block_size)
    spec_k = int(spec_k)
    moe = spec.block == 'parallel_moe'
    if moe and (spec_k > 0 or kv_quantized(kv_dtype)):
        # neither has a test against this block's reference yet
        raise NotImplementedError(
            "block='parallel_moe' runs without speculation and with an "
            "unquantized KV arena (got spec_k=%d, kv_dtype=%s)"
            % (spec_k, kv_dtype))
    attrs = _block_attrs(spec, block_size)
    startup = Program()
    prefill_prog = Program()
    decode_prog = Program()

    with program_guard(prefill_prog, startup):
        params = _lm_params(spec, capacity)
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
        inputs = _common_inputs(params, kc, vc, ks, vs)
        inputs.update({'Ids': [ids], 'Len': [length], 'Cached': [cached],
                       'BlockTable': [table], 'Temp': [temp],
                       'Seed': [seed]})
        outputs = dict(_arena_outputs(kc, vc, ks, vs),
                       NextToken=[nxt])
        prefill_stats_fetch = _moe_stats_output(helper, spec, outputs)
        helper.append_op(type='paged_prefill', inputs=inputs,
                         outputs=outputs, attrs=attrs)
        prefill_fetch = nxt.name

    with program_guard(decode_prog, startup):
        params = _lm_params(spec, capacity)
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
        inputs = _common_inputs(params, kc, vc, ks, vs)
        inputs.update({'Tokens': [tokens], 'SeqLens': [lens],
                       'BlockTables': [tables], 'Temps': [temps],
                       'Seeds': [seeds]})
        outputs = dict(_arena_outputs(kc, vc, ks, vs),
                       NextTokens=[nxt])
        stats_fetch = _moe_stats_output(helper, spec, outputs)
        helper.append_op(type='paged_decode_step', inputs=inputs,
                         outputs=outputs, attrs=attrs)
        decode_fetch = nxt.name

    verify_prog, verify_fetch = None, None
    if spec_k > 0:
        verify_prog = Program()
        with program_guard(verify_prog, startup):
            params = _lm_params(spec, capacity)
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
            inputs = _common_inputs(params, kc, vc, ks, vs)
            inputs.update({'Tokens': [tokens], 'SeqLens': [lens],
                           'BlockTables': [tables], 'Temps': [temps],
                           'Seeds': [seeds]})
            outputs = dict(_arena_outputs(kc, vc, ks, vs),
                           NextTokens=[nxt])
            helper.append_op(type='paged_spec_verify', inputs=inputs,
                             outputs=outputs,
                             attrs=dict(attrs, k=spec_k))
            verify_fetch = nxt.name

    param_names = sorted(v[0].name for v in params.values())
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
        capacity=capacity, kv_dtype=kv_dtype, stats_fetch=stats_fetch,
        prefill_stats_fetch=prefill_stats_fetch)


def random_weights(spec, seed=0):
    """Deterministic numpy weight set matching build_lm_programs'
    parameter names — handy for tests that need two engines to share
    identical weights (float32; an engine keeps each at its declared
    dtype)."""
    rng = np.random.RandomState(seed)
    if spec.block == 'parallel_moe':
        return {name: np.ones(shape, 'float32') if fan_in is None else
                (rng.randn(*shape) * fan_in ** -0.5).astype('float32')
                for name, (shape, fan_in, _) in
                moe_param_shapes(spec).items()}
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
