"""Transformer NMT (reference: the benchmark Transformer "base" en-de
config — 6-layer encoder/decoder, d_model 512, 8 heads, label smoothing).

TPU-first differences from the reference build:
- an attention sublayer is ONE `fused_attention` IR op, its four
  projections included (ops/attention_ops.py: q, k and v leave their
  matmul head-major; between them the XLA-fused jnp attention, ring
  attention on an 'sp' mesh, the Pallas flash kernel only from 512
  positions on and only when opted in), instead of a chain of
  reshape/matmul/softmax ops, and padding masks derive in-graph from a
  per-example `length` vector — the reference feeds precomputed
  [B, H, T, T] bias tensors from the host.
- positional encodings are a non-trainable device-resident table sliced
  per step, not host-fed.
- the whole train step (fwd + bwd + Adam + label smoothing) compiles to
  one XLA program; bf16-friendly (all matmuls hit the MXU).
"""

import re

import numpy as np

from .. import layers
from ..initializer import Normal, NumpyArrayInitializer
from ..param_attr import ParamAttr


def position_encoding_table(max_length, d_model):
    """Sinusoidal position table [max_length, d_model] (host-computed once,
    lives in HBM as a frozen parameter)."""
    pos = np.arange(max_length)[:, None].astype('float64')
    dim = np.arange(0, d_model, 2).astype('float64')
    inv = 1.0 / np.power(10000.0, dim / d_model)
    angles = pos * inv[None, :]
    table = np.zeros((max_length, d_model), dtype='float32')
    table[:, 0::2] = np.sin(angles)
    table[:, 1::2] = np.cos(angles)
    return table


def _multi_head_attention(x, mem, d_key, d_value, d_model, n_head,
                          dropout_rate, causal=False, key_length=None,
                          name='attn'):
    """One attention sublayer as ONE ``fused_attention`` op that carries
    its four weights: queries from ``x``, keys and values from ``mem``
    (``x`` again for self-attention). The weights keep the names and the
    shapes four bias-free fc layers gave them ([d_model, H*D] and
    [H*D, d_model]), so a checkpoint reads either way."""
    from ..layers.helper import LayerHelper
    helper = LayerHelper('fused_attention', name=name)
    dtype = x.dtype

    def weight(suffix, shape):
        return helper.create_parameter(
            attr=ParamAttr(name=name + suffix), shape=shape, dtype=dtype)
    inputs = {'X': [x], 'Mem': [mem],
              'Wq': [weight('_q.w', [x.shape[-1], d_key * n_head])],
              'Wk': [weight('_k.w', [mem.shape[-1], d_key * n_head])],
              'Wv': [weight('_v.w', [mem.shape[-1], d_value * n_head])],
              'Wo': [weight('_out.w', [d_value * n_head, d_model])]}
    if key_length is not None:
        inputs['KeyLength'] = [key_length]
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(x.shape[:2]) + (d_model,)
    helper.append_op(type='fused_attention', inputs=inputs,
                     outputs={'Out': [out]},
                     attrs={'n_head': n_head, 'causal': causal,
                            'dropout_rate': dropout_rate})
    return out


def _ffn(x, d_inner, d_model, dropout_rate, name='ffn'):
    hidden = layers.fc(input=x, size=d_inner, num_flatten_dims=2,
                       act='relu', param_attr=ParamAttr(name=name + '_1.w'),
                       bias_attr=ParamAttr(name=name + '_1.b'))
    if dropout_rate:
        hidden = layers.dropout(hidden, dropout_prob=dropout_rate)
    return layers.fc(input=hidden, size=d_model, num_flatten_dims=2,
                     param_attr=ParamAttr(name=name + '_2.w'),
                     bias_attr=ParamAttr(name=name + '_2.b'))


def _post_process(prev, out, dropout_rate, name='pp'):
    """residual add + layer_norm (+ dropout), the reference's "dan" chain.
    Every parameter is explicitly named so inference graphs (including
    the unrolled decode, which re-runs these layers per step) share the
    trained weights."""
    if dropout_rate:
        out = layers.dropout(out, dropout_prob=dropout_rate)
    added = layers.elementwise_add(x=out, y=prev)
    return layers.layer_norm(added, begin_norm_axis=len(added.shape) - 1,
                             param_attr=ParamAttr(name=name + '_ln.w'),
                             bias_attr=ParamAttr(name=name + '_ln.b'))


def _prepare_input(word_ids, vocab_size, d_model, max_length, dropout_rate,
                   emb_name, pos_table):
    emb = layers.embedding(
        input=word_ids, size=[vocab_size, d_model], dtype='float32',
        param_attr=ParamAttr(name=emb_name,
                             initializer=Normal(0., d_model ** -0.5)))
    if len(emb.shape) == 2:
        # embedding squeezes a trailing dim of 1 (the fluid [B, 1]
        # id-column convention); a length-1 decode prefix must stay 3-D
        # or the step-1 graph would declare wrongly-shaped fc weights.
        emb = layers.reshape(x=emb, shape=[0, 1, d_model])
    emb = layers.scale(x=emb, scale=d_model ** 0.5)
    seq_len = word_ids.shape[1]
    pos_enc = layers.create_parameter(
        shape=[max_length, d_model], dtype='float32',
        name=emb_name + '_pos_enc',
        attr=ParamAttr(name=emb_name + '_pos_enc',
                       initializer=NumpyArrayInitializer(pos_table),
                       trainable=False))
    pos_slice = layers.slice(pos_enc, axes=[0], starts=[0], ends=[seq_len])
    pos_slice = layers.reshape(x=pos_slice, shape=[1, seq_len, d_model])
    out = layers.elementwise_add(x=emb, y=pos_slice)
    if dropout_rate:
        out = layers.dropout(out, dropout_prob=dropout_rate)
    return out


def encoder_layer(x, n_head, d_key, d_value, d_model, d_inner, dropout_rate,
                  src_length=None, name='enc'):
    attn = _multi_head_attention(x, x, d_key, d_value, d_model, n_head,
                                 dropout_rate, key_length=src_length,
                                 name=name + '_slf')
    x = _post_process(x, attn, dropout_rate, name=name + '_pp1')
    ffn = _ffn(x, d_inner, d_model, dropout_rate, name=name + '_ffn')
    return _post_process(x, ffn, dropout_rate, name=name + '_pp2')


def decoder_layer(x, enc_out, n_head, d_key, d_value, d_model, d_inner,
                  dropout_rate, src_length=None, name='dec'):
    slf = _multi_head_attention(x, x, d_key, d_value, d_model, n_head,
                                dropout_rate, causal=True,
                                name=name + '_slf')
    x = _post_process(x, slf, dropout_rate, name=name + '_pp1')
    cross = _multi_head_attention(x, enc_out, d_key, d_value, d_model,
                                  n_head, dropout_rate,
                                  key_length=src_length,
                                  name=name + '_cross')
    x = _post_process(x, cross, dropout_rate, name=name + '_pp2')
    ffn = _ffn(x, d_inner, d_model, dropout_rate, name=name + '_ffn')
    return _post_process(x, ffn, dropout_rate, name=name + '_pp3')


def _stack_param(name, shape, fan_in, fan_out, constant=None):
    """[n_layer, ...] stacked parameter. Xavier fans are passed explicitly
    (the leading layer axis must not enter the fan computation)."""
    from ..initializer import Constant, Xavier
    init = Constant(constant) if constant is not None else \
        Xavier(uniform=True, fan_in=fan_in, fan_out=fan_out)
    return layers.create_parameter(
        shape=shape, dtype='float32', name=name,
        attr=ParamAttr(name=name, initializer=init))


def _stacked_layer_params(prefix, n_layer, n_head, d_key, d_value, d_model,
                          d_inner, decoder=False):
    """The transformer_layer_stack op's weight pytree, stacked on a
    leading [n_layer] axis (ops/transformer_ops.py slot layout)."""
    L = n_layer
    p = {}

    def attn(pre):
        p[pre + '_q'] = _stack_param('%s_%s_q.w' % (prefix, pre),
                                     [L, d_model, d_key * n_head],
                                     d_model, d_key * n_head)
        p[pre + '_k'] = _stack_param('%s_%s_k.w' % (prefix, pre),
                                     [L, d_model, d_key * n_head],
                                     d_model, d_key * n_head)
        p[pre + '_v'] = _stack_param('%s_%s_v.w' % (prefix, pre),
                                     [L, d_model, d_value * n_head],
                                     d_model, d_value * n_head)
        p[pre + '_o'] = _stack_param('%s_%s_o.w' % (prefix, pre),
                                     [L, d_value * n_head, d_model],
                                     d_value * n_head, d_model)

    def ln(slot):
        p[slot + '_w'] = _stack_param('%s_%s.w' % (prefix, slot),
                                      [L, d_model], 0, 0, constant=1.0)
        p[slot + '_b'] = _stack_param('%s_%s.b' % (prefix, slot),
                                      [L, d_model], 0, 0, constant=0.0)

    attn('slf')
    ln('ln1')
    if decoder:
        attn('cross')
        ln('ln2')
    p['ffn_w1'] = _stack_param('%s_ffn_1.w' % prefix,
                               [L, d_model, d_inner], d_model, d_inner)
    p['ffn_b1'] = _stack_param('%s_ffn_1.b' % prefix, [L, d_inner],
                               0, 0, constant=0.0)
    p['ffn_w2'] = _stack_param('%s_ffn_2.w' % prefix,
                               [L, d_inner, d_model], d_inner, d_model)
    p['ffn_b2'] = _stack_param('%s_ffn_2.b' % prefix, [L, d_model],
                               0, 0, constant=0.0)
    ln('ln3' if decoder else 'ln2')
    return p


def _layer_stack(x, params, n_head, dropout_rate, enc_out=None,
                 src_length=None, name='stack'):
    from ..layers.helper import LayerHelper
    from ..ops.transformer_ops import _slot_to_input
    helper = LayerHelper('transformer_layer_stack', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    inputs = {'X': [x]}
    if enc_out is not None:
        inputs['EncOut'] = [enc_out]
    if src_length is not None:
        inputs['SrcLength'] = [src_length]
    for slot, param in params.items():
        inputs[_slot_to_input(slot)] = [param]
    helper.append_op(type='transformer_layer_stack', inputs=inputs,
                     outputs={'Out': [out]},
                     attrs={'n_head': n_head,
                            'dropout_rate': dropout_rate})
    return out


def transformer(src_vocab_size, trg_vocab_size, max_length=256,
                n_layer=6, n_head=8, d_key=64, d_value=64, d_model=512,
                d_inner=2048, dropout_rate=0.1, label_smooth_eps=0.1,
                src_seq_len=None, trg_seq_len=None, batch_size=None,
                weight_sharing=False, scan_layers=None):
    """Build the full training graph. Feeds: src_word [B,S] int64,
    src_length [B] int64, trg_word [B,T] int64 (decoder input),
    lbl_word [B,T] int64 (shifted target), lbl_weight [B,T] float32
    (1 for real tokens, 0 for pads). Returns (avg_cost, logits).

    scan_layers: None reads PADDLE_TPU_SCAN_LAYERS (default off). When
    on, the n_layer encoder/decoder stacks become ONE
    transformer_layer_stack op each (lax.scan over [n_layer, ...]
    stacked weights) — XLA compiles the layer body once, so compile
    time stays flat as stacks deepen."""
    import os
    if scan_layers is None:
        scan_layers = os.environ.get('PADDLE_TPU_SCAN_LAYERS') == '1'
    src_word = layers.data(name='src_word', shape=[src_seq_len],
                           dtype='int64')
    src_length = layers.data(name='src_length', shape=[], dtype='int64')
    trg_word = layers.data(name='trg_word', shape=[trg_seq_len],
                           dtype='int64')
    lbl_word = layers.data(name='lbl_word', shape=[trg_seq_len],
                           dtype='int64')
    lbl_weight = layers.data(name='lbl_weight', shape=[trg_seq_len],
                             dtype='float32')

    pos_table = position_encoding_table(max_length, d_model)

    enc_in = _prepare_input(src_word, src_vocab_size, d_model, max_length,
                            dropout_rate, 'src_emb', pos_table)
    x = enc_in
    if scan_layers:
        enc_params = _stacked_layer_params(
            'enc_stack', n_layer, n_head, d_key, d_value, d_model, d_inner)
        x = _layer_stack(x, enc_params, n_head, dropout_rate,
                         src_length=src_length, name='enc_stack')
    else:
        for i in range(n_layer):
            x = encoder_layer(x, n_head, d_key, d_value, d_model, d_inner,
                              dropout_rate, src_length=src_length,
                              name='enc_%d' % i)
    enc_out = x

    dec_emb_name = 'src_emb' if weight_sharing else 'trg_emb'
    dec_in = _prepare_input(trg_word, trg_vocab_size, d_model, max_length,
                            dropout_rate, dec_emb_name, pos_table)
    y = dec_in
    if scan_layers:
        dec_params = _stacked_layer_params(
            'dec_stack', n_layer, n_head, d_key, d_value, d_model, d_inner,
            decoder=True)
        y = _layer_stack(y, dec_params, n_head, dropout_rate,
                         enc_out=enc_out, src_length=src_length,
                         name='dec_stack')
    else:
        for i in range(n_layer):
            y = decoder_layer(y, enc_out, n_head, d_key, d_value, d_model,
                              d_inner, dropout_rate, src_length=src_length,
                              name='dec_%d' % i)

    logits = layers.fc(input=y, size=trg_vocab_size, num_flatten_dims=2,
                       bias_attr=False,
                       param_attr=ParamAttr(name='out_proj.w'))

    # label smoothing + softmax cross entropy, weighted by non-pad mask
    if label_smooth_eps:
        # fused: never materializes the [B, T, V] smoothed one-hot
        cost = layers.label_smoothed_cross_entropy(
            logits=logits, label=lbl_word, epsilon=label_smooth_eps)
    else:
        lbl3 = layers.unsqueeze(lbl_word, axes=[2])
        cost = layers.softmax_with_cross_entropy(logits=logits, label=lbl3)
    cost = layers.reshape(x=cost, shape=list(lbl_weight.shape))
    weighted = layers.elementwise_mul(x=cost, y=lbl_weight)
    sum_cost = layers.reduce_sum(weighted)
    token_count = layers.reduce_sum(lbl_weight)
    avg_cost = layers.elementwise_div(x=sum_cost, y=token_count)
    return avg_cost, logits


def transformer_base(src_vocab_size=32000, trg_vocab_size=32000,
                     src_seq_len=64, trg_seq_len=64, **overrides):
    """The reference "base" configuration."""
    cfg = dict(n_layer=6, n_head=8, d_key=64, d_value=64, d_model=512,
               d_inner=2048, dropout_rate=0.1, label_smooth_eps=0.1,
               src_seq_len=src_seq_len, trg_seq_len=trg_seq_len)
    cfg.update(overrides)
    return transformer(src_vocab_size, trg_vocab_size, **cfg)


def transformer_big(src_vocab_size=32000, trg_vocab_size=32000,
                    src_seq_len=64, trg_seq_len=64, **overrides):
    """The reference "big" configuration (benchmark NMT suite:
    d_model 1024, 16 heads, d_inner 4096, dropout 0.3)."""
    cfg = dict(n_layer=6, n_head=16, d_key=64, d_value=64, d_model=1024,
               d_inner=4096, dropout_rate=0.3, label_smooth_eps=0.1,
               src_seq_len=src_seq_len, trg_seq_len=trg_seq_len)
    cfg.update(overrides)
    return transformer(src_vocab_size, trg_vocab_size, **cfg)


FEED_NAMES = ['src_word', 'src_length', 'trg_word', 'lbl_word', 'lbl_weight']


def make_fake_batch(batch_size, src_seq_len, trg_seq_len, src_vocab_size,
                    trg_vocab_size, seed=0):
    """Synthetic feed dict for tests/bench (zero-egress environment)."""
    rng = np.random.RandomState(seed)
    return {
        'src_word': rng.randint(1, src_vocab_size,
                                (batch_size, src_seq_len)).astype('int64'),
        'src_length': np.full((batch_size,), src_seq_len, dtype='int64'),
        'trg_word': rng.randint(1, trg_vocab_size,
                                (batch_size, trg_seq_len)).astype('int64'),
        'lbl_word': rng.randint(1, trg_vocab_size,
                                (batch_size, trg_seq_len)).astype('int64'),
        'lbl_weight': np.ones((batch_size, trg_seq_len), dtype='float32'),
    }


_UNROLLED_PARAM_RE = re.compile(
    r'^(enc|dec)_(\d+)_(slf|cross)_(q|k|v|out)\.w$|'
    r'^(enc|dec)_(\d+)_pp(\d)_ln\.(w|b)$|'
    r'^(enc|dec)_(\d+)_ffn_(1|2)\.(w|b)$')


def _unrolled_to_stacked_name(name):
    """Map an unrolled per-layer param name ('enc_0_slf_q.w',
    'dec_3_pp1_ln.w', 'enc_1_ffn_2.b') to its stacked equivalent
    ('enc_stack_slf_q.w', layer index). Returns (None, None) for
    non-layer params (embeddings, pos tables, out_proj)."""
    m = _UNROLLED_PARAM_RE.match(name)
    if not m:
        return None, None
    if m.group(1):
        side, i = m.group(1), int(m.group(2))
        slot = '%s_%s.w' % (m.group(3),
                            'o' if m.group(4) == 'out' else m.group(4))
    elif m.group(5):
        side, i = m.group(5), int(m.group(6))
        slot = 'ln%s.%s' % (m.group(7), m.group(8))
    else:
        side, i = m.group(9), int(m.group(10))
        slot = 'ffn_%s.%s' % (m.group(11), m.group(12))
    return '%s_stack_%s' % (side, slot), i


def stack_trained_weights(scope, n_layer):
    """Convert an unrolled-trained scope in place: np.stack every
    per-layer parameter onto the stacked '[enc|dec]_stack_*' names the
    scan/incremental paths read. Non-layer params (embeddings, pos
    tables, out_proj) already share names. Returns the stacked names."""
    stacks = {}
    for name in scope.keys():
        val = scope.find(name)
        if val is None:
            continue
        sname, i = _unrolled_to_stacked_name(name)
        if sname is not None:
            if i >= n_layer:
                raise ValueError(
                    'stack_trained_weights: %r has layer index %d but '
                    'n_layer=%d' % (name, i, n_layer))
            stacks.setdefault(sname, [None] * n_layer)[i] = np.asarray(val)
    for sname, parts in stacks.items():
        missing = [i for i, p in enumerate(parts) if p is None]
        if missing:
            raise ValueError('stack_trained_weights: %r missing layers %s'
                             % (sname, missing))
        scope.set(sname, np.stack(parts, axis=0))
    return sorted(stacks)


# ---------------------------------------------------------------- inference
def _decode_prefix(prefix_ids, enc_out, src_length, cfg):
    """Run the decoder stack over a [B*, t] prefix; returns last-position
    logits [B*, V]. Parameter names match the training graph (including
    the stacked 'dec_stack_*' names when cfg['scan_layers'] is on), so a
    trained scope decodes directly."""
    dec_in = _prepare_input(prefix_ids, cfg['trg_vocab_size'],
                            cfg['d_model'], cfg['max_length'], 0.0,
                            cfg['dec_emb_name'], cfg['pos_table'])
    y = dec_in
    if cfg['scan_layers']:
        dec_params = _stacked_layer_params(
            'dec_stack', cfg['n_layer'], cfg['n_head'], cfg['d_key'],
            cfg['d_value'], cfg['d_model'], cfg['d_inner'], decoder=True)
        y = _layer_stack(y, dec_params, cfg['n_head'], 0.0,
                         enc_out=enc_out, src_length=src_length,
                         name='dec_stack')
    else:
        for i in range(cfg['n_layer']):
            y = decoder_layer(y, enc_out, cfg['n_head'], cfg['d_key'],
                              cfg['d_value'], cfg['d_model'],
                              cfg['d_inner'], 0.0, src_length=src_length,
                              name='dec_%d' % i)
    logits = layers.fc(input=y, size=cfg['trg_vocab_size'],
                       num_flatten_dims=2, bias_attr=False,
                       param_attr=ParamAttr(name='out_proj.w'))
    t = prefix_ids.shape[1]
    last = layers.slice(logits, axes=[1], starts=[t - 1], ends=[t])
    return layers.reshape(x=last, shape=[0, cfg['trg_vocab_size']])


def _infer_cfg(src_vocab_size, trg_vocab_size, max_length, n_layer, n_head,
               d_key, d_value, d_model, d_inner, weight_sharing,
               scan_layers=None):
    import os
    if scan_layers is None:
        scan_layers = os.environ.get('PADDLE_TPU_SCAN_LAYERS') == '1'
    return dict(trg_vocab_size=trg_vocab_size, d_model=d_model,
                max_length=max_length, n_layer=n_layer, n_head=n_head,
                d_key=d_key, d_value=d_value, d_inner=d_inner,
                dec_emb_name='src_emb' if weight_sharing else 'trg_emb',
                pos_table=position_encoding_table(max_length, d_model),
                scan_layers=scan_layers)


def _build_encoder(src_word, src_length, src_vocab_size, cfg):
    enc_in = _prepare_input(src_word, src_vocab_size, cfg['d_model'],
                            cfg['max_length'], 0.0, 'src_emb',
                            cfg['pos_table'])
    x = enc_in
    if cfg['scan_layers']:
        enc_params = _stacked_layer_params(
            'enc_stack', cfg['n_layer'], cfg['n_head'], cfg['d_key'],
            cfg['d_value'], cfg['d_model'], cfg['d_inner'])
        x = _layer_stack(x, enc_params, cfg['n_head'], 0.0,
                         src_length=src_length, name='enc_stack')
    else:
        for i in range(cfg['n_layer']):
            x = encoder_layer(x, cfg['n_head'], cfg['d_key'],
                              cfg['d_value'], cfg['d_model'],
                              cfg['d_inner'], 0.0,
                              src_length=src_length, name='enc_%d' % i)
    return x


def _incremental_decode_inputs(enc_out, src_length, cfg):
    """Shared inputs dict for the KV-cached decode ops: stacked decoder
    params ('dec_stack_*' — natively present for scan_layers-trained
    scopes; stack_trained_weights converts unrolled-trained ones) plus
    embedding / position / output-projection params under the training
    graph's names."""
    from ..ops.transformer_ops import _slot_to_input

    dec_params = _stacked_layer_params(
        'dec_stack', cfg['n_layer'], cfg['n_head'], cfg['d_key'],
        cfg['d_value'], cfg['d_model'], cfg['d_inner'], decoder=True)
    emb = layers.create_parameter(
        shape=[cfg['trg_vocab_size'], cfg['d_model']], dtype='float32',
        name=cfg['dec_emb_name'],
        attr=ParamAttr(name=cfg['dec_emb_name'],
                       initializer=Normal(0., cfg['d_model'] ** -0.5)))
    pos_enc = layers.create_parameter(
        shape=[cfg['max_length'], cfg['d_model']], dtype='float32',
        name=cfg['dec_emb_name'] + '_pos_enc',
        attr=ParamAttr(name=cfg['dec_emb_name'] + '_pos_enc',
                       initializer=NumpyArrayInitializer(cfg['pos_table']),
                       trainable=False))
    wout = layers.create_parameter(
        shape=[cfg['d_model'], cfg['trg_vocab_size']], dtype='float32',
        name='out_proj.w', attr=ParamAttr(name='out_proj.w'))
    inputs = {'EncOut': [enc_out], 'Emb': [emb], 'PosEnc': [pos_enc],
              'OutProj': [wout]}
    if src_length is not None:
        inputs['SrcLength'] = [src_length]
    for slot, param in dec_params.items():
        inputs[_slot_to_input(slot)] = [param]
    return inputs


def _incremental_greedy(enc_out, src_length, cfg, max_out_len, bos_id,
                        eos_id):
    """Emit the KV-cached transformer_greedy_decode op: one lax.scan
    over positions instead of max_out_len prefix re-runs."""
    from ..layers.helper import LayerHelper
    inputs = _incremental_decode_inputs(enc_out, src_length, cfg)
    helper = LayerHelper('transformer_greedy_decode', name='greedy_decode')
    out = helper.create_variable_for_type_inference('int64')
    out.shape = (enc_out.shape[0], max_out_len)
    helper.append_op(type='transformer_greedy_decode', inputs=inputs,
                     outputs={'Out': [out]},
                     attrs={'n_head': cfg['n_head'],
                            'max_out_len': max_out_len,
                            'bos_id': bos_id, 'eos_id': eos_id})
    return out


def _incremental_beam(enc_out, src_length, cfg, beam_size, max_out_len,
                      bos_id, eos_id):
    """Emit the KV-cached transformer_beam_decode op (one lax.scan;
    caches reordered by parent index each step)."""
    from ..layers.helper import LayerHelper
    inputs = _incremental_decode_inputs(enc_out, src_length, cfg)
    helper = LayerHelper('transformer_beam_decode', name='beam_decode')
    sent = helper.create_variable_for_type_inference('int64')
    sent.shape = (enc_out.shape[0], beam_size, max_out_len - 1)
    scores = helper.create_variable_for_type_inference('float32')
    scores.shape = (enc_out.shape[0], beam_size)
    helper.append_op(type='transformer_beam_decode', inputs=inputs,
                     outputs={'SentenceIds': [sent],
                              'SentenceScores': [scores]},
                     attrs={'n_head': cfg['n_head'],
                            'max_out_len': max_out_len,
                            'beam_size': beam_size,
                            'bos_id': bos_id, 'eos_id': eos_id})
    return sent, scores


def transformer_greedy_infer(src_vocab_size, trg_vocab_size,
                             max_out_len=16, bos_id=0, eos_id=1,
                             src_seq_len=16, max_length=256, n_layer=6,
                             n_head=8, d_key=64, d_value=64, d_model=512,
                             d_inner=2048, weight_sharing=False,
                             scan_layers=None, incremental=False):
    """Greedy decode. incremental=True (TPU-native default path for long
    outputs) uses the KV-cached transformer_greedy_decode op — one
    lax.scan over positions, O(T) compute, flat compile time; decoder
    weights are read in the stacked layout (stack_trained_weights
    converts an unrolled-trained scope). incremental=False unrolls one
    decoder re-run per position (static shapes per step, one XLA
    program; the shape the reference's While-based infer program takes).
    Feeds: src_word [B, S], src_length [B]. Returns out_ids [B, T]."""
    cfg = _infer_cfg(src_vocab_size, trg_vocab_size, max_length, n_layer,
                     n_head, d_key, d_value, d_model, d_inner,
                     weight_sharing, scan_layers)
    src_word = layers.data(name='src_word', shape=[src_seq_len],
                           dtype='int64')
    src_length = layers.data(name='src_length', shape=[], dtype='int64')
    enc_out = _build_encoder(src_word, src_length, src_vocab_size, cfg)
    if incremental:
        ids = _incremental_greedy(enc_out, src_length, cfg, max_out_len,
                                  bos_id, eos_id)
        return ids, ['src_word', 'src_length']

    bos = layers.fill_constant_batch_size_like(
        src_word, shape=[1, 1], dtype='int64', value=bos_id)
    ids = bos
    for _t in range(1, max_out_len):
        logits = _decode_prefix(ids, enc_out, src_length, cfg)
        nxt = layers.argmax(logits, axis=-1)
        nxt = layers.reshape(x=nxt, shape=[0, 1])
        ids = layers.concat([ids, layers.cast(nxt, 'int64')], axis=1)
    # freeze everything after the first EOS to EOS (the beam path gets
    # this from beam_search_decode; greedy does it arithmetically)
    eos = layers.fill_constant_batch_size_like(
        ids, shape=[1, max_out_len], dtype='int64', value=eos_id)
    is_eos = layers.cast(layers.equal(x=ids, y=eos), 'int64')
    before = layers.elementwise_sub(
        x=layers.cumsum(is_eos, axis=1), y=is_eos)   # eos count before t
    zeros = layers.fill_constant_batch_size_like(
        ids, shape=[1, max_out_len], dtype='int64', value=0)
    after = layers.cast(layers.less_than(x=zeros, y=before), 'int64')
    keep = layers.elementwise_sub(
        x=layers.fill_constant_batch_size_like(
            ids, shape=[1, max_out_len], dtype='int64', value=1),
        y=after)
    ids = layers.elementwise_add(
        x=layers.elementwise_mul(x=ids, y=keep),
        y=layers.elementwise_mul(x=eos, y=after))
    return ids, ['src_word', 'src_length']


def transformer_beam_infer(src_vocab_size, trg_vocab_size, beam_size=4,
                           max_out_len=16, bos_id=0, eos_id=1,
                           src_seq_len=16, max_length=256, n_layer=6,
                           n_head=8, d_key=64, d_value=64, d_model=512,
                           d_inner=2048, weight_sharing=False,
                           scan_layers=None, incremental=False):
    """Beam-search decode. incremental=False unrolls one decoder re-run
    per position over the beam_search/beam_gather/beam_search_decode
    ops; incremental=True emits the KV-cached transformer_beam_decode
    op (one lax.scan, caches reordered by parent — same sequences, O(T)
    compute). Returns (sentence_ids [B, beam, T], sentence_scores
    [B, beam])."""
    cfg = _infer_cfg(src_vocab_size, trg_vocab_size, max_length, n_layer,
                     n_head, d_key, d_value, d_model, d_inner,
                     weight_sharing, scan_layers)
    src_word = layers.data(name='src_word', shape=[src_seq_len],
                           dtype='int64')
    src_length = layers.data(name='src_length', shape=[], dtype='int64')
    enc_out = _build_encoder(src_word, src_length, src_vocab_size, cfg)
    if incremental:
        out = _incremental_beam(enc_out, src_length, cfg, beam_size,
                                max_out_len, bos_id, eos_id)
        return out, ['src_word', 'src_length']

    # tile encoder state over the beam: [B, S, D] -> [B*beam, S, D]
    enc_beam = layers.expand(layers.unsqueeze(enc_out, axes=[1]),
                             expand_times=[1, beam_size, 1, 1])
    enc_beam = layers.reshape(x=enc_beam, shape=[-1] +
                              [enc_out.shape[1], enc_out.shape[2]])
    len_beam = layers.expand(layers.unsqueeze(src_length, axes=[1]),
                             expand_times=[1, beam_size])
    len_beam = layers.reshape(x=len_beam, shape=[-1])

    bos = layers.fill_constant_batch_size_like(
        enc_beam, shape=[1, 1], dtype='int64', value=bos_id)
    prefix = bos                                   # [B*beam, t]
    pre_ids = layers.fill_constant_batch_size_like(
        src_word, shape=[1, beam_size], dtype='int64', value=bos_id)
    # only slot 0 live at t=0 (all beams identical otherwise): bias is
    # (one_hot(0) - 1) * 1e9 = [0, -1e9, ...] broadcast over the batch
    slot0 = layers.fill_constant(shape=[1, 1], dtype='int64', value=0)
    oh = layers.reshape(x=layers.one_hot(slot0, depth=beam_size),
                        shape=[1, beam_size])
    init_bias = layers.scale(oh, scale=1e9, bias=-1e9)
    ones = layers.fill_constant_batch_size_like(
        src_word, shape=[1, beam_size], dtype='float32', value=1.0)
    pre_scores = layers.elementwise_mul(x=ones, y=init_bias, axis=-1)

    step_ids, step_parents = [], []
    for _t in range(1, max_out_len):
        logits = _decode_prefix(prefix, enc_beam, len_beam, cfg)
        logp = layers.log_softmax(logits)          # [B*beam, V]
        top_scores, top_ids = layers.topk(logp, k=beam_size)
        cand_ids = layers.reshape(x=layers.cast(top_ids, 'int64'),
                                  shape=[-1, beam_size, beam_size])
        cand_scores = layers.reshape(x=top_scores,
                                     shape=[-1, beam_size, beam_size])
        sel_ids, sel_scores, parent = layers.beam_search(
            pre_ids, pre_scores, cand_ids, cand_scores,
            beam_size=beam_size, end_id=eos_id)
        # realign prefixes to the selected parents and append new token
        prefix_b = layers.reshape(x=prefix, shape=[-1, beam_size,
                                                   prefix.shape[1]])
        prefix_b = layers.beam_gather(prefix_b, parent)
        prefix = layers.reshape(x=prefix_b,
                                shape=[-1, prefix.shape[1]])
        nxt = layers.reshape(x=sel_ids, shape=[-1, 1])
        prefix = layers.concat([prefix, nxt], axis=1)
        pre_ids, pre_scores = sel_ids, sel_scores
        step_ids.append(sel_ids)
        step_parents.append(parent)

    stacked_ids = layers.stack(step_ids, axis=0)       # [T-1, B, beam]
    stacked_parents = layers.stack(step_parents, axis=0)
    sent, sent_scores = layers.beam_search_decode(
        stacked_ids, stacked_parents, final_scores=pre_scores,
        end_id=eos_id)
    return (sent, sent_scores), ['src_word', 'src_length']
