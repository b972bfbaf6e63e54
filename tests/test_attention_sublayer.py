"""The attention sublayer that owns its projections
(ops/attention_ops.py::attention_sublayer: q, k and v leave their matmul
head-major, the output projection contracts (h, d)) against the form it
replaces in the models (three matmuls, a head split, the attention, a
head merge, one matmul): the same function of the same weights, forward
and backward, as jax functions and as programs over one scope."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import attention_ops as ao
from paddle_tpu.param_attr import ParamAttr

B, H, DK, DV, M = 3, 4, 8, 6, 20

# name -> (Tq, Tk, causal, key lengths or None, dropout)
CASES = {
    'self': (7, 7, False, None, 0.0),
    'causal': (7, 7, True, None, 0.0),
    'key_length': (7, 7, False, (7, 3, 5), 0.0),
    'cross': (5, 9, False, (9, 4, 6), 0.0),
    'causal_dropout': (7, 7, True, None, 0.3),
}


def _split_merge_form(x, mem, wq, wk, wv, wo, **kw):
    """The sublayer as the models spelled it before."""
    return ao.fused_attention(x @ wq, mem @ wk, mem @ wv, H, **kw) @ wo


@pytest.mark.parametrize('case', sorted(CASES))
def test_projected_form_is_the_split_merge_form(case):
    """Output and the gradients of x, mem and the four matrices agree to
    float32 rounding; with dropout on, the same key draws the same mask
    (the context is [B, H, T, D] in both forms when it is dropped)."""
    tq, tk, causal, lens, rate = CASES[case]
    rng = np.random.RandomState(sorted(CASES).index(case))
    x = jnp.asarray(rng.randn(B, tq, M), jnp.float32)
    mem = jnp.asarray(rng.randn(B, tk, M), jnp.float32)
    wq, wk, wv, wo = (
        jnp.asarray(rng.randn(*s) * s[0] ** -0.5, jnp.float32)
        for s in ((M, H * DK), (M, H * DK), (M, H * DV), (H * DV, M)))
    kw = dict(causal=causal, dropout_rate=rate,
              key_length=None if lens is None else jnp.asarray(lens),
              rng=jax.random.PRNGKey(5) if rate else None)
    cot = jnp.asarray(rng.randn(B, tq, M), jnp.float32)

    def out_and_grads(form):
        def scalar(*args):
            out = form(*args, **kw)
            return jnp.sum(out * cot), out
        (_, out), grads = jax.value_and_grad(
            scalar, argnums=tuple(range(6)), has_aux=True)(
                x, mem, wq, wk, wv, wo)
        return out, grads
    want, want_grads = out_and_grads(_split_merge_form)
    got, got_grads = out_and_grads(
        lambda *a, **k: ao.attention_sublayer(*a, n_head=H, **k))
    assert got.shape == (B, tq, M)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for name, g, w in zip(('x', 'mem', 'wq', 'wk', 'wv', 'wo'),
                          got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


def _spelled_out_attention(x, mem, d_key, d_value, d_model, n_head,
                           dropout_rate, causal=False, key_length=None,
                           name='attn'):
    """models.transformer._multi_head_attention as it was: three ``mul``,
    ``fused_attention`` over the Q, K, V they produce, one ``mul``, under
    the parameter names the one op keeps."""
    from paddle_tpu.layers.helper import LayerHelper

    def fc(inp, size, suffix):
        return layers.fc(input=inp, size=size, num_flatten_dims=2,
                         bias_attr=False,
                         param_attr=ParamAttr(name=name + suffix))
    q = fc(x, d_key * n_head, '_q.w')
    k = fc(mem, d_key * n_head, '_k.w')
    v = fc(mem, d_value * n_head, '_v.w')
    helper = LayerHelper('fused_attention', name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    out.shape = (q.shape[0], q.shape[1], d_value * n_head)
    inputs = {'Q': [q], 'K': [k], 'V': [v]}
    if key_length is not None:
        inputs['KeyLength'] = [key_length]
    helper.append_op(type='fused_attention', inputs=inputs,
                     outputs={'Out': [out]},
                     attrs={'n_head': n_head, 'causal': causal,
                            'dropout_rate': dropout_rate})
    return fc(out, d_model, '_out.w')


CFG = dict(n_layer=2, n_head=2, d_key=4, d_value=6, d_model=8, d_inner=16,
           dropout_rate=0.1, label_smooth_eps=0.1, src_seq_len=7,
           trg_seq_len=5)
VOCAB = 40


def _inference_logits(feed, amp):
    """Build the training graph with whatever ``_multi_head_attention``
    the module holds and run its inference clone over the current scope,
    as the benchmark's reference check does (the clone carries neither
    ``amp`` nor the attention's inference flag)."""
    fluid.reset_default_programs()
    _, logits = T.transformer(VOCAB, VOCAB, max_length=16, **CFG)
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.global_scope()
    if scope.find('out_proj.w') is None:
        exe.run(fluid.default_startup_program())
    clone = prog.clone(for_test=True)
    clone.amp = amp
    ops = [op.type for op in clone.global_block().ops]
    for op in clone.global_block().ops:
        if op.type == 'fused_attention':
            op.attrs['is_test'] = True
    got, = exe.run(clone, feed=feed, fetch_list=[logits])
    params = sorted((p.name, tuple(p.shape))
                    for p in prog.global_block().all_parameters())
    return np.asarray(got, 'float32'), ops, params


@pytest.mark.parametrize('amp', [None, 'bf16'])
def test_model_graph_matches_the_spelled_out_program(monkeypatch, amp):
    """The model's own graph (one op an attention sublayer) and a
    program that spells each sublayer out, over one scope: the same
    parameters by name and shape, the same inference logits. Five ops a
    sublayer became one, so each of the six sublayers sheds four."""
    feed = T.make_fake_batch(3, CFG['src_seq_len'], CFG['trg_seq_len'],
                             VOCAB, VOCAB, seed=3)
    feed['src_length'] = np.asarray([7, 4, 6], 'int64')
    with fluid.scope_guard(fluid.Scope()):
        got, ops, params = _inference_logits(feed, amp)
        monkeypatch.setattr(T, '_multi_head_attention',
                            _spelled_out_attention)
        want, old_ops, old_params = _inference_logits(feed, amp)
    assert params == old_params
    n_attn = 3 * CFG['n_layer']
    assert ops.count('fused_attention') == n_attn
    assert old_ops.count('fused_attention') == n_attn
    assert len(old_ops) - len(ops) == 4 * n_attn
    assert np.isfinite(got).all() and got.std() > 0
    tol = 2e-5 if amp is None else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
