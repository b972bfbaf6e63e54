"""Plain reference of ``tbig_nmt``: the encoder-decoder Transformer's
forward pass and loss in float32 ``jax.numpy``, every matrix product at
the highest precision, no kernels and no fusion.

It follows Vaswani et al. 2017 (section 3): embeddings scaled by
sqrt(d_model) plus sinusoid positions; encoder layers of self-attention
and a ReLU feed-forward; decoder layers of causal self-attention,
attention over the encoder's output and a feed-forward; every sublayer
followed by residual add and layer norm (post-LN, eps 1e-5); attention
without biases, scores scaled by d_key ** -0.5; an output projection of
its own; label-smoothed cross entropy as (1 - eps) x NLL + eps x the mean
over the vocabulary of -log p. One departure, because the reference is
compared with the program's inference clone: where the training graph
has a dropout layer, inference multiplies by (1 - rate) (Fluid's
``downgrade_in_infer``), and so does ``forward``; the dropout on the
attention output is of the upscale-in-training kind and is the identity
here. Independent of ``paddle_tpu/ops``: it shares only the names of the
weights (``models/transformer.py``).
"""

import jax
import jax.numpy as jnp


def _layer_norm(x, w, name):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * w[name + '_ln.w'] \
        + w[name + '_ln.b']


def _attention(x, memory, w, name, n_head, causal, key_length):
    """x [B, T, D] attends to memory [B, S, D]; keys at or past
    ``key_length`` [B] are masked."""
    b, t, _ = x.shape
    s = memory.shape[1]
    q = (x @ w[name + '_q.w']).reshape(b, t, n_head, -1)
    k = (memory @ w[name + '_k.w']).reshape(b, s, n_head, -1)
    v = (memory @ w[name + '_v.w']).reshape(b, s, n_head, -1)
    scores = jnp.einsum('bthd,bshd->bhts', q, k) * q.shape[-1] ** -0.5
    mask = jnp.ones((b, 1, t, s), bool)
    if causal:
        mask = mask & jnp.tril(jnp.ones((t, s), bool))[None, None]
    if key_length is not None:
        mask = mask & (jnp.arange(s)[None, None, None, :]
                       < key_length[:, None, None, None])
    scores = jnp.where(mask, scores, -jnp.inf)
    mixed = jnp.einsum('bhts,bshd->bthd', jax.nn.softmax(scores, -1), v)
    return mixed.reshape(b, t, -1) @ w[name + '_out.w']


def _feed_forward(x, w, name, keep):
    hidden = jax.nn.relu(x @ w[name + '_1.w'] + w[name + '_1.b']) * keep
    return hidden @ w[name + '_2.w'] + w[name + '_2.b']


def _embed(ids, w, name, keep):
    d_model = w[name].shape[1]
    return (w[name][ids] * d_model ** 0.5
            + w[name + '_pos_enc'][:ids.shape[1]][None]) * keep


def forward(weights, batch, n_layer, n_head, dropout_rate,
            label_smooth_eps):
    """(logits [B, T, V], mean loss over the weighted positions) of
    ``batch`` (src_word, src_length, trg_word, lbl_word, lbl_weight)
    in inference mode."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    keep = 1.0 - dropout_rate
    src_length = jnp.asarray(batch['src_length'], jnp.int32)
    with jax.default_matmul_precision('highest'):
        x = _embed(jnp.asarray(batch['src_word'], jnp.int32), w, 'src_emb',
                   keep)
        for i in range(n_layer):
            at = 'enc_%d' % i
            x = _layer_norm(x + keep * _attention(
                x, x, w, at + '_slf', n_head, False, src_length),
                w, at + '_pp1')
            x = _layer_norm(x + keep * _feed_forward(x, w, at + '_ffn', keep),
                            w, at + '_pp2')
        y = _embed(jnp.asarray(batch['trg_word'], jnp.int32), w, 'trg_emb',
                   keep)
        for i in range(n_layer):
            at = 'dec_%d' % i
            y = _layer_norm(y + keep * _attention(
                y, y, w, at + '_slf', n_head, True, None), w, at + '_pp1')
            y = _layer_norm(y + keep * _attention(
                y, x, w, at + '_cross', n_head, False, src_length),
                w, at + '_pp2')
            y = _layer_norm(y + keep * _feed_forward(y, w, at + '_ffn', keep),
                            w, at + '_pp3')
        logits = y @ w['out_proj.w']
    log_p = jax.nn.log_softmax(logits, axis=-1)
    label = jnp.asarray(batch['lbl_word'], jnp.int32)
    nll = -jnp.take_along_axis(log_p, label[..., None], axis=-1)[..., 0]
    cost = (1.0 - label_smooth_eps) * nll \
        + label_smooth_eps * -jnp.mean(log_p, axis=-1)
    weight = jnp.asarray(batch['lbl_weight'], jnp.float32)
    return logits, jnp.sum(cost * weight) / jnp.sum(weight)
