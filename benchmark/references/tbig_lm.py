"""Plain reference of ``tbig_lm``: the decoder-only LM as one dense
forward pass in float32 ``jax.numpy``.

No pages, no cache, no batching and no kernel: the whole sequence at
once under a lower-triangular mask, every matrix product at the highest
precision. It follows the block of Vaswani et al. 2017 (section 3): token
embedding scaled by sqrt(d_model) plus sinusoid positions; per layer
multi-head self-attention (no biases, scores scaled by d_key ** -0.5)
and a ReLU feed-forward, each followed by residual add and layer norm
(post-LN, eps 1e-5); an output projection of its own. Independent of
``paddle_tpu/ops``: it shares only the names of the weights
(``serving/decode/model.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, gain, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * gain + bias


def logits(weights, tokens, n_head):
    """``tokens`` [T] int32 -> float32 logits [T, V]; row t is the
    distribution of token t + 1 given tokens 0..t. Padding appended to
    the end leaves the earlier rows as they are (causal mask)."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    steps = tokens.shape[0]
    d_model = w['lm_emb'].shape[1]
    with jax.default_matmul_precision('highest'):
        x = w['lm_emb'][tokens] * (d_model ** 0.5) + w['lm_pos_enc'][:steps]
        mask = jnp.tril(jnp.ones((steps, steps), bool))
        for i in range(w['lm_stack_slf_q.w'].shape[0]):
            def part(name):
                return w['lm_stack_' + name][i]
            q = (x @ part('slf_q.w')).reshape(steps, n_head, -1)
            k = (x @ part('slf_k.w')).reshape(steps, n_head, -1)
            v = (x @ part('slf_v.w')).reshape(steps, n_head, -1)
            scores = jnp.einsum('thd,shd->hts', q, k) * q.shape[-1] ** -0.5
            scores = jnp.where(mask[None], scores, -jnp.inf)
            mixed = jnp.einsum('hts,shd->thd', jax.nn.softmax(scores, -1), v)
            x = _layer_norm(x + mixed.reshape(steps, -1) @ part('slf_o.w'),
                            part('ln1.w'), part('ln1.b'))
            hidden = jax.nn.relu(x @ part('ffn_1.w') + part('ffn_1.b'))
            x = _layer_norm(x + hidden @ part('ffn_2.w') + part('ffn_2.b'),
                            part('ln2.w'), part('ln2.b'))
        return x @ w['lm_out_proj.w']


def token_gaps(weights, n_head, prompt, answer, pad_to):
    """How far each served token is from the reference's choice: for
    answer token i, the reference's largest logit at that position minus
    its logit of the served token (0 where they agree), and the
    deviation of the logits there. The served tokens are fed back, so
    one near-tie does not spoil the positions after it. ``weights`` are
    device arrays; every call pads to ``pad_to``, so one program serves
    every length."""
    seq = list(prompt) + list(answer)
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(seq)] = seq
    rows = np.asarray(_logits(weights, padded, n_head))[
        len(prompt) - 1:len(seq) - 1]
    served = rows[np.arange(len(answer)), np.asarray(answer)]
    return (rows.max(axis=1) - served).tolist(), float(rows.std())


_logits = jax.jit(logits, static_argnums=2)
