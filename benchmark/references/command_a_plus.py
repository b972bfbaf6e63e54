"""Plain reference of ``command_a_plus``: CohereLabs command-a-plus-05-2026
(``model_type`` cohere2_moe), the language model's forward pass in
float32 ``jax.numpy``.

No pages, no cache, no batching and no kernel: the whole sequence at
once, every matrix product at the highest precision, the experts one
after another in a plain loop. Independent of ``paddle_tpu/ops``: it
shares only the names and layouts of the weights
(``serving/decode/model.py``).

One layer, for a row ``x`` of width ``hidden_size`` at position ``p``:

    n = LN(x) = (x - mean) * rsqrt(var + eps) * g      (no bias)
    q = n Wq, k = n Wk, v = n Wv                       (H, Hkv, Hkv heads)
    sliding layer: q, k rotated over the whole head width in interleaved
        pairs (2i, 2i+1) by p * theta^(-2i/head_dim); keys j with
        p - window < j <= p are seen
    full layer: nothing is rotated (no positional encoding); j <= p
    query head h reads KV head h // (H / Hkv); softmax at head_dim^-1/2
    a = attn Wo
    s = sigmoid(n Wr) over all published experts; the top_k largest are
        chosen, w = s_top / sum(s_top)
    E(n) = (silu(n Wg) * (n Wu)) Wd
    m = sum_k w_k E_{e_k}(n) + mean_j S_j(n)           (shared experts)
    y = x + a + m                                      (parallel block)

and ``logits = LN_f(y) E^T logit_scale`` with the tied embedding ``E``,
which is not scaled on the way in.

``held = (first, count)`` says which routed experts the weights hold: the
router still scores every published expert and normalises over all it
chose, and only what experts ``first .. first + count - 1`` give is
added (one chip's share of an expert-parallel deployment; the partial
result is what goes on to the next layer). ``held = (0, n_experts)`` is
the whole model.

Weights, by the engine's names; ``L`` layers, ``E`` experts held, ``S``
shared experts, any float dtype (each is upcast as it is used, one
matrix or one expert at a time, so that the reference fits beside the
served model on the chip):

    lm_emb                [V, D]        tied: embedding and output head
    lm_final_ln.w         [D]
    lm_stack_ln.w         [L, D]
    lm_stack_slf_{q,o}.w  [L, D, H*Dh], [L, H*Dh, D]
    lm_stack_slf_{k,v}.w  [L, D, Hkv*Dh]
    lm_stack_router.w     [L, D, n_experts]
    lm_stack_exp_{gate,up}.w  [L, E, D, F];  lm_stack_exp_down.w [L, E, F, D]
    lm_stack_shr_{gate,up}.w  [L, S, D, F];  lm_stack_shr_down.w [L, S, F, D]

``arch`` holds what the shapes do not say: ``n_head``, ``n_kv_head``,
``layer_types`` (one of 'sliding_attention' / 'full_attention' per
layer), ``sliding_window``, ``rope_theta``, ``top_k``, ``eps``,
``logit_scale``; and ``state_dtype``: the precision of
what the configuration states float32 for beside the products (the
residual stream, the router's scores and weights, the attention's scores
and softmax, the logits). It is 'float32' wherever the system is held to
this reference; only a control lowers it, to show what a server that
kept those in a narrower type would be caught by
(``benchmark/probe_precision.py``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    return {'n_head': spec.n_head, 'n_kv_head': spec.n_kv_head,
            'layer_types': list(spec.layer_types),
            'sliding_window': spec.sliding_window,
            'rope_theta': spec.rope_theta,
            'top_k': spec.experts_per_token, 'eps': spec.norm_eps,
            'logit_scale': spec.logit_scale, 'state_dtype': 'float32'}


def held_of(spec):
    return (spec.first_expert, spec.experts_held)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _stated(x, state):
    """``x`` as a value of dtype ``state``: itself at 'float32'."""
    return x.astype(state).astype(jnp.float32)


@jax.jit
def _matmul(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=2)
def layer_norm(x, gain, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(gain)


@functools.partial(jax.jit, static_argnums=1)
def rotate(x, theta):
    """``x`` [T, heads, Dh] at positions 0..T-1: interleaved pairs
    (2i, 2i+1) turned by ``pos * theta^(-2i/Dh)`` (rope_gptj)."""
    steps, _, width = x.shape
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(steps, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _attend(q, k, v, window, state):
    """One KV head's group: ``q`` [G, T, Dh], ``k``/``v`` [T, Dh] ->
    [G, T, Dh]; the query heads one after another (lax.map), so that
    one [T, T] score matrix is alive at a time."""
    steps = k.shape[0]
    row = jnp.arange(steps)[:, None]
    col = jnp.arange(steps)[None, :]
    seen = col <= row
    if window:
        seen &= col > row - window

    def one(qh):
        scores = jnp.matmul(qh, k.T, precision=HIGHEST) * qh.shape[-1] ** -0.5
        scores = jnp.where(seen, _stated(scores, state), -jnp.inf)
        return jnp.matmul(_stated(jax.nn.softmax(scores, -1), state), v,
                          precision=HIGHEST)
    return jax.lax.map(one, q)


def attention(n, w, i, arch):
    steps = n.shape[0]
    heads, kv_heads = arch['n_head'], arch['n_kv_head']
    sliding = arch['layer_types'][i] == 'sliding_attention'
    q = _matmul(n, w['lm_stack_slf_q.w'][i]).reshape(steps, heads, -1)
    k = _matmul(n, w['lm_stack_slf_k.w'][i]).reshape(steps, kv_heads, -1)
    v = _matmul(n, w['lm_stack_slf_v.w'][i]).reshape(steps, kv_heads, -1)
    if sliding:
        q = rotate(q, float(arch['rope_theta']))
        k = rotate(k, float(arch['rope_theta']))
    group = heads // kv_heads
    mixed = []
    for g in range(kv_heads):
        qg = jnp.transpose(q[:, g * group:(g + 1) * group], (1, 0, 2))
        out = _attend(qg, k[:, g], v[:, g],
                      int(arch['sliding_window']) if sliding else 0,
                      arch['state_dtype'])
        mixed.append(jnp.transpose(out, (1, 0, 2)))
    mixed = jnp.concatenate(mixed, axis=1).reshape(steps, -1)
    return _matmul(mixed, w['lm_stack_slf_o.w'][i])


@jax.jit
def expert(n, gate, up, down):
    hidden = jax.nn.silu(jnp.matmul(n, _f32(gate), precision=HIGHEST)) * \
        jnp.matmul(n, _f32(up), precision=HIGHEST)
    return jnp.matmul(hidden, _f32(down), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(2, 3))
def route(n, router, top_k, state='float32'):
    """(chosen experts [T, k], their weights [T, k]): sigmoid scores
    over every published expert, the ``top_k`` largest, normalised over
    all that were chosen, wherever they live."""
    scores = _stated(jax.nn.sigmoid(_stated(jnp.matmul(
        n, _f32(router), precision=HIGHEST), state)), state)
    top, chosen = jax.lax.top_k(scores, top_k)
    return chosen, _stated(top / jnp.sum(top, axis=-1, keepdims=True), state)


def experts(n, w, i, arch, held):
    """Routed sum over the experts held, plus the shared experts' mean."""
    first, count = held
    chosen, weight = route(n, w['lm_stack_router.w'][i], int(arch['top_k']),
                           arch['state_dtype'])
    out = jnp.zeros_like(n)
    for e in range(count):
        share = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
        out += share[:, None] * expert(
            n, w['lm_stack_exp_gate.w'][i, e], w['lm_stack_exp_up.w'][i, e],
            w['lm_stack_exp_down.w'][i, e])
    n_shared = w['lm_stack_shr_gate.w'].shape[1]
    for j in range(n_shared):
        out += expert(
            n, w['lm_stack_shr_gate.w'][i, j], w['lm_stack_shr_up.w'][i, j],
            w['lm_stack_shr_down.w'][i, j]) / n_shared
    return out


def layer(x, w, i, arch, held):
    n = layer_norm(x, w['lm_stack_ln.w'][i], float(arch['eps']))
    return _stated(
        x + attention(n, w, i, arch) + experts(n, w, i, arch, held),
        arch['state_dtype'])


def hidden_states(weights, tokens, arch, held):
    x = _f32(jnp.take(weights['lm_emb'], jnp.asarray(tokens), axis=0))
    for i in range(weights['lm_stack_ln.w'].shape[0]):
        x = layer(x, weights, i, arch, held)
    return x


def logits(weights, tokens, arch, held, rows=None):
    """``tokens`` [T] int32 -> float32 logits [T, V] (or the rows
    ``rows = (lo, hi)`` of them); row t is the distribution of token
    t + 1 given tokens 0..t. Padding appended to the end leaves the
    earlier rows as they are (causal masks)."""
    x = hidden_states(weights, tokens, arch, held)
    if rows is not None:
        x = x[rows[0]:rows[1]]
    y = layer_norm(x, weights['lm_final_ln.w'], float(arch['eps']))
    return _stated(_matmul(y, jnp.transpose(weights['lm_emb'])) *
                   float(arch['logit_scale']),
                   arch['state_dtype'])


def token_gaps(weights, arch, held, prompt, answer, pad_to):
    """How far each served token is from the reference's choice: for
    answer token i, the reference's largest logit at that position minus
    its logit of the served token (0 where they agree), and the
    deviation of the logits there. The served tokens are fed back, so
    one near-tie does not spoil the positions after it. The sequence is
    padded to a multiple of ``pad_to``, so few programs serve every
    length."""
    seq = list(prompt) + list(answer)
    size = -(-len(seq) // pad_to) * pad_to
    padded = np.zeros((size,), np.int32)
    padded[:len(seq)] = seq
    rows = np.asarray(logits(weights, padded, arch, held,
                             rows=(len(prompt) - 1, len(seq) - 1)))
    served = rows[np.arange(len(answer)), np.asarray(answer)]
    return (rows.max(axis=1) - served).tolist(), float(rows.std())
