"""Plain reference of ``longcat_flash_chat``: meituan-longcat
LongCat-Flash-Chat, the language model's forward pass in float32
``jax.numpy``.

No pages, no cache, no batching, no kernel and no absorbed form: the
whole sequence at once, every matrix product at the highest precision,
keys and values expanded from the latents head by head, every position
at or below a row's own attended, the experts one after another in a
plain loop, the identity experts as what they are: the row itself.
Independent of ``paddle_tpu/ops``: it shares only the names and layouts
of the weights (``serving/decode/model.py``: ``shortcut_param_shapes``).

One layer ``l``, for a row ``x`` of width ``hidden_size`` at position
``t`` (``RMS(v) g = v * rsqrt(mean(v^2) + eps) * g``); sublayer ``j`` of
layer ``l`` has index ``2 l + j`` in the attention, norm and dense FFN
stacks:

    a0 = x  + MLA[l,0](RMS(x) g_in[l,0])
    n0 = RMS(a0) g_post[l,0]
    s  = MoE[l](n0)                the shortcut: used only at the end
    b0 = a0 + FFN[l,0](n0)
    a1 = b0 + MLA[l,1](RMS(b0) g_in[l,1])
    n1 = RMS(a1) g_post[l,1]
    y  = a1 + FFN[l,1](n1) + s

MLA (H heads, ranks r_q and r, head widths nope, rope, v; D the hidden
width), per sublayer:

    c_q = s_q RMS(n W_qa) g_q          [q_nope ; q_rope]_h = c_q W_qb
    [c ; k_r] = n W_kva                c_kv = s_kv RMS(c) g_kv
    k_rope = rot(k_r)                  one for all heads, not scaled
    k_nope_h = c_kv W_bk[h]^T          v_h = c_kv W_bv[h]
    score_h(t, s) = (q_nope_h . k_nope_h(s) + rot(q_rope_h) . k_rope(s))
                    / sqrt(nope + rope)               for every s <= t
    o_h = sum_s softmax_s(score_h(t, .)) v_h(s)
    MLA = concat_h(o_h) W_o

with ``s_q = sqrt(D / r_q)`` and ``s_kv = sqrt(D / r)``
(``mla_scale_q_lora``, ``mla_scale_kv_lora``), no gate, no indexer, no
window, and ``rot`` turning interleaved pairs (2i, 2i+1) at position p
by ``p * theta^(-2i/rope)`` (no scaling section: a multiplier of 1).

FFN: ``(silu(n Wg) * (n Wu)) Wd``, width ``ffn_hidden_size``.

MoE: ``p = softmax(n0 W_r)`` over the real and the identity experts
together (``n_real + zero`` outputs, no bias on the classifier); chosen
= the ``top_k`` largest of ``p + e`` (``e`` for the choosing only; ties
to the lower index); ``g_i = scale x p_i``, **not** normalised over the
chosen;

    s = sum_{chosen i < n_real} g_i E_i(n0) + (sum_{chosen i >= n_real} g_i) n0

with ``E_i`` a gated SiLU FFN of ``expert_ffn_hidden_size``, summed over
the chosen experts held here; the identity term whole. No shared expert.

``logits = RMS(y) g_f W_head^T`` with a head of its own; the embedding
is not scaled.

``held = (first, count)`` says which routed experts the weights hold
(``references/command_a_plus.py``: the same convention).

``arch`` holds what the shapes do not say (the attention's
``LatentShape`` as a dict under ``latent``, ``n_layer``, ``n_real``,
``top_k``, ``routed_scale``, ``eps``) and switches that are on wherever
the system is held to this reference and that a control turns off to
show what a server that got it wrong would be caught by:

- ``identity`` (off: the identity term dropped);
- ``scale_routed`` (off: the factor ``routed_scale`` dropped);
- ``raw_weights`` (off: the chosen weights normalised over the chosen);
- ``shortcut_last`` (off: ``s`` added with the first FFN's output, so
  that the second sublayer sees it);
- ``own_rows`` (off: the second sublayer attends over the first's
  cached rows, its own queries against the wrong cache layer);
- ``state_dtype`` ('float32': the precision of the residual stream,
  router scores, softmax and logits).

Long sequences: a layer is computed in blocks of rows (the keys of the
whole sequence first, which are small), one head's keys and values
expanded at a time and one matrix upcast at a time, so that a sequence
of some thousand tokens fits beside the served model on the chip.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 1024
SUBLAYERS = 2


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    (shape,) = spec.latent.values()
    return {'latent': dict(vars(shape)), 'n_layer': spec.n_layer,
            'n_real': spec.n_experts, 'top_k': spec.experts_per_token,
            'routed_scale': spec.routed_scale, 'eps': spec.norm_eps,
            'identity': True, 'scale_routed': True, 'raw_weights': True,
            'shortcut_last': True, 'own_rows': True,
            'state_dtype': 'float32'}


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
def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * _f32(gain)


@jax.jit
def rotate_interleaved(x, pos, freq):
    """``x`` [T, ..., W] at positions ``pos`` [T]: pairs (2i, 2i+1)
    turned by ``pos * freq[i]``."""
    angle = pos.astype(jnp.float32)[:, None] * freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def _frequencies(arch):
    shape = arch['latent']
    width = shape['d_rope']
    return jnp.asarray(float(shape['rope_theta']) ** (
        -2 * np.arange(width // 2, dtype=np.float64) / width), jnp.float32)


# ------------------------------------------------------------- attention
@functools.partial(jax.jit, static_argnums=(7, 8))
def _attend(q_nope, q_rope, c_kv, k_rope, w_bk, w_bv, allowed, scale,
            state):
    """One block of rows against the keys it may see: ``q_nope``
    [B, H, nope], ``q_rope`` [B, H, rope] (rotated), ``c_kv`` [S, r],
    ``k_rope`` [S, rope] (rotated), ``w_bk`` [H, nope, r], ``w_bv``
    [H, r, v], ``allowed`` bool [B, S] -> [B, H, v]. Head by head
    (lax.map): one head's keys, values and [B, S] scores alive at a
    time."""
    def one(args):
        qn, qr, bk, bv = args
        keys = jnp.matmul(c_kv, _f32(bk).T, precision=HIGHEST)
        values = jnp.matmul(c_kv, _f32(bv), precision=HIGHEST)
        scores = (jnp.matmul(qn, keys.T, precision=HIGHEST) +
                  jnp.matmul(qr, k_rope.T, precision=HIGHEST)) * scale
        scores = jnp.where(allowed, _stated(scores, state), -jnp.inf)
        return jnp.matmul(_stated(jax.nn.softmax(scores, -1), state),
                          values, precision=HIGHEST)
    out = jax.lax.map(one, (jnp.swapaxes(q_nope, 0, 1),
                            jnp.swapaxes(q_rope, 0, 1), w_bk, w_bv))
    return jnp.swapaxes(out, 0, 1)


def sequence_keys(n, first, w, i, arch):
    """What every later row reads of the rows ``n`` [B, D] at positions
    ``first ..`` in sublayer ``i``: (c_kv [B, r], k_rope [B, rope])."""
    rank = arch['latent']['kv_rank']
    down = _matmul(n, w['lm_full_kv_a.w'][i])
    c_kv = rms_norm(down[:, :rank], w['lm_full_kv_ln.w'][i],
                    float(arch['eps'])) * math.sqrt(n.shape[1] / rank)
    return c_kv, rotate_interleaved(
        down[:, rank:], first + jnp.arange(n.shape[0]), _frequencies(arch))


def attention(n, first, keys, w, i, arch):
    """Rows ``n`` [B, D] at positions ``first ..`` against the
    sequence's ``keys`` (``sequence_keys``) in sublayer ``i`` -> [B, D]."""
    shape = arch['latent']
    heads, d_nope, d_rope = shape['n_head'], shape['d_nope'], shape['d_rope']
    c_kv, k_rope = keys
    rows = n.shape[0]
    c_q = rms_norm(_matmul(n, w['lm_full_q_a.w'][i]),
                   w['lm_full_q_ln.w'][i], float(arch['eps'])) * \
        math.sqrt(n.shape[1] / shape['q_rank'])
    q = _matmul(c_q, w['lm_full_q_b.w'][i]).reshape(rows, heads, -1)
    q_rope = rotate_interleaved(q[..., d_nope:], first + jnp.arange(rows),
                                _frequencies(arch))
    allowed = jnp.arange(c_kv.shape[0])[None, :] <= \
        (first + jnp.arange(rows))[:, None]
    mixed = _attend(q[..., :d_nope], q_rope, c_kv, k_rope,
                    w['lm_full_kv_bk.w'][i], w['lm_full_kv_bv.w'][i],
                    allowed, (d_nope + d_rope) ** -0.5, arch['state_dtype'])
    return _matmul(mixed.reshape(rows, -1), w['lm_full_o.w'][i])


# ------------------------------------------------------------------- FFN
@jax.jit
def expert(n, gate, up, down):
    hidden = jax.nn.silu(jnp.matmul(n, _f32(gate), precision=HIGHEST)) * \
        jnp.matmul(n, _f32(up), precision=HIGHEST)
    return jnp.matmul(hidden, _f32(down), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def route(n, router, bias, top_k, scale, raw=True, state='float32'):
    """(chosen outputs [T, k], their weights [T, k]): the softmax over
    every output of the router, the ``top_k`` largest of score + bias
    (plain ``top_k``: ties to the lower index), the chosen ones' own
    scores times ``scale``; ``raw`` off, normalised over the chosen
    first."""
    scores = _stated(jax.nn.softmax(_stated(jnp.matmul(
        n, _f32(router), precision=HIGHEST), state), axis=-1), state)
    _, chosen = jax.lax.top_k(scores + _f32(bias), top_k)
    top = jnp.take_along_axis(scores, chosen, axis=1)
    if not raw:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return chosen, _stated(top * scale, state)


def experts(n, w, i, arch, held):
    """The expert branch of layer ``i``: the chosen real experts held
    here under their weights, plus the row under the sum of its
    identity experts' weights."""
    first, count = held
    scale = float(arch['routed_scale']) if arch['scale_routed'] else 1.0
    chosen, weight = route(n, w['lm_moe_router.w'][i],
                           w['lm_moe_router.b'][i], int(arch['top_k']),
                           scale, bool(arch['raw_weights']),
                           arch['state_dtype'])
    out = jnp.zeros_like(n)
    for e in range(count):
        share = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
        out += share[:, None] * expert(
            n, w['lm_moe_exp_gate.w'][i, e], w['lm_moe_exp_up.w'][i, e],
            w['lm_moe_exp_down.w'][i, e])
    if arch['identity']:
        out += jnp.sum(jnp.where(chosen >= int(arch['n_real']), weight,
                                 0.0), -1)[:, None] * n
    return out


def dense(n, w, i):
    return expert(n, w['lm_dense_gate.w'][i], w['lm_dense_up.w'][i],
                  w['lm_dense_down.w'][i])


# ------------------------------------------------------------ the layers
@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(into, rows, first):
    return jax.lax.dynamic_update_slice(into, rows, (first, 0))


def _blocks(steps):
    rows = math.gcd(steps, BLOCK_ROWS)
    return [(a, a + rows) for a in range(0, steps, rows)]


def _keys_of(x, w, i, arch):
    """The keys of the whole sequence in sublayer ``i``: block by block
    (a row's do not depend on the others'), joined, which is small."""
    gain, eps = w['lm_stack_ln1.w'][i], float(arch['eps'])
    parts = [sequence_keys(rms_norm(x[a:b], gain, eps), a, w, i, arch)
             for a, b in _blocks(x.shape[0])]
    return tuple(jnp.concatenate(part) for part in zip(*parts))


def layer(x, w, l, arch, held):
    """``x`` [T, D] -> [T, D], in blocks of rows, a sublayer at a time:
    the second's keys are made from the first's whole output."""
    eps, state = float(arch['eps']), arch['state_dtype']
    i0, i1 = SUBLAYERS * l, SUBLAYERS * l + 1
    early = not arch['shortcut_last']
    keys0 = _keys_of(x, w, i0, arch)
    b0, s = jnp.zeros_like(x), jnp.zeros_like(x)
    for a, b in _blocks(x.shape[0]):
        block = x[a:b]
        a0 = _stated(block + attention(
            rms_norm(block, w['lm_stack_ln1.w'][i0], eps), a, keys0, w, i0,
            arch), state)
        n0 = rms_norm(a0, w['lm_stack_ln2.w'][i0], eps)
        branch = experts(n0, w, l, arch, held)
        s = _put_rows(s, branch, a)
        b0 = _put_rows(b0, _stated(
            a0 + dense(n0, w, i0) + (branch if early else 0.0), state), a)
    # the second sublayer's own rows, or (a control) the first's again
    keys1 = _keys_of(b0, w, i1, arch) if arch['own_rows'] else keys0
    out = jnp.zeros_like(x)
    for a, b in _blocks(x.shape[0]):
        block = b0[a:b]
        a1 = _stated(block + attention(
            rms_norm(block, w['lm_stack_ln1.w'][i1], eps), a, keys1, w, i1,
            arch), state)
        n1 = rms_norm(a1, w['lm_stack_ln2.w'][i1], eps)
        y = a1 + dense(n1, w, i1) + (0.0 if early else s[a:b])
        out = _put_rows(out, _stated(y, state), a)
    return out


def hidden_states(weights, tokens, arch, held):
    x = _f32(jnp.take(weights['lm_emb'], jnp.asarray(tokens), axis=0))
    for l in range(int(arch['n_layer'])):
        x = layer(x, weights, l, arch, held)
    return x


def logits(weights, tokens, arch, held, rows=None):
    """``tokens`` [T] int32 -> float32 logits [T, V] (or the rows
    ``rows = (lo, hi)`` of them); row t is the distribution of token
    t + 1 given tokens 0..t. Padding appended to the end leaves the
    earlier rows as they are (causal masks)."""
    x = hidden_states(weights, tokens, arch, held)
    if rows is not None:
        x = x[rows[0]:rows[1]]
    y = rms_norm(x, weights['lm_final_ln.w'], float(arch['eps']))
    return _stated(_matmul(y, jnp.transpose(weights['lm_head.w'])),
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
