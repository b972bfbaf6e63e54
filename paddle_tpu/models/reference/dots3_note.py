"""Plain reference of ``dots3_note``: dots-studio dots3-note-prev
(``model_type`` dots3_note), the language model's forward pass in float32
``jax.numpy``.

No pages, no cache, no batching, no kernel and no absorbed form: the
whole sequence at once, every matrix product at the highest precision,
keys and values expanded from the latents head by head, the indexer's
scores dense and its choice ``lax.top_k``, the experts one after
another in a plain loop. Independent of ``paddle_tpu/ops``: it shares
only the names and layouts of the weights (``serving/decode/model.py``:
``latent_param_shapes``).

One layer, for a row ``x`` of width ``hidden_size`` at position ``t``
(``RMS(v) g = v * rsqrt(mean(v^2) + eps) * g``):

    n = RMS(x) g1
    h = x + Attn_kind(n)
    y = h + FFN(RMS(h) g2)

Latent attention of a kind (H heads, ranks r_q and r, head widths nope,
rope, v; theta; s_q = sqrt(hidden / r_q), s_kv = sqrt(hidden / r) where
``rescale``, else 1):

    c_q = s_q RMS(n W_qa) g_q          [q_nope ; q_rope]_h = c_q W_qb
    [c ; k_r] = n W_kva                c_kv = s_kv RMS(c) g_kv
    k_rope = RoPE(k_r)                 one for all heads
    k_nope_h = c_kv W_bk[h]^T          v_h = c_kv W_bv[h]
    score_h(t, s) = (q_nope_h . k_nope_h(s) + RoPE(q_rope_h) . k_rope(s))
                    / sqrt(nope + rope)         for s in S_t
    o_h = sigmoid(n W_g)_h * sum_s softmax_s(score_h(t, .)) v_h(s)
    Attn = concat_h(o_h) W_o

RoPE here is over interleaved pairs (2i, 2i+1) by t * theta^(-2i/rope).
``sliding_attention``: S_t = {s : t - window < s <= t}.
``full_attention``: S_t = the ``index_topk`` positions s <= t of largest

    I(t, s) = sum_j w_j ReLU(q^I_j . k^I(s))
    q^I = c_q W^I_q   (index_n_heads x index_head_dim)
    k^I = LayerNorm(n W^I_k) (gain, bias)
    w   = n W^I_w / sqrt(index_n_heads * index_head_dim)

with the first ``rope`` columns of every q^I_j and of k^I rotated over
half-split pairs (i, i + rope/2); all s <= t while t < index_topk.

FFN: layers below ``dense_layers`` ``(silu(n Wg) * (n Wu)) Wd``; the
others ``s = sigmoid(n W_r)`` over every published expert, the ``top_k``
largest of ``s + b`` chosen (``b`` for the choosing only), weights
``s_e / sum_chosen s``, summed over the chosen experts held here, plus
every shared expert at weight 1.

``logits = RMS(y) g_f W_head^T`` with a head of its own; the embedding
is not scaled.

``held = (first, count)`` says which routed experts the weights hold
(``models/reference/command_a_plus.py``: the same convention).

``arch`` holds what the shapes do not say: ``layer_types``, per kind its
``LatentShape`` as a dict under ``latent``, ``sliding_window``,
``dense_layers``, ``index_n_heads``, ``index_topk``, ``top_k``, ``eps``,
and four switches that are on wherever the system is held to this
reference and that a control turns off to show what a server that got
it wrong would be caught by: ``state_dtype`` ('float32': the precision
of the residual stream, router and index scores, softmax and logits),
``gate`` (the headwise output gate), ``rescale`` (s_q, s_kv) and
``select`` (the indexer's choice; off: a full layer sees all s <= t).

Long sequences: a layer is computed in blocks of rows (keys and index
keys of the whole sequence first, which are small), one head's keys and
values expanded at a time and one matrix upcast at a time, so that a
sequence of 33k tokens fits beside the served model on the chip: the
residual stream twice (in and out of a layer) and a block's scores.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FULL, SLIDING = 'full_attention', 'sliding_attention'
_TAG = {FULL: 'full', SLIDING: 'swa'}
BLOCK_ROWS = 1024
# a sliding layer's block of rows sees this many keys before its first
BAND = 1024


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    return {'layer_types': list(spec.layer_types),
            'latent': {kind: dict(vars(shape))
                       for kind, shape in spec.latent.items()},
            'sliding_window': spec.sliding_window,
            'dense_layers': spec.dense_layers,
            'index_n_heads': spec.index_n_heads,
            'index_topk': spec.index_topk,
            'top_k': spec.experts_per_token, 'eps': spec.norm_eps,
            'rescale': spec.lora_rescale, 'gate': True, 'select': True,
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


@functools.partial(jax.jit, static_argnums=3)
def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(gain) + _f32(bias)


def _angles(pos, width, theta):
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angle), jnp.sin(angle)


@functools.partial(jax.jit, static_argnums=2)
def rotate_interleaved(x, pos, theta):
    """``x`` [T, ..., W] at positions ``pos`` [T]: pairs (2i, 2i+1)
    turned by ``pos * theta^(-2i/W)``."""
    cos, sin = _angles(pos, x.shape[-1], theta)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=2)
def rotate_half(x, pos, theta):
    """``x`` [T, ..., W]: pairs (i, i + W/2) turned likewise."""
    cos, sin = _angles(pos, x.shape[-1], theta)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _rotated_first(x, pos, width, theta):
    return jnp.concatenate([rotate_half(x[..., :width], pos, theta),
                            x[..., width:]], -1)


# ------------------------------------------------------------- attention
@functools.partial(jax.jit, static_argnums=(4,))
def index_scores(q, w, keys, first_row, state):
    """Dense ``I(t, s)`` of a block of rows: ``q`` [B, Hi, Di], ``w``
    [B, Hi], ``keys`` [S, Di] of positions 0.. -> [B, S], -inf where
    s > t (row b is position ``first_row + b``)."""
    def add(j, total):
        dots = jnp.matmul(q[:, j], keys.T, precision=HIGHEST)
        return total + w[:, j, None] * jax.nn.relu(_stated(dots, state))
    total = jax.lax.fori_loop(
        0, q.shape[1], add,
        jnp.zeros((q.shape[0], keys.shape[0]), jnp.float32))
    row = first_row + jnp.arange(q.shape[0])[:, None]
    col = jnp.arange(keys.shape[0])[None, :]
    return jnp.where(col <= row, _stated(total, state), -jnp.inf)


@functools.partial(jax.jit, static_argnums=1)
def chosen_columns(scores, k):
    """bool [B, S]: the ``k`` largest of each row (all where S <= k)."""
    rows, cols = scores.shape
    if cols <= k:
        return jnp.ones((rows, cols), bool)
    _, at = jax.lax.top_k(scores, k)
    return jnp.zeros((rows, cols), bool).at[
        jnp.arange(rows)[:, None], at].set(True)


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


def sequence_keys(n, first, w, kind, i, arch):
    """What every later row reads of the rows ``n`` [B, D] at positions
    ``first ..`` in layer ``i`` of ``kind``: (c_kv [B, r], k_rope
    [B, rope], index keys [B, Di] or None)."""
    shape, tag = arch['latent'][kind], _TAG[kind]
    eps, rank = float(arch['eps']), shape['kv_rank']
    pos = first + jnp.arange(n.shape[0])
    down = _matmul(n, w['lm_%s_kv_a.w' % tag][i])
    s_kv = math.sqrt(n.shape[1] / rank) if arch['rescale'] else 1.0
    c_kv = rms_norm(down[:, :rank], w['lm_%s_kv_ln.w' % tag][i], eps) * s_kv
    k_rope = rotate_interleaved(down[:, rank:], pos,
                                float(shape['rope_theta']))
    index = None
    if kind == FULL:
        index = layer_norm(_matmul(n, w['lm_full_idx_k.w'][i]),
                           w['lm_full_idx_k_ln.w'][i],
                           w['lm_full_idx_k_ln.b'][i], eps)
        index = _rotated_first(index, pos, shape['d_rope'],
                               float(shape['rope_theta']))
    return c_kv, k_rope, index


def attention(n, first, keys, w, kind, i, arch):
    """Rows ``n`` [B, D] at positions ``first ..`` against the
    sequence's ``keys`` (``sequence_keys``) -> [B, D]."""
    shape, tag = arch['latent'][kind], _TAG[kind]
    eps, state = float(arch['eps']), arch['state_dtype']
    heads, d_nope, d_rope = shape['n_head'], shape['d_nope'], shape['d_rope']
    theta = float(shape['rope_theta'])
    c_kv, k_rope, index = keys
    rows, steps = n.shape[0], c_kv.shape[0]
    pos = first + jnp.arange(rows)
    s_q = math.sqrt(n.shape[1] / shape['q_rank']) if arch['rescale'] else 1.0
    c_q = rms_norm(_matmul(n, w['lm_%s_q_a.w' % tag][i]),
                   w['lm_%s_q_ln.w' % tag][i], eps) * s_q
    q = _matmul(c_q, w['lm_%s_q_b.w' % tag][i]).reshape(rows, heads, -1)
    q_nope = q[..., :d_nope]
    q_rope = rotate_interleaved(q[..., d_nope:], pos, theta)

    lo = 0
    if kind == SLIDING:
        # the band of keys this block of rows can see
        size = min(steps, rows + BAND)
        lo = int(np.clip(first - BAND, 0, steps - size))
        c_kv, k_rope = c_kv[lo:lo + size], k_rope[lo:lo + size]
    col = lo + jnp.arange(c_kv.shape[0])[None, :]
    allowed = col <= pos[:, None]
    if kind == SLIDING:
        allowed &= col > pos[:, None] - int(arch['sliding_window'])
    elif arch['select']:
        n_index = int(arch['index_n_heads'])
        q_i = _matmul(c_q, w['lm_full_idx_q.w'][i]).reshape(rows, n_index, -1)
        q_i = _rotated_first(q_i, pos, d_rope, theta)
        w_i = _matmul(n, w['lm_full_idx_w.w'][i]) * \
            (n_index * q_i.shape[-1]) ** -0.5
        allowed &= chosen_columns(
            index_scores(q_i, w_i, index, first, state),
            int(arch['index_topk']))
    mixed = _attend(q_nope, q_rope, c_kv, k_rope,
                    w['lm_%s_kv_bk.w' % tag][i], w['lm_%s_kv_bv.w' % tag][i],
                    allowed, (d_nope + d_rope) ** -0.5, state)
    if arch['gate']:
        mixed = mixed * jax.nn.sigmoid(
            _matmul(n, w['lm_%s_gate.w' % tag][i]))[:, :, None]
    return _matmul(mixed.reshape(rows, -1), w['lm_%s_o.w' % tag][i])


# ------------------------------------------------------------------- FFN
@jax.jit
def expert(n, gate, up, down):
    hidden = jax.nn.silu(jnp.matmul(n, _f32(gate), precision=HIGHEST)) * \
        jnp.matmul(n, _f32(up), precision=HIGHEST)
    return jnp.matmul(hidden, _f32(down), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(3, 4))
def route(n, router, bias, top_k, state='float32'):
    """(chosen experts [T, k], their weights [T, k]): sigmoid scores
    over every published expert, the ``top_k`` largest of score + bias,
    the chosen ones' own scores normalised over all that were chosen,
    wherever they live."""
    scores = _stated(jax.nn.sigmoid(_stated(jnp.matmul(
        n, _f32(router), precision=HIGHEST), state)), state)
    _, chosen = jax.lax.top_k(scores + _f32(bias), top_k)
    top = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen, _stated(top / jnp.sum(top, axis=-1, keepdims=True), state)


def experts(n, w, i, arch, held):
    """Routed layer ``i`` (of the routed ones): the sum over the experts
    held, plus every shared expert at weight 1."""
    first, count = held
    chosen, weight = route(n, w['lm_moe_router.w'][i],
                           w['lm_moe_router.b'][i], int(arch['top_k']),
                           arch['state_dtype'])
    out = jnp.zeros_like(n)
    for e in range(count):
        share = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
        out += share[:, None] * expert(
            n, w['lm_moe_exp_gate.w'][i, e], w['lm_moe_exp_up.w'][i, e],
            w['lm_moe_exp_down.w'][i, e])
    for j in range(w['lm_moe_shr_gate.w'].shape[1]):
        out += expert(n, w['lm_moe_shr_gate.w'][i, j],
                      w['lm_moe_shr_up.w'][i, j],
                      w['lm_moe_shr_down.w'][i, j])
    return out


def ffn(n, w, layer, arch, held):
    dense = int(arch['dense_layers'])
    if layer < dense:
        return expert(n, w['lm_dense_gate.w'][layer],
                      w['lm_dense_up.w'][layer], w['lm_dense_down.w'][layer])
    return experts(n, w, layer - dense, arch, held)


# ------------------------------------------------------------ the layers
@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(into, rows, first):
    return jax.lax.dynamic_update_slice(into, rows, (first, 0))


def layer(x, w, layer_no, arch, held):
    """``x`` [T, D] -> [T, D], in blocks of rows."""
    kind = arch['layer_types'][layer_no]
    i = arch['layer_types'][:layer_no].count(kind)
    eps, state = float(arch['eps']), arch['state_dtype']
    steps = x.shape[0]
    rows = math.gcd(steps, BLOCK_ROWS)
    gain1, gain2 = w['lm_stack_ln1.w'][layer_no], w['lm_stack_ln2.w'][layer_no]
    # keys of the whole sequence first: block by block (a row's do not
    # depend on the others'), joined, which is small
    parts = [sequence_keys(rms_norm(x[a:a + rows], gain1, eps), a, w, kind,
                           i, arch) for a in range(0, steps, rows)]
    keys = tuple(None if part[0] is None else jnp.concatenate(part)
                 for part in zip(*parts))
    out = jnp.zeros_like(x)
    for a in range(0, steps, rows):
        block = x[a:a + rows]
        h = _stated(block + attention(rms_norm(block, gain1, eps), a, keys,
                                      w, kind, i, arch), state)
        y = _stated(h + ffn(rms_norm(h, gain2, eps), w, layer_no, arch,
                            held), state)
        out = _put_rows(out, y, a)
    return out


def hidden_states(weights, tokens, arch, held):
    x = _f32(jnp.take(weights['lm_emb'], jnp.asarray(tokens), axis=0))
    for layer_no in range(len(arch['layer_types'])):
        x = layer(x, weights, layer_no, arch, held)
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
