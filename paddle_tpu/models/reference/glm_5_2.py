"""Plain reference of ``glm_5_2``: zai-org GLM-5.2 (``model_type``
glm_moe_dsa), the language model's forward pass in float32
``jax.numpy``.

No pages, no cache, no batching, no kernel and no absorbed form: the
whole sequence at once, every matrix product at the highest precision,
keys and values expanded from the latents head by head, the indexer's
scores dense and its choice ``lax.top_k``, the experts one after
another in a plain loop. Independent of ``paddle_tpu/ops``: it shares
only the names and layouts of the weights (``serving/decode/model.py``:
``latent_param_shapes``).

One layer, for a row ``x`` of width ``hidden_size`` at position ``t``
(``RMS(v) g = v * rsqrt(mean(v^2) + eps) * g``), as DeepSeek-V3's
reference code, whose key names the config carries:

    n = RMS(x) g1
    h = x + Attn(n)
    y = h + FFN(RMS(h) g2)

Latent attention, one shape in every layer (H heads, ranks r_q and r,
head widths nope, rope, v; theta):

    c_q = RMS(n W_qa) g_q              [q_nope ; q_rope]_h = c_q W_qb
    [c ; k_r] = n W_kva                c_kv = RMS(c) g_kv
    k_rope = RoPE(k_r)                 one for all heads
    k_nope_h = c_kv W_bk[h]^T          v_h = c_kv W_bv[h]
    score_h(t, s) = (q_nope_h . k_nope_h(s) + RoPE(q_rope_h) . k_rope(s))
                    / sqrt(nope + rope)         for s in S_l(t)
    o_h = sum_s softmax_s(score_h(t, .)) v_h(s)
    Attn = concat_h(o_h) W_o

RoPE is over interleaved pairs (2i, 2i+1) by t * theta^(-2i/rope), no
scaling. No gate a head and no rescale of the latents (both are
dots3_note's; GLM-5.2's config names neither).

**The selection, with IndexShare.** ``arch['indexer_types']`` gives each
layer as 'full' or 'shared' (the config's own list). A 'full' layer
scores:

    I(t, s) = sum_j w_j ReLU(q^I_j . k^I(s))
    q^I = c_q W^I_q   (index_n_heads x index_head_dim)
    k^I = LayerNorm(n W^I_k) (gain, bias)
    w   = n W^I_w / sqrt(index_n_heads * index_head_dim)

with the first ``rope`` columns of every q^I_j and of k^I rotated in
**interleaved** pairs (``indexer_rope_interleave`` true), and S_l(t) =
the ``index_topk`` positions s <= t of largest I (all of them while
t < index_topk). A 'shared' layer has no W^I and S_l(t) = S_f(t), ``f``
the nearest 'full' layer below ``l``: the same index set, handed on as
it was made. The indexer's weights are stacked over the 'full' layers
alone, in order.

Departures from the source, each stated in ``configs/glm_5_2.json``:
the multi-token-prediction module is outside (``assumed.scope``); index
scores take the stated precision's operands, not FP8
(``assumed.indexer``).

FFN: layers below ``dense_layers`` ``(silu(n Wg) * (n Wu)) Wd``; the
others ``s = sigmoid(n W_r)`` over every published expert, the ``top_k``
largest of ``s + b`` chosen (``b`` for the choosing only; one group),
weights ``routed_scale * s_e / sum_chosen s``, summed over the chosen
experts held here, plus every shared expert at weight 1.

``logits = RMS(y) g_f W_head^T`` with a head of its own; the embedding
is not scaled.

``held = (first, count)`` says which routed experts the weights hold
(``models/reference/command_a_plus.py``: the same convention).

``arch`` holds what the shapes do not say: ``indexer_types``, the one
``LatentShape`` as a dict under ``latent``, ``dense_layers``,
``index_n_heads``, ``index_topk``, ``top_k``, ``routed_scale``, ``eps``,
and three switches that are as stated wherever the system is held to
this reference and that a control changes to show what a server that
got it wrong would be caught by: ``state_dtype`` ('float32': the
precision of the residual stream, router and index scores, softmax and
logits), ``select`` (the indexer's choice; off: every layer sees all
s <= t) and ``carry`` (True: a 'shared' layer attends over the selection
handed to it; False: it scores for itself with the weights of the
nearest 'full' layer below it, what a program that dropped the carry
and kept one indexer a period would compute).

Long sequences: a layer is computed in blocks of rows (keys and index
keys of the whole sequence first, which are small), one head's keys and
values expanded at a time and one matrix upcast at a time; a scoring
layer's selection of the whole sequence is kept as bits (bool [T, T]
packed eight columns a byte: 152 MB at 34,816 positions) for the layers
that share it, so that a sequence of 34,816 tokens fits beside the
served model on the chip.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FULL = 'full_attention'
BLOCK_ROWS = 1024


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    return {'indexer_types': list(spec.indexer_types),
            'latent': dict(vars(spec.latent[FULL])),
            'dense_layers': spec.dense_layers,
            'index_n_heads': spec.index_n_heads,
            'index_topk': spec.index_topk,
            'top_k': spec.experts_per_token,
            'routed_scale': spec.routed_scale, 'eps': spec.norm_eps,
            'select': True, 'carry': True, 'state_dtype': 'float32'}


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


@functools.partial(jax.jit, static_argnums=2)
def rotate_interleaved(x, pos, theta):
    """``x`` [T, ..., W] at positions ``pos`` [T]: pairs (2i, 2i+1)
    turned by ``pos * theta^(-2i/W)``."""
    width = x.shape[-1]
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def _rotated_first(x, pos, width, theta):
    return jnp.concatenate([rotate_interleaved(x[..., :width], pos, theta),
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


def scoring_place(layer_no, arch):
    """(whether layer ``layer_no`` scores for itself under the arch's
    switches, its indexer's place in the indexer's stacks): a 'full'
    layer its own; a 'shared' layer none, or with ``carry`` off the
    nearest 'full' layer's below it."""
    types = arch['indexer_types']
    own = types[layer_no] == 'full'
    below = types[:layer_no + 1].count('full') - 1
    return own or not arch['carry'], below


def sequence_keys(n, first, w, layer_no, arch):
    """What every later row reads of the rows ``n`` [B, D] at positions
    ``first ..`` in layer ``layer_no``: (c_kv [B, r], k_rope [B, rope],
    index keys [B, Di] or None where the layer does not score)."""
    shape, eps = arch['latent'], float(arch['eps'])
    rank, theta = shape['kv_rank'], float(shape['rope_theta'])
    pos = first + jnp.arange(n.shape[0])
    down = _matmul(n, w['lm_full_kv_a.w'][layer_no])
    c_kv = rms_norm(down[:, :rank], w['lm_full_kv_ln.w'][layer_no], eps)
    k_rope = rotate_interleaved(down[:, rank:], pos, theta)
    index = None
    scores, j = scoring_place(layer_no, arch)
    if scores and arch['select']:
        index = layer_norm(_matmul(n, w['lm_full_idx_k.w'][j]),
                           w['lm_full_idx_k_ln.w'][j],
                           w['lm_full_idx_k_ln.b'][j], eps)
        index = _rotated_first(index, pos, shape['d_rope'], theta)
    return c_kv, k_rope, index


@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(into, rows, first):
    return jax.lax.dynamic_update_slice(into, rows, (first, 0))


def attention(n, first, keys, w, layer_no, arch, selection):
    """Rows ``n`` [B, D] at positions ``first ..`` against the
    sequence's ``keys`` (``sequence_keys``) -> ([B, D], the rows'
    selection bool [B, S] or None). ``selection``: what the rows were
    handed (a 'shared' layer's; None where nothing selects)."""
    shape = arch['latent']
    eps, state = float(arch['eps']), arch['state_dtype']
    heads, d_nope, d_rope = shape['n_head'], shape['d_nope'], shape['d_rope']
    theta = float(shape['rope_theta'])
    c_kv, k_rope, index = keys
    rows = n.shape[0]
    pos = first + jnp.arange(rows)
    c_q = rms_norm(_matmul(n, w['lm_full_q_a.w'][layer_no]),
                   w['lm_full_q_ln.w'][layer_no], eps)
    q = _matmul(c_q, w['lm_full_q_b.w'][layer_no]).reshape(rows, heads, -1)
    q_nope = q[..., :d_nope]
    q_rope = rotate_interleaved(q[..., d_nope:], pos, theta)

    allowed = jnp.arange(c_kv.shape[0])[None, :] <= pos[:, None]
    if index is not None:
        j = scoring_place(layer_no, arch)[1]
        n_index = int(arch['index_n_heads'])
        q_i = _matmul(c_q, w['lm_full_idx_q.w'][j]).reshape(rows, n_index, -1)
        q_i = _rotated_first(q_i, pos, d_rope, theta)
        w_i = _matmul(n, w['lm_full_idx_w.w'][j]) * \
            (n_index * q_i.shape[-1]) ** -0.5
        selection = chosen_columns(
            index_scores(q_i, w_i, index, first, state),
            int(arch['index_topk']))
    if selection is not None:
        allowed &= selection
    mixed = _attend(q_nope, q_rope, c_kv, k_rope,
                    w['lm_full_kv_bk.w'][layer_no],
                    w['lm_full_kv_bv.w'][layer_no],
                    allowed, (d_nope + d_rope) ** -0.5, state)
    return _matmul(mixed.reshape(rows, -1), w['lm_full_o.w'][layer_no]), \
        selection


# ------------------------------------------------------------------- FFN
@jax.jit
def expert(n, gate, up, down):
    hidden = jax.nn.silu(jnp.matmul(n, _f32(gate), precision=HIGHEST)) * \
        jnp.matmul(n, _f32(up), precision=HIGHEST)
    return jnp.matmul(hidden, _f32(down), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def route(n, router, bias, top_k, scale=1.0, state='float32'):
    """(chosen experts [T, k], their weights [T, k]): sigmoid scores
    over every published expert, the ``top_k`` largest of score + bias,
    the chosen ones' own scores normalised over all that were chosen,
    wherever they live, times ``scale``."""
    scores = _stated(jax.nn.sigmoid(_stated(jnp.matmul(
        n, _f32(router), precision=HIGHEST), state)), state)
    _, chosen = jax.lax.top_k(scores + _f32(bias), top_k)
    top = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen, _stated(
        scale * top / jnp.sum(top, axis=-1, keepdims=True), state)


def experts(n, w, i, arch, held):
    """Routed layer ``i`` (of the routed ones): the sum over the experts
    held, plus every shared expert at weight 1."""
    first, count = held
    chosen, weight = route(n, w['lm_moe_router.w'][i],
                           w['lm_moe_router.b'][i], int(arch['top_k']),
                           float(arch['routed_scale']), arch['state_dtype'])
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
def layer(x, w, layer_no, arch, held, selection):
    """``x`` [T, D] -> ([T, D], the selection in force after the layer:
    bool [T, T] packed eight columns a byte, or None), in blocks of
    rows. ``selection`` is what the layers below handed on."""
    eps, state = float(arch['eps']), arch['state_dtype']
    steps = x.shape[0]
    rows = math.gcd(steps, BLOCK_ROWS)
    gain1, gain2 = w['lm_stack_ln1.w'][layer_no], w['lm_stack_ln2.w'][layer_no]
    # keys of the whole sequence first: block by block (a row's do not
    # depend on the others'), joined, which is small
    parts = [sequence_keys(rms_norm(x[a:a + rows], gain1, eps), a, w,
                           layer_no, arch) for a in range(0, steps, rows)]
    keys = tuple(None if part[0] is None else jnp.concatenate(part)
                 for part in zip(*parts))
    scores = keys[2] is not None
    made = jnp.zeros((steps, -(-steps // 8)), jnp.uint8) if scores else None
    out = jnp.zeros_like(x)
    for a in range(0, steps, rows):
        block = x[a:a + rows]
        given = None if scores or selection is None else jnp.unpackbits(
            selection[a:a + rows], axis=1, count=steps).astype(bool)
        attn, chosen = attention(rms_norm(block, gain1, eps), a, keys, w,
                                 layer_no, arch, given)
        if scores:
            made = _put_rows(made, jnp.packbits(chosen, axis=1), a)
        h = _stated(block + attn, state)
        y = _stated(h + ffn(rms_norm(h, gain2, eps), w, layer_no, arch,
                            held), state)
        out = _put_rows(out, y, a)
    return out, made if scores else selection


def hidden_states(weights, tokens, arch, held):
    x = _f32(jnp.take(weights['lm_emb'], jnp.asarray(tokens), axis=0))
    selection = None
    for layer_no in range(len(arch['indexer_types'])):
        if scoring_place(layer_no, arch)[0]:
            selection = None            # the layer makes its own
        x, selection = layer(x, weights, layer_no, arch, held, selection)
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
