"""Plain reference of ``kimi_k2_6``: moonshotai Kimi-K2.6 (``model_type``
kimi_k2), the language model's forward pass in float32 ``jax.numpy``.

No pages, no cache, no batching, no kernel and no absorbed form: the
whole sequence at once, every matrix product at the highest precision,
keys and values expanded from the latents head by head, every position
at or below a row's own attended, the experts one after another in a
plain loop. Independent of ``paddle_tpu/ops``: it shares only the names
and layouts of the weights (``serving/decode/model.py``:
``latent_param_shapes``).

One layer, for a row ``x`` of width ``hidden_size`` at position ``t``
(``RMS(v) g = v * rsqrt(mean(v^2) + eps) * g``):

    n = RMS(x) g1
    h = x + Attn(n)
    y = h + FFN(RMS(h) g2)

Attention (H heads, ranks r_q and r, head widths nope, rope, v):

    c_q = RMS(n W_qa) g_q              [q_nope ; q_rope]_h = c_q W_qb
    [c ; k_r] = n W_kva                c_kv = RMS(c) g_kv
    k_rope = rot(k_r)                  one for all heads
    k_nope_h = c_kv W_bk[h]^T          v_h = c_kv W_bv[h]
    score_h(t, s) = (q_nope_h . k_nope_h(s) + rot(q_rope_h) . k_rope(s))
                    * m^2 / sqrt(nope + rope)        for every s <= t
    o_h = sum_s softmax_s(score_h(t, .)) v_h(s)
    Attn = concat_h(o_h) W_o

with no gate on the output and no rescale of the latents: the published
config has neither key.

``rot`` turns interleaved pairs (2i, 2i+1) at position p by ``p * g_i``.
YaRN (``rope_scaling``: factor F, original length L0, beta_fast,
beta_slow, mscale, mscale_all_dim; d = rope, theta), as DeepSeek-V3's
reference code computes it, whose key names the config carries:

    f_i  = theta^(-2i/d)                                 i = 0 .. d/2 - 1
    c(b) = d ln(L0 / (2 pi b)) / (2 ln theta)
    low  = max(floor(c(beta_fast)), 0)    high = min(ceil(c(beta_slow)), d - 1)
    r_i  = clip((i - low) / (high - low), 0, 1)
    g_i  = f_i (1 - r_i) + f_i / F r_i
    m    = 0.1 mscale_all_dim ln F + 1

(the fast pairs keep their frequency, the slow ones are stretched F
times); cos and sin are multiplied by
``(0.1 mscale ln F + 1) / (0.1 mscale_all_dim ln F + 1)``, which is 1
where the two are equal. Departures from the published description:
none in the equations; the pairing of the rotated columns is assumed
interleaved (the config does not say; ``configs/kimi_k2_6.json``:
``assumed.rotary``).

FFN: layers below ``dense_layers`` ``(silu(n Wg) * (n Wu)) Wd``; the
others ``s = sigmoid(n W_r)`` over every published expert, the ``top_k``
largest of ``s + b`` chosen (``b`` for the choosing only; one group),
weights ``s_e / sum_chosen s * routed_scale``, summed over the chosen
experts held here, plus every shared expert at weight 1.

``logits = RMS(y) g_f W_head^T`` with a head of its own; the embedding
is not scaled.

``held = (first, count)`` says which routed experts the weights hold
(``references/command_a_plus.py``: the same convention).

``arch`` holds what the shapes do not say: the attention's
``LatentShape`` as a dict under ``latent`` (with its ``rope_scaling``),
``n_layer``, ``dense_layers``, ``top_k``, ``routed_scale``, ``eps``, and
switches that are on wherever the system is held to this reference and
that a control turns off to show what a server that got it wrong would
be caught by: ``state_dtype`` ('float32': the precision of the residual
stream, router scores, softmax and logits), ``yarn`` (off: g_i = f_i),
``softmax_mscale`` (off: m = 1), ``scale_routed`` (off: routed_scale 1)
and ``offset_from`` (None; set, the rows from that position on are
rotated as if they were counted from 0 again: a hit's suffix prefilled
at the wrong positions).

Long sequences: a layer is computed in blocks of rows (the keys of the
whole sequence first, which are small), one head's keys and values
expanded at a time and one matrix upcast at a time, so that a sequence
of 33k tokens fits beside the served model on the chip: the residual
stream twice (in and out of a layer) and a block's scores.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 1024


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    (shape,) = spec.latent.values()
    return {'latent': dict(vars(shape)), 'n_layer': spec.n_layer,
            'dense_layers': spec.dense_layers,
            'top_k': spec.experts_per_token,
            'routed_scale': spec.routed_scale, 'eps': spec.norm_eps,
            'yarn': True, 'softmax_mscale': True, 'scale_routed': True,
            'offset_from': None, 'state_dtype': 'float32'}


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


# ---------------------------------------------------------------- YaRN
def yarn_range(width, theta, scaling):
    """(low, high) of the blend, by the closed form above."""
    original = float(scaling['original_max_position_embeddings'])

    def c(beta):
        return width * math.log(original / (2 * math.pi * beta)) \
            / (2 * math.log(theta))
    return (max(math.floor(c(float(scaling['beta_fast']))), 0),
            min(math.ceil(c(float(scaling['beta_slow']))), width - 1))


def pair_frequencies(width, theta, scaling):
    """g_i [width / 2] float64; f_i where ``scaling`` is None."""
    i = np.arange(width // 2, dtype=np.float64)
    plain = theta ** (-2 * i / width)
    if not scaling:
        return plain
    low, high = yarn_range(width, theta, scaling)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return plain * (1 - ramp) + plain / float(scaling['factor']) * ramp


def mscale(factor, weight):
    return 0.1 * weight * math.log(factor) + 1.0 if factor > 1 else 1.0


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


def _rope(arch):
    """(pair frequencies float32, what cos and sin are multiplied by,
    m^2) of the attention."""
    shape = arch['latent']
    scaling = shape.get('rope_scaling') if arch['yarn'] else None
    freq = jnp.asarray(pair_frequencies(
        shape['d_rope'], float(shape['rope_theta']), scaling), jnp.float32)
    given = shape.get('rope_scaling')
    if not given:
        return freq, 1.0, 1.0
    factor = float(given['factor'])
    m = mscale(factor, float(given['mscale_all_dim']))
    on_cos = mscale(factor, float(given['mscale'])) / m if scaling else 1.0
    return freq, on_cos, m * m if arch['softmax_mscale'] else 1.0


def _positions(first, rows, arch):
    pos = first + jnp.arange(rows)
    offset = arch['offset_from']
    return pos if offset is None else jnp.where(pos >= offset,
                                                pos - offset, pos)


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
    ``first ..`` in layer ``i``: (c_kv [B, r], k_rope [B, rope])."""
    rank = arch['latent']['kv_rank']
    freq, on_cos, _ = _rope(arch)
    down = _matmul(n, w['lm_full_kv_a.w'][i])
    c_kv = rms_norm(down[:, :rank], w['lm_full_kv_ln.w'][i],
                    float(arch['eps']))
    return c_kv, rotate_interleaved(
        down[:, rank:], _positions(first, n.shape[0], arch), freq) * on_cos


def attention(n, first, keys, w, i, arch):
    """Rows ``n`` [B, D] at positions ``first ..`` against the
    sequence's ``keys`` (``sequence_keys``) -> [B, D]."""
    shape = arch['latent']
    heads, d_nope, d_rope = shape['n_head'], shape['d_nope'], shape['d_rope']
    freq, on_cos, m2 = _rope(arch)
    c_kv, k_rope = keys
    rows = n.shape[0]
    c_q = rms_norm(_matmul(n, w['lm_full_q_a.w'][i]),
                   w['lm_full_q_ln.w'][i], float(arch['eps']))
    q = _matmul(c_q, w['lm_full_q_b.w'][i]).reshape(rows, heads, -1)
    q_rope = rotate_interleaved(q[..., d_nope:],
                                _positions(first, rows, arch), freq) * on_cos
    allowed = jnp.arange(c_kv.shape[0])[None, :] <= \
        (first + jnp.arange(rows))[:, None]
    mixed = _attend(q[..., :d_nope], q_rope, c_kv, k_rope,
                    w['lm_full_kv_bk.w'][i], w['lm_full_kv_bv.w'][i],
                    allowed, (d_nope + d_rope) ** -0.5 * m2,
                    arch['state_dtype'])
    return _matmul(mixed.reshape(rows, -1), w['lm_full_o.w'][i])


# ------------------------------------------------------------------- FFN
@jax.jit
def expert(n, gate, up, down):
    hidden = jax.nn.silu(jnp.matmul(n, _f32(gate), precision=HIGHEST)) * \
        jnp.matmul(n, _f32(up), precision=HIGHEST)
    return jnp.matmul(hidden, _f32(down), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def route(n, router, bias, top_k, scale, state='float32'):
    """(chosen experts [T, k], their weights [T, k]): sigmoid scores
    over every published expert, the ``top_k`` largest of score + bias,
    the chosen ones' own scores normalised over all that were chosen,
    wherever they live, times ``scale``."""
    scores = _stated(jax.nn.sigmoid(_stated(jnp.matmul(
        n, _f32(router), precision=HIGHEST), state)), state)
    _, chosen = jax.lax.top_k(scores + _f32(bias), top_k)
    top = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen, _stated(
        top / jnp.sum(top, axis=-1, keepdims=True) * scale, state)


def experts(n, w, i, arch, held):
    """Routed layer ``i`` (of the routed ones): the scaled sum over the
    experts held, plus every shared expert at weight 1."""
    first, count = held
    scale = float(arch['routed_scale']) if arch['scale_routed'] else 1.0
    chosen, weight = route(n, w['lm_moe_router.w'][i],
                           w['lm_moe_router.b'][i], int(arch['top_k']),
                           scale, arch['state_dtype'])
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


def layer(x, w, i, arch, held):
    """``x`` [T, D] -> [T, D], in blocks of rows."""
    eps, state = float(arch['eps']), arch['state_dtype']
    steps = x.shape[0]
    rows = math.gcd(steps, BLOCK_ROWS)
    gain1, gain2 = w['lm_stack_ln1.w'][i], w['lm_stack_ln2.w'][i]
    # keys of the whole sequence first: block by block (a row's do not
    # depend on the others'), joined, which is small
    parts = [sequence_keys(rms_norm(x[a:a + rows], gain1, eps), a, w, i,
                           arch) for a in range(0, steps, rows)]
    keys = tuple(jnp.concatenate(part) for part in zip(*parts))
    out = jnp.zeros_like(x)
    for a in range(0, steps, rows):
        block = x[a:a + rows]
        h = _stated(block + attention(rms_norm(block, gain1, eps), a, keys,
                                      w, i, arch), state)
        y = _stated(h + ffn(rms_norm(h, gain2, eps), w, i, arch, held),
                    state)
        out = _put_rows(out, y, a)
    return out


def hidden_states(weights, tokens, arch, held):
    x = _f32(jnp.take(weights['lm_emb'], jnp.asarray(tokens), axis=0))
    for i in range(int(arch['n_layer'])):
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
