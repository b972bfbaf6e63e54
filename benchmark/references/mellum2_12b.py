"""Plain reference of ``mellum2_12b``: JetBrains Mellum2-12B-A2.5B-Instruct
(``model_type`` mellum), the language model's forward pass in float32
``jax.numpy``.

No pages, no cache, no batching, no kernel: the whole sequence at once,
every matrix product at the highest precision, a window as a mask over
the whole sequence's keys, the experts one after another in a plain
loop. Independent of ``paddle_tpu/ops``: it shares only the names and
layouts of the weights (``serving/decode/model.py``:
``gqa_param_shapes``).

One layer, for a row ``x`` of width ``hidden_size`` at position ``t``
(``RMS(v) g = v * rsqrt(mean(v^2) + eps) * g``):

    n = RMS(x) g1
    h = x + Attn(n)
    y = h + MoE(RMS(h) g2)

Attention (H query heads over K KV heads of width d, G = H / K):

    q_h = rot(n W_q)_h         k_j = rot(n W_k)_j        v_j = (n W_v)_j
    score_h(t, s) = q_h(t) . k_{h // G}(s) / sqrt(d)
    o_h = sum_s softmax_s(score_h(t, .)) v_{h // G}(s)
    Attn = concat_h(o_h) W_o

over ``s <= t`` in a ``full_attention`` layer and ``t - window < s <= t``
in a ``sliding_attention`` layer (the query's own position counts).
``rot`` turns the half-split pairs (i, i + d/2) of a head at position p
by ``p * g_i`` and multiplies cos and sin by the kind's
``attention_factor``. The sliding layers' table is the plain one,
``g_i = theta^(-2i/d)`` and factor 1; the full layers' is YaRN's
(``rope_parameters.full_attention``: factor F, original length L0,
beta_fast, beta_slow), as the transformers library computes it
(``truncate`` on):

    f_i  = theta^(-2i/d)                                 i = 0 .. d/2 - 1
    c(b) = d ln(L0 / (2 pi b)) / (2 ln theta)
    low  = max(floor(c(beta_fast)), 0)    high = min(ceil(c(beta_slow)), d - 1)
    r_i  = clip((i - low) / (high - low), 0, 1)
    g_i  = f_i (1 - r_i) + f_i / F r_i

MoE, in every layer: ``p = softmax(n W_r)`` over every published expert,
the ``top_k`` largest chosen, weights ``p_e / sum_chosen p``
(``norm_topk_prob``), summed over the chosen experts held here, each
``(silu(n Wg) * (n Wu)) Wd``; no shared expert.

``logits = RMS(y) g_f W_head^T`` with a head of its own; the embedding
is not scaled. Departures from the published description: none in the
equations; what config.json does not state (no per-head q/k norm, the
rotation's pairing, no multi-token-prediction head) is listed in
``configs/mellum2_12b.json``: ``assumed``.

``held = (first, count)`` says which routed experts the weights hold
(``references/command_a_plus.py``: the same convention).

``arch`` holds what the shapes do not say: ``n_head``, ``d_head``,
``layer_types``, ``window``, ``rope`` ({layer kind: the published
section}), ``top_k``, ``eps``, and switches that are on wherever the
system is held to this reference and that a control turns off to show
what a server that got it wrong would be caught by: ``state_dtype``
('float32': the precision of the residual stream, router scores,
softmax and logits), ``yarn`` (off: the full layers turn by the plain
table, factor 1) and ``windowed`` (off: a sliding layer sees every
position at or below its own).

Long sequences: a layer is computed in blocks of rows (the keys and
values of the whole sequence first, which are small: K KV heads), one
KV head's group of query heads at a time and one matrix or expert
upcast at a time, so that a sequence of 33k tokens fits beside the
served model on the chip.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 256
FULL, SLIDING = 'full_attention', 'sliding_attention'


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    return {'n_head': spec.n_head, 'd_head': spec.d_key,
            'layer_types': tuple(spec.layer_types),
            'window': spec.sliding_window,
            'rope': {kind: dict(section) for kind, section
                     in spec.rope_parameters.items()},
            'top_k': spec.experts_per_token, 'eps': spec.norm_eps,
            'yarn': True, 'windowed': True, 'state_dtype': 'float32'}


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


# ------------------------------------------------------------- positions
def yarn_range(width, theta, section):
    """(low, high) of the blend, by the closed form above."""
    original = float(section['original_max_position_embeddings'])

    def c(beta):
        return width * math.log(original / (2 * math.pi * beta)) \
            / (2 * math.log(theta))
    return (max(math.floor(c(float(section['beta_fast']))), 0),
            min(math.ceil(c(float(section['beta_slow']))), width - 1))


def pair_frequencies(width, section, yarn=True):
    """(g_i [width / 2] float64, what cos and sin are multiplied by) of
    one kind's ``rope_parameters`` section."""
    theta = float(section['rope_theta'])
    i = np.arange(width // 2, dtype=np.float64)
    plain = theta ** (-2 * i / width)
    if section.get('rope_type', 'default') != 'yarn' or not yarn:
        return plain, 1.0
    low, high = yarn_range(width, theta, section)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    factor = float(section['factor'])
    return (plain * (1 - ramp) + plain / factor * ramp,
            float(section.get('attention_factor',
                              0.1 * math.log(factor) + 1.0)))


@jax.jit
def rotate_halves(x, pos, freq, factor):
    """``x`` [T, heads, W] at positions ``pos`` [T]: pairs (i, i + W/2)
    turned by ``pos * freq[i]``, cos and sin times ``factor``: column i
    of the first half becomes ``a_i cos - b_i sin`` and of the second
    ``a_i sin + b_i cos``, written as ``x cos + swapped(x) sin`` with
    the halves swapped by a roll and the first one's sign turned (the
    halves joined by a concatenate of 64 columns abort the v5e's
    compiler at these shapes: ``IsFusibleUnalignedDUS``, PR 43)."""
    half = x.shape[-1] // 2
    angle = pos.astype(jnp.float32)[:, None] * jnp.tile(freq, 2)[None, :]
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    sign = jnp.where(jnp.arange(x.shape[-1]) < half, -1.0, 1.0)
    return x * cos + jnp.roll(x, half, axis=-1) * sign * sin


def _table(arch, kind):
    freq, factor = pair_frequencies(int(arch['d_head']), arch['rope'][kind],
                                    arch['yarn'])
    return jnp.asarray(freq, jnp.float32), jnp.float32(factor)


# ------------------------------------------------------------- attention
@functools.partial(jax.jit, static_argnums=(4, 5))
def _attend(q, keys, values, allowed, scale, state):
    """One block of rows against the keys it may see: ``q`` [B, K, G, d]
    (rotated), ``keys`` / ``values`` [S, K, d], ``allowed`` bool [B, S]
    -> [B, K, G, d]. KV head by KV head (lax.map): one head's [G, B, S]
    scores alive at a time."""
    def one(args):
        qk, kk, vk = args                       # [B, G, d], [S, d], [S, d]
        scores = jnp.einsum('bgd,sd->gbs', qk, kk, precision=HIGHEST) * scale
        scores = jnp.where(allowed[None], _stated(scores, state), -jnp.inf)
        return jnp.einsum('gbs,sd->bgd',
                          _stated(jax.nn.softmax(scores, -1), state), vk,
                          precision=HIGHEST)
    out = jax.lax.map(one, (jnp.swapaxes(q, 0, 1), jnp.swapaxes(keys, 0, 1),
                            jnp.swapaxes(values, 0, 1)))
    return jnp.swapaxes(out, 0, 1)


def sequence_keys(n, first, w, i, arch):
    """What every later row reads of the rows ``n`` [B, D] at positions
    ``first ..`` in layer ``i``: (keys [B, K, d] rotated, values
    [B, K, d])."""
    d = int(arch['d_head'])
    freq, factor = _table(arch, arch['layer_types'][i])
    rows = n.shape[0]
    k = _matmul(n, w['lm_stack_slf_k.w'][i]).reshape(rows, -1, d)
    v = _matmul(n, w['lm_stack_slf_v.w'][i]).reshape(rows, -1, d)
    return rotate_halves(k, first + jnp.arange(rows), freq, factor), v


def attention(n, first, keys, w, i, arch):
    """Rows ``n`` [B, D] at positions ``first ..`` against the
    sequence's ``keys`` (``sequence_keys``) -> [B, D]."""
    d, heads = int(arch['d_head']), int(arch['n_head'])
    kind = arch['layer_types'][i]
    freq, factor = _table(arch, kind)
    k, v = keys
    rows = n.shape[0]
    pos = first + jnp.arange(rows)
    # the query projection's matrix is stored [heads x d, hidden]
    q = rotate_halves(_matmul(n, jnp.transpose(
        w['lm_stack_slf_q.w'][i])).reshape(rows, heads, d), pos, freq,
        factor)
    cols = jnp.arange(k.shape[0])[None, :]
    allowed = cols <= pos[:, None]
    if kind == SLIDING and arch['windowed']:
        allowed &= cols > pos[:, None] - int(arch['window'])
    mixed = _attend(q.reshape(rows, k.shape[1], -1, d), k, v, allowed,
                    d ** -0.5, arch['state_dtype'])
    return _matmul(mixed.reshape(rows, -1), w['lm_stack_slf_o.w'][i])


# ------------------------------------------------------------------- MoE
@jax.jit
def expert(n, gate, up, down):
    hidden = jax.nn.silu(jnp.matmul(n, _f32(gate), precision=HIGHEST)) * \
        jnp.matmul(n, _f32(up), precision=HIGHEST)
    return jnp.matmul(hidden, _f32(down), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(2, 3))
def route(n, router, top_k, state='float32'):
    """(chosen experts [T, k], their weights [T, k]): the softmax over
    every published expert, the ``top_k`` largest, normalised over the
    chosen, wherever they live."""
    scores = _stated(jax.nn.softmax(_stated(jnp.matmul(
        n, _f32(router), precision=HIGHEST), state), -1), state)
    top, chosen = jax.lax.top_k(scores, top_k)
    return chosen, _stated(top / jnp.sum(top, axis=-1, keepdims=True), state)


@functools.partial(jax.jit, static_argnums=(6,))
def _routed_sum(n, chosen, weight, gates, ups, downs, first, layer):
    """The sum over the experts held, one after another in a plain loop
    (inside one program, so that a block of rows costs one dispatch and
    not one an expert): expert ``e`` of the ``count`` held is published
    expert ``first + e``, and a row's share of it is its weight where
    it chose it, else 0. One expert's matrices are upcast at a time."""
    def one(e, out):
        share = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
        return out + share[:, None] * expert(
            n, gates[layer, e], ups[layer, e], downs[layer, e])
    return jax.lax.fori_loop(0, gates.shape[1], one, jnp.zeros_like(n))


def experts(n, w, i, arch, held):
    """Layer ``i``'s routed sum over the experts held."""
    first, count = held
    chosen, weight = route(n, w['lm_stack_router.w'][i], int(arch['top_k']),
                           arch['state_dtype'])
    assert count == w['lm_stack_exp_gate.w'].shape[1]
    return _routed_sum(n, chosen, weight, w['lm_stack_exp_gate.w'],
                       w['lm_stack_exp_up.w'], w['lm_stack_exp_down.w'],
                       int(first), jnp.int32(i))


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
    # keys and values of the whole sequence first: block by block (a
    # row's do not depend on the others'), joined, which is small
    parts = [sequence_keys(rms_norm(x[a:a + rows], gain1, eps), a, w, i,
                           arch) for a in range(0, steps, rows)]
    keys = tuple(jnp.concatenate(part) for part in zip(*parts))
    out = jnp.zeros_like(x)
    for a in range(0, steps, rows):
        block = x[a:a + rows]
        h = _stated(block + attention(rms_norm(block, gain1, eps), a, keys,
                                      w, i, arch), state)
        y = _stated(h + experts(rms_norm(h, gain2, eps), w, i, arch, held),
                    state)
        out = _put_rows(out, y, a)
    return out


def hidden_states(weights, tokens, arch, held):
    x = _f32(jnp.take(weights['lm_emb'], jnp.asarray(tokens), axis=0))
    for i in range(len(arch['layer_types'])):
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
