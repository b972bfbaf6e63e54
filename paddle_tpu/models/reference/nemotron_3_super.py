"""Plain reference of ``nemotron_3_super``: nvidia
NVIDIA-Nemotron-3-Super-120B-A12B (``model_type`` nemotron_h), the
language model's forward pass in float32 ``jax.numpy``.

No pages, no cache, no slots, no batching, no kernel, no chunked scan,
no tile list: the whole sequence at once, every matrix product at the
highest precision, causal attention as a mask over the whole sequence's
keys, the state-space recurrence as a plain ``lax.scan`` over tokens,
the experts in a plain loop over the ones held. Independent of
``paddle_tpu/ops``: it shares only the names and layouts of the weights
(``serving/decode/model.py``: ``ssm_param_shapes``).

``x0 = E[token]``. A layer is **one sublayer** (``RMS(v) g = v *
rsqrt(mean(v^2) + eps) * g``), its kind read off
``hybrid_override_pattern`` (``M`` mamba, ``*`` attention, ``E`` moe)::

    y = x + Mixer_kind(RMS(x) g)

``logits = RMS(x_L) g_f W_head^T`` (the head is a matrix of its own).

``mamba`` (Mamba-2: H heads of width P in G groups of H / G, state N, a
depthwise causal convolution of K taps with a bias)::

    [z (H P); u (H P + 2 G N); dt (H)] = n W_inproj
    c_t = silu(sum_{j < K} w_conv[j] * u_{t - K + 1 + j} + b_conv)    (u before the start = 0)
    [x (H x P); B (G x N); C (G x N)] = c_t
    D_t = softplus(dt_t + dt_bias)           A = -exp(A_log)          (a head each; no clipping)
    S_t = exp(D_t A) S_{t-1} + D_t * (x_t outer B_t,g)                (S_{-1} = 0; head h: P x N, g = h // (H / G))
    y_t = S_t C_t,g + Dskip * x_t
    Mixer = RMS_g(y_t * silu(z_t)) g W_outproj                         (gate, then a norm over each group's H P / G channels)

``attention`` (H query heads over K KV heads of width d; no bias; **no
position**: the nemotron_h model code rotates nothing in its attention
layers, the state-space layers carry the order)::

    score_h(t, s) = q_h(t) . k_{h // (H / K)}(s) * d^-1/2            s <= t
    Attn = concat_h(sum_s softmax_s(score_h(t, .)) v(s)) W_o

``moe`` (LatentMoE: the router on the hidden width, the experts inside a
latent of width L)::

    s = sigmoid(n W_r)                                  (float32; every published expert)
    chosen = the top_k largest of s + bias              (ties to the lower index)
    w_e = s_e / sum_{chosen} s * routed_scale
    u = n W_in                                          (hidden -> L)
    r = sum_{e chosen and held} w_e relu(u W1_e)^2 W2_e (L -> f -> L, no gate matrix)
    MoE = r W_out + relu(n V1)^2 V2                     (L -> hidden; the shared expert at weight 1)

``held = (first, count)``: the experts ``first .. first + count - 1``
are computed and the others' part of ``r`` is left out, as the one chip
of an expert-parallel group leaves it out; ``shared=False`` leaves the
shared expert out too (the shares-add-up test counts it once).

``arch`` holds what the shapes do not say and switches that are on
wherever the system is held to this reference and that a control turns
off or down: ``state_dtype`` ('float32': the precision of the residual
stream, the softmax, the logits, the router's scores **and of the
recurrent state, rounded after every token**), ``d_skip``, ``dt_bias``,
``gate``, ``group_norm`` (False: one norm over the whole inner width)
and ``shared``.

Long sequences: a layer is computed in blocks of rows where rows are
independent, a Mamba-2 layer in blocks of tokens in order with its state
carried between them, and one matrix or one expert is upcast at a time,
so that a sequence of 18k tokens fits in the 2.7 GB the chip has left
beside the served model (whole, the Mamba-2 projection of 18k rows
alone is 1.3 GB and the run of seed 5800000102 did not load it).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 256
# tokens a block of a Mamba-2 layer, its state carried to the next
TIME_BLOCK = 2048
MAMBA, ATTENTION, MOE = 'mamba', 'attention', 'moe'


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    return {'n_head': spec.n_head, 'd_head': spec.d_key,
            'layer_types': tuple(spec.layer_types),
            'ssm_heads': spec.ssm_heads, 'ssm_state': spec.ssm_state,
            'ssm_groups': spec.ssm_groups, 'eps': spec.norm_eps,
            'attn_scale': spec.attn_scale,
            'top_k': spec.experts_per_token,
            'routed_scale': spec.routed_scale,
            'state_dtype': 'float32', 'd_skip': True, 'dt_bias': True, 'gate': True,
            'group_norm': True, 'shared': True}


def held_of(spec):
    """(first, count) of the experts this share computes."""
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
def _matmul_t(x, w):
    """``x w^T``: a matrix kept as its transpose, where it lies."""
    return jnp.einsum('td,vd->tv', x, _f32(w), precision=HIGHEST)


def _by_rows(fn, x):
    """``fn`` over blocks of ``BLOCK_ROWS`` rows of ``x`` and what is
    left over (rows are independent)."""
    return jnp.concatenate([fn(x[a:a + BLOCK_ROWS])
                            for a in range(0, x.shape[0], BLOCK_ROWS)])


# ------------------------------------------------------------ attention
@functools.partial(jax.jit, static_argnums=(5, 6))
def _attend(q, k, v, first, scale, n_head, state):
    """A block of queries q [R, H d] at positions ``first`` .. over the
    whole sequence's k and v [T, K d]: causal, no position; query head
    h reads KV head h // (H / K)."""
    rows, steps = q.shape[0], k.shape[0]
    d = q.shape[1] // n_head
    k = k.reshape(steps, -1, d)
    v = v.reshape(steps, -1, d)
    q = q.reshape(rows, k.shape[1], -1, d)          # [R, K, H / K, d]
    scores = _stated(jnp.einsum('tkgd,skd->kgts', q, k, precision=HIGHEST)
                     * scale, state)
    seen = jnp.arange(steps)[None, :] <= first + jnp.arange(rows)[:, None]
    weights = _stated(jax.nn.softmax(
        jnp.where(seen[None, None], scores, -jnp.inf), axis=-1), state)
    return jnp.einsum('kgts,skd->tkgd', weights, v,
                      precision=HIGHEST).reshape(rows, -1)


def attention(n, w, i, arch):
    """Attention layer ``i`` (of the attention layers) over the whole
    sequence ``n`` [T, D]: the keys and values of the whole sequence
    first (small: K KV heads), then the queries in blocks of rows."""
    k = _by_rows(lambda rows: _matmul(rows, w['lm_attn_k.w'][i]), n)
    v = _by_rows(lambda rows: _matmul(rows, w['lm_attn_v.w'][i]), n)
    out = jnp.concatenate([
        _attend(_matmul(n[a:a + BLOCK_ROWS], w['lm_attn_q.w'][i]), k, v,
                jnp.int32(a), float(arch['attn_scale']),
                int(arch['n_head']), arch['state_dtype'])
        for a in range(0, n.shape[0], BLOCK_ROWS)])
    return _by_rows(lambda rows: _matmul(rows, w['lm_attn_o.w'][i]), out)


# ---------------------------------------------------------- state space
@functools.partial(jax.jit, static_argnums=(4,))
def convolved(u, before, taps, bias, n_taps):
    """u [T, C] behind the K - 1 inputs ``before`` it (zeros at a
    sequence's start) -> silu of the depthwise causal convolution:
    output t reads inputs t - K + 1 .. t."""
    steps = u.shape[0]
    padded = jnp.concatenate([before, u])
    out = sum(padded[j:j + steps] * _f32(taps)[j][None, :]
              for j in range(n_taps))
    return jax.nn.silu(out + _f32(bias)[None, :])


@functools.partial(jax.jit, static_argnums=(5,))
def recurrence(x, b, c, dt, a, state, first=None):
    """The selective-state recurrence, token by token, from the state
    ``first`` [H, P, N] (None: zeros): x [T, H, P], b and c [T, G, N]
    (head h reads group h // (H / G)), dt [T, H] (after softplus), a [H]
    (negative) -> (y [T, H, P] without the skip term, the state it ends
    in)."""
    per_group = x.shape[1] // b.shape[1]

    def one(s, row):
        xt, bt, ct, dtt = row
        bt, ct = (jnp.repeat(v, per_group, axis=0) for v in (bt, ct))
        s = jnp.exp(dtt * a)[:, None, None] * s + \
            (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        s = _stated(s, state)
        return s, jnp.sum(s * ct[:, None, :], axis=-1)
    if first is None:
        first = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), jnp.float32)
    last, y = jax.lax.scan(one, first, (x, b, c, dt))
    return y, last


def mamba(n, w, i, arch):
    """Mamba-2 layer ``i`` (of the mamba layers) over the whole
    sequence ``n`` [T, D], ``TIME_BLOCK`` tokens at a time in order, the
    state and the convolution's last K - 1 inputs carried from one block
    of tokens to the next (the same recurrence; it bounds what is alive
    at once: at 18k tokens the projection alone is 1.3 GB)."""
    heads, n_state = int(arch['ssm_heads']), int(arch['ssm_state'])
    groups = int(arch['ssm_groups'])
    inner = w['lm_mamba_out.w'].shape[1]
    taps = w['lm_mamba_conv.w'][i]
    gain = _f32(w['lm_mamba_norm.w'][i])
    neg = -jnp.exp(_f32(w['lm_mamba_a_log'][i]))
    carried = None
    before = jnp.zeros((taps.shape[0] - 1, taps.shape[1]), jnp.float32)
    out = []
    for at in range(0, n.shape[0], TIME_BLOCK):
        proj = _matmul(n[at:at + TIME_BLOCK], w['lm_mamba_in.w'][i])
        steps = proj.shape[0]
        z, u, dt = (proj[:, :inner], proj[:, inner:-heads],
                    proj[:, -heads:])
        conv = convolved(u, before, taps, w['lm_mamba_conv.b'][i],
                         taps.shape[0])
        before = jnp.concatenate([before, u])[-before.shape[0]:]
        x = conv[:, :inner].reshape(steps, heads, -1)
        b = conv[:, inner:inner + groups * n_state].reshape(
            steps, groups, -1)
        c = conv[:, inner + groups * n_state:].reshape(steps, groups, -1)
        if arch['dt_bias']:
            dt = dt + _f32(w['lm_mamba_dt.b'][i])[None, :]
        y, carried = recurrence(x, b, c, jax.nn.softplus(dt), neg,
                                arch['state_dtype'], carried)
        if arch['d_skip']:
            y = y + _f32(w['lm_mamba_d'][i])[None, :, None] * x
        y = y.reshape(steps, -1)
        if arch['gate']:
            y = y * jax.nn.silu(z)
        if arch['group_norm']:
            # a norm over each group's channels, the gain's own slice
            y = rms_norm(y.reshape(steps, groups, -1),
                         gain.reshape(groups, -1),
                         float(arch['eps'])).reshape(steps, -1)
        else:
            y = rms_norm(y, gain, float(arch['eps']))
        out.append(_matmul(y, w['lm_mamba_out.w'][i]))
    return jnp.concatenate(out)


# -------------------------------------------------------------- experts
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def routing(n, router, bias, top_k, scale, dtype):
    """(chosen [T, k], weights [T, k]): sigmoid scores at ``dtype``
    ('float32': the highest precision), the ``top_k`` largest of score +
    bias, the chosen scores normalised and times ``scale``."""
    if dtype == 'float32':
        logits = jnp.matmul(n, _f32(router), precision=HIGHEST)
    else:
        logits = jnp.matmul(n.astype(dtype), router.astype(dtype),
                            preferred_element_type=jnp.float32
                            ).astype(dtype).astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + _f32(bias)[None, :], top_k)
    top = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True) * scale


@jax.jit
def _relu2(x):
    return jnp.square(jax.nn.relu(x))


@jax.jit
def _expert(u, w1, w2, gate):
    """One expert over every row under its gate column: ``relu(u
    W1)^2 W2 * gate`` (0 for a row that did not choose it)."""
    return _matmul(_relu2(_matmul(u, w1)), w2) * gate[:, None]


def experts(n, w, i, arch, held):
    """Expert layer ``i`` (of the moe layers) over ``n`` [T, D]: the
    held experts' part of the routed sum, projected out, and the shared
    expert."""
    first, count = held
    chosen, weight = routing(
        n, w['lm_moe_router.w'][i], w['lm_moe_router.b'][i],
        int(arch['top_k']), float(arch['routed_scale']),
        arch['state_dtype'])

    u = _by_rows(lambda rows: _matmul(rows, w['lm_moe_lat_in.w'][i]), n)
    r = jnp.zeros_like(u)
    for e in range(count):
        gate = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=1)
        r = r + _expert(u, w['lm_moe_exp_up.w'][i, e],
                        w['lm_moe_exp_down.w'][i, e], gate)
    out = _by_rows(lambda rows: _matmul(rows, w['lm_moe_lat_out.w'][i]), r)
    if arch['shared']:
        out = out + _by_rows(lambda rows: _matmul(
            _relu2(_matmul(rows, w['lm_moe_shr_up.w'][i])),
            w['lm_moe_shr_down.w'][i]), n)
    return out


# ------------------------------------------------------------ the layers
def layer(x, w, i, arch, held):
    """``x`` [T, D] -> [T, D]: the one sublayer of layer ``i``."""
    kind = arch['layer_types'][i]
    of_kind = arch['layer_types'][:i].count(kind)
    n = rms_norm(x, w['lm_stack_ln1.w'][i], float(arch['eps']))
    if kind == MOE:
        mixed = experts(n, w, of_kind, arch, held)
    else:
        mixed = (mamba if kind == MAMBA else attention)(n, w, of_kind, arch)
    return _stated(x + mixed, arch['state_dtype'])


def hidden_states(weights, tokens, arch, held):
    x = _f32(jnp.take(weights['lm_emb'], jnp.asarray(tokens), axis=0))
    for i in range(len(arch['layer_types'])):
        x = layer(x, weights, i, arch, held)
    return x


def logits(weights, tokens, arch, held, rows=None):
    """``tokens`` [T] int32 -> float32 logits [T, V] (or the rows
    ``rows = (lo, hi)`` of them); row t is the distribution of token
    t + 1 given tokens 0..t. Padding appended to the end leaves the
    earlier rows as they are (causal mask, causal recurrence)."""
    x = hidden_states(weights, tokens, arch, held)
    if rows is not None:
        x = x[rows[0]:rows[1]]
    y = rms_norm(x, weights['lm_final_ln.w'], float(arch['eps']))
    head = weights['lm_head.w']
    return _stated(_by_rows(lambda b: _matmul_t(b, head), y),
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
