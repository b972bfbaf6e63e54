"""Plain reference of ``granite_4_0_h_micro``: ibm-granite
granite-4.0-h-micro (``model_type`` granitemoehybrid), the language
model's forward pass in float32 ``jax.numpy``.

No pages, no cache, no slots, no batching, no kernel, no chunked scan:
the whole sequence at once, every matrix product at the highest
precision, causal attention as a mask over the whole sequence's keys,
the state-space recurrence as a plain ``lax.scan`` over tokens.
Independent of ``paddle_tpu/ops``: it shares only the names and layouts
of the weights (``serving/decode/model.py``: ``ssm_param_shapes``).

``x0 = embedding_multiplier * E[token]``. One layer, for a row ``x`` of
width ``hidden_size`` (``RMS(v) g = v * rsqrt(mean(v^2) + eps) * g``;
``r`` = ``residual_multiplier``):

    h = x + r * Mixer(RMS(x) g1)
    [a; b] = RMS(h) g2 W_in             (hidden -> 2 x shared_intermediate)
    y = h + r * (silu(a) * b) W_out

``logits = RMS(x_L) g_f E^T / logits_scaling`` (the embedding is tied).

The mixer of an ``attention`` layer (H query heads over K KV heads of
width d, G = H / K; no bias; **no position**: ``position_embedding_type``
nope):

    score_h(t, s) = q_h(t) . k_{h // G}(s) * attention_multiplier   s <= t
    o_h = sum_s softmax_s(score_h(t, .)) v_{h // G}(s)
    Attn = concat_h(o_h) W_o

The mixer of a ``mamba`` layer (Mamba-2: H heads of width P, state N,
one group, a depthwise causal convolution of K taps):

    [z (H P); u (H P + 2 N); dt (H)] = n W_inproj
    c_t = silu(sum_{j < K} w_conv[j] * u_{t - K + 1 + j} + b_conv)    (u before the start = 0)
    [x (H x P); B (N); C (N)] = c_t
    D_t = softplus(dt_t + dt_bias)           A = -exp(A_log)          (a head each)
    S_t = exp(D_t A) S_{t-1} + D_t * (x_t outer B_t)                  (S_{-1} = 0; a head: P x N)
    y_t = S_t C_t + Dskip * x_t
    Mixer = RMS(y_t * silu(z_t)) g W_outproj                           (gate, then norm over all H P)

Departures from the published implementation (transformers'
``GraniteMoeHybridMambaLayer``), each also in
``configs/granite_4_0_h_micro.json``: ``assumed``: none in the
equations. ``time_step_limit`` is (0, inf) there, so ``D_t`` is not
clipped; ``mamba_n_groups`` is 1, so every head reads the one B and C
and the gated norm is over all ``H P`` columns at once; the published
cache keeps the state at the model's dtype, here it is float32 like the
rest of the recurrence.

``arch`` holds what the shapes do not say (``n_head``, ``d_head``,
``layer_types``, ``ssm_heads``, ``ssm_state``, ``eps`` and the four
multipliers) and switches that are on wherever the system is held to
this reference and that a control turns off to show what a server that
got it wrong would be caught by: ``state_dtype`` ('float32': the
precision of the residual stream, the softmax, the logits **and of the
recurrent state, rounded after every token**), ``d_skip``, ``dt_bias``
and ``gate``.

Long sequences: a layer is computed in blocks of rows where rows are
independent (projections, MLP) and one matrix is upcast at a time, so
that a sequence of 4k tokens fits beside the served model on the chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 256
MAMBA, ATTENTION = 'mamba', 'attention'


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    return {'n_head': spec.n_head, 'd_head': spec.d_key,
            'layer_types': tuple(spec.layer_types),
            'ssm_heads': spec.ssm_heads, 'ssm_state': spec.ssm_state,
            'eps': spec.norm_eps, 'embed_scale': spec.embed_scale,
            'residual_scale': spec.residual_scale,
            'attn_scale': spec.attn_scale,
            'logit_scale': spec.logit_scale,
            'state_dtype': 'float32', 'd_skip': True, 'dt_bias': True,
            'gate': True}


def held_of(spec):
    """Nothing is held in part (the runner's convention asks)."""
    return None


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
    whole sequence's k and v [T, K d]: causal, no position."""
    rows, steps = q.shape[0], k.shape[0]
    d = q.shape[1] // n_head
    q = q.reshape(rows, n_head, d)
    k = k.reshape(steps, -1, d)
    v = v.reshape(steps, -1, d)
    group = n_head // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = _stated(jnp.einsum('thd,shd->hts', q, k, precision=HIGHEST)
                     * scale, state)
    seen = jnp.arange(steps)[None, :] <= first + jnp.arange(rows)[:, None]
    weights = _stated(jax.nn.softmax(
        jnp.where(seen[None], scores, -jnp.inf), axis=-1), state)
    return jnp.einsum('hts,shd->thd', weights, v,
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
@functools.partial(jax.jit, static_argnums=(3,))
def convolved(u, taps, bias, n_taps):
    """u [T, C] -> silu of the depthwise causal convolution: output t
    reads inputs t - K + 1 .. t, zeros before the start."""
    steps = u.shape[0]
    padded = jnp.concatenate([jnp.zeros((n_taps - 1, u.shape[1]), u.dtype),
                              u])
    out = sum(padded[j:j + steps] * _f32(taps)[j][None, :]
              for j in range(n_taps))
    return jax.nn.silu(out + _f32(bias)[None, :])


@functools.partial(jax.jit, static_argnums=(5,))
def recurrence(x, b, c, dt, a, state):
    """The selective-state recurrence, token by token: x [T, H, P],
    b and c [T, N], dt [T, H] (after softplus), a [H] (negative) ->
    y [T, H, P] without the skip term."""
    def one(s, row):
        xt, bt, ct, dtt = row
        s = jnp.exp(dtt * a)[:, None, None] * s + \
            (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        s = _stated(s, state)
        return s, jnp.sum(s * ct[None, None, :], axis=-1)
    first = jnp.zeros((x.shape[1], x.shape[2], b.shape[1]), jnp.float32)
    return jax.lax.scan(one, first, (x, b, c, dt))[1]


def mamba(n, w, i, arch):
    """Mamba-2 layer ``i`` (of the mamba layers) over the whole
    sequence ``n`` [T, D]."""
    heads, n_state = int(arch['ssm_heads']), int(arch['ssm_state'])
    state = arch['state_dtype']
    proj = _by_rows(lambda rows: _matmul(rows, w['lm_mamba_in.w'][i]), n)
    inner = w['lm_mamba_out.w'].shape[1]
    z, u, dt = (proj[:, :inner], proj[:, inner:-heads], proj[:, -heads:])
    taps = w['lm_mamba_conv.w'][i]
    conv = convolved(u, taps, w['lm_mamba_conv.b'][i], taps.shape[0])
    x = conv[:, :inner].reshape(n.shape[0], heads, -1)
    b = conv[:, inner:inner + n_state]
    c = conv[:, inner + n_state:]
    if arch['dt_bias']:
        dt = dt + _f32(w['lm_mamba_dt.b'][i])[None, :]
    dt = jax.nn.softplus(dt)
    y = recurrence(x, b, c, dt, -jnp.exp(_f32(w['lm_mamba_a_log'][i])),
                   state)
    if arch['d_skip']:
        y = y + _f32(w['lm_mamba_d'][i])[None, :, None] * x
    y = y.reshape(n.shape[0], -1)
    if arch['gate']:
        y = y * jax.nn.silu(z)
    y = rms_norm(y, w['lm_mamba_norm.w'][i], float(arch['eps']))
    return _by_rows(lambda rows: _matmul(rows, w['lm_mamba_out.w'][i]), y)


# ------------------------------------------------------------ the layers
@jax.jit
def _gated(a, b):
    return jax.nn.silu(a) * b


def mlp(n, w, i):
    """The gated MLP every layer has: ``(silu(a) * b) W_out``; the two
    halves of the published input matrix are kept as two matrices."""
    def rows(block):
        return _matmul(_gated(_matmul(block, w['lm_stack_mlp_gate.w'][i]),
                              _matmul(block, w['lm_stack_mlp_up.w'][i])),
                       w['lm_stack_mlp_down.w'][i])
    return _by_rows(rows, n)


def layer(x, w, i, arch):
    """``x`` [T, D] -> [T, D]."""
    eps, state = float(arch['eps']), arch['state_dtype']
    r = float(arch['residual_scale'])
    kind = arch['layer_types'][i]
    of_kind = arch['layer_types'][:i].count(kind)
    n = rms_norm(x, w['lm_stack_ln1.w'][i], eps)
    mixed = (mamba if kind == MAMBA else attention)(n, w, of_kind, arch)
    h = _stated(x + r * mixed, state)
    return _stated(h + r * mlp(rms_norm(h, w['lm_stack_ln2.w'][i], eps),
                               w, i), state)


def hidden_states(weights, tokens, arch):
    x = float(arch['embed_scale']) * _f32(
        jnp.take(weights['lm_emb'], jnp.asarray(tokens), axis=0))
    for i in range(len(arch['layer_types'])):
        x = layer(x, weights, i, arch)
    return x


def logits(weights, tokens, arch, held=None, rows=None):
    """``tokens`` [T] int32 -> float32 logits [T, V] (or the rows
    ``rows = (lo, hi)`` of them); row t is the distribution of token
    t + 1 given tokens 0..t. Padding appended to the end leaves the
    earlier rows as they are (causal mask, causal recurrence)."""
    x = hidden_states(weights, tokens, arch)
    if rows is not None:
        x = x[rows[0]:rows[1]]
    y = rms_norm(x, weights['lm_final_ln.w'], float(arch['eps']))
    emb = weights['lm_emb']
    return _stated(_by_rows(lambda b: _matmul_t(b, emb), y)
                   * float(arch['logit_scale']), arch['state_dtype'])


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
