"""Plain reference of ``qwen3_next``: Qwen Qwen3-Next-80B-A3B-Instruct
(``model_type`` qwen3_next), the language model's forward pass in
float32 ``jax.numpy``.

No pages, no cache, no slots, no batching, no kernel, no chunked form of
the delta rule, no tile list: the whole sequence at once, every matrix
product at the highest precision, causal attention as a mask over the
whole sequence's keys in blocks of query rows, the gated delta rule as a
plain ``lax.scan`` over tokens, the experts in a plain loop over the
ones held. Independent of ``paddle_tpu/ops``: it shares only the names
and layouts of the weights (``serving/decode/model.py``:
``delta_param_shapes``).

``x0 = E[token]``. ``RMS0(v) g = v * rsqrt(mean(v^2) + eps) * (1 + g)``
(the zero-centred norm). Layer ``i`` is ``full_attention`` where ``(i +
1) % 4 == 0``, else ``linear_attention`` (``arch['layer_types']``)::

    h = x + Mixer_kind(RMS0(x) g1)
    y = h + MoE(RMS0(h) g2)

``logits = RMS0(x_L) g_f W_head^T`` (the head is a matrix of its own).

``linear_attention`` (Gated DeltaNet: G key heads of K, H value heads of
V, value head h reads key head h // (H / G); a depthwise causal
convolution of ``taps`` taps, no bias)::

    [q (G K); k (G K); v (H V); z (H V)] = n W_in         [b (H); a (H)] = n W_ba
    [q; k; v]_t <- silu(sum_{j < taps} w_conv[j] * [q; k; v]_{t - taps + 1 + j})
    q <- l2norm(q) / sqrt(K)     k <- l2norm(k)            l2norm(v) = v * rsqrt(sum v^2 + 1e-6)
    beta = sigmoid(b)            alpha = exp(-exp(A_log) softplus(a + dt_bias))
    S <- alpha S;  m = S^T k;  d = beta (v - m);  S <- S + k d^T;  o = S^T q      (S_{-1} = 0; a head: K x V)
    Mixer = (RMS(o) w * silu(z)) W_out                     (a plain gain over a head's V; norm first, then the gate)

``full_attention`` (H query heads over Kv KV heads of width d; no bias)::

    q = n W_q, gate = n W_gate (a query and a gate a head)   k = n W_k   v = n W_v
    q, k <- RMS0 over each head (gains of their own); the first ``rotary_dim``
    columns of a head turned in half-split pairs (i, i + rotary_dim / 2) by
    pos * theta^(-2i / rotary_dim), the others not
    score_h(t, s) = q_h(t) . k_{h // (H / Kv)}(s) * d^-1/2            s <= t
    Attn = (concat_h(sum_s softmax_s(score_h(t, .)) v(s)) * sigmoid(gate)) W_o

``MoE`` (every layer)::

    p = softmax(n W_r)                                  (float32; every published expert)
    chosen = the top_k largest of p                     (ties to the lower index)
    w_e = p_e / sum_{chosen} p                          (norm_topk_prob)
    r = sum_{e chosen and held} w_e (silu(n G_e) * (n U_e)) D_e
    MoE = r + sigmoid(n w_sg) * (silu(n Gs) * (n Us)) Ds    (one shared expert behind a gate of its own)

``held = (first, count)``: the experts ``first .. first + count - 1``
are computed and the others' part of ``r`` is left out, as the one chip
of an expert-parallel group leaves it out; ``shared=False`` leaves the
shared expert out too (the shares-add-up test counts it once).

Departures from the published code (none changes an equation under
weights drawn from a seed): ``W_in`` holds the columns of the published
``in_proj_qkvz`` as ``[q; k; v; z]``, where the published matrix
interleaves the four by key head (a layout of trained weights: the same
matrix under a permutation of its columns), and ``W_ba`` likewise ``[b;
a]``; the published ``q_proj`` (a head's query and gate side by side) is
two matrices, both kept as their transposes; the multi-token-prediction
module is outside the language model's next-token logits and is not
here.

``arch`` holds what the shapes do not say and switches that are on
wherever the system is held to this reference and that a control turns
off or down: ``state_dtype`` ('float32': the precision of the residual
stream, the softmax, the logits, the router's scores **and of the
recurrent state, rounded after every token**), ``l2norm``, ``beta``,
``decay``, ``gate_after_norm`` (False: the gate first, then the norm,
as a Mamba-2 layer), ``qk_norm``, ``rotary_dim`` (the head's width: the
whole head turned), ``attn_gate``, ``shared_gate``, ``norm_topk`` and
``shared``.

Long sequences: a layer is computed in blocks of rows where rows are
independent, a linear-attention layer in blocks of tokens in order with
its state and its convolution's last inputs carried between them, and
one matrix or one expert is upcast at a time, so that a sequence of 35k
tokens fits beside the served model on the chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 256
# query rows a block of an attention layer (16 heads' scores over 35k keys)
ATTN_ROWS = 128
# tokens a block of a linear-attention layer, its state carried on
TIME_BLOCK = 1024
LINEAR, FULL = 'linear_attention', 'full_attention'
L2_EPS = 1e-6


def arch_of(spec):
    """``arch`` from anything with an LMSpec's attributes."""
    return {'n_head': spec.n_head, 'd_head': spec.d_key,
            'layer_types': tuple(spec.layer_types),
            'value_heads': spec.ssm_heads, 'key_heads': spec.ssm_groups,
            'd_key': spec.ssm_state, 'eps': spec.norm_eps,
            'rope_theta': spec.rope_theta, 'rotary_dim': spec.rotary_dim,
            'top_k': spec.experts_per_token,
            'state_dtype': 'float32', 'l2norm': True, 'beta': True,
            'decay': True, 'gate_after_norm': True, 'qk_norm': True,
            'attn_gate': True, 'shared_gate': True, 'norm_topk': True,
            'shared': True}


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


@jax.jit
def _matmul_t(x, w):
    """``x w^T``: a matrix kept as its transpose, where it lies."""
    return jnp.einsum('td,vd->tv', x, _f32(w), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=2)
def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * _f32(gain)


def rms_norm0(x, gain, eps):
    """The zero-centred norm: the gain is ``1 + g``."""
    return rms_norm(x, 1.0 + _f32(gain), eps)


@functools.partial(jax.jit, static_argnums=2)
def _rows(x, first, size):
    """``x[first:first + size]`` with ``first`` an argument and not a
    constant of the program: one program for every block of a sequence
    (a slice at a constant place is compiled anew for each place: 400
    programs a sequence of 33k tokens)."""
    return jax.lax.dynamic_slice_in_dim(x, first, size, axis=0)


def _blocks(x, block):
    """(first row, the rows) of each block of ``block`` rows of ``x`` and
    of what is left over."""
    for a in range(0, x.shape[0], block):
        yield a, _rows(x, a, min(block, x.shape[0] - a))


def _by_rows(fn, x, block=BLOCK_ROWS):
    """``fn`` over blocks of ``block`` rows of ``x`` and what is left
    over (rows are independent)."""
    return jnp.concatenate([fn(rows) for _, rows in _blocks(x, block)])


# ------------------------------------------------------------ attention
@functools.partial(jax.jit, static_argnums=(3,))
def rotated(x, first, theta, rotary_dim):
    """x [R, heads, d] at positions ``first`` ..: the first
    ``rotary_dim`` columns of a head turned in half-split pairs."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / rotary_dim)
    angle = (first + jnp.arange(x.shape[0], dtype=jnp.float32))[:, None] \
        * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., rotary_dim:]], axis=-1)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _attend(q, k, v, first, scale, n_head, state):
    """A block of queries q [R, H, d] at positions ``first`` .. over the
    whole sequence's k and v [T, Kv, d]: causal; query head h reads KV
    head h // (H / Kv)."""
    rows, steps = q.shape[0], k.shape[0]
    q = q.reshape(rows, k.shape[1], -1, q.shape[-1])   # [R, Kv, H / Kv, d]
    scores = _stated(jnp.einsum('tkgd,skd->kgts', q, k, precision=HIGHEST)
                     * scale, state)
    seen = jnp.arange(steps)[None, :] <= first + jnp.arange(rows)[:, None]
    weights = _stated(jax.nn.softmax(
        jnp.where(seen[None, None], scores, -jnp.inf), axis=-1), state)
    return jnp.einsum('kgts,skd->tkgd', weights, v,
                      precision=HIGHEST).reshape(rows, -1)


def attention(n, w, i, arch):
    """Full-attention layer ``i`` (of the full-attention layers) over the
    whole sequence ``n`` [T, D]: the keys and values of the whole
    sequence first (small: Kv KV heads), then the queries in blocks of
    rows."""
    d, eps = int(arch['d_head']), float(arch['eps'])
    theta, turned = float(arch['rope_theta']), int(arch['rotary_dim'])

    def normed(x, gain):
        x = x.reshape(x.shape[0], -1, d)
        return rms_norm0(x, gain, eps) if arch['qk_norm'] else x

    def keys(rows, at):
        return rotated(normed(_matmul(rows, w['lm_attn_k.w'][i]),
                              w['lm_attn_k_ln.w'][i]),
                       jnp.float32(at), theta, turned)
    k = jnp.concatenate([keys(rows, a) for a, rows in
                         _blocks(n, BLOCK_ROWS)])
    v = _by_rows(lambda rows: _matmul(rows, w['lm_attn_v.w'][i]), n
                 ).reshape(n.shape[0], -1, d)
    out = []
    for a, rows in _blocks(n, ATTN_ROWS):
        q = rotated(normed(_matmul_t(rows, w['lm_attn_q.w'][i]),
                           w['lm_attn_q_ln.w'][i]),
                    jnp.float32(a), theta, turned)
        got = _attend(q, k, v, jnp.int32(a), d ** -0.5,
                      int(arch['n_head']), arch['state_dtype'])
        if arch['attn_gate']:
            got = got * jax.nn.sigmoid(
                _matmul_t(rows, w['lm_attn_gate.w'][i]))
        out.append(_matmul(got, w['lm_attn_o.w'][i]))
    return jnp.concatenate(out)


# ----------------------------------------------------- linear attention
@functools.partial(jax.jit, static_argnums=(3,))
def convolved(u, before, taps, n_taps):
    """u [T, C] behind the taps - 1 inputs ``before`` it (zeros at a
    sequence's start) -> silu of the depthwise causal convolution:
    output t reads inputs t - taps + 1 .. t. No bias."""
    steps = u.shape[0]
    padded = jnp.concatenate([before, u])
    return jax.nn.silu(sum(padded[j:j + steps] * _f32(taps)[j][None, :]
                           for j in range(n_taps)))


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


@functools.partial(jax.jit, static_argnums=(5,))
def recurrence(q, k, v, alpha, beta, state, first=None):
    """The gated delta rule, token by token, from the state ``first``
    [H, K, V] (None: zeros): q and k [T, H, K] (a value head's own), v
    [T, H, V], alpha and beta [T, H] -> (o [T, H, V], the state it ends
    in)."""
    def one(s, row):
        qt, kt, vt, at, bt = row
        s = s * at[:, None, None]
        m = jnp.sum(s * kt[:, :, None], axis=1)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - m))[:, None, :]
        s = _stated(s, state)
        return s, jnp.sum(s * qt[:, :, None], axis=1)
    if first is None:
        first = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    last, o = jax.lax.scan(one, first, (q, k, v, alpha, beta))
    return o, last


def delta_net(n, w, i, arch):
    """Linear-attention layer ``i`` (of the linear-attention layers)
    over the whole sequence ``n`` [T, D], ``TIME_BLOCK`` tokens at a
    time in order, the state and the convolution's last inputs carried
    from one block of tokens to the next."""
    heads, key_heads = int(arch['value_heads']), int(arch['key_heads'])
    d_key = int(arch['d_key'])
    keys = key_heads * d_key
    inner = w['lm_gdn_out.w'].shape[1]
    taps = w['lm_gdn_conv.w'][i]
    gain = _f32(w['lm_gdn_norm.w'][i])
    neg = -jnp.exp(_f32(w['lm_gdn_a_log'][i]))
    dt_bias = _f32(w['lm_gdn_dt.b'][i])
    carried = None
    before = jnp.zeros((taps.shape[0] - 1, taps.shape[1]), jnp.float32)
    out = []
    for _, rows in _blocks(n, TIME_BLOCK):
        steps = rows.shape[0]
        proj = _matmul(rows, w['lm_gdn_in.w'][i])
        u, z = proj[:, :2 * keys + inner], proj[:, 2 * keys + inner:]
        ba = _matmul(rows, w['lm_gdn_ba.w'][i])
        mixed = convolved(u, before, taps, taps.shape[0])
        before = jnp.concatenate([before, u])[-before.shape[0]:]
        q = mixed[:, :keys].reshape(steps, key_heads, -1)
        k = mixed[:, keys:2 * keys].reshape(steps, key_heads, -1)
        v = mixed[:, 2 * keys:].reshape(steps, heads, -1)
        if arch['l2norm']:
            q, k = l2_norm(q), l2_norm(k)
        q = q * d_key ** -0.5
        q, k = (jnp.repeat(x, heads // key_heads, axis=1) for x in (q, k))
        beta = jax.nn.sigmoid(ba[:, :heads]) if arch['beta'] \
            else jnp.ones((steps, heads), jnp.float32)
        alpha = jnp.exp(neg[None, :] * jax.nn.softplus(
            ba[:, heads:] + dt_bias[None, :])) if arch['decay'] \
            else jnp.ones((steps, heads), jnp.float32)
        o, carried = recurrence(q, k, v, alpha, beta, arch['state_dtype'],
                                carried)
        z = z.reshape(steps, heads, -1)
        if arch['gate_after_norm']:
            o = rms_norm(o, gain, float(arch['eps'])) * jax.nn.silu(z)
        else:
            o = rms_norm(o * jax.nn.silu(z), gain, float(arch['eps']))
        out.append(_matmul(o.reshape(steps, -1), w['lm_gdn_out.w'][i]))
    return jnp.concatenate(out)


# -------------------------------------------------------------- experts
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def routing(n, router, top_k, norm_topk, dtype):
    """(chosen [T, k], weights [T, k]): the softmax over every expert at
    ``dtype`` ('float32': the highest precision), the ``top_k`` largest,
    normalised over those where ``norm_topk``."""
    if dtype == 'float32':
        logits = jnp.matmul(n, _f32(router), precision=HIGHEST)
    else:
        logits = jnp.matmul(n.astype(dtype), router.astype(dtype),
                            preferred_element_type=jnp.float32
                            ).astype(dtype).astype(jnp.float32)
    top, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return chosen, top


@jax.jit
def _expert(n, stacks, layer, held, chosen, weight, expert):
    """Expert ``expert`` (of all the router scores), held at place
    ``held`` of layer ``layer``'s stacks, over every row under its gate
    column: ``(silu(n G) * (n U)) D * gate`` (0 for a row that did not
    choose it). The places are arguments and not constants of the
    program: one program for all the experts of all the layers."""
    gate_w, up_w, down_w = (jax.lax.dynamic_index_in_dim(
        jax.lax.dynamic_index_in_dim(stack, layer, keepdims=False), held,
        keepdims=False) for stack in stacks)
    gate = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=1)
    return _matmul(jax.nn.silu(_matmul(n, gate_w)) * _matmul(n, up_w),
                   down_w) * gate[:, None]


def experts(n, w, i, arch, held):
    """The experts of layer ``i`` over ``n`` [T, D]: the held experts'
    part of the routed sum and the shared expert behind its gate."""
    first, count = held
    chosen, weight = routing(n, w['lm_moe_router.w'][i], int(arch['top_k']),
                             bool(arch['norm_topk']), arch['state_dtype'])
    out = jnp.zeros_like(n)
    stacks = tuple(w['lm_moe_exp_%s.w' % m] for m in ('gate', 'up', 'down'))
    for e in range(count):
        out = out + _expert(n, stacks, i, e, chosen, weight, first + e)
    if arch['shared']:
        def shared(rows):
            got = _matmul(
                jax.nn.silu(_matmul(rows, w['lm_moe_shr_gate.w'][i]))
                * _matmul(rows, w['lm_moe_shr_up.w'][i]),
                w['lm_moe_shr_down.w'][i])
            if arch['shared_gate']:
                got = got * jax.nn.sigmoid(_matmul(
                    rows, w['lm_moe_shr_sg.w'][i][:, None]))
            return got
        out = out + _by_rows(shared, n)
    return out


# ------------------------------------------------------------ the layers
def layer(x, w, i, arch, held):
    """``x`` [T, D] -> [T, D]: layer ``i``."""
    kind = arch['layer_types'][i]
    of_kind = arch['layer_types'][:i].count(kind)
    eps, state = float(arch['eps']), arch['state_dtype']
    n1 = rms_norm0(x, w['lm_stack_ln1.w'][i], eps)
    mixer = delta_net if kind == LINEAR else attention
    h = _stated(x + mixer(n1, w, of_kind, arch), state)
    n2 = rms_norm0(h, w['lm_stack_ln2.w'][i], eps)
    return _stated(h + experts(n2, w, i, arch, held), state)


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
    y = rms_norm0(x, weights['lm_final_ln.w'], float(arch['eps']))
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
