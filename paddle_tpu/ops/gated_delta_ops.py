"""The gated delta rule of a linear-attention layer over a slot arena:
its chunked form for a prefill chunk and its one-step form for a decode
batch, both reading and writing a sequence's state where it lies.

The recurrence, a value head ``h`` of width V over keys of width K (its
``q`` and ``k`` those of key head ``h // (H / G)``), ``S`` its ``[K, V]``
state, ``alpha = exp(g)`` its decay and ``beta`` its write strength::

    S <- alpha_t S;  m = S^T k_t;  d = beta_t (v_t - m)
    S <- S + k_t d^T;  o_t = S^T q_t

which is another recurrence than Mamba-2's (``ops/ssm_ops.py``): the
write depends on what the state already returns for the key, so a step
reads a head's whole state before it writes any of it, and the chunked
form has a triangular system to solve.

**Where the state lives.** ``state`` is the arena ``[layers, slots + 1,
H, K, V]`` float32 and ``conv`` ``[layers, slots + 1, (taps - 1) C]`` at
the weights' dtype, as the Mamba-2 layers' (``serving/decode/model.py``:
the cache kinds with a size a sequence): a head's state is ``K`` rows of
``V`` lane-dense elements, key-major, so ``S^T k`` and ``S^T q`` are sums
down the rows and the rank-one write a column times a row. The ops touch
the arenas at ``(layer, slot)`` alone (``ssm_ops._slot_of``,
``_put_slot``): nothing of arena size is gathered, scattered or copied
(``serving/decode/hlo_check.py``). The slot past the pool is a spare.

``delta_chunk_scan`` (a prefill chunk of one sequence, rows padded to a
bucket). Inside a scan chunk of ``Q`` rows, with ``c_t`` the running sum
of ``g`` (the decay to row ``t`` from the chunk's start is ``exp(c_t)``)::

    A = strict_lower(beta_t (k_t . k_s) exp(c_t - c_s))
    T = (I + A)^-1;  W = T (beta K exp(c));  U = T (beta V)

(the WY form: what the rows of the chunk would write, were the state
they start from zero, and what they read of the state they do start
from), and then chunk by chunk with the state carried::

    V' = U - W S
    O  = (Q exp(c)) S + (Q K^T exp(c_t - c_s) [s <= t]) V'
    S <- exp(c_Q) S + (K exp(c_Q - c))^T V'

``T`` comes from products alone: ``A`` is strictly lower triangular, so
``(I + A)^-1 = (I - A)(I + A^2)(I + A^4) ...`` ends after ``log2 Q``
factors. Every product here is float32 at the highest precision: the
state and its arithmetic are float32 in both forms, and all of it is
under 3% of a chunk's operations at the published widths. Every
difference of ``c`` that is exponentiated is of a later row from an
earlier, so no exponent is positive. A padded row has ``g = 0`` and
``beta = 0``: it decays nothing, writes nothing and lies behind the
rows that count.

``delta_decode_update`` (one token a row of a decode batch): row ``i``'s
slot (``H x K x V``: 2 MB at the published widths) takes one step in
float32 on the vector unit. Two forms, chosen by the platform the
program is lowered for (``jax.lax.platform_dependent``) and by nothing
else, as Mamba-2's:

- **on a TPU, one Pallas kernel a layer** over the live rows' slots
  (``ops/pallas/ssm_state_update.py::delta_state_update``: the second
  body of that pipeline, a tile whole heads);
- **everywhere else, a loop over the rows** up to the last live one
  (``_delta_row_by_row``), which is also what the kernel is held to
  (``tests/test_qwen3_next_block.py``: the same state bit for bit).
"""

import jax
import jax.numpy as jnp

from .pallas.ssm_state_update import delta_state_update
from .ssm_ops import _put_slot, _slot_of

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec, left, right):
    return jnp.einsum(spec, left, right, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` [..., Q, Q], by
    products: ``(I - a)(I + a^2)(I + a^4) ...`` (``a^Q`` = 0)."""
    q = a.shape[-1]
    power = -a
    inverse = jnp.eye(q, dtype=a.dtype) + power
    for _ in range(max(q - 1, 1).bit_length() - 1):
        power = _mm('...ij,...jk->...ik', power, power)
        inverse = inverse + _mm('...ij,...jk->...ik', inverse, power)
    return inverse


def delta_chunk_scan(state, layer, slot, q, k, v, g, beta, fresh, chunk):
    """The chunked form over one sequence's rows: ``q`` and ``k`` [S, G,
    K] (normalised, ``q`` scaled), ``v`` [S, H, V], ``g`` (the decay's
    logarithm; 0 on padded rows) and ``beta`` (0 on padded rows) [S, H],
    all float32; seeded from ``state[layer, slot]`` (zeros where
    ``fresh``) and leaving the final state there. ``S`` is a whole
    number of scan chunks of ``chunk`` rows, or fewer rows than one.
    Returns (o [S, H, V] float32, the arena)."""
    rows, heads, _ = v.shape
    per = heads // q.shape[1]
    size = min(int(chunk), rows)
    if rows % size:
        raise ValueError('delta_chunk_scan: %d rows in scan chunks of %d'
                         % (rows, size))
    carried = _slot_of(state, layer, slot)                    # [H, K, V]
    carried = jnp.where(fresh, jnp.zeros_like(carried), carried)

    def by_chunk(x):
        """[S, heads, ...] -> [chunks, H, Q, ...], a key head's rows for
        each of its value heads."""
        x = x.reshape((rows // size, size) + x.shape[1:])
        x = jnp.moveaxis(x, 2, 1)
        return x if x.shape[1] == heads else jnp.repeat(x, per, axis=1)

    with jax.named_scope('gdn_chunk_scan'):
        q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)
        g, beta = by_chunk(g), by_chunk(beta)                 # [n, H, Q]
        cum = jnp.cumsum(g, axis=-1)
        seen = jnp.tril(jnp.ones((size, size), bool))
        # exp(c_t - c_s) at or below the diagonal, 0 above it
        decay = jnp.exp(jnp.where(
            seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        lower = _mm('nhtk,nhsk->nhts', k, k) * decay \
            * beta[..., :, None] * jnp.tril(jnp.ones((size, size)), -1)
        solved = _unit_lower_inverse(lower)
        w = _mm('nhts,nhsk->nhtk', solved,
                k * (beta * jnp.exp(cum))[..., None])
        u = _mm('nhts,nhsv->nhtv', solved, v * beta[..., None])
        inside = _mm('nhtk,nhsk->nhts', q, k) * decay
        q_in = q * jnp.exp(cum)[..., None]
        k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]
        to_end = jnp.exp(cum[..., -1])                        # [n, H]

        def one(s, xs):
            w, u, q_in, k_out, inside, to_end = xs
            new = u - _mm('htk,hkv->htv', w, s)
            out = _mm('htk,hkv->htv', q_in, s) \
                + _mm('hts,hsv->htv', inside, new)
            return s * to_end[:, None, None] \
                + _mm('htk,htv->hkv', k_out, new), out

        carried, out = jax.lax.scan(
            one, carried, (w, u, q_in, k_out, inside, to_end))
        out = jnp.moveaxis(out, 1, 2).reshape(rows, heads, -1)
    return out, _put_slot(state, layer, slot, carried)


def delta_decode_update(state, conv, layer, slots, live, q, k, v, g, beta,
                        window):
    """One step of the rule a row: ``q`` and ``k`` [B, G, K], ``v`` [B,
    H, V], ``g`` and ``beta`` [B, H] (0 on a row that is not live),
    float32; row ``i``'s state in ``state[layer, slots[i]]``, read,
    advanced and written back where it lies, and the convolution's last
    taps - 1 inputs (``window[:, 1:]``, [B, taps, C]) written to
    ``conv[layer, slots[i]]``. Rows past the last ``live`` one are not
    touched at all; one that is not live below it points at the spare
    slot. Returns (o [B, H, V] float32, state, conv)."""
    rows, heads, width = v.shape
    per = heads // q.shape[1]
    q, k = (jnp.repeat(x, per, axis=1) for x in (q, k))       # [B, H, K]
    spread = (rows, heads, width)
    keep = jnp.broadcast_to(jnp.exp(g)[:, :, None], spread)
    beta = jnp.broadcast_to(beta[:, :, None], spread)
    kept = window[:, 1:].astype(conv.dtype).reshape(rows, -1)
    upper = jnp.max(jnp.where(live, jnp.arange(1, rows + 1), 0))
    with jax.named_scope('gdn_state_update'):
        return jax.lax.platform_dependent(
            state, conv, layer, slots, upper, keep, beta, q, k, v, kept,
            tpu=delta_state_update, default=_delta_row_by_row)


def _delta_row_by_row(state, conv, layer, slots, upper, keep, beta, q, k, v,
                      kept):
    """The update as a loop over rows ``0 .. upper - 1``, one row's slot
    sliced, advanced and written back after another: the form of every
    platform but the TPU, and what the kernel is held to."""
    def one(i, carry):
        state, conv, out = carry
        keep_i, beta_i, q_i, k_i, v_i = (
            jax.lax.dynamic_index_in_dim(x, i, keepdims=False)
            for x in (keep, beta, q, k, v))                   # [H, 128]
        s = _slot_of(state, layer, slots[i]) * keep_i[:, None, :]
        m = jnp.sum(s * k_i[:, :, None], axis=1)
        s = s + k_i[:, :, None] * (beta_i * (v_i - m))[:, None, :]
        o = jnp.sum(s * q_i[:, :, None], axis=1)
        state = _put_slot(state, layer, slots[i], s)
        conv = _put_slot(conv, layer, slots[i],
                         jax.lax.dynamic_index_in_dim(kept, i))
        return state, conv, jax.lax.dynamic_update_slice(
            out, o[None], (i, 0, 0))

    state, conv, out = jax.lax.fori_loop(
        0, upper, one, (state, conv, jnp.zeros_like(v)))
    return out, state, conv
