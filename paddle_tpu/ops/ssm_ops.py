"""The selective-state recurrence of a Mamba-2 layer over a slot arena:
its chunked form for a prefill chunk and its one-step form for a decode
batch, both reading and writing a sequence's state where it lies.

The recurrence, a head ``h`` of width P over a state of N, its ``B``
and ``C`` those of its group ``g = h // (H / G)`` (granite: one group,
every head reads the row's one ``B`` and ``C``; nemotron_h: 8 groups of
16 heads)::

    S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h (x_t,h outer B_t,g)
    y_t,h = S_t C_t,g                     (the skip term is the caller's)

``b`` and ``c`` are ``[rows, N]`` with one group and ``[rows, G, N]``
with more: a static form of the one function, not a second one.

**Where the state lives.** ``state`` is the arena ``[layers, slots + 1,
N, H P]`` float32 and ``conv`` ``[layers, slots + 1, (K - 1) C]`` (a
slot's K - 1 rows end to end in one lane-dense row) at the
weights' dtype (``serving/decode/model.py``: the cache kinds with a size
a sequence): a sequence's slot holds its state **state-major**, row
``n`` the column ``n`` of every head's ``P x N`` state side by side.
With one group that is the layout in which each product of the chunked
form is one matmul for all heads (``B^T`` against the chunk's weighted
inputs ``[Q, H P]``; ``C`` against the carried state ``[N, H P]``), the
decode step's read-out is a sum down the rows, and a row is ``H P``
lane-dense elements, so the compiler keeps the arena row-major. With G
groups a group is a block of ``H P / G`` lanes (16 heads x 64 = 1,024 of
nemotron_h's 8,192), whole lane tiles, and each of those products is
one matmul a group, batched over the groups.

The ops touch the arenas at ``(layer, slot)`` alone, as
``paged_decode_ops._write_in_place`` writes K and V: nothing of arena
size is gathered, scattered or copied (``serving/decode/hlo_check.py``
counts such instructions in a compiled program). The slot past the pool
(``slots``) is a spare: rows that hold no slot read and write it.

``ssm_chunk_scan`` (a prefill chunk of one sequence, rows padded to a
bucket): inside a scan chunk of ``Q`` rows the masked product
``(C B^T * L) (dt x)`` with ``L[t, s] = exp(sum_{s < r <= t} dt_r A)``,
the carried state's part ``exp(cumsum(dt A)) C S_prev``, and the state
the chunk leaves, ``exp(sum dt A) S_prev + B^T (decay to the end * dt
x)``; scan chunks one after another, the state carried in float32.
Products take their operands at ``mm_dtype`` (the weights') and
accumulate in float32; the decays, their exponentials and the state are
float32. A padded row has ``dt = 0``: it decays nothing and adds
nothing. The one slot is sliced out (``_slot_of``) and written back with
``dynamic_update_slice``.

``ssm_decode_update`` (one token a row of a decode batch): row ``i``'s
slot (``N x H P``: 2 MB at the published widths) takes one step of the
recurrence in float32 on the vector unit (a product through the matrix
unit would round the state to the operands' precision), ``S <- S keep +
B[:, None] xdt``, and ``y`` is read out as ``sum_n S[n, :] C[n]``; the
decays ``keep``, the weighted inputs ``xdt`` and the convolution's kept
rows are computed for the batch before it, rows past the last live one
move nothing. The least a step can move is what it moves: each live
row's state read once and written once. A gather of the rows, a batched
update and a scatter back would move it twice more and re-lay the arena.
It has two forms, chosen by the platform the program is lowered for
(``jax.lax.platform_dependent``) and by nothing else:

- **on a TPU, one Pallas kernel a layer** that walks the live rows'
  slots (``ops/pallas/ssm_state_update.py``): both arenas passed whole
  and aliased in and out, a slot in row tiles through VMEM, the next
  tile on its way in and the last on its way out while this one is
  computed, the convolution's rows through the same pipeline. One form
  for every batch size; a live count of 1 walks one row;
- **everywhere else, a loop over the rows** up to the last live one
  (``_update_row_by_row``): it slices a row's slot, advances it and
  writes it back, one row after another (on the v5e 11.4 us a row and
  layer against 5.2 at the HBM peak, since an iteration carries the
  arena and the next row's read cannot start under this row's
  arithmetic: PERF.md, PRs 45 and 46). It is kept as the reference the
  kernel is held to (``tests/test_granite_block.py``: the same state bit
  for bit) and because an interpreted kernel would slow every CPU test
  that decodes. The two share ``keep``, ``xdt`` and ``kept`` and nothing
  else.

The barrier in ``_slot_of`` still guards the two places that slice a
slot in XLA: the loop above and the prefill chunk.
"""

import jax
import jax.numpy as jnp

from .pallas.ssm_state_update import state_update


def _at(arena, layer, slot):
    return (layer, slot) + (0,) * (arena.ndim - 2)


def _slot_of(arena, layer, slot):
    """One slot of one layer of ``arena``, read once. The barrier keeps
    the slice a value of its own: left to fuse, each consumer slices the
    arena again for itself, the arena is then an operand of a
    computation that runs beside its own in-place update, and the
    compiler copies it whole to keep the two apart (two copies of the
    arena an iteration of the decode loop; compiled on the CPU, PR 45)."""
    return jax.lax.optimization_barrier(jax.lax.dynamic_slice(
        arena, _at(arena, layer, slot), (1, 1) + arena.shape[2:])[0, 0])


def _put_slot(arena, layer, slot, value):
    """``value`` written to ``arena[layer, slot]`` where it lies."""
    return jax.lax.dynamic_update_slice(
        arena, value.astype(arena.dtype).reshape((1, 1) + arena.shape[2:]),
        _at(arena, layer, slot))


def conv_window(conv, layer, slot, u, fresh):
    """The convolution's input for rows ``u`` [S, C] of the sequence in
    ``slot``: the K - 1 rows its slot holds (zeros where ``fresh``: a
    sequence's first chunk, whatever the slot's last owner left) and
    ``u`` behind them, [K - 1 + S, C] at the arena's dtype."""
    held = _slot_of(conv, layer, slot).reshape(-1, u.shape[1])
    held = jnp.where(fresh, jnp.zeros_like(held), held)
    return jnp.concatenate([held, u.astype(conv.dtype)])


def keep_conv_rows(conv, layer, slot, rows):
    """``rows`` [K - 1, C] written to ``conv[layer, slot]``."""
    return _put_slot(conv, layer, slot, rows)


def causal_conv(window, taps, bias, rows):
    """silu of the depthwise causal convolution over ``window``
    [K - 1 + rows, C] (or [B, K, C]: one output a row): output t reads
    window rows t .. t + K - 1, tap K - 1 the row's own input. float32."""
    k = taps.shape[0]
    w = taps.astype(jnp.float32)
    window = window.astype(jnp.float32)
    if window.ndim == 3:
        out = jnp.sum(window * w[None], axis=1)
    else:
        out = sum(window[j:j + rows] * w[j][None, :] for j in range(k))
    return jax.nn.silu(out + bias.astype(jnp.float32))


def ssm_chunk_scan(state, layer, slot, x, b, c, dt, a, fresh, chunk,
                   mm_dtype):
    """The chunked scan of one sequence's rows: ``x`` [S, H, P], ``b``
    and ``c`` [S, N] (or [S, G, N]), ``dt`` [S, H] (after softplus; 0 on
    padded rows), ``a`` [H] (negative), all float32; seeded from
    ``state[layer, slot]`` (zeros where ``fresh``) and leaving the final
    state there. Returns (y [S, H, P] float32 without the skip term, the
    arena)."""
    rows, heads, width = x.shape
    # ``g``: the group axis of the products' operands, absent with one
    g, groups = ('', ()) if b.ndim == 2 else ('g', b.shape[1:2])
    carried = _slot_of(state, layer, slot)                    # [N, H P]
    carried = jnp.where(fresh, jnp.zeros_like(carried), carried)
    q = min(int(chunk), rows)
    tril = jnp.tril(jnp.ones((q, q), bool))

    def mm(spec, left, right):
        return jnp.einsum(spec % {'g': g}, left.astype(mm_dtype),
                          right.astype(mm_dtype),
                          preferred_element_type=jnp.float32)

    def by_group(lanes):
        """[..., H P] with the lanes split by group: [..., G, H P / G]."""
        return lanes.reshape(lanes.shape[:-1] + groups + (-1,))

    out = []
    with jax.named_scope('ssm_chunk_scan'):
        for lo in range(0, rows, q):
            xq, bq, cq = x[lo:lo + q], b[lo:lo + q], c[lo:lo + q]
            dtq = dt[lo:lo + q]
            cum = jnp.cumsum(dtq * a[None, :], axis=0)            # [Q, H]
            xdt = xq * dtq[:, :, None]                            # [Q, H, P]
            # inside the chunk: (C B^T * L) (dt x), a head at a time
            seg = cum.T[:, :, None] - cum.T[:, None, :]           # [H, Q, Q]
            decay = jnp.exp(jnp.where(tril[None], seg, -jnp.inf))
            scores = mm('t%(g)sn,s%(g)sn->%(g)sts', cq, bq)   # [(G,) Q, Q]
            # a head's scores are its group's
            scores = jnp.repeat(scores, heads // groups[0], axis=0) \
                if groups else scores[None]
            y = mm('hts,shp->thp', scores * decay, xdt)
            # the carried state's part
            y += jnp.exp(cum)[:, :, None] * mm(
                't%(g)sn,n%(g)sf->t%(g)sf', cq, by_group(carried)
            ).reshape(q, heads, width)
            out.append(y)
            # the state the chunk leaves
            to_end = jnp.exp(cum[-1:, :] - cum)                   # [Q, H]
            grown = mm('t%(g)sn,t%(g)sf->n%(g)sf', bq, by_group(
                (xdt * to_end[:, :, None]).reshape(q, -1)))
            carried = carried * jnp.repeat(
                jnp.exp(cum[-1]), width)[None, :] + grown.reshape(
                    carried.shape)
    return jnp.concatenate(out), _put_slot(state, layer, slot, carried)


def ssm_decode_update(state, conv, layer, slots, live, x, b, c, dt, a,
                      window):
    """One step of the recurrence a row: ``x`` [B, H, P], ``b`` and
    ``c`` [B, N] (or [B, G, N]), ``dt`` [B, H], ``a`` [H], float32; row
    ``i``'s state in
    ``state[layer, slots[i]]``, read, advanced and written back where it
    lies, and the convolution's last K - 1 inputs (``window[:, 1:]``,
    [B, K, C]) written to ``conv[layer, slots[i]]``. Rows past the last
    ``live`` one are not touched at all; one that is not live below it
    points at the spare slot. Returns (y [B, H, P] float32 without the
    skip term, state, conv)."""
    rows, heads, width = x.shape
    keep = jnp.repeat(jnp.exp(dt * a[None, :]), width, axis=1)   # [B, H P]
    xdt = (x * dt[:, :, None]).reshape(rows, -1)                 # [B, H P]
    kept = window[:, 1:].astype(conv.dtype).reshape(rows, -1)
    upper = jnp.max(jnp.where(live, jnp.arange(1, rows + 1), 0))
    with jax.named_scope('ssm_state_update'):
        ys, state, conv = jax.lax.platform_dependent(
            state, conv, layer, slots, upper, keep, xdt, b, c, kept,
            tpu=state_update, default=_update_row_by_row)
    return ys.reshape(rows, heads, width), state, conv


def _update_row_by_row(state, conv, layer, slots, upper, keep, xdt, b, c,
                       kept):
    """The update as a loop over rows ``0 .. upper - 1``, one row's slot
    sliced, advanced and written back after another: the form of every
    platform but the TPU, and what the kernel is held to."""
    def column(of, i):
        """Row ``i`` of ``b`` or ``c`` down the state's rows: [N, 1], or
        with groups each group's over its own lanes, [N, H P]."""
        own = jax.lax.dynamic_index_in_dim(of, i, keepdims=False)
        if of.ndim == 2:
            return own[:, None]
        return jnp.repeat(own.T, keep.shape[1] // of.shape[1], axis=1)

    def one(i, carry):
        state, conv, ys = carry
        s = _slot_of(state, layer, slots[i])
        s = s * jax.lax.dynamic_index_in_dim(keep, i, keepdims=True) + \
            column(b, i) * \
            jax.lax.dynamic_index_in_dim(xdt, i, keepdims=True)
        y = jnp.sum(s * column(c, i), axis=0, keepdims=True)
        state = _put_slot(state, layer, slots[i], s)
        conv = _put_slot(conv, layer, slots[i],
                         jax.lax.dynamic_index_in_dim(kept, i))
        return state, conv, jax.lax.dynamic_update_slice(ys, y, (i, 0))

    state, conv, ys = jax.lax.fori_loop(
        0, upper, one, (state, conv, jnp.zeros_like(keep)))
    return ys, state, conv
