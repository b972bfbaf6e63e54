"""The decode step's state update of one recurrent layer as one Pallas TPU
kernel over the live rows' slots: one pipeline, two bodies. Mamba-2's
(``state_update``: ``ops/ssm_ops.py::ssm_decode_update`` is the caller
and says what the update is) and the gated delta rule's
(``delta_state_update``: ``ops/gated_delta_ops.py::delta_decode_update``).
Each caller's row loop is the form on every other platform and what its
body is held to (``tests/test_granite_block.py``,
``tests/test_qwen3_next_block.py``). What follows is the pipeline and
Mamba-2's body; the delta rule's differences are at ``_delta_body``.

The grid is ``(live rows, tiles)``, its first extent the live count the
step arrives with: rows past it cost no grid step. A slot ``[N, H P]``
float32 (2 MB at the published widths) goes through VMEM in row tiles of
``TILE_BYTES``, each one contiguous in the arena. Both arenas are passed
whole and aliased in and out; a tile is addressed by ``(layer, slot,
tile)`` through the prefetched scalars and rides Pallas's own block
pipeline, so the next tile (or the next row's first) is on its way in
and the last one on its way out while this one is computed. What is a
row of the batch (``keep``, ``xdt``, ``kept``, ``y``) stays in VMEM for
the whole call as the array the program has, rows of 8 to a tile: a
block of one row would ask the program for another layout, and a copy
of each array a layer to get there (five copies a layer, some 30 us
beside a kernel of 220: my chip run, PR 46).

The convolution's kept rows cannot leave by a DMA a row (a row of a
2-byte arena is half of a packed word, and Mosaic slices a tiled
dimension by whole tiles), so they go through the same pipeline by
``GROUP`` slots: a group's rows come in, the row's own is replaced, the
group goes out. Two rows whose slots share a group must then not be in
flight at once, so the rows are walked **in the order of their slots**:
such rows are neighbours, the pipeline keeps a block whose index does
not change where it is, and the second row writes into the first one's
result (``carried``).

With no live row at all the one step hands its blocks back as they came.

The arithmetic is the loop's, in float32 on the vector unit: ``S <- S
keep + B[:, None] xdt``, ``y = sum_n S[n, :] C[n]`` summed tile by tile;
with G groups of heads (nemotron_h: 8) a group at a time, its block of
``H P / G`` lanes (whole lane tiles) under its own ``B`` and ``C``.
A row's ``B`` and ``C`` lie along the lanes and the state's rows want
them along the sublanes; they are turned by a masked sum along the lanes
against the identity (one term is not zero, so it is exact). Handed over
transposed they would be read as they lie, but the compiler then lays
the whole convolution output they are sliced from batch-minor and
copies four arrays a layer back (5% of the device's time: my chip run,
PR 46). A row of ``kept`` is picked out of its ``PACK`` aligned rows
the same way, along the sublanes.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

# a tile of a slot in flight, two buffers each way: two a slot at the
# published widths. From 512 KB to a whole slot the chip reads 75.2, 76.0
# and 76.3% of the HBM peak at 41 rows (78% at 64, the same for all: the
# DMAs bound it, not the steps; my chip run, PR 46)
TILE_BYTES = 1024 * 1024
# slots whose convolution rows share a tile of the arena: the least a
# block of them can be
GROUP = 8
# rows of ``kept`` read together: a packed tile of a 2-byte type
PACK = 16


def _tile_rows(n_state, width):
    """The state rows a tile holds: ``TILE_BYTES`` worth in whole
    sublane groups of 8 where that divides the state, else the slot."""
    rows = max(8, TILE_BYTES // (4 * width) // 8 * 8)
    return rows if n_state % rows == 0 else n_state


def _tile_heads(heads, head_bytes):
    """The whole heads of ``head_bytes`` a tile of the delta rule's
    state holds: ``TILE_BYTES`` worth where that divides the heads, else
    all of them."""
    per = max(1, TILE_BYTES // head_bytes)
    return per if heads % per == 0 else heads


def _mamba_body(i, t, state_ref, out_ref, y_ref, keep_ref, xdt_ref, b_ref,
                c_ref, *, groups):
    """Row ``i``'s tile ``t`` of Mamba-2's step (module docstring)."""
    row = pl.ds(i, 1)
    rows = state_ref.shape[0]
    # B and C of the tile's state rows as columns: the row of the
    # batch laid along the sublanes by a masked sum along the lanes
    n_state = b_ref.shape[-1]
    wide = state_ref.shape[1] // groups
    own = jax.lax.broadcasted_iota(jnp.int32, (rows, n_state), 0) \
        + t * rows == jax.lax.broadcasted_iota(
            jnp.int32, (rows, n_state), 1)
    # a group at a time: its heads' lanes under its own B and C
    # (one group: every lane under the row's one B and C)
    for g in range(groups):
        # [1, N]: with groups the batch's row is a leading index and
        # the group a whole sublane row of its (G, N) tile (a lane
        # offset into a row picked by a traced index does not lower)
        its = (row, slice(None)) if groups == 1 \
            else (i, pl.ds(g, 1), slice(None))
        lanes = slice(None) if groups == 1 else pl.ds(g * wide, wide)
        tile = Ellipsis if groups == 1 else (slice(None), lanes)
        b = jnp.sum(jnp.where(own, b_ref[its], 0.0), axis=1,
                    keepdims=True)
        c = jnp.sum(jnp.where(own, c_ref[its], 0.0), axis=1,
                    keepdims=True)
        s = state_ref[tile] * keep_ref[row, lanes] \
            + b * xdt_ref[row, lanes]
        out_ref[tile] = s
        part = jnp.sum(s * c, axis=0, keepdims=True)

        @pl.when(t == 0)
        def _():
            y_ref[row, lanes] = part

        @pl.when(t > 0)
        def _():
            y_ref[row, lanes] += part


def _delta_body(i, t, state_ref, out_ref, y_ref, keep_ref, beta_ref, q_ref,
                k_ref, v_ref, *, tiles):
    """Row ``i``'s tile ``t`` of the gated delta rule's step: the tile
    is whole heads ``[heads a tile, K, V]`` (key-major: row ``n`` of a
    head the column ``n`` of its ``S^T``), because the rule reads ``S^T
    k``, a sum down a head's whole key dimension, before it writes. A
    head at a time, float32 on the vector unit::

        S <- keep S;  m = S^T k;  d = beta (v - m);  S <- S + k d^T
        o = S^T q

    ``keep`` and ``beta`` arrive spread over a head's V lanes and ``q``,
    ``k``, ``v`` as ``[B, H, 128]``: a row of the batch is a leading
    index and a head a whole sublane row. ``k`` and ``q`` lie along the
    lanes and the state's rows want them along the sublanes: turned by
    the masked sum against the identity, as Mamba-2's ``B`` and ``C``.
    The tile's place among the heads is static under ``pl.when``: one
    unrolled body a tile (two at the published widths)."""
    per = state_ref.shape[0]
    n_key = state_ref.shape[1]
    own = jax.lax.broadcasted_iota(jnp.int32, (n_key, n_key), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n_key, n_key), 1)

    def column(ref, at):
        return jnp.sum(jnp.where(own, ref[at], 0.0), axis=1, keepdims=True)

    def heads_of(first):
        for h in range(per):
            at = (i, pl.ds(first + h, 1), slice(None))       # [1, 128]
            k = column(k_ref, at)
            s = state_ref[h] * keep_ref[at]
            m = jnp.sum(s * k, axis=0, keepdims=True)
            s = s + k * (beta_ref[at] * (v_ref[at] - m))
            out_ref[h] = s
            y_ref[at] = jnp.sum(s * column(q_ref, at), axis=0,
                                keepdims=True)

    for tile in range(tiles):
        pl.when(t == tile)(functools.partial(heads_of, tile * per))


def _kernel(body, layer_ref, slots_ref, order_ref, n_ref, state_ref, *refs):
    """The pipeline's step ``(j, t)``: ``body`` over row ``order[j]``'s
    tile ``t`` (``refs``: what is a row of the batch, then ``kept`` and
    the group of convolution rows in; the state tile, ``y`` and the
    group out), and with the row's first tile its kept rows."""
    *operands, kept_ref, held_ref, out_ref, y_ref, conv_ref = refs
    j, t = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(n > 0)
    def _():
        i = order_ref[j]
        body(i, t, state_ref, out_ref, y_ref, *operands)

        @pl.when(t == 0)
        def _():
            # the convolution's kept rows: row ``i`` of ``kept``, picked
            # out of its aligned rows, into its slot's row of the group
            near = kept_ref[pl.ds(pl.multiple_of(i // PACK * PACK, PACK),
                                  PACK), :].astype(jnp.float32)
            kept = jnp.sum(jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, near.shape, 0) == i % PACK, near, 0.0), axis=0,
                keepdims=True)
            slot = slots_ref[i]
            its = jax.lax.broadcasted_iota(
                jnp.int32, conv_ref.shape, 0) == slot % GROUP
            before = slots_ref[order_ref[jnp.maximum(j, 1) - 1]] // GROUP
            carried = jnp.logical_and(j > 0, before == slot // GROUP)

            def put(group):
                conv_ref[...] = jnp.where(
                    its, kept, group.astype(jnp.float32)
                ).astype(conv_ref.dtype)

            @pl.when(carried)
            def _():
                put(conv_ref[...])

            @pl.when(jnp.logical_not(carried))
            def _():
                put(held_ref[...])

    @pl.when(n == 0)
    def _():
        out_ref[...] = state_ref[...]
        conv_ref[...] = held_ref[...]


def _over_live_slots(name, body, state, conv, layer, slots, n, operands,
                     kept, tile, tiles):
    """``body`` over rows ``0 .. n - 1`` of the batch, each in
    ``state[layer, slots[i]]`` (in ``tiles`` tiles of block shape
    ``tile``) and ``conv[layer, slots[i]]``; ``operands``: what is a row
    of the batch, the first of them the shape of ``y``. Returns (y,
    zeros from row ``n`` on as the loops leave them; state; conv)."""
    batch = operands[0].shape[0]
    slots = slots.astype(jnp.int32)
    n = jnp.reshape(n, (1,)).astype(jnp.int32)
    # the rows in the order of their slots, those past ``n`` behind them
    order = jnp.argsort(jnp.where(jnp.arange(batch) < n, slots,
                                  state.shape[1])).astype(jnp.int32)
    kept = jnp.pad(kept, ((0, -batch % PACK), (0, 0)))

    def of_slot(j, t, layer_ref, slots_ref, order_ref, n_ref):
        return (layer_ref[0], slots_ref[order_ref[j]], t) \
            + (0,) * (len(tile) - 1)

    def of_group(j, t, layer_ref, slots_ref, order_ref, n_ref):
        return (layer_ref[0], slots_ref[order_ref[j]] // GROUP, 0)

    def whole(arr):
        return pl.BlockSpec(arr.shape, lambda *_: (0,) * arr.ndim,
                            memory_space=pltpu.VMEM)

    tile_spec = pl.BlockSpec((None, None) + tuple(tile), of_slot,
                             memory_space=pltpu.VMEM)
    group = pl.BlockSpec((None, GROUP, conv.shape[2]), of_group,
                         memory_space=pltpu.VMEM)
    # what stays for the whole call (``y`` is another first operand) in
    # the pipeline's two buffers, the four tiles in flight, and room for
    # the groups and the compiler's own scratch
    vmem = 2 * sum(a.size * a.dtype.itemsize
                   for a in operands + (operands[0], kept)) \
        + 4 * 4 * math.prod(tile) + (8 << 20)
    state, y, conv = pl.pallas_call(
        functools.partial(_kernel, body),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # a row a live row: with none, one step hands its blocks back
            grid=(jnp.maximum(n[0], 1), tiles),
            in_specs=[tile_spec] + [whole(a) for a in operands]
            + [whole(kept), group],
            out_specs=[tile_spec, whole(operands[0]), group]),
        # the arenas stay in HBM: left to choose, the compiler moves the
        # convolution's whole arena (61 MB) into VMEM before a period's
        # first kernel and back behind its last, every step (my chip run,
        # PR 46)
        out_shape=[pltpu.HBM(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(operands[0].shape, jnp.float32),
                   pltpu.HBM(conv.shape, conv.dtype)],
        # operands count the prefetched scalars: 4 is the state, the
        # convolution's arena the last
        input_output_aliases={4: 0, 6 + len(operands): 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=vmem),
        name=name,
        interpret=interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, order, n, state,
      *operands, kept, conv)
    # the kernel writes no row of ``y`` from ``n`` on
    live = (jnp.arange(batch) < n).reshape((batch,) + (1,) * (y.ndim - 1))
    return jnp.where(live, y, 0.0), state, conv


def state_update(state, conv, layer, slots, n, keep, xdt, b, c, kept):
    """Mamba-2's step over rows ``0 .. n - 1`` of the batch, each in
    ``state[layer, slots[i]]`` and ``conv[layer, slots[i]]``: ``keep``
    and ``xdt`` [B, H P] and ``b``, ``c`` [B, N] (or [B, G, N]: group
    ``g``'s are those of lanes ``g H P / G`` on) float32, ``kept`` [B,
    (K - 1) C] at ``conv``'s dtype. Returns (y [B, H P] float32, zeros
    from row ``n`` on as the loop leaves them; state; conv)."""
    width = keep.shape[1]
    n_state = state.shape[2]
    groups = 1 if b.ndim == 2 else b.shape[1]
    rows = _tile_rows(n_state, width)
    return _over_live_slots(
        'ssm_state_update', functools.partial(_mamba_body, groups=groups),
        state, conv, layer, slots, n, (keep, xdt, b, c), kept,
        (rows, width), n_state // rows)


def delta_state_update(state, conv, layer, slots, n, keep, beta, q, k, v,
                       kept):
    """The gated delta rule's step over rows ``0 .. n - 1`` of the
    batch, each in ``state[layer, slots[i]]`` ([H, K, V] float32) and
    ``conv[layer, slots[i]]``: ``keep`` and ``beta`` [B, H, V] (a head's
    decay and write strength spread over its lanes), ``q`` and ``k`` [B,
    H, K] (a value head's own: its key head's), ``v`` [B, H, V],
    float32; ``kept`` as ``state_update``'s. Returns (o [B, H, V]
    float32, zeros from row ``n`` on; state; conv)."""
    heads, n_key, n_value = state.shape[2:]
    per = _tile_heads(heads, 4 * n_key * n_value)
    tiles = heads // per
    return _over_live_slots(
        'gdn_state_update', functools.partial(_delta_body, tiles=tiles),
        state, conv, layer, slots, n, (keep, beta, q, k, v), kept,
        (per, n_key, n_value), tiles)
