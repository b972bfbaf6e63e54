"""Where a learned selection's k-th largest score lies, row by row, as
one Pallas TPU kernel over row tiles
(``ops/latent_moe_ops.py::select_topk`` is the caller, says what the
selection is and makes the choice from what this returns;
``kth_and_cut_dense`` there is the form on every other platform and
what this kernel is held to, ``tests/test_latent_moe_block.py``).

A row's choice is fixed by two numbers: the k-th largest of its keys
(the scores' bits mapped onto int32 in the floats' order) and, among
the columns that hold exactly that key, the column of the last one the
choice has room for (ties go to the lower column, as ``lax.top_k``
takes them). Both are found by counting: the key bit by bit from the
top (32 passes that count the keys at or above a candidate), the column
bit by bit the same way (one pass a bit of a column index, counting the
ties below a candidate). The counting passes are what costs, and they
run here over a tile's keys **in VMEM**, over **the column blocks its
longest row holds** and no further: a tile of up to ``TILE_ROWS`` rows comes
in once through Pallas's own pipeline, its keys are made once (columns
at and past a row's length the lowest key there is), and every pass
reads them where they lie. A tile none of whose rows holds more than
``k`` positions counts nothing (each such row takes every position it
holds), so a dead tile and a chunk at the head of a prompt cost the
tile's way in and out. The lengths arrive twice: prefetched as scalars
for the loops' bounds, and as a column for the rows' masks.

``columns_counted`` is the bound as a function of the lengths alone,
for the engine's counters (``decode.selection_columns_counted``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

# rows a tile, at most: whole sublane groups of a 32-bit register. A pass
# over a tile of 32 takes half the time a row of one over a tile of 8
# (0.18 / 0.25 / 0.51 ms a glm chunk of 512 rows that ends at 5,300 /
# 12,000 / 34,816 against 0.38 / 0.51 / 0.96: my chip run, PR 57); the
# rows of a chunk are of one length to within the chunk, and a step's
# call is its fixed 36 us at any tile
TILE_ROWS = 32
# lane tiles a column block may hold: the passes' inner loop is unrolled
# over them, one register a lane tile
BLOCK_LANES = 16
LANES = 128
# the lowest key: what a column a row does not hold counts as
LOWEST = np.iinfo(np.int32).min


def ordered_keys(scores):
    """float32 -> int32 in the floats' order: a larger score has a
    larger key, and equal bits an equal one."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)


def tile_rows(rows):
    """Rows a tile of the kernel holds: the most of 32, 16 and 8 that
    divide the rows, else all of them as one tile."""
    return next((t for t in (TILE_ROWS, 16, 8) if rows % t == 0), rows)


def block_columns(columns):
    """Columns a block of the passes' loop holds: the most whole lane
    tiles up to ``BLOCK_LANES`` that divide the columns, so every block
    is full; all the columns where they are not whole lane tiles."""
    if columns % LANES:
        return columns
    tiles = columns // LANES
    return LANES * max(d for d in range(1, BLOCK_LANES + 1)
                       if tiles % d == 0)


def columns_counted(lens, k, columns, xp=jnp):
    """The (row, column) keys one counting pass of the kernel reads for
    rows of lengths ``lens`` [N] under a selection of ``k`` of
    ``columns``: a tile whose longest row holds more than ``k`` runs the
    column blocks up to that row's last, every other tile none. A pure
    function of the lengths over ``xp`` (numpy where the engine counts),
    as ``paged_attention.block_bounds`` is of the attention's."""
    rows, block = tile_rows(len(lens)), block_columns(columns)
    longest = lens.reshape(-1, rows).max(axis=1)
    blocks = xp.where(longest > k, -(-longest // block), 0)
    return blocks.sum() * block * rows


def _kernel(lens_ref, scores_ref, held_ref, kth_ref, cut_ref, keys_ref, *,
            k):
    rows, columns = scores_ref.shape
    block = block_columns(columns)
    lanes = LANES if block % LANES == 0 else block
    first = pl.program_id(0) * rows
    longest = lens_ref[first]
    for r in range(1, rows):
        longest = jnp.maximum(longest, lens_ref[first + r])
    blocks = (longest + block - 1) // block
    held = held_ref[...]                                     # [rows, 1]
    # what a row that holds at most k positions gets: every key lies
    # above the lowest, and every column at or below the last
    kth_ref[...] = jnp.full((rows, 1), LOWEST, jnp.int32)
    cut_ref[...] = jnp.full((rows, 1), columns, jnp.int32)

    def columns_of(j):
        return pl.ds(pl.multiple_of(j * block, block), block)

    def column_ids(j):
        return j * block + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block), 1)

    def tally(test):
        """[rows, 1]: a row's keys that ``test(keys, block)`` holds of,
        over the live blocks; lane tile by lane tile into one register a
        row tile, the lanes summed once at the end."""
        def one(j, acc):
            hit = jnp.where(test(keys_ref[:, columns_of(j)], j), 1, 0)
            # pairwise, so that the adds do not wait on one another
            parts = [hit[:, c * lanes:(c + 1) * lanes]
                     for c in range(block // lanes)]
            while len(parts) > 1:
                parts = [a + b for a, b in zip(parts[::2], parts[1::2])] \
                    + parts[len(parts) // 2 * 2:]
            return acc + parts[0]
        acc = jax.lax.fori_loop(0, blocks, one,
                                jnp.zeros((rows, lanes), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    @pl.when(longest > k)
    def _():
        def keyed(j, _):
            keys_ref[:, columns_of(j)] = jnp.where(
                column_ids(j) < held,
                ordered_keys(scores_ref[:, columns_of(j)]), LOWEST)
            return 0
        jax.lax.fori_loop(0, blocks, keyed, 0)

        def grow(i, kth):
            # bit 31 - i of the key as an unsigned number: in int32's
            # order the top bit counts the other way round
            cand = kth ^ (jnp.int32(1) << (31 - i))
            enough = tally(lambda keys, j: keys >= cand) >= k
            return jnp.where(enough, cand, kth)
        kth = jax.lax.fori_loop(0, 32, grow,
                                jnp.full((rows, 1), LOWEST, jnp.int32))
        room = k - tally(lambda keys, j: keys > kth)
        ties = tally(lambda keys, j: keys == kth)
        n_bits = max(columns - 1, 1).bit_length()

        def narrow(i, cut):
            # the largest column with fewer than ``room`` ties below it
            # is the room-th tie's own
            cand = cut | (jnp.int32(1) << (n_bits - 1 - i))
            below = tally(lambda keys, j:
                          (keys == kth) & (column_ids(j) < cand))
            return jnp.where(below < room, cand, cut)
        # where every tie at the k-th key has room, as nearly always,
        # no column has to be found: the last does for the last tie
        crowded = (held > k) & (ties > room)
        cut = jax.lax.cond(
            jnp.max(jnp.where(crowded, 1, 0)) > 0,
            lambda: jax.lax.fori_loop(0, n_bits, narrow,
                                      jnp.zeros((rows, 1), jnp.int32)),
            lambda: jnp.zeros((rows, 1), jnp.int32))
        kth_ref[...] = jnp.where(held > k, kth, LOWEST)
        cut_ref[...] = jnp.where(crowded, cut, columns)


def kth_and_cut(scores, lens, *, k):
    """``scores`` float32 [N, C], ``lens`` int32 [N] -> (kth [N], cut
    [N]) int32: of each row that holds more than ``k`` positions the
    k-th largest key among the columns below its length and, where
    more columns hold that key than the choice has room for, the column
    of the last one it takes (else C); (``LOWEST``, C) of every other
    row (``latent_moe_ops.select_topk`` has the choice
    they make)."""
    kth, cut = _kth_and_cut(scores, lens.astype(jnp.int32), k=k,
                            interpret=interpret_mode())
    return kth[:, 0], cut[:, 0]


# One jitted function a process: both scoring sites of a program reuse
# the one traced kernel a shape
@functools.partial(jax.jit, static_argnames=('k', 'interpret'))
def _kth_and_cut(scores, lens, *, k, interpret):
    n, columns = scores.shape
    rows = tile_rows(n)

    def tile(t, lens_ref):
        return (t, 0)

    one = pl.BlockSpec((rows, 1), tile, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // rows,),
            in_specs=[pl.BlockSpec((rows, columns), tile,
                                   memory_space=pltpu.VMEM), one],
            out_specs=[one, one],
            scratch_shapes=[pltpu.VMEM((rows, columns), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',),
            # a tile's scores on their way in, twice, and its keys
            vmem_limit_bytes=3 * rows * columns * 4 + (4 << 20)),
        name='selection_kth',
        interpret=interpret,
    )(lens, scores, lens[:, None])
