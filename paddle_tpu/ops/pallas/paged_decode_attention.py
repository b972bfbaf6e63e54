"""The latent decode attention of one layer as one Pallas TPU kernel over
the step's (row, column block) pair list
(``paged_attention.py::paged_attention_blocked`` is the caller, makes the
list and says what a pair is; its loop over the list is the form on
every other platform and what this kernel is held to,
``tests/test_latent_decode_kernel.py``).

The grid is the live pairs (``count``, a traced value): a row that holds
no pair costs no grid step. The latent arena ``[L, NB, bs, W]`` is
passed whole and stays in HBM; a pair's ``per`` pages, each ``bs`` rows
of ``W`` contiguous in the arena, come in by one DMA a page through the
prefetched page ids into one of two VMEM buffers, the next pair's while
this pair multiplies (where the loop gathers eight pairs' pages, then
runs two products and eight merges, one fusion after another). Scores
``[H, per bs]`` float32 from the rows as they lie, the softmax partial
and ``p rows[:, :r]``; a row's running maximum, normaliser and
accumulator stay in VMEM scratch from its first pair to its last and
its partials are merged into them in column order by the loop's own
rule (the side that holds the larger maximum is taken as it is), so a
row's result is its own columns' alone, whatever else the batch holds.
The pair that closes a row writes ``acc / norm`` to that row's block of
the result; a row's query block and result block ride Pallas's own
pipeline, fetched and written back where the pair list moves to another
row. Rows that hold no pair are never written: the caller masks them to
0.

``layer`` is an operand, never a Python constant, and the kernel's
wrapper is one jitted function a process: a lead layer and a scanned
one, a second call site and a second program all reuse one traced
kernel a head shape (what a warm start pays for the kernel: PERF.md
section 6, PR 51).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

# what a column no row sees scores, as in the loop
_NEG_INF = -1e9
# the rows of ``listed``
ROW, BLOCK, LO, HI, OPENS, CLOSES = range(6)


def _kernel(layer_ref, count_ref, listed_ref, pages_ref, q_ref, arena_ref,
            *refs, per, rank, selects):
    if selects:
        chosen_ref, refs = refs[0], refs[1:]
    out_ref, rows_ref, sem_ref, top_ref, norm_ref, acc_ref = refs
    t = pl.program_id(0)
    count = count_ref[0]
    bs = rows_ref.shape[1] // per
    bk = per * bs

    def pages(pair, slot):
        """A pair's page copies into buffer ``slot``. Unrolled: issued
        from a ``fori_loop`` a pair took 1.82 us where it takes 1.51 at
        longcat_flash_chat's shape, for 0.03 s more of lowering (my
        chip run and CPU count, PR 51)."""
        return [pltpu.make_async_copy(
            arena_ref.at[layer_ref[0], pages_ref[pair * per + k]],
            rows_ref.at[slot, pl.ds(k * bs, bs)], sem_ref.at[slot])
            for k in range(per)]

    def fetch(pair, slot):
        for page in pages(pair, slot):
            page.start()

    # with no pair at all the one step there is reads nothing
    @pl.when(count > 0)
    def _():
        slot = t % 2
        pl.when(t == 0)(lambda: fetch(0, 0))
        # the next pair's pages are on their way while this one multiplies
        pl.when(t + 1 < count)(lambda: fetch(t + 1, 1 - slot))
        for page in pages(t, slot):
            page.wait()

        rows = rows_ref[slot]                                  # [bk, W]
        # float32 operands multiply as float32, as in the loop
        exact = jax.lax.Precision.HIGHEST \
            if rows.dtype == jnp.float32 else None
        scores = jax.lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32)                # [H, bk]
        col = listed_ref[BLOCK, t] * bk + \
            jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        seen = (col >= listed_ref[LO, t]) & (col < listed_ref[HI, t])
        if selects:
            seen &= chosen_ref[pl.ds(listed_ref[BLOCK, t], 1), :] > 0
        scores = jnp.where(seen, scores, _NEG_INF)
        top = jnp.max(scores, axis=-1, keepdims=True)          # [H, 1]
        w = jnp.where(seen, jnp.exp(scores - top), 0.0)
        norm = jnp.sum(w, axis=-1, keepdims=True)
        acc = jnp.dot(w.astype(rows.dtype), rows[:, :rank],
                      precision=exact,
                      preferred_element_type=jnp.float32)      # [H, r]

        @pl.when(listed_ref[OPENS, t] > 0)
        def _():
            top_ref[...] = jnp.full_like(top_ref, _NEG_INF)
            norm_ref[...] = jnp.zeros_like(norm_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # the loop's merge: the side that holds the larger maximum is
        # taken as it is (its factor would be exactly 1)
        row_top, row_norm = top_ref[...], norm_ref[...]
        older = row_top >= top
        after = jnp.maximum(row_top, top)
        keep, scale = jnp.exp(row_top - after), jnp.exp(top - after)
        row_norm = jnp.where(older, row_norm + scale * norm,
                             keep * row_norm + norm)
        row_acc = jnp.where(older, acc_ref[...] + scale * acc,
                            keep * acc_ref[...] + acc)
        top_ref[...] = after
        norm_ref[...] = row_norm
        acc_ref[...] = row_acc

        @pl.when(listed_ref[CLOSES, t] > 0)
        def _():
            out_ref[...] = row_acc / jnp.where(row_norm == 0.0, 1.0,
                                               row_norm)


def pair_attention(q, arena, layer, listed, pages, count, chosen=None, *,
                   per, rank):
    """The kernel over pairs ``0 .. count - 1`` of the list: q [B, H, W]
    at the arena's dtype, already scaled; ``arena`` [L, NB, bs, W];
    ``listed`` int32 [6, N], a pair's row, column block, the bounds
    ``lo <= column < hi`` of what its row sees, whether it opens and
    whether it closes its row; ``pages`` int32 [N * per], a pair's page
    ids (each a real page); ``chosen`` [B, blocks a table, per * bs]
    (nonzero: the row sees the column) or None. Returns float32 [B, H,
    rank]; a row that holds no pair is not written."""
    return _pair_attention(q, arena, layer, listed, pages, count, chosen,
                           per=per, rank=rank, interpret=interpret_mode())


# One jitted function a process: every call site of a head shape, in
# every program, reuses the one traced kernel
@functools.partial(jax.jit, static_argnames=('per', 'rank', 'interpret'))
def _pair_attention(q, arena, layer, listed, pages, count, chosen, *, per,
                    rank, interpret):
    b, h, width = q.shape
    bs = arena.shape[2]
    selects = chosen is not None

    def of_row(t, layer_ref, count_ref, listed_ref, pages_ref):
        return (listed_ref[ROW, t], 0, 0)

    in_specs = [pl.BlockSpec((None, h, width), of_row,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q, arena]
    if selects:
        in_specs.append(pl.BlockSpec((None,) + chosen.shape[1:], of_row,
                                     memory_space=pltpu.VMEM))
        operands.append(chosen.astype(jnp.int32))
    scalars = [jnp.reshape(layer, (1,)), jnp.reshape(count, (1,)), listed,
               pages]
    return pl.pallas_call(
        functools.partial(_kernel, per=per, rank=rank, selects=selects),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(jnp.maximum(count, 1),),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, h, rank), of_row,
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, per * bs, width), arena.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        name='paged_decode_attention',
        interpret=interpret,
    )(*[s.astype(jnp.int32) for s in scalars], *operands)
