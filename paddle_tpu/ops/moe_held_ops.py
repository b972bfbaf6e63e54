"""Routed experts for serving: score all, compute the ones held here.

One chip of an expert-parallel deployment holds ``E`` consecutive
experts of the ``n_experts`` the router scores (``first .. first + E -
1``). Every row is routed over all of them (sigmoid scores or a
softmax over them, by the block; the ``top_k`` largest, weights
normalised over all that were chosen, wherever they live, or the
chosen scores themselves times a factor), and this
chip adds what its own experts give: the
partial result that the exchange between chips would sum. A router may
be wider than the experts: its outputs past ``n_experts`` are identity
experts (``identity_weight``), which match no held index, make no
assignment and cost a row one multiply. There is no
capacity and nothing is dropped, so ``ops/moe_ops.py``'s ``[S, E, C]``
dispatch (Switch/GShard, for the training programs) has no part here.

The routed experts' product follows the routing
(``routed_experts``): the (row, held expert) assignments of the live
rows are grouped by expert and the three products

    hidden[i, :] = silu(x[n_i] Wg[e]) * (x[n_i] Wu[e]) * gate[n_i, e]
    y[i]         = hidden[i, :] Wd[e]

(or, for an expert of two matrices and no gate matrix, nemotron_h's:
``hidden[i, :] = relu(x[n_i] W1[e])^2 * gate[n_i, e]``, ``y[i] =
hidden[i, :] W2[e]``; ``routed_experts`` with ``w_gate`` None, a static
form of the same function through the same tile list, kernel and
``serves`` rule) run over row tiles of ``TILE_ROWS`` rows of one expert
each, as many tiles as the step has (the sum over the experts of ceil(rows that chose
it / TILE_ROWS)): an expert no live row chose costs nothing and none of
its bytes is read. A tile takes its expert out of the layer-stacked
weights where they lie. A row's result is the float32 sum of its own
experts' ``y`` in ascending expert order, whichever tile each landed in
and whoever shared it, so it does not depend on the other rows of the
batch. A program of at most ``TILE_ROWS`` rows (every decode step)
needs no grouping: a tile is all its rows under the expert's gate
column, which is 0 for the rows that did not choose it.

It took the place of the gate-masked product over every expert held,
which read all of them whatever was chosen and spent ``E`` times the
products a prefill row needs (PERF.md, PR 33). It has two forms, chosen
by the platform the program is lowered for
(``jax.lax.platform_dependent``) and by the static shape:

- **on a TPU, one Pallas kernel a layer** over the step's tile list
  (``ops/pallas/moe_routed_product.py``): the stacks passed whole, a
  tile's blocks of its expert's three matrices through the block
  pipeline, the next block (and across a tile's end the next expert's
  first) on its way in while this one multiplies, ``hidden`` never out
  of VMEM; a chunk's tile picks its rows out of the chunk and adds its
  results into the chunk's inside the kernel. The width of a block of
  ``F`` follows the expert's size. The rows and their result stay in
  VMEM, so the kernel takes a chunk as long as that allows (``serves``:
  4,096 rows at mellum2_12b's widths, 2,048 at command_a_plus', 1,024
  at kimi_k2_6's and dots3_note's) and a longer one keeps the loop;
- **everywhere else, a loop over the tiles** (``fori_loop``), an
  iteration three fusions that each slice their matrix out of the
  stacks; a chunk's tiles are gathered before and their results written
  to their places and summed a row at a time behind the loop. It is
  what the kernel is held to (``tests/test_moe_routed_kernel.py``) and
  what an interpreted kernel would slow every CPU test that serves by.

On a TPU v5e at the published widths, the product alone under a scan of
the layers, all that a layer's routed experts cost a tile, loop ->
kernel (``tools/moe_routed_microbench.py``, my chip run, PR 47; PERF.md
section 6 has the table), a decode step's 32 live rows and a chunk of
512: mellum2_12b (12.4 MB an expert, 15.1 us at the HBM peak) 21.6 ->
16.6 us and 35.9 -> 17.2 (the loop's gather of the tile's rows, its
three fusions that start cold, the write of the tile's results and the
rounds that sum them, against one pipelined kernel that does all four);
command_a_plus (100 MB, 123 us) 142.5 -> 136.0 and 164.4 -> 136.5;
dots3_note (47 MB, 58 us) 68.9 -> 63.1 and 95.4 -> 64.0; kimi_k2_6 (88
MB, 107 us) 127.4 -> 124.4 and 177.4 -> 126.6. At the longest chunk the
kernel takes: mellum2_12b's 4,096 rows 42.8 -> 25.6 (a tile's one-hot
product over the chunk's rows is 12 us of the matrix unit there: 2,048
rows 41.7 -> 18.8), command_a_plus' 2,048 173.1 -> 140.4, kimi_k2_6's
1,024 213.4 -> 138.4, dots3_note's 1,024 113.3 -> 66.4: the kernel is
the shorter wherever it fits. Where it is not: kimi_k2_6's decode step
of a row or two, which finds 0 to 3 of the 12 held experts chosen in
five layers: 240 -> 244 us a call at 1 tile, 424 -> 455 to 464 at 3 (a
call's first block has nothing to come in under), 223 to 226 -> 228 to
238 at none. The decode step's layout (in place) is kept beside the
chunk's because the chunk's, run at 32 rows, costs its sort and its
one-hot product: 16.8 against 16.6 us a tile at 32 live rows of
mellum2_12b's, 18.3 against 17.2 at 2; 169 to 171 against 152 to 155 at
4 of kimi_k2_6's. The loop had been the only form tried since PR 33; an
iteration of it cannot read the next expert while it multiplies this
one.

The shared experts, which every row takes, run the dense gate-masked
product over all of them (``gated_experts``) with the constant gate
``1 / S`` (their mean); it is also the tests' oracle for the routed
ones.
"""

import functools

import jax
import jax.numpy as jnp

from .pallas.moe_routed_product import TILE_ROWS, routed_product, serves

__all__ = ['route_sigmoid_topk', 'route_softmax_topk', 'held_gates',
           'identity_weight',
           'gated_experts', 'routed_experts', 'row_tiles', 'load_stats',
           'TILE_ROWS']

# TILE_ROWS: the rows of one expert that one tile of the routed product
# takes, the matrix unit's 128 on every TPU generation this runs on


def _router_logits(x, router):
    return jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def route_softmax_topk(x, router, top_k, bias=None, scale=None):
    """``route_sigmoid_topk`` for a softmax router: the scores are the
    softmax of the logits over every output of the router, float32 at
    the highest precision. As mellum has it (``scale`` None): the
    ``top_k`` largest are chosen and the weights are those scores
    normalised over the chosen (``norm_topk_prob``), which is the
    softmax over the chosen experts' logits alone. As longcat_flash has
    it (``scale`` given): the ``top_k`` largest of score + ``bias``
    [outputs] are chosen (ties to the lower index) and weigh their own
    scores times ``scale``, **not** normalised over the chosen: how much
    of a row goes through the experts at all is the router's to say."""
    scores = jax.nn.softmax(_router_logits(x, router), axis=-1)
    if scale is None:
        top, chosen = jax.lax.top_k(scores, top_k)
        return chosen, top / jnp.sum(top, axis=-1, keepdims=True)
    picked = scores if bias is None else scores + bias.astype(jnp.float32)
    _, chosen = jax.lax.top_k(picked, top_k)
    return chosen, jnp.take_along_axis(scores, chosen, axis=1) * scale


def identity_weight(chosen, weights, n_real):
    """float32 [N]: the sum of each row's weights on identity experts,
    the router's outputs at and past ``n_real``. An identity expert
    returns its input, so all of a row's together are this one factor
    times the row: they make no assignment, no row tile and read no
    weight, wherever the real experts live."""
    return jnp.sum(jnp.where(chosen >= n_real, weights, 0.0), axis=1)


def route_sigmoid_topk(x, router, top_k, bias=None, scale=1.0):
    """``x`` [N, D] float32, ``router`` [D, n_experts] -> (chosen
    [N, k] int32, weights [N, k] float32). Scores and weights in
    float32 at the highest matmul precision whatever the weights'
    dtype: a choice that flips moves a row's whole expert sum.
    ``bias`` [n_experts] (``noaux_tc``) is added to the scores for the
    choosing only: the weights are the chosen experts' own scores,
    normalised, times ``scale`` (``routed_scaling_factor``: the routed
    sum is scaled, the shared experts are not)."""
    scores = jax.nn.sigmoid(_router_logits(x, router))
    if bias is None:
        top, chosen = jax.lax.top_k(scores, top_k)
    else:
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, chosen, axis=1)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    return chosen, weights * scale


def held_gates(chosen, weights, first, n_held):
    """(gate [N, E] float32, hit [N, E] bool): each row's weight for
    the experts ``first .. first + n_held - 1``, and which it chose. A
    chosen index outside them (an expert of another chip, an identity
    expert past the real ones) is nobody's here."""
    held = first + jnp.arange(n_held, dtype=chosen.dtype)
    match = chosen[:, :, None] == held[None, None, :]      # [N, k, E]
    gate = jnp.sum(jnp.where(match, weights[:, :, None], 0.0), axis=1)
    return gate, jnp.any(match, axis=1)


def gated_experts(x, gate, w_gate, w_up, w_down):
    """``x`` [N, D], ``gate`` [N, E], ``w_gate``/``w_up`` [E, D, F],
    ``w_down`` [E, F, D] -> float32 [N, D]. Products take their
    operands at the weights' dtype and accumulate in float32; the
    experts' results are summed in float32."""
    e = w_gate.shape[0]
    rows = jnp.broadcast_to(x.astype(w_gate.dtype), (e,) + x.shape)
    hidden = jax.nn.silu(jnp.einsum(
        'end,edf->enf', rows, w_gate,
        preferred_element_type=jnp.float32)) * jnp.einsum(
            'end,edf->enf', rows, w_up, preferred_element_type=jnp.float32)
    hidden = hidden * jnp.transpose(gate)[:, :, None]
    # expert by expert like the two above, then summed: contracting
    # over (expert, width) at once needs w_down as [E * F, D], and that
    # reshape between the layer loop's slice of the stacked weights and
    # the product made the compiler copy the slice out (0.5 GB a layer
    # at the published widths; v5e compile of the decode step, PR 28)
    out = jnp.einsum('enf,efd->end', hidden.astype(w_down.dtype), w_down,
                     preferred_element_type=jnp.float32)
    return jnp.sum(out, axis=0)


def row_tiles(rows):
    """The row tiles of ``TILE_ROWS`` that ``rows`` rows of one expert
    make (an int, or int32 [E] for each expert's load)."""
    return -(-rows // TILE_ROWS)


def routed_experts(x, gate, hit, valid, most, w_gate, w_up, w_down, layer):
    """``x`` [N, D]; ``gate`` / ``hit`` [N, E] from ``held_gates``;
    ``valid`` [N] bool (a row that is not live makes no assignment);
    ``most`` the held experts one row can choose, min(top_k, E);
    ``w_gate`` / ``w_up`` [L, E, D, F] and ``w_down`` [L, E, F, D]
    stacked over the layers, of which ``layer`` is this one (``w_gate``
    None: the expert is ``relu(x w_up)^2`` then ``w_down``) ->
    float32 [N, D]. ``sum(row_tiles(load))`` tiles are run: the
    kernel's grid on a TPU, the loop's trip count elsewhere.
    Operand dtypes as ``gated_experts``."""
    live = hit & valid[:, None]
    args = (x.astype(w_up.dtype), jnp.where(live, gate, 0.0), live,
            w_gate, w_up, w_down, layer)
    if x.shape[0] <= TILE_ROWS:
        by_loop, by_kernel = _in_place_by_loop, _in_place_by_kernel
    else:
        by_loop, by_kernel = (functools.partial(form, most) for form in (
            _grouped_by_loop, _grouped_by_kernel))
    # the kernel keeps the rows and their result in VMEM: a chunk too
    # long for that keeps the loop (``serves`` has the lengths)
    if not serves(*x.shape, w_up.shape[3], w_up.shape[1],
                  w_up.dtype.itemsize, 2 if w_gate is None else 3):
        return by_loop(*args)
    return jax.lax.platform_dependent(*args, tpu=by_kernel, default=by_loop)


def _product_of(w_gate, w_up, w_down, layer):
    """The loop's products of one row tile under one expert: three, or
    two where the expert has no gate matrix (``w_gate`` None)."""
    def of(stack, expert):
        # sliced where it lies: the compiler fuses it into the product
        return jax.lax.dynamic_slice(
            stack, (layer, expert, 0, 0), (1, 1) + stack.shape[2:])[0, 0]

    def product(tile, tile_gate, expert):
        if w_gate is None:
            hidden = jnp.square(jax.nn.relu(jnp.matmul(
                tile, of(w_up, expert),
                preferred_element_type=jnp.float32)))
        else:
            hidden = jax.nn.silu(jnp.matmul(
                tile, of(w_gate, expert),
                preferred_element_type=jnp.float32)) * jnp.matmul(
                    tile, of(w_up, expert),
                    preferred_element_type=jnp.float32)
        hidden = hidden * tile_gate[:, None]
        return jnp.matmul(hidden.astype(w_down.dtype), of(w_down, expert),
                          preferred_element_type=jnp.float32)
    return product


def _expert_of(tile_end, t):
    """Tile ``t``'s expert (``t`` an int32 or int32 [T]): the first
    whose tiles end past it; the last expert for a tile past them all."""
    t = jnp.asarray(t)
    return jnp.minimum(
        jnp.sum(tile_end <= t[..., None], axis=-1, dtype=jnp.int32),
        tile_end.shape[0] - 1)


def _tile_ends(live):
    """(load, tiles, tile_end), int32 [E] each: the live rows on each
    expert, the tiles they make and where each expert's tiles end."""
    load = jnp.sum(live, axis=0, dtype=jnp.int32)
    tiles = row_tiles(load)
    return load, tiles, jnp.cumsum(tiles)


# In place: one tile an expert, all the rows under its gate column; the
# rows that did not choose it add exact zeros.

def _in_place_by_loop(rows, gate, live, w_gate, w_up, w_down, layer):
    tile_end = _tile_ends(live)[2]
    product = _product_of(w_gate, w_up, w_down, layer)

    def one(t, out):
        expert = _expert_of(tile_end, t)
        return out + product(rows, jax.lax.dynamic_index_in_dim(
            gate, expert, axis=1, keepdims=False), expert)
    return jax.lax.fori_loop(0, tile_end[-1], one,
                             jnp.zeros(rows.shape, jnp.float32))


def _in_place_by_kernel(rows, gate, live, w_gate, w_up, w_down, layer):
    n = rows.shape[0]
    tile_end = _tile_ends(live)[2]
    # whole packed sublanes of a 2-byte type: every program's rows are
    # (a batch of 32); a test's 13 are padded by rows under gates of 0
    pad = -n % 16
    return routed_product(
        jnp.pad(rows, ((0, pad), (0, 0))),
        jnp.pad(jnp.transpose(gate), ((0, 0), (0, pad))),
        _expert_of(tile_end, jnp.arange(gate.shape[1])), tile_end[-1],
        w_gate, w_up, w_down, layer)[:n]


# Grouped: the assignments in order of (expert, row); a tile is
# TILE_ROWS consecutive ones of one expert from a multiple of TILE_ROWS
# past its first. The loop writes each tile's results to their places
# in that order and then sums a row's own; the kernel adds a tile's
# rows into the result as it goes, which is the same sums in the same
# order: a row meets its experts in ascending order either way.

def _keys(live):
    """int32 [E * N]: (expert, row) of every live assignment as expert *
    N + row in that order, E * N for one that is not; sorted, the live
    ones lead in order of (expert, row)."""
    n, e = live.shape
    key = jnp.where(live, jnp.arange(e, dtype=jnp.int32)[None, :] * n
                    + jnp.arange(n, dtype=jnp.int32)[:, None], e * n)
    return key.T.reshape(-1)


def _grouped_by_kernel(most, rows, gate, live, w_gate, w_up, w_down,
                       layer):
    n, e = live.shape
    load, tiles, tile_end = _tile_ends(live)
    # every tile the routing can make: a tile a TILE_ROWS assignments
    # and one more an expert, and no more than all the rows on every
    # expert
    most_tiles = min(n * most // TILE_ROWS + e, e * row_tiles(n))
    expert = _expert_of(tile_end, jnp.arange(most_tiles))         # [T]
    ahead = jnp.arange(most_tiles) - (tile_end - tiles)[expert]
    nth_of = jnp.cumsum(live, axis=0, dtype=jnp.int32) - 1        # [N, E]
    return routed_product(
        rows, jnp.transpose(gate), expert, tile_end[-1],
        w_gate, w_up, w_down, layer, groups=(
            jnp.transpose(jnp.where(live, nth_of, -1)), ahead,
            (jnp.cumsum(load) - load)[expert] + ahead * TILE_ROWS,
            jnp.clip(load[expert] - ahead * TILE_ROWS, 0, TILE_ROWS),
            jnp.sort(_keys(live))[:n * most] % n))


def _grouped_by_loop(most, rows, gate, live, w_gate, w_up, w_down, layer):
    n, d = rows.shape
    load, tiles, tile_end = _tile_ends(live)
    start = jnp.cumsum(load) - load                               # [E]
    key, by_place = jax.lax.sort((_keys(live), gate.T.reshape(-1)),
                                 num_keys=1)
    cap = n * most
    row_at = jnp.pad(key[:cap] % n, (0, TILE_ROWS))
    gate_at = jnp.pad(by_place[:cap], (0, TILE_ROWS))
    product = _product_of(w_gate, w_up, w_down, layer)

    def one(t, results):
        expert = _expert_of(tile_end, t)
        ahead = t - (tile_end[expert] - tiles[expert])
        at = start[expert] + ahead * TILE_ROWS
        mine = jnp.arange(TILE_ROWS) < load[expert] - ahead * TILE_ROWS
        tile = jnp.take(rows, jax.lax.dynamic_slice(
            row_at, (at,), (TILE_ROWS,)), axis=0)
        tile_gate = jnp.where(mine, jax.lax.dynamic_slice(
            gate_at, (at,), (TILE_ROWS,)), 0.0)
        # the places past this expert's last belong to tiles that come
        # later and write them again
        return jax.lax.dynamic_update_slice(
            results, product(tile, tile_gate, expert), (at, 0))
    results = jax.lax.fori_loop(
        0, tile_end[-1], one,
        jnp.zeros((cap + TILE_ROWS, d), jnp.float32))
    # a row's own results, in ascending expert order, as many rounds as
    # the row with the most has; ``place[n, e]`` is where row n's lies
    place = start[None, :] + jnp.cumsum(live, axis=0, dtype=jnp.int32) - 1
    nth = jnp.cumsum(live, axis=1, dtype=jnp.int32)               # [N, E]

    def add(j, out):
        pick = live & (nth == j + 1)
        y = jnp.take(results, jnp.sum(jnp.where(pick, place, 0), axis=1),
                     axis=0)
        return out + jnp.where(jnp.any(pick, axis=1)[:, None], y, 0.0)
    return jax.lax.fori_loop(0, jnp.max(nth), add,
                             jnp.zeros((n, d), jnp.float32))


def load_stats(hit, valid, chosen=None, n_real=None):
    """int32 [4] over the rows that are ``valid``: choices that landed
    on an expert held here, rows on the busiest of them, how many of
    them any row chose, and the row tiles ``routed_experts`` runs for
    them (its loop's trip count). Under a router with identity experts
    (``chosen`` [N, k], ``n_real``) k + 1 more: the valid rows that
    chose 0, 1 .. k real experts (held here or not), from which the
    real and the identity assignments follow."""
    load = jnp.sum(hit & valid[:, None], axis=0, dtype=jnp.int32)  # [E]
    out = jnp.stack([jnp.sum(load), jnp.max(load),
                     jnp.sum(load > 0, dtype=jnp.int32),
                     jnp.sum(row_tiles(load))])
    if chosen is None:
        return out
    real = jnp.sum(chosen < n_real, axis=1, dtype=jnp.int32)       # [N]
    counts = jnp.arange(chosen.shape[1] + 1, dtype=jnp.int32)
    return jnp.concatenate([out, jnp.sum(
        (real[:, None] == counts[None, :]) & valid[:, None], axis=0,
        dtype=jnp.int32)])
