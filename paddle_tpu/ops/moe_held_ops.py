"""Routed experts for serving: score all, compute the ones held here.

One chip of an expert-parallel deployment holds ``E`` consecutive
experts of the ``n_experts`` the router scores (``first .. first + E -
1``). Every row is routed over all of them (sigmoid scores or a
softmax over them, by the block; the ``top_k`` largest, weights
normalised over all that were chosen, wherever they live), and this
chip adds what its own experts give: the
partial result that the exchange between chips would sum. There is no
capacity and nothing is dropped, so ``ops/moe_ops.py``'s ``[S, E, C]``
dispatch (Switch/GShard, for the training programs) has no part here.

The routed experts' product follows the routing
(``routed_experts``): the (row, held expert) assignments of the live
rows are grouped by expert and the three products

    hidden[i, :] = silu(x[n_i] Wg[e]) * (x[n_i] Wu[e]) * gate[n_i, e]
    y[i]         = hidden[i, :] Wd[e]

run over row tiles of ``TILE_ROWS`` rows of one expert each, in a loop
whose trip count is the number of tiles the step has (the sum over the
experts of ceil(rows that chose it / TILE_ROWS)): an expert no live row
chose costs no iteration and none of its bytes is read. Each iteration
slices its expert out of the layer-stacked weights inside the product.
A row's result is the float32 sum of its own experts' ``y`` in
ascending expert order, whichever tile each landed in and whoever
shared it, so it does not depend on the other rows of the batch. A
program of at most ``TILE_ROWS`` rows (every decode step) needs no
grouping: a tile is all its rows under the expert's gate column, which
is 0 for the rows that did not choose it.

It took the place of the gate-masked product over every expert held,
which read all of them whatever was chosen and spent ``E`` times the
products a prefill row needs. On a TPU v5e at the published widths (16
experts held of 3 x 4096 x 4096 bf16, router and four layers, the
product alone; PERF.md, PR 33): a decode step's 13 live rows, which
touch 8 experts a layer, 9.64 -> 5.48 ms; a 512-row chunk 19.22 ->
11.82 ms; where every expert is touched and each one's rows fit one
tile (programs of 64 to 256 rows) the loop costs 0.3 to 0.9 ms more
than the one fused product did (0.144 ms a tile in a decode step),
because an iteration cannot read the next expert while it multiplies
this one. Only this form, the plain XLA loop, was tried: no
grouped-matmul kernel was written, so none lost.

The shared experts, which every row takes, run the dense gate-masked
product over all of them (``gated_experts``) with the constant gate
``1 / S`` (their mean); it is also the tests' oracle for the routed
ones.
"""

import jax
import jax.numpy as jnp

__all__ = ['route_sigmoid_topk', 'route_softmax_topk', 'held_gates',
           'gated_experts', 'routed_experts', 'row_tiles', 'load_stats',
           'TILE_ROWS']

# rows of one expert that one iteration of the routed product takes: the
# matrix unit's 128, on every TPU generation this runs on
TILE_ROWS = 128


def _router_logits(x, router):
    return jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def route_softmax_topk(x, router, top_k):
    """``route_sigmoid_topk`` for a softmax router (mellum): the scores
    are the softmax of the logits over every expert, the ``top_k``
    largest are chosen and the weights are those scores normalised over
    the chosen (``norm_topk_prob``), which is the softmax over the
    chosen experts' logits alone. float32 at the highest precision."""
    scores = jax.nn.softmax(_router_logits(x, router), axis=-1)
    top, chosen = jax.lax.top_k(scores, top_k)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


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
    the experts ``first .. first + n_held - 1``, and which it chose."""
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
    stacked over the layers, of which ``layer`` is this one ->
    float32 [N, D]. The loop runs ``sum(row_tiles(load))`` times.
    Operand dtypes as ``gated_experts``."""
    n, d = x.shape
    live = hit & valid[:, None]
    load = jnp.sum(live, axis=0, dtype=jnp.int32)                 # [E]
    tiles = row_tiles(load)
    tile_end = jnp.cumsum(tiles)                            # [E]
    rows = x.astype(w_gate.dtype)
    gate = jnp.where(live, gate, 0.0)

    def of(stack, expert):
        # sliced where it lies: the compiler fuses it into the product
        return jax.lax.dynamic_slice(
            stack, (layer, expert, 0, 0), (1, 1) + stack.shape[2:])[0, 0]

    def product(tile, tile_gate, expert):
        hidden = jax.nn.silu(jnp.matmul(
            tile, of(w_gate, expert),
            preferred_element_type=jnp.float32)) * jnp.matmul(
                tile, of(w_up, expert), preferred_element_type=jnp.float32)
        hidden = hidden * tile_gate[:, None]
        return jnp.matmul(hidden.astype(w_down.dtype), of(w_down, expert),
                          preferred_element_type=jnp.float32)

    def expert_of(t):
        # tile t's expert: the first whose tiles end past t
        return jnp.sum(tile_end <= t, dtype=jnp.int32)

    def column(a, expert):
        return jax.lax.dynamic_index_in_dim(a, expert, axis=1,
                                            keepdims=False)

    if n <= TILE_ROWS:
        # one tile an expert, all the rows in place: the rows that did
        # not choose it add exact zeros
        def one(t, out):
            expert = expert_of(t)
            return out + product(rows, column(gate, expert), expert)
        return jax.lax.fori_loop(0, tile_end[-1], one,
                                 jnp.zeros((n, d), jnp.float32))

    # The assignments in order of (expert, row), ``place[n, e]`` the
    # place of row n's in that order; a tile is TILE_ROWS consecutive
    # places from a multiple of TILE_ROWS past its expert's first.
    start = jnp.cumsum(load) - load                               # [E]
    place = start[None, :] + jnp.cumsum(live, axis=0, dtype=jnp.int32) - 1
    e = gate.shape[1]
    key = jnp.where(live, jnp.arange(e, dtype=jnp.int32)[None, :] * n
                    + jnp.arange(n, dtype=jnp.int32)[:, None], e * n)
    key, by_place = jax.lax.sort(
        (key.T.reshape(-1), gate.T.reshape(-1)), num_keys=1)
    cap = n * most
    row_at = jnp.pad(key[:cap] % n, (0, TILE_ROWS))
    gate_at = jnp.pad(by_place[:cap], (0, TILE_ROWS))

    def one(t, results):
        expert = expert_of(t)
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
    # the row with the most has
    nth = jnp.cumsum(live, axis=1, dtype=jnp.int32)               # [N, E]

    def add(j, out):
        pick = live & (nth == j + 1)
        y = jnp.take(results, jnp.sum(jnp.where(pick, place, 0), axis=1),
                     axis=0)
        return out + jnp.where(jnp.any(pick, axis=1)[:, None], y, 0.0)
    return jax.lax.fori_loop(0, jnp.max(nth), add,
                             jnp.zeros((n, d), jnp.float32))


def load_stats(hit, valid):
    """int32 [4] over the rows that are ``valid``: choices that landed
    on an expert held here, rows on the busiest of them, how many of
    them any row chose, and the row tiles ``routed_experts`` runs for
    them (its loop's trip count)."""
    load = jnp.sum(hit & valid[:, None], axis=0, dtype=jnp.int32)  # [E]
    return jnp.stack([jnp.sum(load), jnp.max(load),
                      jnp.sum(load > 0, dtype=jnp.int32),
                      jnp.sum(row_tiles(load))])
