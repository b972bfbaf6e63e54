"""Routed experts for serving: score all, compute the ones held here.

One chip of an expert-parallel deployment holds ``E`` consecutive
experts of the ``n_experts`` the router scores (``first .. first + E -
1``). Every row is routed over all of them (sigmoid scores, the
``top_k`` largest, weights normalised over all that were chosen,
wherever they live), and this chip adds what its own experts give: the
partial result that the exchange between chips would sum. There is no
capacity and nothing is dropped, so ``ops/moe_ops.py``'s ``[S, E, C]``
dispatch (Switch/GShard, for the training programs) has no part here.

The formulation is a gate-masked product over the experts held:

    hidden[e, n, :] = silu(x[n] Wg[e]) * (x[n] Wu[e]) * gate[n, e]
    out[n]          = sum_e (hidden[e, n, :] Wd[e])

with ``gate[n, e]`` the row's weight for expert ``first + e`` (0 where
it did not choose it). Each expert's three matrices are read once
whatever the rows chose. At decode that is what bounds the layer (32
rows against 16 experts of 3 x 4096 x 4096: 1.6 GB of weights, 52 GFLOP);
at prefill it spends ``E`` times the products a row needs for each
choice, and sorting the rows by expert would spend ``top_k / 8`` of
them (PERF.md, PR 28). Every row's result is a fixed sequence of
products over a fixed shape, so it does not depend on which other rows
share the batch.

The shared experts go through the same product with the constant gate
``1 / S`` (their mean).
"""

import jax
import jax.numpy as jnp

__all__ = ['route_sigmoid_topk', 'held_gates', 'gated_experts',
           'load_stats']


def route_sigmoid_topk(x, router, top_k):
    """``x`` [N, D] float32, ``router`` [D, n_experts] -> (chosen
    [N, k] int32, weights [N, k] float32). Scores and weights in
    float32 at the highest matmul precision whatever the weights'
    dtype: a choice that flips moves a row's whole expert sum."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    top, chosen = jax.lax.top_k(scores, top_k)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


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


def load_stats(hit, valid):
    """int32 [3] over the rows that are ``valid``: choices that landed
    on an expert held here, rows on the busiest of them, and how many
    of them any row chose."""
    load = jnp.sum(hit & valid[:, None], axis=0, dtype=jnp.int32)  # [E]
    return jnp.stack([jnp.sum(load), jnp.max(load),
                      jnp.sum(load > 0, dtype=jnp.int32)])
