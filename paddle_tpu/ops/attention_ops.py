"""Fused multi-head attention op.

Reference parity: the reference builds attention from primitive ops
(fluid/nets.py scaled_dot_product_attention; the transformer model in its
book/benchmark configs). TPU-native design: the attention sublayer is
ONE IR op, the four projections included, so its lowering decides the
layout of what lies between them: queries, keys and values leave their
projection head-major ([B, H, T, D]) and the output projection contracts
(h, d) out of the context, so no head split or merge is left for the
compiler to materialise as a pass over HBM (``attention_sublayer``).
A caller that built Q, K and V itself hands in the head-merged
projections [B, T, H*D] (``fused_attention``). Masking is computed from
attrs (causal) and an optional per-example KeyLength vector: no
[B, H, T, T] bias tensors cross the feed boundary as they do in the
reference transformer config.

What runs between the projections: ring attention on a mesh with an
active 'sp' axis; the Pallas flash kernel (ops/pallas/flash_attention.py)
only for 512 positions or more and only when opted in
(PADDLE_TPU_USE_PALLAS, or the tuning table under PADDLE_TPU_AUTOTUNE);
everywhere else, which is every length the models train at by default,
the jnp ``reference_attention`` that XLA fuses.
"""

import jax
import jax.numpy as jnp

from ..core.registry import register
from .random_ops import keep_mask

_NEG_INF = -1e9


def _split_heads(x, n_head):
    b, t, d = x.shape
    return x.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)


def _merge_heads(x):
    x = x.transpose(0, 2, 1, 3)
    b, t, h, d = x.shape
    return x.reshape(b, t, h * d)


def reference_attention(q, k, v, causal=False, key_length=None,
                        query_length=None, scale=None, bias=None):
    """jnp reference: q,k,v are [B, H, T, D] (already head-split)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum('bhqd,bhkd->bhqk', q * scale, k)
    if bias is not None:
        logits = logits + bias
    tq, tk = logits.shape[-2], logits.shape[-1]
    if causal:
        causal_mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), tk - tq)
        logits = jnp.where(causal_mask[None, None], logits, _NEG_INF)
    if key_length is not None:
        kmask = jnp.arange(tk)[None, :] < key_length.reshape(-1, 1)
        logits = jnp.where(kmask[:, None, None, :], logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bhqk,bhkd->bhqd', weights, v)
    if query_length is not None:
        qmask = jnp.arange(tq)[None, :] < query_length.reshape(-1, 1)
        out = out * qmask[:, None, :, None].astype(out.dtype)
    return out


def _ring_dispatch(q, k, v, mesh, causal, key_length=None):
    """Sequence-parallel exact attention: shard_map over the mesh's 'sp'
    axis with K/V rotating on ICI (parallel/ring_attention.py). Called
    inside the executor's jit — GSPMD reshards q/k/v to the sp layout if
    the transpiler hasn't already.

    Nests under a pipelined stage (pp x sp): when tracing inside a
    shard_map that is already manual over 'pp', the inner map INHERITS
    the context's abstract mesh — passing the concrete mesh would
    mismatch its Manual axis types. Varying-axis checking stays ON:
    with check_vma=False the nested backward silently mis-accounted
    the pp-varying cotangents (measured ~1e-3 loss drift vs single
    device; exact with the default)."""
    from jax.sharding import PartitionSpec as P
    from ..parallel.ring_attention import ring_attention
    spec = P(None, None, 'sp', None)
    if key_length is None:
        in_specs = (spec, spec, spec)
        args = (q, k, v)

        def fn(q_, k_, v_):
            return ring_attention(q_, k_, v_, axis_name='sp',
                                  causal=causal)
    else:
        # lengths are replicated over sp (each shard masks by GLOBAL
        # key position — ring_attention kv_len semantics, r5)
        in_specs = (spec, spec, spec, P(None))
        args = (q, k, v, key_length)

        def fn(q_, k_, v_, l_):
            return ring_attention(q_, k_, v_, axis_name='sp',
                                  causal=causal, kv_len=l_)

    kwargs = dict(in_specs=in_specs, out_specs=spec)
    ctx = jax.sharding.get_abstract_mesh()
    if not any(t == jax.sharding.AxisType.Manual for t in ctx.axis_types):
        kwargs['mesh'] = mesh
    return jax.shard_map(fn, **kwargs)(*args)


def _sp_size(mesh):
    if mesh is None:
        return 1
    return dict(mesh.shape).get('sp', 1)


def _attend(q, k, v, causal, key_length, query_length, dropout_rate, rng,
            is_test, mesh):
    """q, k, v: [B, H, T, D], head-major. Returns the context
    [B, H, Tq, Dv] with the output dropout applied.

    Dispatch order: ring attention when the program runs on a mesh with
    an active 'sp' axis (long-context sequence parallelism — K/V blocks
    ride the ICI ring instead of all-gathering); the Pallas flash kernel
    when opted in and profitable; otherwise the XLA-fused jnp reference.
    """
    import os
    sp = _sp_size(mesh)
    use_ring = (sp > 1 and
                q.shape[-2] % sp == 0 and k.shape[-2] % sp == 0 and
                os.environ.get('PADDLE_TPU_RING_ATTENTION', '1')
                not in ('0', 'false'))

    # Pallas flash gate (r5, VERDICT r4 next-#4): key_length no longer
    # blocks the fused path — the kernel takes per-example kv lengths
    # (masked key blocks are skipped, so short rows save MXU work), so
    # variable-length NMT batches ride the same kernel as dense ones.
    # Dropout doesn't block it either: this op's dropout is on the
    # attention OUTPUT (see below), applied identically after any path.
    #
    # r8: with PADDLE_TPU_AUTOTUNE=on the per-shape tuning table picks
    # the kernel (and the Pallas block sizes) instead of the global
    # gate — the r4 capture shows the winner flips with seq length. An
    # EXPLICITLY set PADDLE_TPU_USE_PALLAS still overrides the table.
    use_pallas = False
    tuned_blocks = (None, None)
    if not use_ring and q.shape[-2] >= 512 and \
            q.shape[-2] % 128 == 0 and k.shape[-2] % 128 == 0 and \
            q.shape[-1] % 64 == 0:
        from .pallas import pallas_enabled
        from .. import tuning
        picked = None
        if tuning.autotune_mode() != 'off' and \
                not tuning.env_gate_set('PADDLE_TPU_USE_PALLAS'):
            b, h, tq, d = q.shape
            picked = tuning.decide_attention(
                b, h, tq, k.shape[-2], d, str(q.dtype), causal,
                key_length is not None)
        if picked is not None:
            use_pallas = picked.get('impl') == 'pallas'
            tuned_blocks = (picked.get('block_q'), picked.get('block_k'))
        else:
            use_pallas = pallas_enabled()
    if use_ring:
        out = _ring_dispatch(q, k, v, mesh, causal,
                             key_length=key_length)
    elif use_pallas:
        from .pallas.flash_attention import flash_attention
        out = flash_attention(q, k, v, causal=causal, kv_len=key_length,
                              block_q=tuned_blocks[0],
                              block_k=tuned_blocks[1])
    else:
        out = reference_attention(q, k, v, causal=causal,
                                  key_length=key_length,
                                  query_length=query_length)
    if query_length is not None and (use_ring or use_pallas):
        # ring/flash kernels mask keys in-kernel; the query-side zeroing
        # (reference_attention does it internally) applies here once
        qmask = jnp.arange(out.shape[-2])[None, :] < \
            query_length.reshape(-1, 1)
        out = out * qmask[:, None, :, None].astype(out.dtype)
    if dropout_rate and not is_test:
        # dropout on attention output (weights-dropout would block the
        # flash/ring paths; output-dropout is the TPU-friendly equivalent)
        mask, kept = keep_mask(rng, 1.0 - dropout_rate, out.shape)
        out = jnp.where(mask, out / kept, 0.0)
    return out


def fused_attention(q3, k3, v3, n_head, causal=False, key_length=None,
                    query_length=None, dropout_rate=0.0, rng=None,
                    is_test=False, mesh=None):
    """q3/k3/v3: [B, T, H*D], projected by the caller. Returns
    [B, Tq, H*Dv]. The split and the merge are transposes the compiler
    materialises at training widths: a sublayer that owns its weights
    goes through ``attention_sublayer``."""
    out = _attend(_split_heads(q3, n_head), _split_heads(k3, n_head),
                  _split_heads(v3, n_head), causal, key_length,
                  query_length, dropout_rate, rng, is_test, mesh)
    return _merge_heads(out)


def attention_sublayer(x, mem, wq, wk, wv, wo, n_head, causal=False,
                       key_length=None, dropout_rate=0.0, rng=None,
                       is_test=False, mesh=None):
    """The matmul part of an attention sublayer. x: [B, Tq, M] (the
    queries' side), mem: [B, Tk, M'] (keys and values; x itself for
    self-attention); wq, wk: [M, H*Dk], wv: [M', H*Dv], wo: [H*Dv, Mo],
    as they are stored. Returns [B, Tq, Mo].

    Each projection is one contraction over the model dimension against
    the weight viewed [M, H, D] (a bitcast), so its result is born
    [B, H, T, D]; the output projection contracts (h, d) against the
    weight viewed [H, D, Mo]. Nothing is reshaped between a projection
    and the attention, which is what lets the compiler lay each result
    out as the attention's dots want it where the matmul stores it."""
    def heads(w):
        return w.reshape(w.shape[0], n_head, w.shape[1] // n_head)
    q = jnp.einsum('btm,mhd->bhtd', x, heads(wq))
    k = jnp.einsum('btm,mhd->bhtd', mem, heads(wk))
    v = jnp.einsum('btm,mhd->bhtd', mem, heads(wv))
    out = _attend(q, k, v, causal, key_length, None, dropout_rate, rng,
                  is_test, mesh)
    return jnp.einsum('bhtd,hdm->btm', out,
                      wo.reshape(n_head, wo.shape[0] // n_head,
                                 wo.shape[1]))


@register('fused_attention')
def _fused_attention(ctx):
    """Two forms, told apart by the inputs the op carries: X, Mem and the
    four weights (the sublayer, projections included), or Q, K, V that
    the caller projected."""
    key_length = ctx.input('KeyLength') if ctx.has_input('KeyLength') \
        else None
    dropout_rate = ctx.attr('dropout_rate', 0.0)
    common = dict(causal=ctx.attr('causal', False), key_length=key_length,
                  dropout_rate=dropout_rate,
                  rng=ctx.rng_key() if dropout_rate else None,
                  is_test=ctx.is_test,
                  mesh=getattr(ctx.block.program, 'mesh', None))
    n_head = ctx.attr('n_head', 1)
    if ctx.has_input('Wq'):
        out = attention_sublayer(
            ctx.input('X'), ctx.input('Mem'), ctx.input('Wq'),
            ctx.input('Wk'), ctx.input('Wv'), ctx.input('Wo'), n_head,
            **common)
    else:
        query_length = ctx.input('QueryLength') \
            if ctx.has_input('QueryLength') else None
        out = fused_attention(ctx.input('Q'), ctx.input('K'),
                              ctx.input('V'), n_head,
                              query_length=query_length, **common)
    ctx.set_output('Out', out)
