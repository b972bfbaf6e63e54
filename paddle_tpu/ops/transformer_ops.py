"""Scan-over-layers transformer stack op.

Reference parity: the reference transformer config unrolls its 6 encoder /
decoder layers into the ProgramDesc op list (one op chain per layer).
TPU-first design: identical layers are ONE `lax.scan` over weights stacked
along a leading [n_layer, ...] axis — XLA compiles the layer body once
instead of n_layer times, so compile time stays flat as stacks deepen
(SURVEY §5 "scan-over-layers" lever). The per-layer math exactly mirrors
models/transformer.py encoder_layer/decoder_layer (fused attention →
residual+LN → FFN → residual+LN, dropout in the same places with the same
downgrade_in_infer scheme layers.dropout uses).

Emitted by models/transformer.py when scan_layers=True; parity with the
unrolled graph is asserted in tests/test_transformer_scan.py.
"""

import jax
import jax.numpy as jnp

from ..core.registry import register
from .attention_ops import attention_sublayer
from .random_ops import keep_mask


def _dropout(x, rate, key, is_test):
    """layers.dropout default (downgrade_in_infer) semantics."""
    if not rate:
        return x
    if is_test:
        return x * (1.0 - rate)
    mask, _ = keep_mask(key, 1.0 - rate, x.shape)
    return x * mask.astype(x.dtype)


def _post_process(prev, out, p, rate, key, is_test, ln_slot):
    # fused_layer_norm: fp32 statistics, activation handed back in x's
    # dtype, Pallas kernel when profitable — the same path the
    # layer_norm op lowering dispatches through.
    from .pallas.layer_norm import fused_layer_norm
    out = _dropout(out, rate, key, is_test)
    return fused_layer_norm(prev + out, p[ln_slot + '_w'],
                            p[ln_slot + '_b'], eps=1e-5,
                            begin_norm_axis=-1)


def _attn(x, mem, p, pre, n_head, causal, key_length, rate, key, is_test,
          mesh):
    return attention_sublayer(
        x, mem, p[pre + '_q'], p[pre + '_k'], p[pre + '_v'], p[pre + '_o'],
        n_head, causal=causal, key_length=key_length, dropout_rate=rate,
        rng=key, is_test=is_test, mesh=mesh)


def _ffn(x, p, rate, key, is_test):
    h = jax.nn.relu(x @ p['ffn_w1'] + p['ffn_b1'])
    h = _dropout(h, rate, key, is_test)
    return h @ p['ffn_w2'] + p['ffn_b2']


ENC_SLOTS = ('slf_q', 'slf_k', 'slf_v', 'slf_o', 'ln1_w', 'ln1_b',
             'ffn_w1', 'ffn_b1', 'ffn_w2', 'ffn_b2', 'ln2_w', 'ln2_b')
DEC_SLOTS = ('slf_q', 'slf_k', 'slf_v', 'slf_o', 'ln1_w', 'ln1_b',
             'cross_q', 'cross_k', 'cross_v', 'cross_o', 'ln2_w', 'ln2_b',
             'ffn_w1', 'ffn_b1', 'ffn_w2', 'ffn_b2', 'ln3_w', 'ln3_b')


def _slot_to_input(slot):
    """'slf_q' -> the op input slot name 'SlfQ'."""
    return ''.join(part.capitalize() for part in slot.split('_'))


def _pipeline_state(ctx):
    """(mesh, pp_conf, pipelined) for a stack op. pipelined is True when
    the program was transpiled with ParallelStrategy(pipeline_parallel=
    True) onto a mesh with an active 'pp' axis — the lowering then runs
    the GPipe microbatch schedule (parallel/pipeline.py) instead of one
    flat lax.scan, with stage s holding layers [s*L/pp, (s+1)*L/pp)."""
    program = ctx.block.program
    mesh = getattr(program, 'mesh', None)
    pp_conf = getattr(program, 'pipeline', None)
    pipelined = bool(pp_conf) and mesh is not None and \
        dict(mesh.shape).get('pp', 1) > 1
    return mesh, pp_conf, pipelined


@register('transformer_layer_stack')
def _transformer_layer_stack(ctx):
    x = ctx.input('X')
    is_decoder = ctx.has_input('EncOut')
    enc_out = ctx.input('EncOut') if is_decoder else None
    key_length = ctx.input('SrcLength') if ctx.has_input('SrcLength') \
        else None
    n_head = ctx.attr('n_head', 1)
    rate = ctx.attr('dropout_rate', 0.0)
    is_test = ctx.attr('is_test', False) or ctx.is_test
    mesh, pp_conf, pipelined = _pipeline_state(ctx)

    slots = DEC_SLOTS if is_decoder else ENC_SLOTS
    params = {s: ctx.env[ctx.op.input(_slot_to_input(s))] for s in slots}
    n_layer = next(iter(params.values())).shape[0]

    if ctx.amp == 'bf16':
        x = x.astype(jnp.bfloat16)
        if enc_out is not None:
            enc_out = enc_out.astype(jnp.bfloat16)
        for s in slots:
            # matmul operands ride the MXU in bf16; LN params stay fp32
            # (their math runs in fp32 inside _layer_norm)
            if not s.startswith('ln'):
                params[s] = params[s].astype(jnp.bfloat16)

    # one folded key per (layer, dropout site); scanned alongside params
    n_sites = 6 if is_decoder else 4
    if rate and not is_test:
        site_keys = jax.random.split(
            ctx.rng_key(), n_layer * n_sites).reshape(n_layer, n_sites)
        xs = (params, site_keys)
    else:
        xs = (params,)

    # The pipelined stage runs inside a shard_map that is manual over
    # 'pp' only: GSPMD still manages dp/tp within the stage, and the
    # ring-attention dispatch nests as an sp-manual inner shard_map
    # that inherits the context mesh (_ring_dispatch) — pp composes
    # with dp, tp, AND sp, so attention sees the mesh either way.

    def make_body(ext, fold):
        # ext: this microbatch's slice of the batch-aligned side inputs
        # (full arrays in the non-pipelined path); fold: microbatch index
        # folded into dropout keys so masks stay per-microbatch
        enc_m = ext.get('enc')
        kl_m = ext.get('kl')

        def body(h, sl):
            p = sl[0]
            kk = list(sl[1]) if len(sl) > 1 else [None] * n_sites
            if fold is not None:
                kk = [None if k is None else jax.random.fold_in(k, fold)
                      for k in kk]
            slf = _attn(h, h, p, 'slf', n_head, is_decoder,
                        None if is_decoder else kl_m,
                        rate, kk[0], is_test, mesh)
            h = _post_process(h, slf, p, rate, kk[1], is_test, 'ln1')
            if is_decoder:
                cross = _attn(h, enc_m, p, 'cross', n_head, False,
                              kl_m, rate, kk[4], is_test, mesh)
                h = _post_process(h, cross, p, rate, kk[5], is_test, 'ln2')
            ffn = _ffn(h, p, rate, kk[2], is_test)
            h = _post_process(h, ffn, p, rate, kk[3], is_test,
                              'ln3' if is_decoder else 'ln2')
            return h, None

        return body

    extras = {}
    if enc_out is not None:
        extras['enc'] = enc_out
    if key_length is not None:
        extras['kl'] = key_length

    if pipelined:
        from ..parallel.pipeline import pipeline_layer_scan
        out = pipeline_layer_scan(make_body, x, xs, mesh,
                                  pp_conf['n_micro'], extras=extras)
    else:
        out, _ = jax.lax.scan(make_body(extras, None), x, xs)
    ctx.set_output('Out', out)


MOE_SLOTS = ('slf_q', 'slf_k', 'slf_v', 'slf_o', 'ln1_w', 'ln1_b',
             'gate_w', 'moe_w1', 'moe_b1', 'moe_w2', 'moe_b2',
             'ln2_w', 'ln2_b')


@register('moe_layer_stack')
def _moe_layer_stack(ctx):
    """Scan-over-layers for MoE transformer blocks: causal fused
    attention -> residual+LN -> Switch/top-k MoE FFN -> residual+LN,
    ONE lax.scan over [n_layer, ...] stacked weights (expert weights
    stack [n_layer, E, ...]). Mirrors models/moe.py's unrolled block;
    per-layer aux losses come back summed. Composes the two scaling
    levers: flat compile time over depth (transformer_layer_stack) and
    expert parallelism (the per-layer dispatch is switch_moe_reference,
    so 'ep' sharding constraints still apply inside the scan)."""
    from .moe_ops import (constrain_experts, moe_capacity,
                          switch_moe_reference)

    x = ctx.input('X')
    n_head = ctx.attr('n_head', 1)
    rate = ctx.attr('dropout_rate', 0.0)
    cap_factor = ctx.attr('capacity_factor', 1.25)
    k = ctx.attr('top_k', 1)
    is_test = ctx.attr('is_test', False) or ctx.is_test
    mesh, pp_conf, pipelined = _pipeline_state(ctx)
    params = {s: ctx.env[ctx.op.input(_slot_to_input(s))]
              for s in MOE_SLOTS}
    n_layer = next(iter(params.values())).shape[0]
    if ctx.amp == 'bf16':
        x = x.astype(jnp.bfloat16)
        for s in MOE_SLOTS:
            # router (gate_w) and LN params stay fp32
            if not s.startswith('ln') and s != 'gate_w':
                params[s] = params[s].astype(jnp.bfloat16)

    b, t, d = x.shape
    # pipelined: each microbatch routes independently, so capacity is
    # per-microbatch tokens (capacity_factor semantics preserved; the
    # routing population differs from full-batch by design, like any
    # microbatched MoE schedule)
    route_b = b // pp_conf['n_micro'] if pipelined else b
    capacity = moe_capacity(cap_factor, k, route_b * t,
                            params['gate_w'].shape[-1])

    if rate and not is_test:
        # one key per layer: dropout lives only inside the attention op
        # (models/moe.py's unrolled block has no post-process sites)
        site_keys = jax.random.split(
            ctx.rng_key(), n_layer).reshape(n_layer, 1)
        xs = (params, site_keys)
    else:
        xs = (params,)

    def make_body(_ext, fold):
        def body(carry, sl):
            h, aux_sum = carry
            p = sl[0]
            key = sl[1][0] if len(sl) > 1 else None
            if fold is not None and key is not None:
                key = jax.random.fold_in(key, fold)
            slf = _attn(h, h, p, 'slf', n_head, True, None, rate, key,
                        is_test, mesh)
            h = _post_process(h, slf, p, 0.0, None, is_test, 'ln1')
            hb, ht, hd = h.shape
            h2 = h.reshape(hb * ht, hd)
            w1, b1, w2, b2 = constrain_experts(
                mesh, (p['moe_w1'], p['moe_b1'], p['moe_w2'],
                       p['moe_b2']))
            moe_out, aux, _ = switch_moe_reference(
                h2, p['gate_w'], w1, b1, w2, b2, capacity, k=k)
            h = _post_process(h, moe_out.reshape(hb, ht, hd), p, 0.0,
                              None, is_test, 'ln2')
            return (h, aux_sum + aux), None

        return body

    if pipelined:
        from ..parallel.pipeline import pipeline_layer_scan
        out, aux_total = pipeline_layer_scan(
            make_body, x, xs, mesh, pp_conf['n_micro'], aux=True)
    else:
        (out, aux_total), _ = jax.lax.scan(
            make_body({}, None), (x, jnp.zeros((), jnp.float32)), xs)
    ctx.set_output('Out', out)
    ctx.set_output('AuxLoss', aux_total)


# --------------------------------------------------------- incremental decode
def _mha_one_step(q1, kc, vc, n_head, live):
    """One-query attention against a cached key/value buffer.

    q1: [B, HD] (the current position), kc/vc: [B, Tmax, HD] head-merged
    caches, live: [B] or scalar — number of valid cache positions; the
    rest are masked. Returns [B, HD]. fp32 softmax."""
    b, tmax, hd = kc.shape
    d = hd // n_head
    q = q1.reshape(b, n_head, 1, d)
    k = kc.reshape(b, tmax, n_head, d).transpose(0, 2, 1, 3)
    v = vc.reshape(b, tmax, n_head, d).transpose(0, 2, 1, 3)
    logits = jnp.einsum('bhqd,bhkd->bhqk', (q * d ** -0.5), k)
    mask = jnp.arange(tmax)[None, :] < jnp.reshape(live, (-1, 1))
    logits = jnp.where(mask[:, None, None, :], logits, -1e9)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum('bhqk,bhkd->bhqd', w.astype(v.dtype), v)
    return out.transpose(0, 2, 1, 3).reshape(b, hd)


def _incremental_layer_scan(params, n_head, cross_live, x, kcs, vcs, ck,
                            cv, t):
    """One decoder step through all layers (inner lax.scan): append this
    position's K/V into the caches, self-attend over live cache, cross-
    attend over the precomputed encoder K/V, FFN; residual+LN as in
    decoder_layer. Returns (h, new kcaches, new vcaches)."""
    from .pallas.layer_norm import fused_layer_norm

    def ln(h, p, slot):
        return fused_layer_norm(h, p[slot + '_w'], p[slot + '_b'],
                                eps=1e-5, begin_norm_axis=-1)

    def body(h, sl):
        p, kc, vc, ckl, cvl = sl
        kc = jax.lax.dynamic_update_slice(
            kc, (h @ p['slf_k'])[:, None, :], (0, t, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, (h @ p['slf_v'])[:, None, :], (0, t, 0))
        slf = _mha_one_step(h @ p['slf_q'], kc, vc, n_head, t + 1)
        h = ln(h + slf @ p['slf_o'], p, 'ln1')
        cross = _mha_one_step(h @ p['cross_q'], ckl, cvl, n_head,
                              cross_live)
        h = ln(h + cross @ p['cross_o'], p, 'ln2')
        ffn = jax.nn.relu(h @ p['ffn_w1'] + p['ffn_b1']) \
            @ p['ffn_w2'] + p['ffn_b2']
        h = ln(h + ffn, p, 'ln3')
        return h, (kc, vc)

    h, (kcs, vcs) = jax.lax.scan(body, x, (params, kcs, vcs, ck, cv))
    return h, kcs, vcs


def _decode_op_inputs(ctx):
    """Shared input unpack + amp policy for the incremental decode ops."""
    enc_out = ctx.input('EncOut')
    src_len = ctx.input('SrcLength') if ctx.has_input('SrcLength') else None
    emb = ctx.input('Emb')
    pos = ctx.input('PosEnc')
    wout = ctx.input('OutProj')
    params = {s: ctx.env[ctx.op.input(_slot_to_input(s))]
              for s in DEC_SLOTS}
    if ctx.amp == 'bf16':
        enc_out = enc_out.astype(jnp.bfloat16)
        emb = emb.astype(jnp.bfloat16)
        wout = wout.astype(jnp.bfloat16)
        pos = pos.astype(jnp.bfloat16)
        for s in DEC_SLOTS:
            if not s.startswith('ln'):
                params[s] = params[s].astype(jnp.bfloat16)
    return enc_out, src_len, emb, pos, wout, params


@register('transformer_greedy_decode')
def _transformer_greedy_decode(ctx):
    """KV-cached greedy decode: ONE lax.scan over output positions (inner
    scan over decoder layers), instead of re-running the decoder over the
    whole prefix per emitted token as the reference's While-based infer
    program does. Compute drops from O(T^2 L) to O(T L); compile time is
    flat in max_out_len. Emitted by
    models.transformer.transformer_greedy_infer(incremental=True)."""
    enc_out, src_len, emb, pos, wout, params = _decode_op_inputs(ctx)
    n_head = ctx.attr('n_head', 1)
    t_max = ctx.attr('max_out_len')
    bos_id = ctx.attr('bos_id', 0)
    eos_id = ctx.attr('eos_id', 1)
    d_model = emb.shape[-1]

    b = enc_out.shape[0]
    n_layer = params['slf_q'].shape[0]
    hdk = params['slf_q'].shape[-1]
    hdv = params['slf_v'].shape[-1]
    s_len = enc_out.shape[1]
    cross_live = src_len if src_len is not None else s_len

    # cross-attention K/V never change over time: compute once per layer
    ck = jnp.einsum('bsd,ldh->lbsh', enc_out, params['cross_k'])
    cv = jnp.einsum('bsd,ldh->lbsh', enc_out, params['cross_v'])

    kc0 = jnp.zeros((n_layer, b, t_max, hdk), enc_out.dtype)
    vc0 = jnp.zeros((n_layer, b, t_max, hdv), enc_out.dtype)
    ids0 = jnp.full((b,), bos_id, jnp.int32)

    def step(carry, t):
        ids, kcs, vcs = carry
        x = jnp.take(emb, ids, axis=0) * (d_model ** 0.5) + \
            jax.lax.dynamic_index_in_dim(pos, t, keepdims=False)
        h, kcs, vcs = _incremental_layer_scan(
            params, n_head, cross_live, x, kcs, vcs, ck, cv, t)
        logits = (h @ wout).astype(jnp.float32)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, kcs, vcs), nxt

    _, steps = jax.lax.scan(step, (ids0, kc0, vc0),
                            jnp.arange(t_max - 1))
    ids = jnp.concatenate([jnp.full((b, 1), bos_id, jnp.int32),
                           steps.T], axis=1)          # [B, T]
    # freeze everything after the first EOS to EOS
    is_eos = (ids == eos_id).astype(jnp.int32)
    before = jnp.cumsum(is_eos, axis=1) - is_eos
    ids = jnp.where(before > 0, eos_id, ids)
    ctx.set_output('Out', ids.astype(ctx.out_dtype('Out', 'int64')))


@register('transformer_beam_decode')
def _transformer_beam_decode(ctx):
    """KV-cached beam search in ONE lax.scan: the per-step candidate
    expansion/pruning is the exact math of the beam_search op
    (decode_ops.py), caches are reordered by parent in place of the
    unrolled graph's prefix beam_gather + full re-run, and the final
    backtrack is the beam_search_decode recurrence. Emits identical
    sequences to the unrolled transformer_beam_infer graph."""
    enc_out, src_len, emb, pos, wout, params = _decode_op_inputs(ctx)
    n_head = ctx.attr('n_head', 1)
    t_max = ctx.attr('max_out_len')
    beam = ctx.attr('beam_size', 4)
    bos_id = ctx.attr('bos_id', 0)
    eos_id = ctx.attr('eos_id', 1)
    d_model = emb.shape[-1]

    b = enc_out.shape[0]
    n_layer = params['slf_q'].shape[0]
    hdk = params['slf_q'].shape[-1]
    hdv = params['slf_v'].shape[-1]
    s_len = enc_out.shape[1]

    # tile examples over the beam: [B, S, D] -> [B*beam, S, D]
    enc_beam = jnp.repeat(enc_out, beam, axis=0)
    cross_live = jnp.repeat(src_len, beam, axis=0) \
        if src_len is not None else s_len
    ck = jnp.einsum('bsd,ldh->lbsh', enc_beam, params['cross_k'])
    cv = jnp.einsum('bsd,ldh->lbsh', enc_beam, params['cross_v'])

    kc0 = jnp.zeros((n_layer, b * beam, t_max, hdk), enc_out.dtype)
    vc0 = jnp.zeros((n_layer, b * beam, t_max, hdv), enc_out.dtype)
    last0 = jnp.full((b * beam,), bos_id, jnp.int32)
    pre_ids0 = jnp.full((b, beam), bos_id, jnp.int32)
    # only beam slot 0 live at t=0 (all beams start identical)
    pre_scores0 = jnp.where(jnp.arange(beam)[None, :] == 0, 0.0, -1e9) * \
        jnp.ones((b, 1), jnp.float32)

    def gather_caches(c, parent):
        # c: [L, B*beam, Tmax, HD]; parent: [B, beam] — reorder beams
        cb = c.reshape(n_layer, b, beam, t_max, c.shape[-1])
        idx = parent[None, :, :, None, None]
        return jnp.take_along_axis(cb, idx, axis=2).reshape(c.shape)

    def step(carry, t):
        last, pre_ids, pre_scores, kcs, vcs = carry
        x = jnp.take(emb, last, axis=0) * (d_model ** 0.5) + \
            jax.lax.dynamic_index_in_dim(pos, t, keepdims=False)
        h, kcs, vcs = _incremental_layer_scan(
            params, n_head, cross_live, x, kcs, vcs, ck, cv, t)
        logp = jax.nn.log_softmax((h @ wout).astype(jnp.float32), axis=-1)
        top_scores, top_ids = jax.lax.top_k(logp, beam)
        from .decode_ops import beam_search_step
        sel_ids, sel_scores, parent = beam_search_step(
            pre_ids, pre_scores, top_ids.reshape(b, beam, beam),
            top_scores.reshape(b, beam, beam), beam, eos_id)
        kcs = gather_caches(kcs, parent)
        vcs = gather_caches(vcs, parent)
        carry = (sel_ids.reshape(-1).astype(jnp.int32), sel_ids,
                 sel_scores, kcs, vcs)
        return carry, (sel_ids, parent)

    (_, _, final_scores, _, _), (step_ids, step_parents) = jax.lax.scan(
        step, (last0, pre_ids0, pre_scores0, kc0, vc0),
        jnp.arange(t_max - 1))

    from .decode_ops import beam_backtrack
    seq = beam_backtrack(step_ids, step_parents, eos_id)  # [B, beam, T-1]
    ctx.set_output('SentenceIds',
                   seq.astype(ctx.out_dtype('SentenceIds', 'int64')))
    ctx.set_output('SentenceScores', final_scores)
