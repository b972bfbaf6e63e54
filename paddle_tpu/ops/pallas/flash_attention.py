"""Flash attention (forward + FA2 backward) as Pallas TPU kernels.

Replaces the reference's composed matmul→softmax→matmul attention chain
(which materializes the [B, H, Tq, Tk] score tensor in HBM) with an
online-softmax kernel that keeps one (block_q, block_k) score tile in VMEM
at a time — O(T) memory instead of O(T²), and the q·kᵀ / p·v matmuls hit
the MXU back-to-back without an HBM round-trip.

Design follows the standard flash-attention-v2 recurrence (running max m,
running denominator l, rescaled accumulator); written against the Pallas
TPU API per /opt/skills/guides/pallas_guide.md. The backward pass is the
FA2 two-kernel recompute form (dK/dV kernel accumulating over query
blocks, dQ kernel accumulating over key blocks) driven by the forward's
saved logsumexp; PADDLE_TPU_PALLAS_BWD=0 falls back to a rematerializing
XLA recompute. PADDLE_TPU_PALLAS_INTERPRET=1 runs the kernels in
interpret mode (CPU test parity, tests/test_pallas_kernels.py).

Round-5 revisions (VERDICT r4 next-#3):
- Dots run at the INPUT dtype (bf16 inputs → bf16×bf16 MXU passes with
  fp32 accumulation via preferred_element_type). The previous kernels
  upcast every q/k/v tile to fp32 before the dots, forcing fp32-rate
  MXU passes where XLA's fused attention runs bf16 — the measured
  seq-1024 loss (108.8k vs 126.6k tok/s). Softmax math (max, exp, the
  l/m recurrence) stays fp32; p is cast back to the value dtype for
  the p·v dot, as XLA itself does under bf16 amp.
- block_k is tunable (PADDLE_TPU_PALLAS_BLOCK_K, default 128) for the
  on-chip sweep; block_q picks the largest of 512/256/128 dividing Tq.
  Both knobs are read PER CALL (resolve_blocks) — not at import — so
  the autotuner (paddle_tpu/tuning) can sweep block sizes in-process
  and a shell `export` after import still takes effect.
- Padding masks: kv_len (per-example valid key length, [B] int32)
  masks key columns ≥ len — variable-length NMT batches no longer
  fall back to the unfused path (VERDICT r4 next-#4). Lengths ride
  SMEM, one scalar per (b·h) grid row; masked key BLOCKS are skipped
  entirely (the run predicate), so short rows also save MXU work.
"""

import functools
import os

import jax
import jax.numpy as jnp

from . import interpret_mode

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _pallas_bwd():
    return os.environ.get('PADDLE_TPU_PALLAS_BWD', '1') not in (
        '0', 'false', 'False')


def _pick_block(t, prefer):
    """Largest power-of-two block ≤ prefer that divides t. Env overrides
    (e.g. PADDLE_TPU_PALLAS_BLOCK_K=192) are rounded DOWN to a power of
    two and halved — below 128 if necessary — until they divide t, so a
    non-dividing override degrades to a smaller valid block instead of
    tripping the divisibility assert at trace time."""
    b = max(1, min(int(prefer), int(t)))
    b = 1 << (b.bit_length() - 1)   # round down to a power of two
    while b > 1 and t % b != 0:
        b //= 2
    return b


def resolve_blocks(tq, tk, block_q=None, block_k=None):
    """The (block_q, block_k) pair one kernel invocation actually uses —
    the ONE place forward and backward agree on tile sizes. None falls
    back to the PADDLE_TPU_PALLAS_BLOCK_Q/_K env knobs, read HERE per
    call (not at import) so env changes after import — and the
    autotuner's in-process block sweeps — take effect; explicit
    arguments (a tuned winner) skip the env entirely."""
    if block_q is None:
        block_q = int(os.environ.get('PADDLE_TPU_PALLAS_BLOCK_Q',
                                     str(DEFAULT_BLOCK_Q)))
    if block_k is None:
        block_k = int(os.environ.get('PADDLE_TPU_PALLAS_BLOCK_K',
                                     str(DEFAULT_BLOCK_K)))
    return _pick_block(tq, block_q), _pick_block(tk, block_k)


def attention_block_variants(tq, tk, q_grid=(512, 256),
                             k_grid=(128, 256, 512)):
    """The (block_q, block_k) pairs worth microbenchmarking at this
    shape: grid entries that divide the sequence lengths exactly (a
    non-dividing entry would silently degrade to a smaller block —
    already covered by another grid point). The autotuner's candidate
    enumeration; always non-empty (the degraded default pair backstops
    tiny shapes)."""
    pairs = []
    for bq in q_grid:
        if _pick_block(tq, bq) != min(bq, tq):
            continue
        for bk in k_grid:
            if _pick_block(tk, bk) != min(bk, tk):
                continue
            pair = (_pick_block(tq, bq), _pick_block(tk, bk))
            if pair not in pairs:
                pairs.append(pair)
    if not pairs:
        pairs.append(resolve_blocks(tq, tk, DEFAULT_BLOCK_Q,
                                    DEFAULT_BLOCK_K))
    return pairs


def _tile_mask(s, qi, ki, kv_len, causal, block_q, block_k):
    """Apply causal and/or key-padding masks to one [bq, bk] score tile.
    kv_len is a scalar (this row's valid key count) or None."""
    need_cols = causal or kv_len is not None
    if not need_cols:
        return s
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = None
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        keep = rows >= cols
    if kv_len is not None:
        kkeep = cols < kv_len
        keep = kkeep if keep is None else (keep & kkeep)
    return jnp.where(keep, s, _NEG_INF)


def _run_pred(qi, ki, kv_len, causal, block_q, block_k):
    """Whether this (qi, ki) tile has any live key: under the causal
    band and below the padding length. Skipped tiles cost no MXU work."""
    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)
    if kv_len is not None:
        live = (ki * block_k) < kv_len
        run = live if run is True else (run & live)
    return run


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, masked,
                block_q, block_k, num_k_blocks):
    from jax.experimental import pallas as pl

    if masked:
        len_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        kv_len = len_ref[pl.program_id(0)]
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        kv_len = None

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_run_pred(qi, ki, kv_len, causal, block_q, block_k))
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        # input-dtype dot, fp32 accumulation: bf16 inputs take the
        # bf16×bf16→fp32 MXU rate instead of an upcast fp32 pass
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk] f32
        s = _tile_mask(s, qi, ki, kv_len, causal, block_q, block_k)

        m_prev = m_scr[:]                     # [bq, 128] lane-replicated
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)          # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)                # [bq, 128]
        alpha = jnp.exp(m_prev - m_next)                   # [bq, 128]
        p = jnp.exp(s - m_next[:, :1])                     # [bq, bk] f32
        l_cur = jnp.sum(p, axis=1, keepdims=True)          # [bq, 1]
        l_next = alpha * l_prev + l_cur                    # [bq, 128]
        m_scr[:] = m_next
        l_scr[:] = l_next
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, d] f32
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        denom = l_scr[:][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # lse rides a [bh, 1, tq] array so its (1, block_q) block tile
        # satisfies the TPU (8, 128)-or-equal constraint
        lse_ref[0] = (m_scr[:][:, 0] +
                      jnp.log(denom[:, 0])).reshape(1, block_q)


def _lens_rows(kv_len, b, h):
    """[B] lengths → [B*H] int32, one scalar per (b·h) grid row. The
    whole vector sits in SMEM and each grid step reads its own entry: a
    (1, 1) block over a [B*H, 1] array is refused by the TPU lowering
    (second-minor block of 1 that is neither B*H nor a multiple of 8)."""
    return jnp.broadcast_to(
        kv_len.astype(jnp.int32).reshape(b, 1), (b, h)).reshape(b * h)


def _flash_fwd(q, k, v, kv_len, causal, sm_scale, block_q, block_k=None):
    """Returns (out [B,H,Tq,D], lse [B*H, 1, Tq]) — lse feeds the
    backward (row-vector layout per the TPU block-tile constraint)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_q, block_k = resolve_blocks(tq, tk, block_q, block_k)
    assert tq % block_q == 0 and tk % block_k == 0, \
        'flash_attention: seq lens must divide block sizes'
    num_k_blocks = tk // block_k
    masked = kv_len is not None

    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)

    grid = (b * h, tq // block_q, num_k_blocks)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, masked=masked,
        block_q=block_q, block_k=block_k, num_k_blocks=num_k_blocks)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
    ]
    inputs = [qr, kr, vr]
    if masked:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(_lens_rows(kv_len, b, h))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret_mode(),
    )(*inputs)
    return out.reshape(b, h, tq, d), lse


def _bwd_tile(q, k, v, do, lse, delta, qi, ki, kv_len, *, sm_scale,
              causal, block_q, block_k):
    """Shared [bq, bk] tile math of the FA2 backward: recompute p from
    the saved logsumexp, then ds = p * (dp - delta) * scale. Dots run at
    input dtype with fp32 accumulation; p/ds cast back for the MXU."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale          # [bq, bk]
    s = _tile_mask(s, qi, ki, kv_len, causal, block_q, block_k)
    p = jnp.exp(s - lse.reshape(block_q, 1))                    # [bq, bk]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                     # [bq, bk]
    ds = p * (dp - delta.reshape(block_q, 1)) * sm_scale
    return p, ds


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, sm_scale, causal, masked, block_q, block_k,
                    num_q_blocks):
    from jax.experimental import pallas as pl

    if masked:
        len_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
        kv_len = len_ref[pl.program_id(0)]
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        kv_len = None

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_run_pred(qi, ki, kv_len, causal, block_q, block_k))
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p, ds = _bwd_tile(q, k, v, do, lse_ref[0], delta_ref[0], qi, ki,
                          kv_len, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, d]
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, d]

    @pl.when(qi == num_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, sm_scale, causal, masked, block_q, block_k,
                   num_k_blocks):
    from jax.experimental import pallas as pl

    if masked:
        len_ref, dq_ref, dq_scr = rest
        kv_len = len_ref[pl.program_id(0)]
    else:
        dq_ref, dq_scr = rest
        kv_len = None

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_run_pred(qi, ki, kv_len, causal, block_q, block_k))
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        _, ds = _bwd_tile(q, k, v, do, lse_ref[0], delta_ref[0], qi, ki,
                          kv_len, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bq, d]

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, kv_len, causal, sm_scale, block_q,
               block_k=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_q, block_k = resolve_blocks(tq, tk, block_q, block_k)
    num_q_blocks = tq // block_q
    num_k_blocks = tk // block_k
    masked = kv_len is not None

    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    dor = g.reshape(b * h, tq, d)
    # delta = rowsum(dO * O): tiny elementwise+reduce, XLA fuses it;
    # [bh, 1, tq] row-vector layout like lse (TPU block-tile constraint)
    delta = jnp.sum(dor.astype(jnp.float32) *
                    o.reshape(b * h, tq, d).astype(jnp.float32),
                    axis=-1).reshape(b * h, 1, tq)
    lens = _lens_rows(kv_len, b, h) if masked else None
    len_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
        pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
    ]
    inputs = [qr, kr, vr, dor, lse, delta]
    if masked:
        in_specs.append(len_spec)
        inputs.append(lens)
    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, masked=masked, block_q=block_q,
                          block_k=block_k, num_q_blocks=num_q_blocks),
        grid=(b * h, num_k_blocks, num_q_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret_mode(),
    )(*inputs)

    in_specs_q = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
    ]
    inputs_q = [qr, kr, vr, dor, lse, delta]
    if masked:
        in_specs_q.append(len_spec)
        inputs_q.append(lens)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, masked=masked, block_q=block_q,
                          block_k=block_k, num_k_blocks=num_k_blocks),
        grid=(b * h, num_q_blocks, num_k_blocks),
        in_specs=in_specs_q,
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret_mode(),
    )(*inputs_q)

    shape = (b, h, tq, d)
    return (dq.reshape(shape), dkv[0].reshape(b, h, tk, d),
            dkv[1].reshape(b, h, tk, d))


def _reference(q, k, v, causal, sm_scale, kv_len=None):
    logits = jnp.einsum('bhqd,bhkd->bhqk', q * sm_scale, k)
    tq, tk = logits.shape[-2], logits.shape[-1]
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), tk - tq)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    if kv_len is not None:
        kmask = jnp.arange(tk)[None, :] < kv_len.reshape(-1, 1)
        logits = jnp.where(kmask[:, None, None, :], logits, _NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', w, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_core(q, k, v, kv_len, causal, sm_scale, block_q, block_k):
    return _flash_fwd(q, k, v, kv_len, causal, sm_scale, block_q,
                      block_k)[0]


def _vjp_fwd(q, k, v, kv_len, causal, sm_scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, kv_len, causal, sm_scale, block_q,
                          block_k)
    return out, (q, k, v, kv_len, out, lse)


def _vjp_bwd(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, kv_len, o, lse = res
    if _pallas_bwd():
        dq, dk, dv = _flash_bwd(q, k, v, o, lse, g, kv_len, causal,
                                sm_scale, block_q, block_k)
    else:
        # Rematerialized XLA backward (PADDLE_TPU_PALLAS_BWD=0).
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference(q_, k_, v_, causal, sm_scale,
                                          kv_len), q, k, v)
        dq, dk, dv = vjp(g)
    if kv_len is None:
        return dq, dk, dv, None
    # integer lengths carry a float0 tangent (no gradient)
    dlen = jnp.zeros(kv_len.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dlen


_flash_core.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=None, kv_len=None, block_k=None):
    """q,k,v: [B, H, T, D]; kv_len: optional [B] int32 valid key counts
    (key columns ≥ kv_len[b] are masked out and their key BLOCKS are
    skipped). block_q/block_k=None resolve from the env knobs PER CALL
    (resolve_blocks) — the autotuner passes explicit tuned values.
    Returns [B, H, Tq, D]."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    return _flash_core(q, k, v, kv_len, causal, scale, block_q, block_k)
