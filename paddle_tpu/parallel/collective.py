"""Functional collectives (reference: paddle/pserver gradient aggregation,
NCCL allreduce in ParallelExecutor). Thin wrappers over jax.lax for use
inside shard_map bodies and custom kernels, plus the quantized
allreduce schedule (PAPERS "EQuARX: Efficient Quantized AllReduce in
XLA") the trainer's dp gradient path models, and the gradient-bucketing
policy/assignment the executor's bucketed-allreduce path uses
(``PADDLE_TPU_GRAD_BUCKET_MB`` — read per call, repo_lint enforced)."""

import os

import jax


# ------------------------------------------------- gradient bucketing
def grad_bucket_policy(program=None):
    """Per-call resolver for the gradient-allreduce bucketing knob.

    Precedence mirrors ``quant.core.grad_allreduce_policy``: an explicit
    ``PADDLE_TPU_GRAD_BUCKET_MB`` env value wins in either direction
    ('0'/'off' disables; a number is the per-bucket size target in MB);
    when unset, the program's ``grad_bucket_mb`` attribute (set by
    ``ParallelStrategy(grad_bucket_mb=...)``) decides. Returns a
    hashable policy tuple ``('mb', size_mb)`` — folded into the
    executor's compile-cache key so flipping the env recompiles instead
    of silently reusing the other mode — or None when off."""
    raw = os.environ.get('PADDLE_TPU_GRAD_BUCKET_MB')
    if raw is None or raw.strip() == '':
        mb = getattr(program, 'grad_bucket_mb', None)
    else:
        s = raw.strip().lower()
        mb = None if s in ('0', 'off', 'false') else float(s)
    if mb is None or float(mb) <= 0:
        return None
    return ('mb', float(mb))


def assign_grad_buckets(items, target_bytes):
    """Deterministic size-targeted bucket assignment.

    ``items`` is ``[(size_bytes, group), ...]`` in PARAMETER ORDER (the
    forward order); the walk runs in REVERSE — the backward produces
    gradients roughly last-layer-first, so reversed parameter order
    approximates production order and the first bucket closes (and its
    collective can issue) while earlier layers are still
    differentiating. Greedy: a bucket closes when adding the next
    gradient would exceed ``target_bytes`` (a single oversized gradient
    gets its own bucket) or when the group key changes (buckets never
    mix groups — concatenation must not promote dtypes). Returns a list
    of buckets, each a list of original item indices; pure and
    deterministic, so trace and re-trace agree bit-for-bit."""
    target = max(1, int(target_bytes))
    buckets = []
    cur, cur_bytes, cur_group = [], 0, None
    for i in reversed(range(len(items))):
        size, group = items[i]
        size = int(size)
        if cur and (cur_bytes + size > target or group != cur_group):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += size
        cur_group = group
    if cur:
        buckets.append(cur)
    return buckets


def all_reduce(x, axis_name='dp', op='sum'):
    if op == 'sum':
        return jax.lax.psum(x, axis_name)
    if op == 'mean':
        return jax.lax.pmean(x, axis_name)
    if op == 'max':
        return jax.lax.pmax(x, axis_name)
    if op == 'min':
        return jax.lax.pmin(x, axis_name)
    raise ValueError('unsupported all_reduce op %r' % op)


def quantized_all_reduce(x, axis_name='dp', op='sum', block=256,
                         key=None):
    """Block-scaled int8 allreduce (EQuARX schedule, explicit form):

    1. quantize the local tensor per-``block`` to int8 (+ one fp32
       scale per block; stochastic rounding when ``key`` is given),
    2. **reduce_scatter in int8**: an all_to_all hands every device
       the n peer copies of its own block shard — int8 payload plus
       the fp32 scale sideband is all that crosses the wire,
    3. **fp32 accumulate**: each device dequantizes its n received
       copies and sums them in fp32,
    4. **all_gather of requantized shards**: the reduced shard is
       requantized to int8 and gathered, so the return leg is int8
       too; every device dequantizes the full result.

    Wire bytes per device ≈ 2·(n-1)/n·nelem·(1 + 4/block) vs the fp32
    ring's 2·(n-1)/n·nelem·4 — ~3.94x less at block=256 (the analytic
    model in quant.core.quantized_allreduce_wire_bytes, asserted by
    tests/test_quant.py). The result is identical on every
    device (rounding keys fold the sender's axis index, and the final
    gather is of already-rounded shards).

    ``op``: 'sum' or 'mean'. ``key=None`` rounds to nearest
    (deterministic); a PRNG key switches to unbiased stochastic
    rounding — what gradient traffic wants."""
    import jax.numpy as jnp

    from ..quant import core as _q

    if op not in ('sum', 'mean'):
        raise ValueError('quantized_all_reduce supports sum/mean, got '
                         '%r' % op)
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    orig_dtype, orig_shape = x.dtype, x.shape
    flat = x.astype(jnp.float32).reshape(-1)
    numel = flat.shape[0]
    # pad so the block count divides the axis (every device owns an
    # equal shard of blocks)
    nblocks = -(-max(numel, 1) // block)
    nblocks = -(-nblocks // n) * n
    pad = nblocks * block - numel
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(nblocks, block)
    scales = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1), 1e-30) \
        / _q.QMAX_INT8
    k1 = k2 = None
    if key is not None:
        k1 = jax.random.fold_in(key, me)
        k2 = jax.random.fold_in(k1, 1)
    q = _q._round_int8(blocks / scales[:, None], k1)

    # (2) int8 reduce_scatter: row-shard j of q goes to device j; the
    # received rows group as [n peers, my nblocks/n blocks, block]
    qr = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
    sr = jax.lax.all_to_all(scales, axis_name, split_axis=0,
                            concat_axis=0, tiled=True)
    shard_blocks = nblocks // n
    parts = qr.reshape(n, shard_blocks, block).astype(jnp.float32) \
        * sr.reshape(n, shard_blocks, 1)
    shard = parts.sum(axis=0)                      # (3) fp32 accumulate

    # (4) requantize the reduced shard, gather int8
    s2 = jnp.maximum(jnp.max(jnp.abs(shard), axis=1), 1e-30) \
        / _q.QMAX_INT8
    q2 = _q._round_int8(shard / s2[:, None], k2)
    qg = jax.lax.all_gather(q2, axis_name, axis=0, tiled=True)
    sg = jax.lax.all_gather(s2, axis_name, axis=0, tiled=True)
    out = (qg.astype(jnp.float32) * sg[:, None]).reshape(-1)
    if pad:
        out = out[:numel]
    if op == 'mean':
        out = out / n
    return out.reshape(orig_shape).astype(orig_dtype)


def all_gather(x, axis_name='tp', axis=0):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def reduce_scatter(x, axis_name='tp', axis=0):
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)


def all_to_all(x, axis_name='sp', split_axis=0, concat_axis=0):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def ppermute(x, axis_name, perm):
    return jax.lax.ppermute(x, axis_name, perm)


def broadcast(x, axis_name, root=0):
    """Root's value on every device, by recursive doubling: ceil(log2 n)
    ppermute hops, each device selecting the received value exactly
    when the hop reaches it. O(1) compute per element — the previous
    psum(where(...)) formulation materialized a zeros tensor per
    device and paid a full N-way reduction tree for what is pure
    data movement."""
    import jax.numpy as jnp
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    rel = (idx - root) % n                 # distance from the root
    val = x
    hop = 1
    while hop < n:
        recv = jax.lax.ppermute(
            val, axis_name, [(i, (i + hop) % n) for i in range(n)])
        take = (rel >= hop) & (rel < 2 * hop)
        val = jnp.where(take, recv, val)
        hop *= 2
    return val
