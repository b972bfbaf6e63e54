"""Pipeline parallelism: GPipe-style microbatch schedule over the 'pp'
mesh axis.

Reference analog: the pserver-era reference has no pipeline engine; this
is the TPU-native design the transpiler targets (SURVEY.md §2.4): stage
parameters are stacked on a leading stage dim sharded over 'pp', every
device runs the SAME stage_fn (SPMD), and activations hop stage→stage via
`ppermute` while microbatches stream in — the classic bubble schedule
(n_micro + n_stages - 1 ticks). Differentiable end-to-end: ppermute's
transpose is the reverse permute, so jax.grad recovers the usual
backward pipeline.
"""

import jax
import jax.numpy as jnp


def pipeline(stage_fn, stage_params, microbatches, axis_name='pp',
             with_mb_index=False, with_aux=False):
    """Run inside shard_map over `axis_name`.

    stage_fn(params, x) -> y           one pipeline stage (same shape in/out)
    stage_params: pytree whose leaves are this device's stage params
                  (leading stage dim already stripped by shard_map)
    microbatches: [n_micro, mb, ...]   replicated input microbatches
    with_mb_index: call stage_fn(params, x, m) where m is the index of
    the microbatch this stage processes at this tick (t - stage,
    clamped) — lets the stage fold m into dropout PRNG keys so masks
    stay per-microbatch, matching the semantics of one big batch split
    into n_micro pieces.
    with_aux: stage_fn additionally returns a scalar auxiliary loss
    (MoE load-balancing); contributions are summed over this stage's
    VALID ticks only (warm-up/cool-down ticks process clamped garbage
    microbatches and must not pollute the total) and returned as the
    second output — psum over the pipe and divide by n_micro to
    recover the full-batch mean.
    Returns [n_micro, mb, ...] final-stage outputs (valid on the LAST
    stage; other stages hold garbage — combine with out_specs that index
    the last shard, or psum-mask as convenient); with_aux returns
    (outputs, aux_sum).
    """
    n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    total = n_micro + n_stages - 1
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        buf, aux_acc = carry
        # stage 0 ingests microbatch t (clamped; masked later)
        mb = microbatches[jnp.clip(t, 0, n_micro - 1)]
        x = jnp.where(stage == 0, mb, buf)
        args = (stage_params, x)
        if with_mb_index:
            args = args + (jnp.clip(t - stage, 0, n_micro - 1),)
        y = stage_fn(*args)
        if with_aux:
            y, aux = y
            valid = (t >= stage) & (t - stage < n_micro)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        nxt = jax.lax.ppermute(y, axis_name, fwd_perm)
        return (nxt, aux_acc), y

    # mark the carry varying over pp (ppermute outputs are varying; an
    # unvarying init would make the scan carry types mismatch)
    def _mark_varying(x):
        return jax.lax.pcast(x, (axis_name,), to='varying')

    buf0 = _mark_varying(jnp.zeros_like(microbatches[0]))
    aux0 = _mark_varying(jnp.zeros((), jnp.float32))
    (_, aux_sum), ys = jax.lax.scan(tick, (buf0, aux0),
                                    jnp.arange(total))
    # last stage emits microbatch m at tick m + n_stages - 1
    out = jax.lax.dynamic_slice_in_dim(ys, n_stages - 1, n_micro, axis=0)
    if with_aux:
        return out, aux_sum
    return out


def pipelined_apply(stage_fn, stacked_params, x, n_micro, mesh,
                    axis_name='pp'):
    """Host-level convenience: shard_map-wrap `pipeline` over `mesh`.

    stacked_params: pytree with leading dim n_stages (will shard on pp).
    x: [batch, ...] global input; split into n_micro microbatches.
    Returns [batch, ...] output of the whole stage stack.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis_name]
    batch = x.shape[0]
    assert batch % n_micro == 0, 'batch must divide into microbatches'
    mb_x = x.reshape((n_micro, batch // n_micro) + x.shape[1:])

    param_specs = jax.tree.map(
        lambda _: P(*((axis_name,) + (None,) * (_.ndim - 1))),
        stacked_params)
    mb_axes = (None,) * (mb_x.ndim)

    def inner(params, mb):
        # shard_map keeps the sharded stage dim as size 1 — strip it
        params = jax.tree.map(lambda p: p[0], params)
        out = pipeline(stage_fn, params, mb, axis_name)
        # emit only the last stage's result; zeros elsewhere so a psum
        # over pp reconstructs the true output on every device.
        is_last = jax.lax.axis_index(axis_name) == \
            jax.lax.axis_size(axis_name) - 1
        out = jnp.where(is_last, out, jnp.zeros_like(out))
        return jax.lax.psum(out, axis_name)

    mapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(param_specs, P(*mb_axes)),
        out_specs=P(*mb_axes), check_vma=False)
    out = mapped(jax.tree.map(jnp.asarray, stacked_params), mb_x)
    return out.reshape((batch,) + out.shape[2:])


def pipeline_layer_scan(make_body, x, xs, mesh, n_micro, extras=(),
                        axis_name='pp', aux=False):
    """Pipeline a scan-over-layers op body over `mesh`'s pp axis — the
    Program-level pipeline path (a transformer_layer_stack op whose
    program was transpiled with ParallelStrategy(pipeline_parallel=True)
    lands here instead of one flat lax.scan).

    The [n_layer, ...] stacked weight pytree `xs` is read as n_stages
    contiguous chunks of n_layer/n_stages layers (shard_map splits the
    leading axis over 'pp'); each device's stage scans its local layers,
    activations hop stage->stage via the GPipe schedule in `pipeline`.
    Differentiable end-to-end, so the executor's value_and_grad recovers
    the backward pipeline and grads come back pp-sharded like their
    params (the transpiler pins both).

    make_body(ext_m, m) -> body(h, slice) builds the per-layer scan body:
    `ext_m` is the microbatch-m slice of `extras` (batch-aligned side
    inputs — a decoder stack's enc_out / src_length) and `m` is the
    microbatch index, for folding into dropout keys.

    x: [batch, ...] activations; batch must divide n_micro. The
    shard_map is MANUAL over 'pp' only (axis_names={'pp'}): every other
    mesh axis stays compiler-managed inside the stage, so 'dp' batch
    sharding flows through untouched and intra-stage 'tp' (Megatron
    column/row splits of the stacked weights, P('pp', None, 'tp') /
    P('pp', 'tp', None) from the transpiler) gets its psums from GSPMD
    — the scaling-book pp x tp composition with no hand collectives.
    """
    from jax.sharding import PartitionSpec as P

    mesh_shape = dict(mesh.shape)
    n_stages = mesh_shape[axis_name]
    n_layer = jax.tree.leaves(xs)[0].shape[0]
    if n_layer % n_stages:
        raise ValueError(
            'pipeline_layer_scan: n_layer %d not divisible by pp=%d'
            % (n_layer, n_stages))
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError(
            'pipeline_layer_scan: batch %d not divisible by n_micro %d'
            % (batch, n_micro))
    mb = batch // n_micro
    mb_x = x.reshape((n_micro, mb) + x.shape[1:])
    # batch-aligned side inputs are microbatched the same way; the stage
    # picks row-block m so cross attention sees ITS examples' memory
    mb_extras = jax.tree.map(
        lambda e: e.reshape((n_micro, mb) + e.shape[1:]), extras)

    # specs constrain the MANUAL axis only: stage dim of the stacked
    # weights on pp, activations replicated over pp (stage 0 ingests)
    param_specs = jax.tree.map(
        lambda a: P(*((axis_name,) + (None,) * (a.ndim - 1))), xs)

    def inner(local_xs, mbx, ext):
        def stage_fn(local, h, m):
            ext_m = jax.tree.map(lambda e: e[m], ext)
            body = make_body(ext_m, m)
            if aux:
                # body carry is (h, aux_sum) — MoE stacks accumulate
                # their per-layer load-balancing loss through the scan
                (out, a), _ = jax.lax.scan(
                    body, (h, jnp.zeros((), jnp.float32)), local)
                return out, a
            out, _ = jax.lax.scan(body, h, local)
            return out

        res = pipeline(stage_fn, local_xs, mbx, axis_name,
                       with_mb_index=True, with_aux=aux)
        out, aux_sum = res if aux else (res, None)
        # emit only the last stage's result; zeros elsewhere so the psum
        # over pp reconstructs the true output on every device
        is_last = jax.lax.axis_index(axis_name) == n_stages - 1
        out = jnp.where(is_last, out, jnp.zeros_like(out))
        out = jax.lax.psum(out, axis_name)
        if aux:
            # each stage summed its own layers' aux over its n_micro
            # valid ticks; psum totals the pipe, /n_micro recovers the
            # full-batch per-token mean the unpipelined scan computes
            return out, jax.lax.psum(aux_sum, axis_name) / n_micro
        return out

    out_specs = (P(), P()) if aux else P()
    mapped = jax.shard_map(
        inner, mesh=mesh, axis_names=frozenset({axis_name}),
        in_specs=(param_specs, P(), jax.tree.map(lambda _: P(),
                                                 mb_extras)),
        out_specs=out_specs, check_vma=False)
    res = mapped(xs, mb_x, mb_extras)
    out, aux_total = res if aux else (res, None)
    out = out.reshape((batch,) + out.shape[2:])
    if aux:
        return out, aux_total
    return out
