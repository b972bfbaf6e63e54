"""DistributeTranspiler — SPMD edition.

Reference: python/paddle/fluid/distribute_transpiler.py splits a program
into trainer + pserver halves and inserts send/recv. TPU-native: the
program stays whole; this transpiler attaches a PartitionSpec to every var
(params, grads, activations, optimizer state) and sets program.mesh, after
which the Executor's GSPMD path lets XLA insert psum/all_gather/
reduce_scatter over the ICI mesh — the allreduce IS the pserver.

Strategies:
  data-parallel   : batch dim of data vars -> 'dp'; params replicated.
  tensor-parallel : fc/embedding weights column/row split on 'tp' by the
                    megatron pairing rule (column then row per block).
  sequence        : time dim of long activations -> 'sp' (ring attention).
  pipeline        : scan-stacked layer weights stage-sharded on 'pp'; the
                    layer-stack op runs the GPipe microbatch schedule
                    (pipeline.py) inside the jitted step.
  expert          : [E, ...] expert weights on 'ep' (set by switch_moe).
"""

import os

from jax.sharding import PartitionSpec as P

from .. import observe as _obs
from ..core.backward import GRAD_SUFFIX
from ..core.program import Parameter


class ParallelStrategy(object):
    def __init__(self, data_parallel=True, tensor_parallel=False,
                 sequence_parallel=False, tp_rules=None, sp_vars=None,
                 shard_embeddings=True, pipeline_parallel=False,
                 pipeline_microbatches=None, shard_optimizer_states=False,
                 fully_shard_parameters=False, quantized_allreduce=False,
                 shard_optimizer_state=None, grad_bucket_mb=None):
        self.data_parallel = data_parallel
        # Quantized gradient allreduce (PAPERS "EQuARX"): dense dp
        # gradients cross the wire as per-block-scaled int8 with
        # stochastic rounding instead of fp32 — ~3.9x less ICI traffic
        # on the training path's dominant collective. The executor
        # models the wire format on each dp-reduced gradient (see
        # quant/core.qdq); the explicit two-leg schedule lives in
        # collective.quantized_all_reduce. PADDLE_TPU_QUANT_ALLREDUCE
        # overrides per call.
        self.quantized_allreduce = quantized_allreduce
        # ZeRO-1 (beyond reference; the scaling-book optimizer-state
        # recipe): optimizer accumulators additionally shard over 'dp'
        # on their first free divisible axis. GSPMD then derives the
        # comms — the grad allreduce becomes reduce-scatter at the
        # update and the fresh params all-gather into the next forward;
        # per-chip state memory drops by ~dp x (2x params for Adam).
        # `shard_optimizer_state` (singular — the ZeRO-paper spelling)
        # is an explicit alias that wins over the plural default;
        # PADDLE_TPU_SHARD_OPT_STATE overrides both per transpile call.
        if shard_optimizer_state is not None:
            shard_optimizer_states = bool(shard_optimizer_state)
        self.shard_optimizer_states = shard_optimizer_states
        # Gradient-allreduce bucket size target in MB (see
        # collective.grad_bucket_policy / assign_grad_buckets; the
        # executor realizes one collective per bucket so XLA overlaps
        # them with the remaining backward). None = leave the dp
        # reduction as one fused collective after the whole backward.
        self.grad_bucket_mb = grad_bucket_mb
        # ZeRO-3 / FSDP: the PARAMETERS themselves (and their grads,
        # and — via the structural state loop — their accumulators)
        # also take 'dp' on a free divisible axis. XLA all-gathers each
        # weight at its use site and reduce-scatters its grad; weight
        # memory drops ~dp x at the cost of per-layer all-gathers.
        # Row-sharded sparse tables keep their own scheme (skipped).
        self.fully_shard_parameters = fully_shard_parameters
        self.tensor_parallel = tensor_parallel
        self.sequence_parallel = sequence_parallel
        # tp_rules: list of (param-name-substring, axis-index) pairs deciding
        # which weight dim is split over 'tp'.
        self.tp_rules = tp_rules or []
        self.sp_vars = sp_vars or []
        # Row-shard embedding tables flagged by layers.embedding(is_sparse/
        # is_distributed) — the pserver sparse-row role (go/pserver/
        # service.go) done as GSPMD gather partitioning.
        self.shard_embeddings = shard_embeddings
        # Pipeline parallelism over the mesh 'pp' axis: the program's
        # scan-stacked layer ops (transformer_layer_stack, built with
        # scan_layers=True) split their [n_layer, ...] weights into
        # contiguous stage chunks and run the GPipe microbatch schedule
        # (parallel/pipeline.py). Reference analog: the transpiler owns
        # program partitioning (distribute_transpiler.py:133 splits one
        # program into trainer/pserver halves); here it partitions the
        # layer stack across the pp axis.
        self.pipeline_parallel = pipeline_parallel
        # Microbatches per pipeline pass (default: the pp axis size).
        # Bubble fraction is (pp-1)/(n_micro+pp-1): at pp=4 the default
        # n_micro=4 idles ~43% of stage-ticks, n_micro=16 ~16%. Raise it
        # as far as per-microbatch batch size (batch % n_micro == 0 and
        # enough tokens per step to fill the MXU) allows.
        self.pipeline_microbatches = pipeline_microbatches


def shard_opt_state_env(default):
    """Per-call ``PADDLE_TPU_SHARD_OPT_STATE`` resolver (repo_lint
    env-scoped): '1'/'on'/'true' forces ZeRO-1 on, '0'/'off'/'false'
    forces it off, unset defers to the strategy flag — the env wins in
    either direction, matching the quant/bucket knob conventions."""
    raw = os.environ.get('PADDLE_TPU_SHARD_OPT_STATE')
    if raw is None or raw.strip() == '':
        return bool(default)
    return raw.strip().lower() not in ('0', 'off', 'false')


def optimizer_state_bytes(program, mesh=None):
    """Analytic optimizer-state memory model (the ZeRO-1 ledger, in the
    style of ``linalg.per_shard_peak_bytes``): walks every op carrying a
    'Param' input slot and sums the bytes of its persistable state
    inputs (Moment/Velocity/BetaPow/..., structurally — the same rule
    the accumulator-sharding loop in :func:`transpile` uses). Per-device
    bytes divide each accumulator by the extent of the mesh axes in its
    attached spec, so with ``shard_optimizer_states`` the reduction
    approaches dp x (minus the [1]-shaped beta-pow scalars that have no
    qualifying axis and stay replicated)."""
    import numpy as np

    from ..core.dtypes import to_jnp_dtype
    mesh = mesh if mesh is not None else program.mesh
    axes = dict(mesh.shape) if mesh is not None else {}
    block = program.global_block()
    shardings = program.var_shardings
    total = 0
    per_device = 0.0
    n_state = 0
    seen = set()
    for op in block.ops:
        if not op.inputs.get('Param'):
            continue
        for slot, names in op.inputs.items():
            if slot in ('Param', 'Grad', 'LearningRate'):
                continue
            for n in names:
                if n in seen:
                    continue
                v = block._find_var_recursive(n)
                if v is None or not v.persistable or v.shape is None:
                    continue
                seen.add(n)
                numel = 1
                for d in v.shape:
                    numel *= int(d)
                nbytes = numel * np.dtype(to_jnp_dtype(v.dtype)).itemsize
                extent = 1
                spec = shardings.get(n)
                for entry in (spec or ()):
                    parts = (entry,) if isinstance(entry, str) \
                        else tuple(entry or ())
                    for ax in parts:
                        extent *= int(axes.get(ax, 1))
                total += nbytes
                per_device += nbytes / max(extent, 1)
                n_state += 1
    per_device = int(per_device)
    return {'total': int(total), 'per_device': per_device,
            'reduction': float(total) / max(per_device, 1),
            'n_dp': int(axes.get('dp', 1)), 'n_state_vars': n_state}


def _tp_spec_for(param, rules):
    for substr, axis in rules:
        if substr in param.name:
            ndim = len(param.shape)
            spec = [None] * ndim
            spec[axis % ndim] = 'tp'
            return P(*spec)
    return None


_TP_PROPAGATE = frozenset((
    'relu', 'gelu', 'tanh', 'sigmoid', 'softsign', 'softplus', 'leaky_relu',
    'elu', 'dropout', 'scale', 'cast', 'elementwise_add', 'elementwise_mul',
    'elementwise_sub', 'elementwise_div'))


def _auto_tp_specs(program):
    """Derive Megatron column/row weight splits from the DATAFLOW, not
    names: a mul/matmul consuming an unsharded activation gets its weight
    column-split ('tp' on the output dim) and marks its activation
    tp-sharded; a mul/matmul consuming a tp-sharded activation gets its
    weight row-split (GSPMD inserts the psum), restoring replication.
    Elementwise/activation ops propagate the marker; the bias of a
    column-split layer is split the same way. Mis-detection only costs
    resharding traffic — GSPMD keeps numerics exact either way."""
    block = program.global_block()
    specs = {}
    tp_last = set()  # vars currently sharded 'tp' on their last dim
    for op in block.ops:
        if op.type in ('mul', 'matmul'):
            xn = op.inputs.get('X', [None])[0]
            yn = op.inputs.get('Y', [None])[0]
            yvar = block._find_var_recursive(yn) if yn else None
            if isinstance(yvar, Parameter) and yn not in specs:
                ndim = len(yvar.shape)
                if xn in tp_last:
                    specs[yn] = P(*(['tp'] + [None] * (ndim - 1)))
                else:
                    specs[yn] = P(*([None] * (ndim - 1) + ['tp']))
                    tp_last.update(op.output_names())
        elif op.type == 'fused_attention':
            # a sublayer that owns its projections splits its heads: q,
            # k and v by columns, the output projection by rows (its
            # psum restores replication, so the output stays unmarked)
            for slot in ('Wq', 'Wk', 'Wv', 'Wo'):
                for n in op.inputs.get(slot, ()):
                    if n not in specs and isinstance(
                            block._find_var_recursive(n), Parameter):
                        specs[n] = P('tp', None) if slot == 'Wo' \
                            else P(None, 'tp')
        elif op.type == 'elementwise_add' and \
                op.inputs.get('X', [None])[0] in tp_last:
            yn = op.inputs.get('Y', [None])[0]
            yvar = block._find_var_recursive(yn) if yn else None
            if isinstance(yvar, Parameter) and len(yvar.shape) == 1 \
                    and yn not in specs:
                specs[yn] = P('tp')  # bias of a column-split layer
            tp_last.update(op.output_names())
        elif op.type in _TP_PROPAGATE:
            if any(n in tp_last for n in op.input_names()):
                tp_last.update(op.output_names())
    return specs


# Megatron pairing for the stacked-layer weight slots (pp x tp): qkv +
# ffn-in column split (tp on the output-features dim), out-proj +
# ffn-out row split (tp on the input dim; GSPMD inserts the psum).
_STACK_TP_COL = frozenset(('SlfQ', 'SlfK', 'SlfV', 'CrossQ', 'CrossK',
                           'CrossV', 'FfnW1'))
_STACK_TP_ROW = frozenset(('SlfO', 'CrossO', 'FfnW2'))


_PP_STACK_OPS = ('transformer_layer_stack', 'moe_layer_stack')


def _pp_stack_specs(program, n_stages, with_tp=False, with_ep=False):
    """Stage-shard the scan-stacked layer weights: every parameter input
    of a transformer_layer_stack / moe_layer_stack op gets P('pp', ...)
    on its leading [n_layer] axis, so stage s of the GPipe schedule
    holds layers [s*L/pp, (s+1)*L/pp) — the op lowering runs the
    schedule itself (ops/transformer_ops.py pipelined paths). With
    with_tp, the 3-D matmul weights additionally column/row split over
    'tp' inside each stage; with with_ep, [n_layer, E, ...] expert
    weights keep their 'ep' split on axis 1. Both compose because the
    shard_map is manual over pp only — GSPMD manages the intra-stage
    tp/ep collectives."""
    specs = {}
    block = program.global_block()
    found_stack = False
    for op in block.ops:
        if op.type not in _PP_STACK_OPS:
            continue
        found_stack = True
        for slot, names in op.inputs.items():
            if slot in ('X', 'EncOut', 'SrcLength'):
                continue
            for n in names:
                v = block._find_var_recursive(n)
                if not isinstance(v, Parameter):
                    continue
                if v.shape[0] % n_stages:
                    raise ValueError(
                        'pipeline_parallel: stacked param %r has '
                        'n_layer=%d, not divisible by pp=%d'
                        % (n, v.shape[0], n_stages))
                spec = ['pp'] + [None] * (len(v.shape) - 1)
                if with_ep and getattr(v, 'expert_shard', False):
                    ax = getattr(v, 'expert_shard_axis', 1)
                    if ax < 1:
                        # axis 0 is the stage axis here; an [E, ...]
                        # expert annotation cannot sit on a stacked op
                        raise ValueError(
                            'stacked expert param %r has '
                            'expert_shard_axis=%d; scan-stacked MoE '
                            'weights are [n_layer, E, ...] (axis >= 1)'
                            % (n, ax))
                    spec[ax] = 'ep'
                elif with_tp and len(v.shape) == 3:
                    if slot in _STACK_TP_COL:
                        spec[2] = 'tp'
                    elif slot in _STACK_TP_ROW:
                        spec[1] = 'tp'
                specs[n] = P(*spec)
    if not found_stack:
        raise ValueError(
            'pipeline_parallel requires scan-stacked layers: build the '
            'model with scan_layers=True (transformer_layer_stack / '
            'moe_layer_stack ops) so the transpiler can partition the '
            'stack into pp stages')
    return specs


def _row_shard_axis(mesh):
    """Mesh axis for embedding row-sharding: prefer the model-parallel
    axis (rows stay put while dp batches move), fall back to dp."""
    for axis in ('tp', 'ep', 'sp', 'dp'):
        if mesh.shape.get(axis, 1) > 1:
            return axis
    return None


def _row_shard_spec_for(param, mesh):
    if not getattr(param, 'row_shard', False):
        return None
    axis = _row_shard_axis(mesh)
    if axis is None:
        return None
    return P(*([axis] + [None] * (len(param.shape) - 1)))


def _expert_shard_spec_for(param, mesh):
    """Expert-stacked weights (layers.switch_moe) shard their expert
    axis over 'ep' — each chip holds E/ep experts. The axis defaults to
    0 ([E, ...]); scan-stacked MoE layers ([n_layer, E, ...]) set
    expert_shard_axis = 1."""
    if not getattr(param, 'expert_shard', False):
        return None
    if dict(mesh.shape).get('ep', 1) <= 1:
        return None
    axis = getattr(param, 'expert_shard_axis', 0)
    spec = [None] * len(param.shape)
    spec[axis] = 'ep'
    return P(*spec)


def transpile(program, mesh, strategy=None):
    """Attach shardings for `mesh` to `program` in place; returns program."""
    strategy = strategy or ParallelStrategy()
    shardings = {}
    block = program.global_block()

    auto_tp = {}
    if strategy.tensor_parallel and not strategy.tp_rules:
        auto_tp = _auto_tp_specs(program)

    pp_specs = {}
    # re-transpiling with pipeline off must clear a previous schedule —
    # the stack lowerings key off program.pipeline, and the version bump
    # below guarantees they get re-traced with the new decision
    program.pipeline = None
    if strategy.pipeline_parallel:
        n_pp = dict(mesh.shape).get('pp', 1)
        if n_pp <= 1:
            raise ValueError(
                'pipeline_parallel=True but the mesh has no pp axis > 1 '
                '(mesh shape %s) — build it with make_mesh(pp=n_stages)'
                % dict(mesh.shape))
        pp_specs = _pp_stack_specs(
            program, n_pp,
            with_tp=(strategy.tensor_parallel and
                     dict(mesh.shape).get('tp', 1) > 1),
            with_ep=dict(mesh.shape).get('ep', 1) > 1)
        program.pipeline = {
            'n_micro': int(strategy.pipeline_microbatches or n_pp)}

    n_dp = dict(mesh.shape).get('dp', 1)
    shard_opt = shard_opt_state_env(strategy.shard_optimizer_states)

    def _dp_extend(spec, shape, enabled):
        """Extend a spec with 'dp' on the first free axis whose size
        divides the dp extent (the ZeRO family's sharding move).
        Returns the original spec when disabled, dp <= 1, 'dp' is
        already used, or no axis qualifies."""
        if not enabled or n_dp <= 1 or not shape:
            return spec
        parts = list(spec) + [None] * (len(shape) - len(spec))
        if 'dp' in parts:
            return spec
        for i, (p, dim) in enumerate(zip(parts, shape)):
            if p is None and dim and dim % n_dp == 0:
                parts[i] = 'dp'
                return P(*parts)
        return spec

    for var in program.list_vars():
        if var.shape is None:
            continue
        if isinstance(var, Parameter):
            spec = pp_specs.get(var.name)
            if spec is None and strategy.tensor_parallel:
                spec = _tp_spec_for(var, strategy.tp_rules) \
                    if strategy.tp_rules else auto_tp.get(var.name)
            if spec is None:
                spec = _expert_shard_spec_for(var, mesh)
            row_sharded = False
            if spec is None and strategy.shard_embeddings:
                spec = _row_shard_spec_for(var, mesh)
                row_sharded = spec is not None
            if not row_sharded:
                # ZeRO-3/FSDP: weights themselves take 'dp'; row-sharded
                # sparse tables keep their own scheme
                spec = _dp_extend(spec if spec is not None else P(),
                                  var.shape,
                                  strategy.fully_shard_parameters)
                if spec == P():
                    spec = None
            shardings[var.name] = spec if spec is not None else P()
            # ZeRO-1: the gradient additionally takes 'dp' on a free
            # divisible axis — the executor applies this spec at the
            # grad-assignment boundary, so XLA turns the dp allreduce
            # into a reduce-scatter feeding the shard-local update.
            gspec = _dp_extend(spec if spec is not None else P(),
                               var.shape, shard_opt)
            if spec is not None or gspec != P():
                shardings[var.name + GRAD_SUFFIX] = gspec
        elif var.is_data and strategy.data_parallel:
            ndim = len(var.shape)
            spec = ['dp'] + [None] * (ndim - 1)
            if strategy.sequence_parallel and var.name in strategy.sp_vars \
                    and ndim >= 2:
                spec[1] = 'sp'
            shardings[var.name] = P(*spec)

    # Optimizer accumulators follow their parameter's sharding — derived
    # STRUCTURALLY from the optimizer op (every op carrying a 'Param' input
    # slot pairs that param with its same-shape state inputs: Moment,
    # Velocity, ...). Name strings play no part, so colliding names
    # cannot mis-shard (reference analog: accumulators live beside the
    # param on its pserver shard, go/pserver/service.go).
    for op in block.ops:
        pnames = op.inputs.get('Param')
        if not pnames:
            continue
        pvar = block._find_var_recursive(pnames[0])
        spec = shardings.get(pnames[0])
        if pvar is None or spec is None:
            continue
        for slot, names in op.inputs.items():
            if slot in ('Param', 'Grad'):
                continue
            for n in names:
                v = block._find_var_recursive(n)
                if v is not None and v.persistable and n not in shardings \
                        and v.shape == pvar.shape:
                    shardings[n] = _dp_extend(spec, v.shape, shard_opt)

    # Remaining persistable state (lr, beta_pow, BN stats, ...) replicates.
    for var in program.list_vars():
        if var.persistable and var.shape is not None \
                and var.name not in shardings:
            shardings[var.name] = P()

    program.var_shardings.update(shardings)
    program.mesh = mesh
    program.quant_allreduce = bool(strategy.quantized_allreduce) or None
    program.grad_bucket_mb = strategy.grad_bucket_mb
    if _obs.enabled():
        m = optimizer_state_bytes(program, mesh)
        _obs.set_gauge('trainer.optimizer_state_bytes_total', m['total'])
        _obs.set_gauge('trainer.optimizer_state_bytes_per_device',
                       m['per_device'])
        _obs.set_gauge('trainer.optimizer_state_reduction_x',
                       m['reduction'])
    # invalidate compiled-step caches: a step compiled BEFORE transpile
    # has no sharding constraints (and no pipeline schedule) traced in —
    # reusing it would silently train without the requested layout
    program._bump_version()
    return program


class DistributeTranspiler(object):
    """API-compatible facade over transpile() (reference
    distribute_transpiler.py:DistributeTranspiler)."""

    def __init__(self):
        self._program = None

    def transpile(self, trainer_id=0, program=None, pservers=None,
                  trainers=1, mesh=None, strategy=None, **kwargs):
        from ..core.program import default_main_program
        program = program or default_main_program()
        if mesh is None:
            from .mesh import make_mesh
            mesh = make_mesh()
        self._program = transpile(program, mesh, strategy)
        return self._program

    def get_trainer_program(self):
        # SPMD: every worker runs the same whole program.
        return self._program

    def get_pserver_program(self, endpoint=None):
        # No parameter server exists under SPMD; updates are fused into the
        # train step and grads ride ICI collectives.
        return self._program
