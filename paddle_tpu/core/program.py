"""Program IR: Variable / Operator / Block / Program.

Reference: python/paddle/fluid/framework.py (Program/Block/Variable/Operator)
and paddle/fluid/framework/{program_desc,block_desc,op_desc}.{h,cc}.

TPU-native twist: the Program is a pure description. Nothing executes at
build time; the Executor lowers a whole Program (forward + backward + update)
into ONE jitted XLA computation. Mutating a Program bumps its version so
compiled-executable caches invalidate.
"""

import contextlib
import os
import sys

from . import unique_name
from .dtypes import canonical_dtype

# Root of the paddle_tpu package: frames under it are framework
# machinery, frames outside it are the user code an op's construction
# provenance should point at (core/program.py -> core -> paddle_tpu).
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) + os.sep


def _capture_provenance():
    """'file.py:line' of the nearest non-framework frame on the stack —
    the user statement that (transitively) appended this op. Every
    analysis diagnostic and Operator.__repr__ points there, so a shape
    error deep in a 200-op graph names the layers call that built it,
    not the tracer. One short frame walk per append_op; hot
    program-building loops can switch it off with
    PADDLE_TPU_PROVENANCE=0 (None is stored, diagnostics degrade to
    op indices). Returns None when the whole stack is framework frames
    (programs built by clone/serialize keep the ORIGINAL op's
    provenance instead — see Program.clone)."""
    if os.environ.get('PADDLE_TPU_PROVENANCE') == '0':
        return None
    f = sys._getframe(2)   # skip _capture_provenance + append/prepend_op
    depth = 0
    while f is not None and depth < 40:
        filename = f.f_code.co_filename
        if not filename.startswith(_PKG_DIR) and \
                not filename.startswith('<'):
            return '%s:%d' % (filename, f.f_lineno)
        f = f.f_back
        depth += 1
    return None


class Variable(object):
    """A named tensor slot inside a Block.

    shape uses -1 for the (leading) batch dimension of data vars; concrete
    shapes are bound at Executor compile time from the feed.
    """

    def __init__(self, block, name, shape=None, dtype='float32', lod_level=0,
                 persistable=False, stop_gradient=False, is_data=False,
                 trainable=False, **kwargs):
        self.block = block
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = canonical_dtype(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.trainable = trainable
        self._error_clip = kwargs.get('error_clip', None)

    @property
    def error_clip(self):
        return self._error_clip

    @error_clip.setter
    def error_clip(self, value):
        # compile-relevant mutation: a clip set AFTER a run must not be
        # ignored by the executor's warm compile cache
        self._error_clip = value
        if self.block is not None and self.block.program is not None:
            self.block.program._bump_version()

    @property
    def program(self):
        return self.block.program

    def __repr__(self):
        return 'Variable(%s, shape=%s, dtype=%s%s)' % (
            self.name, self.shape, self.dtype,
            ', persistable' if self.persistable else '')

    # Arithmetic sugar (reference: fluid/layers/math_op_patch.py
    # monkey_patch_variable). Implemented via the layers API lazily to avoid
    # an import cycle.
    def _binary(self, other, op, reverse=False):
        from ..layers import ops as _ops
        from ..layers import tensor as _tensor
        if not isinstance(other, Variable):
            other = _tensor.fill_constant(
                shape=[1], dtype=self.dtype, value=float(other))
        a, b = (other, self) if reverse else (self, other)
        return op(a, b)

    def __add__(self, other):
        from ..layers import ops as _ops
        return self._binary(other, _ops.elementwise_add)

    __radd__ = __add__

    def __sub__(self, other):
        from ..layers import ops as _ops
        return self._binary(other, _ops.elementwise_sub)

    def __rsub__(self, other):
        from ..layers import ops as _ops
        return self._binary(other, _ops.elementwise_sub, reverse=True)

    def __mul__(self, other):
        from ..layers import ops as _ops
        return self._binary(other, _ops.elementwise_mul)

    __rmul__ = __mul__

    def __truediv__(self, other):
        from ..layers import ops as _ops
        return self._binary(other, _ops.elementwise_div)

    # NOTE: __eq__/__lt__ are intentionally NOT overloaded (identity
    # semantics stay default, matching the reference) — building compare ops
    # from `==` would corrupt `in`-checks and dict use with silent op
    # side effects. Use layers.equal / layers.less_than.

    def astype(self, dtype):
        from ..layers import tensor as _tensor
        return _tensor.cast(self, dtype)


class Parameter(Variable):
    """A trainable persistable Variable (reference: framework.py Parameter)."""

    def __init__(self, block, name, shape, dtype, **kwargs):
        super(Parameter, self).__init__(
            block, name, shape=shape, dtype=dtype, persistable=True,
            trainable=kwargs.pop('trainable', True), **{
                k: v for k, v in kwargs.items() if k in ('lod_level',)
            })
        self.optimize_attr = kwargs.get('optimize_attr', {'learning_rate': 1.0})
        self.regularizer = kwargs.get('regularizer', None)
        self.gradient_clip_attr = kwargs.get('gradient_clip_attr', None)
        self.do_model_average = kwargs.get('do_model_average', None)
        self.initializer = kwargs.get('initializer', None)


class Operator(object):
    """One op invocation. inputs/outputs map slot name -> list of var names.

    `provenance` is the 'file.py:line' of the user statement that built
    the op (captured by Block.append_op; None with
    PADDLE_TPU_PROVENANCE=0 or for purely framework-built programs).
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None,
                 provenance=None):
        self.block = block
        self.type = type
        self.inputs = {k: list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})
        self.provenance = provenance

    def input(self, slot):
        names = self.inputs.get(slot, [])
        return names[0] if names else None

    def output(self, slot):
        names = self.outputs.get(slot, [])
        return names[0] if names else None

    def input_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    def output_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def __repr__(self):
        where = ' @ %s' % self.provenance if self.provenance else ''
        return 'Op(%s, in=%s, out=%s%s)' % (self.type, self.inputs,
                                            self.outputs, where)


def _to_name_list(value):
    """Normalize op input/output values to a list of variable names."""
    if value is None:
        return []
    if isinstance(value, (Variable, str)):
        value = [value]
    return [v.name if isinstance(v, Variable) else v for v in value]


class Block(object):
    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}
        self.ops = []

    @property
    def parent(self):
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    def create_var(self, name=None, **kwargs):
        if name is None:
            name = unique_name.generate('tmp')
        if name in self.vars:
            return self.vars[name]
        var = Variable(self, name, **kwargs)
        self.vars[name] = var
        self.program._bump_version()
        return var

    def create_parameter(self, name, shape, dtype, **kwargs):
        if name in self.vars:
            return self.vars[name]
        param = Parameter(self, name, shape, dtype, **kwargs)
        self.vars[name] = param
        self.program._bump_version()
        return param

    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise KeyError('Variable %r not found in block %d' % (name, self.idx))
        return v

    def _find_var_recursive(self, name):
        if name in self.vars:
            return self.vars[name]
        if self.parent is not None:
            return self.parent._find_var_recursive(name)
        return None

    def has_var(self, name):
        return self._find_var_recursive(name) is not None

    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        inputs = {k: _to_name_list(v) for k, v in (inputs or {}).items()}
        outputs = {k: _to_name_list(v) for k, v in (outputs or {}).items()}
        op = Operator(self, type, inputs, outputs, attrs,
                      provenance=_capture_provenance())
        self.ops.append(op)
        self.program._bump_version()
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None):
        inputs = {k: _to_name_list(v) for k, v in (inputs or {}).items()}
        outputs = {k: _to_name_list(v) for k, v in (outputs or {}).items()}
        op = Operator(self, type, inputs, outputs, attrs,
                      provenance=_capture_provenance())
        self.ops.insert(0, op)
        self.program._bump_version()
        return op

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def __repr__(self):
        return 'Block(%d, %d vars, %d ops)' % (self.idx, len(self.vars),
                                               len(self.ops))


class Program(object):
    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self._version = 0
        self._seed = None
        # What the compiled XLA module is called (``jit_<name>`` in a
        # trace's module line and in a load error). None: the executor
        # says ``train_step`` or ``infer_step``. Read when a signature
        # compiles; not part of any cache key.
        self.name = None
        # The startup Program that holds this program's param-init ops
        # (recorded by LayerHelper.create_parameter; used by
        # optimizer.minimize when no startup_program is passed).
        self._startup_ref = None
        # Sharding annotations attached by parallel.transpile:
        # var name -> jax.sharding.PartitionSpec (or None)
        self.var_shardings = {}
        self.mesh = None
        # Pipeline parallelism config attached by parallel.transpile when
        # strategy.pipeline_parallel is set: {'n_micro': int}. Scan-stacked
        # layer ops (transformer_layer_stack) read it and run the GPipe
        # microbatch schedule over the mesh's 'pp' axis.
        self.pipeline = None
        # Mixed precision: None (full fp32) or 'bf16' — matmul/conv-class
        # ops autocast inputs to bfloat16 (MXU-native) while params,
        # grads, optimizer state and loss-class ops stay fp32
        # (master-weight AMP; reference analog: fluid's float16 lists).
        self.amp = None
        # Rematerialization policy set by memory_optimize(): None, 'full',
        # 'dots_saveable', or 'nothing_saveable' (jax.checkpoint).
        self.remat_policy = None
        # Quantized gradient allreduce (EQuARX wire format) over the dp
        # axis, set by ParallelStrategy(quantized_allreduce=True); the
        # per-call PADDLE_TPU_QUANT_ALLREDUCE env knob overrides in
        # either direction (quant/core.grad_allreduce_policy).
        self.quant_allreduce = None

    def _bump_version(self):
        self._version += 1

    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx=None):
        parent_idx = self.current_block_idx if parent_idx is None else parent_idx
        block = Block(self, len(self.blocks), parent_idx)
        self.blocks.append(block)
        self.current_block_idx = block.idx
        self._bump_version()
        return block

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx
        if self.current_block_idx < 0:
            self.current_block_idx = 0

    def block(self, idx):
        return self.blocks[idx]

    def all_parameters(self):
        params = []
        for b in self.blocks:
            params.extend(b.all_parameters())
        return params

    def list_vars(self):
        for b in self.blocks:
            for v in b.vars.values():
                yield v

    @property
    def random_seed(self):
        return self._seed

    @random_seed.setter
    def random_seed(self, seed):
        self._seed = seed

    def clone(self, for_test=False):
        """Deep-copy the program. for_test=True flips is_test attrs and drops
        backward/optimize ops (reference: framework.py Program.clone +
        inference_optimize)."""
        p = Program()
        p._seed = self._seed
        p.var_shardings = dict(self.var_shardings)
        p.mesh = self.mesh
        p.pipeline = dict(self.pipeline) if self.pipeline else None
        p.quant_allreduce = self.quant_allreduce
        for i, b in enumerate(self.blocks):
            nb = p.blocks[0] if i == 0 else p.create_block(b.parent_idx)
            for name, v in b.vars.items():
                if isinstance(v, Parameter):
                    nv = Parameter(nb, name, v.shape, v.dtype,
                                   trainable=v.trainable,
                                   optimize_attr=dict(v.optimize_attr),
                                   regularizer=v.regularizer,
                                   gradient_clip_attr=v.gradient_clip_attr,
                                   initializer=v.initializer)
                    nv.stop_gradient = v.stop_gradient
                else:
                    nv = Variable(nb, name, shape=v.shape, dtype=v.dtype,
                                  lod_level=v.lod_level,
                                  persistable=v.persistable,
                                  stop_gradient=v.stop_gradient,
                                  is_data=v.is_data, trainable=v.trainable)
                # carry layer-attached annotations (v2 input types,
                # row_shard hints) through the copy
                for extra in ('_v2_type', '_v2_len_var', 'row_shard',
                              'expert_shard', 'expert_shard_axis',
                              '_error_clip', 'sparse_grad', 'sparse_ids'):
                    if hasattr(v, extra):
                        setattr(nv, extra, getattr(v, extra))
                nb.vars[name] = nv
            for op in b.ops:
                if for_test and op.type in ('backward_marker',) :
                    break  # everything after backward is train-only
                attrs = dict(op.attrs)
                if for_test and 'is_test' in attrs:
                    attrs['is_test'] = True
                if for_test and op.type in ('dropout', 'batch_norm'):
                    attrs['is_test'] = True
                # keep the ORIGINAL construction site, not the clone call
                nb.append_op(op.type, op.inputs, op.outputs,
                             attrs).provenance = op.provenance
        p.current_block_idx = 0
        return p

    def prune(self, targets):
        """Return a clone keeping only ops needed for target vars
        (reference: framework/prune.cc). Liveness descends into
        while/if_else sub-blocks, same as the executor's prune."""
        from .executor import _op_reads
        target_names = set(t.name if isinstance(t, Variable) else t
                           for t in targets)
        p = self.clone()
        b = p.global_block()
        needed = set(target_names)
        kept = []
        for op in reversed(b.ops):
            if set(op.output_names()) & needed or op.type == 'backward_marker':
                kept.append(op)
                needed.update(_op_reads(op, p))
        b.ops = list(reversed(kept))
        return p

    def to_string(self, throw_on_error=False):
        lines = []
        for b in self.blocks:
            lines.append('-- block %d (parent %d) --' % (b.idx, b.parent_idx))
            for name, v in b.vars.items():
                lines.append('  var %s : %s %s%s' % (
                    name, v.dtype, v.shape,
                    ' [persistable]' if v.persistable else ''))
            for op in b.ops:
                lines.append('  %r' % (op,))
        return '\n'.join(lines)

    __str__ = to_string


_main_program = Program()
_startup_program = Program()


def default_main_program():
    return _main_program


def default_startup_program():
    return _startup_program


def switch_main_program(program):
    global _main_program
    old = _main_program
    _main_program = program
    return old


def switch_startup_program(program):
    global _startup_program
    old = _startup_program
    _startup_program = program
    return old


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


def reset_default_programs():
    global _main_program, _startup_program
    _main_program = Program()
    _startup_program = Program()
    unique_name.reset()
