"""Does a compiled paged program move an arena, or gather a whole
table? Read from its HLO.

The paged ops (ops/paged_decode_ops.py) write the KV arenas in place:
in the program the compiler hands back, the only instruction that may
touch arena-sized data is an attention gather, and since the attention
goes in column blocks, eight (row, column block) pairs an iteration
(ops/pallas/paged_attention.py), a gather's own result is those
pairs' pages, never the extent of the batch's tables. Whether that holds is a property of the *optimised*
HLO (a layout the TPU picks, a scatter it re-lays its operand for, a
loop output it double-buffers all show up there as ``copy``
instructions and nowhere in the jaxpr), so this module reads
``compiled.as_text()`` — on the CPU for a described chip, or on the
chip itself (chip_smoke.py) — and needs no trace.

``arena_sized_instructions(text, min_elements)`` lists the
instructions that materialise ``min_elements`` or more:

- every ``copy`` (and ``copy-done``) of that size, wherever it is;
- every other instruction of that size, except those that move
  nothing (parameters, tuples and their elements, bitcasts, loops), an
  in-place ``dynamic-update-slice`` (alone or as a fusion) and, unless
  ``gathers=True``, a gather (alone or as a fusion): it reads the
  arena and writes a block;
- of a ``custom-call`` (a kernel), the results it does not alias to an
  operand (``output_to_operand_aliasing``): a result that is its
  operand's buffer is written where it lies, and the kernel moves what
  its own blocks move, never the arena.

Callers pass a layer's arena elements, NB * bs * H * D (what consumes
a gather's result is not exempt: an iteration's pages are far under
an arena wherever the pool is larger than BLOCK_ROWS column blocks), or, with ``gathers=True``, the whole-table extent
N * P * bs * H * D that no instruction of a serving program may reach.

Instructions inside a fusion never reach memory and are skipped; a
fusion counts by what it calls.

The arenas of a cache kind with a size a sequence (a recurrent layer's
state, ``[layers, slots + 1, ...]``: model.CacheKind.per_seq) are held to
the same rule by the same function, with a layer of the arena as
``min_elements``: the decode step compiled for the TPU walks the live
rows' slots in one kernel a layer that takes both arenas whole and
aliases them to its results (ops/pallas/ssm_state_update.py; on every
other platform a loop slices a live row's slot, advances it and writes
it back with ``dynamic-update-slice`` at (layer, slot)), a prefill chunk
slices and writes the one slot of its sequence, and nothing else of that
size may appear (tests/test_v5e_compile.py compiles both for a described
v5e at the published state geometry and also holds every ``copy`` of an
arena's shape to the entry computation). What to look for there: a slot's
slice that fuses into two consumers makes the arena an operand of a
computation beside its own in-place update, and the compiler then copies
the arena whole (ops/ssm_ops.py::_slot_of keeps the slice one value).
"""

import collections
import re

__all__ = ['Instruction', 'arena_sized_instructions']

Instruction = collections.namedtuple(
    'Instruction', ['name', 'opcode', 'shape', 'elements', 'computation'])

# nothing is moved by these: they name, group or alias buffers
_MOVES_NOTHING = frozenset([
    'parameter', 'tuple', 'get-tuple-element', 'bitcast', 'constant',
    'while', 'conditional', 'call', 'copy-start', 'opt-barrier'])

_COMPUTATION = re.compile(
    r'^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$')
_ASSIGN = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$')
_ARRAY = re.compile(r'\b[a-z]+[0-9]*[a-z0-9]*\[([0-9,]*)\]')
_OPCODE = re.compile(r'^\s*([\w\-]+)\(')
_CALLS = re.compile(r'\bcalls=%?([\w.\-]+)')
_ALIASING = 'output_to_operand_aliasing='
_ALIASED = re.compile(r'\{(\d*)\}:')


def _balanced(s, start):
    """Index just past the parenthesis that closes ``s[start]``."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == '(':
            depth += 1
        elif s[i] == ')':
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def _results(shape):
    """The result shapes of a (possibly tuple) shape string, in order."""
    if not shape.startswith('('):
        return [shape]
    out, depth, last = [], 0, 1
    for i, ch in enumerate(shape):
        depth += ch in '([{'
        depth -= ch in ')]}'
        if (ch == ',' and depth == 1) or depth == 0:
            out.append(shape[last:i])
            last = i + 1
    return out


def _unaliased(shape, rest):
    """``shape`` without the results that ``rest``, an instruction's text
    behind its shape, aliases to operands (``{{0}: (4, {}), {2}: (10,
    {})}``: results 0 and 2)."""
    at = rest.find(_ALIASING)
    if at < 0:
        return shape
    aliased = {int(i or 0) for i in
               _ALIASED.findall(rest[at:rest.find(')}', at)])}
    return ', '.join(r for i, r in enumerate(_results(shape))
                     if i not in aliased)


def _elements(shape):
    """Largest array of a (possibly tuple) shape string."""
    best = 0
    for dims in _ARRAY.findall(shape):
        n = 1
        for d in dims.split(','):
            if d:
                n *= int(d)
        best = max(best, n)
    return best


def _parse(text):
    """{computation: [(name, opcode, shape, callee)]} in program
    order; a custom call's ``shape`` is what it does not alias."""
    comps = collections.OrderedDict()
    cur = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        if cur is None:
            continue
        if line.startswith('}'):
            cur = None
            continue
        m = _ASSIGN.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        end = _balanced(rhs, 0) if rhs.startswith('(') else \
            (rhs.find(' ') if ' ' in rhs else len(rhs))
        shape, rest = rhs[:end], rhs[end:]
        op = _OPCODE.match(rest)
        if not op:
            continue
        behind = rest[_balanced(rest, rest.index('(')):]
        callee = _CALLS.search(behind)
        if op.group(1) == 'custom-call':
            shape = _unaliased(shape, behind)
        cur.append((name, op.group(1), shape,
                    callee.group(1) if callee else None))
    return comps


def arena_sized_instructions(hlo_text, min_elements, gathers=False):
    """The instructions of an optimised HLO module that materialise
    ``min_elements`` or more (see the module docstring), gathers
    included only with ``gathers=True``; empty when the arenas are
    written in place and no table is gathered whole. Returns
    ``Instruction`` tuples in program order."""
    comps = _parse(hlo_text)
    fused = {callee for body in comps.values()
             for _, opcode, _, callee in body
             if opcode == 'fusion' and callee}

    def kind(opcode, callee):
        """What a fusion is, by the instructions it calls."""
        if opcode != 'fusion':
            return opcode
        inner = comps.get(callee, ())
        if any(op == 'gather' for _, op, _, _ in inner):
            return 'gather'
        if any(op == 'dynamic-update-slice'
               and _elements(shape) >= min_elements
               for _, op, shape, _ in inner):
            return 'dynamic-update-slice'
        return 'fusion'

    found = []
    for cname, body in comps.items():
        if cname in fused:
            continue
        for name, opcode, shape, callee in body:
            n = _elements(shape)
            if n < min_elements:
                continue
            what = kind(opcode, callee)
            if what in _MOVES_NOTHING or what == 'dynamic-update-slice' \
                    or (what == 'gather' and not gathers):
                continue
            found.append(Instruction(name, opcode, shape, n, cname))
    return found
