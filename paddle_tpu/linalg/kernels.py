"""Distributed dense linear-algebra kernels over the device mesh.

PAPERS "Large Scale Distributed Linear Algebra With Tensor Processing
Units": TPU pods run dense matmul/QR/eigensolvers at sizes (100k x
100k+) no single host holds, by keeping every matrix blocked across
the mesh and moving PANELS — never whole operands — over ICI. These
are the shard_map bodies that implement that discipline on the repo's
dp x tp mesh:

- :func:`summa_matmul` — SUMMA blocked matmul on the 2-D dp x tp grid.
  A is blocked [dp, tp], B is blocked [dp, tp], C accumulates in place
  [dp, tp]. For each k-panel the owning grid column broadcasts its A
  panel along the row ('tp' axis) and the owning grid row broadcasts
  its B panel along the column ('dp' axis); every device accumulates
  the local panel product. The panel fetch for step t+1 is issued
  BEFORE step t's dot (double-buffered scan carry), so XLA overlaps
  the broadcast ppermute chain with the previous panel's matmul.
- :func:`blocked_cholesky` — right-looking blocked Cholesky with the
  matrix row-blocked over one axis: the panel owner's diagonal block
  is broadcast, every device panel-solves its local rows, the column
  panel is all-gathered, and the trailing Schur complement updates
  locally.
- :func:`blocked_qr` — blocked Householder QR: each column panel is
  all-gathered ([N, b] — the ONE tall-skinny temporary, never the
  full matrix) and factored redundantly through the backend's
  Householder QR; the trailing block row of R is a psum-reduced
  projection and the trailing matrix updates locally (block
  Gram-Schmidt between panels).
- :func:`power_iter_step` — one power-iteration step with A
  column-blocked: z = A v is a local [N, N/P] matvec followed by an
  N-element allreduce, which routes through exact ``psum`` or the PR
  13 ``quantized_all_reduce`` — the compression/accuracy trade on an
  allreduce-DOMINATED workload (the reduction is the step).

Per-shard peak memory stays O(N^2/P) everywhere: the only cross-shard
temporaries are panels (O(N b / P_axis)) and the QR/Cholesky gathered
panel (O(N b)). :func:`paddle_tpu.linalg.per_shard_peak_bytes` is the
analytic model tests/test_linalg.py asserts against.

Panel/block sizes: explicit argument > ``PADDLE_TPU_SUMMA_PANEL`` /
``PADDLE_TPU_LINALG_BLOCK`` env knobs (read per call) > the PR 8
autotuner's ``linalg`` op family (``tuning.decide_summa_panel`` /
``decide_linalg_block``) > :func:`default_panel`. Resolution lives in
``ops/linalg_ops.py`` so direct kernel callers pass concrete sizes.
"""

import math

import jax
import jax.numpy as jnp

from ..parallel.collective import broadcast, quantized_all_reduce

__all__ = ['summa_matmul', 'blocked_cholesky', 'blocked_qr',
           'power_iter_step', 'matmul_reference', 'cholesky_reference',
           'qr_reference', 'legal_panels', 'default_panel',
           'default_block', 'legal_blocks', 'axis_sizes_of',
           'per_shard_peak_bytes']


# ------------------------------------------------------------- helpers
def axis_sizes_of(mesh, *axes):
    """Sizes of the named axes on `mesh` (1 when absent or mesh None)."""
    shape = dict(mesh.shape) if mesh is not None else {}
    return tuple(int(shape.get(a, 1)) for a in axes)


def _divisors(n):
    out = []
    d = 1
    while d * d <= n:
        if n % d == 0:
            out.append(d)
            if d != n // d:
                out.append(n // d)
        d += 1
    return sorted(out)


def legal_panels(k, n_dp, n_tp):
    """Legal SUMMA panel sizes for contraction length `k` on a
    dp x tp grid: a panel must divide BOTH local block extents
    (K/tp for A's columns, K/dp for B's rows) so no panel ever
    straddles an owner boundary."""
    if k % max(n_dp, 1) or k % max(n_tp, 1):
        return []
    g = math.gcd(k // max(n_tp, 1), k // max(n_dp, 1))
    return _divisors(g)


def default_panel(k, n_dp, n_tp, n=None, m=None, dtype='float32'):
    """Untuned SUMMA panel: the largest legal panel <= 256 (an
    MXU-friendly contraction tile) that also keeps the double-buffered
    panel temporaries inside the 1.5x O(N^2/P) memory contract when
    the full (n, m) shape is known — the default never trades the
    contract away; the autotuner's ladder may, explicitly. Coarser
    panels win when per-step collective latency dominates, finer when
    overlap does."""
    panels = legal_panels(k, n_dp, n_tp)
    if not panels:
        raise ValueError(
            'summa_matmul: contraction dim %d not divisible by the '
            'dp=%d x tp=%d grid' % (k, n_dp, n_tp))
    capped = [p for p in panels if p <= 256] or panels[:1]
    if n is not None and m is not None:
        shape = {'dp': n_dp, 'tp': n_tp}
        fits = [p for p in capped
                if per_shard_peak_bytes('summa_matmul', shape,
                                        (n, k, m), dtype=dtype,
                                        panel=p)['factor'] <= 1.5]
        if fits:
            capped = fits
    return capped[-1]


def legal_blocks(n, local=None):
    """Legal Cholesky/QR panel widths: divisors of the factored extent
    `n` that (when `local` is given) also divide the per-shard
    row-block extent, so a panel's diagonal block lives on exactly one
    owner."""
    blocks = _divisors(n)
    if local is not None:
        blocks = [b for b in blocks if local % b == 0]
    return blocks


def default_block(n, local=None):
    """Untuned factorization panel width: largest legal <= 64 (panel
    factorizations are O(N b^2) serial work — small panels keep the
    trailing updates, which parallelize, dominant)."""
    blocks = legal_blocks(n, local=local)
    if not blocks:
        raise ValueError('no legal factorization block for extent %d '
                         '(local %r)' % (n, local))
    capped = [b for b in blocks if b <= 64]
    return capped[-1] if capped else blocks[0]


# ------------------------------------------------------- memory model
def _itemsize(dtype):
    import numpy as np
    return int(np.dtype(str(dtype).replace('bfloat16', 'uint16'))
               .itemsize)


def per_shard_peak_bytes(op, mesh, dims, dtype='float32', panel=None,
                         block=None):
    """Analytic per-shard peak resident bytes for one linalg op — the
    memory contract ``tests/test_linalg.py`` asserts. Returns
    ``{'peak', 'ideal', 'factor', 'participants'}`` where `ideal` is
    the operand+result footprint divided evenly over the participating
    shards (the O(N^2/P) floor) and `factor` = peak/ideal. The model
    counts everything a shard holds at once: its operand blocks, the
    fp32 accumulator/working set, and the panel temporaries (double-
    buffered for SUMMA, the gathered [N, b] panel for QR/Cholesky).

    `mesh` may be a Mesh or a plain {axis: size} mapping (the analysis
    pass and stdlib callers use the latter)."""
    shape = dict(mesh.shape) if hasattr(mesh, 'shape') else \
        dict(mesh or {})
    isz = _itemsize(dtype)
    if op == 'summa_matmul':
        n, k, m = dims
        dp = int(shape.get('dp', 1))
        tp = int(shape.get('tp', 1))
        p = dp * tp
        a_loc = (n // dp) * (k // tp) * isz
        b_loc = (k // dp) * (m // tp) * isz
        # fp32 output IS the accumulator (the final astype is identity);
        # narrower dtypes materialize a separate cast result
        out_loc = 0 if isz == 4 else (n // dp) * (m // tp) * isz
        acc = (n // dp) * (m // tp) * 4
        pb = int(panel or default_panel(k, dp, tp))
        panels = 2 * ((n // dp) + (m // tp)) * pb * isz  # double-buffered
        peak = a_loc + b_loc + out_loc + acc + panels
        ideal = (n * k + k * m + n * m) * isz // p
    elif op in ('blocked_cholesky', 'blocked_qr'):
        n, m = dims
        dp = int(shape.get('dp', 1))
        p = dp
        nb = n // dp
        blk = int(block or default_block(
            n if op == 'blocked_cholesky' else m,
            local=nb if op == 'blocked_cholesky' else None))
        in_loc = nb * m * isz
        work = nb * m * 4                      # fp32 working copy
        out_loc = nb * m * 4 + (0 if op == 'blocked_cholesky'
                                else m * m * 4)   # L / (Q, replicated R)
        gathered = n * blk * 4                 # the [N, b] panel
        peak = in_loc + work + out_loc + gathered
        ideal = 2 * n * m * isz // p
    elif op == 'power_iter_step':
        (n,) = dims if isinstance(dims, (tuple, list)) else (dims,)
        dp = int(shape.get('dp', 1))
        p = dp
        a_loc = n * (n // dp) * isz
        vecs = 4 * n * 4                       # v, v_loc, z_part, z
        peak = a_loc + vecs
        ideal = n * n * isz // p
    else:
        raise ValueError('per_shard_peak_bytes: unknown op %r' % op)
    return {'peak': int(peak), 'ideal': int(max(ideal, 1)),
            'factor': peak / float(max(ideal, 1)),
            'participants': int(p)}


# -------------------------------------------------- single-device refs
def matmul_reference(a, b):
    return jnp.matmul(a, b)


def cholesky_reference(a):
    return jnp.linalg.cholesky(a)


def qr_reference(a):
    return jnp.linalg.qr(a, mode='reduced')


# --------------------------------------------------------------- SUMMA
def summa_matmul(a, b, mesh, panel, row_axis='dp', col_axis='tp'):
    """SUMMA blocked matmul: global ``a [N, K] @ b [K, M] -> [N, M]``
    with every operand blocked ``P(row_axis, col_axis)`` across the
    mesh. Call inside the executor's jit (or any jit) — the shard_map
    partitions the global values. Accumulation is fp32 regardless of
    input dtype; panel ordering is fixed by the k-offset, so the
    result is independent of the mesh WIDTH for exactly-representable
    inputs (the dyadic bit-identity test)."""
    n_dp, n_tp = axis_sizes_of(mesh, row_axis, col_axis)
    if mesh is None or (n_dp == 1 and n_tp == 1):
        return matmul_reference(a, b)
    from jax.sharding import PartitionSpec as P

    n, k = a.shape
    k2, m = b.shape
    if k != k2:
        raise ValueError('summa_matmul: inner dims %d vs %d' % (k, k2))
    if n % n_dp or m % n_tp or k % n_dp or k % n_tp:
        raise ValueError(
            'summa_matmul: shape (%d, %d) x (%d, %d) not divisible by '
            'the dp=%d x tp=%d grid' % (n, k, k, m, n_dp, n_tp))
    ak = k // n_tp          # local A columns
    bk = k // n_dp          # local B rows
    panel = int(panel)
    if panel <= 0 or ak % panel or bk % panel:
        raise ValueError(
            'summa_matmul: panel %d must divide both local block '
            'extents K/tp=%d and K/dp=%d' % (panel, ak, bk))
    n_steps = k // panel

    def body(a_loc, b_loc):
        # a_loc [N/dp, K/tp], b_loc [K/dp, M/tp]
        offs = jnp.arange(n_steps, dtype=jnp.int32) * panel
        a_roots = offs // ak            # grid column owning A panel t
        b_roots = offs // bk            # grid row owning B panel t
        a_offs = offs - a_roots * ak    # local col offset on the owner
        b_offs = offs - b_roots * bk    # local row offset on the owner

        def fetch(t):
            # off-owner slices are clamped junk; broadcast() keeps only
            # the root's value, so they never pollute the product
            ap = jax.lax.dynamic_slice(
                a_loc, (0, a_offs[t]), (a_loc.shape[0], panel))
            bp = jax.lax.dynamic_slice(
                b_loc, (b_offs[t], 0), (panel, b_loc.shape[1]))
            ap = broadcast(ap, col_axis, root=a_roots[t])
            bp = broadcast(bp, row_axis, root=b_roots[t])
            return ap, bp

        ap0, bp0 = fetch(0)
        # the product of two sharded blocks varies over both grid axes;
        # an unvarying zero init would mismatch the scan carry's type
        acc0 = jax.lax.pcast(
            jnp.zeros((a_loc.shape[0], b_loc.shape[1]), jnp.float32),
            (row_axis, col_axis), to='varying')

        def step(carry, t):
            acc, ap, bp = carry
            # issue step t+1's broadcast BEFORE step t's dot: the
            # ppermute chain has no data dependence on the product, so
            # XLA overlaps the k-panel transfer with the local matmul
            ap_n, bp_n = fetch(jnp.minimum(t + 1, n_steps - 1))
            acc = acc + jnp.matmul(ap.astype(jnp.float32),
                                   bp.astype(jnp.float32))
            return (acc, ap_n, bp_n), None

        (acc, _, _), _ = jax.lax.scan(
            step, (acc0, ap0, bp0),
            jnp.arange(n_steps, dtype=jnp.int32))
        return acc.astype(a_loc.dtype)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(row_axis, col_axis)),
        out_specs=P(row_axis, col_axis))
    return fn(a, b)


# ------------------------------------------------------------ Cholesky
def blocked_cholesky(a, mesh, block, axis='dp'):
    """Right-looking blocked Cholesky of SPD ``a [N, N]`` row-blocked
    ``P(axis, None)``. Returns the lower-triangular factor with the
    same distribution. ``block`` must divide the per-shard row extent
    N/dp so each panel's diagonal block has one owner."""
    (n_dp,) = axis_sizes_of(mesh, axis)
    if mesh is None or n_dp == 1:
        return cholesky_reference(a)
    from jax.sharding import PartitionSpec as P

    n = a.shape[0]
    if a.shape[1] != n:
        raise ValueError('blocked_cholesky: square input required')
    if n % n_dp:
        raise ValueError('blocked_cholesky: N=%d %% dp=%d != 0'
                         % (n, n_dp))
    nb = n // n_dp
    b = int(block)
    if b <= 0 or nb % b:
        raise ValueError('blocked_cholesky: block %d must divide the '
                         'per-shard row extent N/dp=%d' % (b, nb))
    n_panels = n // b

    def body(a_loc):
        idx = jax.lax.axis_index(axis)
        grow = idx * nb + jnp.arange(nb)        # global row ids
        s = a_loc.astype(jnp.float32)
        l_out = jnp.zeros_like(s)
        for p in range(n_panels):
            c0 = p * b
            owner = c0 // nb                    # static python int
            loc0 = c0 - owner * nb
            # the owner's diagonal Schur block, shipped to everyone
            # (off-owner slices are junk until the broadcast replaces
            # them); the b^3 factorization is then redundant on every
            # device — cheaper than a second broadcast of the factor
            diag = jax.lax.dynamic_slice(s, (loc0, c0), (b, b))
            diag = broadcast(diag, axis, root=owner)
            lpp = jnp.linalg.cholesky(diag)
            span = jax.lax.dynamic_slice(s, (0, c0), (nb, b))
            sol = jax.scipy.linalg.solve_triangular(
                lpp, span.T, lower=True).T      # [nb, b]
            below = (grow >= c0 + b)[:, None]
            inpanel = ((grow >= c0) & (grow < c0 + b))[:, None]
            lpp_rows = lpp[jnp.clip(grow - c0, 0, b - 1)]
            pan = jnp.where(below, sol,
                            jnp.where(inpanel, lpp_rows, 0.0))
            l_out = jax.lax.dynamic_update_slice(l_out, pan, (0, c0))
            pan_full = jax.lax.all_gather(pan, axis, axis=0,
                                          tiled=True)  # [N, b]
            trail = (jnp.arange(n) >= c0 + b)[None, :]
            s = s - jnp.where(below & trail, pan @ pan_full.T, 0.0)
        return l_out.astype(a_loc.dtype)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis, None),),
                          out_specs=P(axis, None))
    return fn(a)


# ------------------------------------------------------------------ QR
def blocked_qr(a, mesh, block, axis='dp'):
    """Blocked Householder QR of ``a [N, M]`` (N >= M) row-blocked
    ``P(axis, None)``: returns (Q [N, M] row-blocked, R [M, M]
    replicated). Each column panel is all-gathered — a [N, block]
    tall-skinny temporary, the only time more than a 1/P slice of
    anything crosses a shard — and factored through the backend's
    Householder QR on every device; panels compose by block
    Gram-Schmidt with psum-reduced projections."""
    (n_dp,) = axis_sizes_of(mesh, axis)
    if mesh is None or n_dp == 1:
        return qr_reference(a)
    from jax.sharding import PartitionSpec as P

    n, m = a.shape
    if m > n:
        raise ValueError('blocked_qr: N=%d < M=%d (tall input '
                         'required)' % (n, m))
    if n % n_dp:
        raise ValueError('blocked_qr: N=%d %% dp=%d != 0' % (n, n_dp))
    nb = n // n_dp
    b = int(block)
    if b <= 0 or m % b:
        raise ValueError('blocked_qr: block %d must divide M=%d'
                         % (b, m))
    n_panels = m // b

    def body(a_loc):
        idx = jax.lax.axis_index(axis)
        row0 = idx * nb
        s = a_loc.astype(jnp.float32)
        q_out = jnp.zeros((nb, m), jnp.float32)
        r_out = jnp.zeros((m, m), jnp.float32)
        for p in range(n_panels):
            c0 = p * b
            panel = jax.lax.dynamic_slice(s, (0, c0), (nb, b))
            pan_full = jax.lax.all_gather(panel, axis, axis=0,
                                          tiled=True)    # [N, b]
            qf, rf = jnp.linalg.qr(pan_full, mode='reduced')
            q_loc = jax.lax.dynamic_slice(qf, (row0, 0), (nb, b))
            r_out = jax.lax.dynamic_update_slice(r_out, rf, (c0, c0))
            rest = m - c0 - b
            if rest > 0:
                s_rest = jax.lax.dynamic_slice(s, (0, c0 + b),
                                               (nb, rest))
                proj = jax.lax.psum(q_loc.T @ s_rest, axis)
                r_out = jax.lax.dynamic_update_slice(
                    r_out, proj, (c0, c0 + b))
                s = jax.lax.dynamic_update_slice(
                    s, s_rest - q_loc @ proj, (0, c0 + b))
            q_out = jax.lax.dynamic_update_slice(q_out, q_loc, (0, c0))
        return q_out.astype(a_loc.dtype), r_out.astype(a_loc.dtype)

    # check_vma off: R is assembled from all-gathered panels and psum
    # projections — identical on every device by construction, but the
    # replication checker cannot infer it through the gathered-panel QR
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis, None),),
                          out_specs=(P(axis, None), P(None, None)),
                          check_vma=False)
    return fn(a)


# ------------------------------------------------------ power iteration
def power_iter_step(a, v, mesh, axis='dp', quantized=False, qblock=256,
                    key=None):
    """One power-iteration step with ``a [N, N]`` COLUMN-blocked
    ``P(None, axis)`` and ``v [N]`` replicated: ``z = A v`` is a local
    [N, N/P] matvec plus an N-element allreduce — through exact
    ``psum`` or (``quantized=True``) the PR 13 block-scaled int8
    ``quantized_all_reduce``. Returns ``(v_next [N] replicated,
    rayleigh [1])`` where rayleigh = v . A v (v is unit-norm by
    construction after the first step).

    The allreduce IS this workload's step — power iteration stresses
    collectives the way gradient aggregation does, with none of the
    surrounding matmul tonnage, which is what makes it the second
    measurement axis for the quantized-collective trade."""
    (n_dp,) = axis_sizes_of(mesh, axis)
    n = a.shape[0]
    if a.shape[1] != n:
        raise ValueError('power_iter_step: square input required')
    if mesh is None or n_dp == 1:
        z = jnp.matmul(a.astype(jnp.float32), v.astype(jnp.float32))
        lam = jnp.vdot(v.astype(jnp.float32), z)
        vn = z / jnp.maximum(jnp.linalg.norm(z), 1e-30)
        return vn.astype(v.dtype), lam.reshape(1).astype(v.dtype)
    from jax.sharding import PartitionSpec as P

    if n % n_dp:
        raise ValueError('power_iter_step: N=%d %% dp=%d != 0'
                         % (n, n_dp))
    nb = n // n_dp

    def body(a_loc, v_full):
        idx = jax.lax.axis_index(axis)
        v_loc = jax.lax.dynamic_slice(v_full, (idx * nb,), (nb,))
        z_part = jnp.matmul(a_loc.astype(jnp.float32),
                            v_loc.astype(jnp.float32))
        if quantized:
            z = quantized_all_reduce(z_part, axis, block=qblock,
                                     key=key)
        else:
            z = jax.lax.psum(z_part, axis)
        lam = jnp.vdot(v_full.astype(jnp.float32), z)
        vn = z / jnp.maximum(jnp.linalg.norm(z), 1e-30)
        return vn.astype(v_full.dtype), lam.reshape(1).astype(
            v_full.dtype)

    # check_vma off: the quantized allreduce ends in an all_gather of
    # already-rounded shards — identical on every device by
    # construction, but not provably replicated to the checker
    fn = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(None, axis), P(None)),
                          out_specs=(P(None), P(None)),
                          check_vma=False)
    return fn(a, v)
