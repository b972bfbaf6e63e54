"""Switch-MoE: routing semantics, e2e training, and expert-parallel
sharding on the 8-virtual-device CPU mesh (mesh axis 'ep')."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.transpiler import ParallelStrategy, transpile


def _numpy_switch_moe(x2, gate_w, w1, b1, w2, b2, capacity, k=1):
    """Independent numpy re-derivation of the top-k dispatch: choice-
    major capacity filling (all first choices claim slots first),
    gates renormalized for k>=2, dropped assignments contribute zero."""
    s, d = x2.shape
    e = gate_w.shape[-1]
    logits = x2 @ gate_w
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    top_idx = np.argsort(-p, axis=-1)[:, :k]             # [S, k]
    top_gates = np.take_along_axis(p, top_idx, axis=-1)
    if k > 1:
        top_gates = top_gates / top_gates.sum(-1, keepdims=True)
    out = np.zeros_like(x2)
    count = np.zeros(e, np.int64)
    for j in range(k):                       # choice-major
        for si in range(s):                  # sequential capacity filling
            ei = top_idx[si, j]
            if count[ei] >= capacity:
                continue                     # dropped -> zero contribution
            count[ei] += 1
            h = np.maximum(x2[si] @ w1[ei] + b1[ei], 0.0)
            out[si] += top_gates[si, j] * (h @ w2[ei] + b2[ei])
    frac = np.eye(e)[top_idx[:, 0]].mean(0)
    aux = e * float((frac * p.mean(0)).sum())
    return out, aux


@pytest.mark.parametrize('k', [1, 2])
def test_switch_moe_matches_numpy_reference(k):
    import jax.numpy as jnp
    from paddle_tpu.ops.moe_ops import switch_moe_reference
    rng = np.random.RandomState(0)
    s, d, e, h, cap = 16, 8, 4, 12, 3    # capacity binds for some experts
    x2 = rng.randn(s, d).astype('float32')
    gate_w = rng.randn(d, e).astype('float32')
    w1 = rng.randn(e, d, h).astype('float32') * 0.3
    b1 = rng.randn(e, h).astype('float32') * 0.1
    w2 = rng.randn(e, h, d).astype('float32') * 0.3
    b2 = rng.randn(e, d).astype('float32') * 0.1
    got, aux, _ = switch_moe_reference(
        jnp.asarray(x2), jnp.asarray(gate_w), jnp.asarray(w1),
        jnp.asarray(b1), jnp.asarray(w2), jnp.asarray(b2), cap, k=k)
    want, aux_want = _numpy_switch_moe(x2, gate_w, w1, b1, w2, b2, cap,
                                       k=k)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(float(aux), aux_want, rtol=1e-5)


def _train_moe_lm(mesh=None, steps=5, seed=0, num_experts=4, top_k=1):
    from paddle_tpu.models.moe import switch_transformer_lm
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    vocab, seq = 32, 8
    avg, _ = switch_transformer_lm(vocab, seq, n_layer=2, n_head=2,
                                   d_model=16, d_inner=32,
                                   num_experts=num_experts, top_k=top_k)
    fluid.default_main_program().random_seed = 7
    fluid.optimizer.Adam(learning_rate=3e-3).minimize(avg)
    if mesh is not None:
        transpile(fluid.default_main_program(), mesh,
                  ParallelStrategy(data_parallel=True))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(seed)
    words = rng.randint(1, vocab, (8, seq)).astype('int64')
    labels = np.roll(words, -1, axis=1)
    losses = []
    for _ in range(steps):
        losses.append(float(np.asarray(exe.run(
            feed={'word': words, 'label': labels},
            fetch_list=[avg])[0]).reshape(())))
    return losses


def test_moe_lm_trains():
    losses = _train_moe_lm(steps=8)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize('top_k', [1, 2])
def test_moe_expert_parallel_matches_unsharded(top_k):
    """dp=2 x ep=4 sharded run follows the unsharded trajectory: expert
    weights [E, ...] shard E/ep per device, routing/dispatch numerics
    unchanged (GSPMD exchanges tokens, never reroutes them)."""
    base = _train_moe_lm(mesh=None, top_k=top_k)
    mesh = make_mesh(dp=2, ep=4)
    ep = _train_moe_lm(mesh=mesh, top_k=top_k)
    np.testing.assert_allclose(ep, base, rtol=2e-4, atol=1e-5)


def test_moe_params_marked_and_sharded():
    from paddle_tpu.models.moe import switch_transformer_lm
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    avg, _ = switch_transformer_lm(32, 8, n_layer=1, n_head=2,
                                   d_model=16, d_inner=32, num_experts=4)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(avg)
    mesh = make_mesh(dp=2, ep=4)
    prog = transpile(fluid.default_main_program(), mesh,
                     ParallelStrategy(data_parallel=True))
    expert_params = [v for v in prog.list_vars()
                     if getattr(v, 'expert_shard', False)]
    assert len(expert_params) == 4, [v.name for v in expert_params]
    for v in expert_params:
        spec = prog.var_shardings[v.name]
        assert tuple(spec)[0] == 'ep', (v.name, spec)
    # the router gate stays replicated
    gates = [v for v in prog.list_vars() if v.name.endswith('gate.w')]
    assert gates and all(
        tuple(prog.var_shardings[g.name]) in ((), (None,) * 2)
        for g in gates)


def test_moe_scan_layers_matches_unrolled():
    """moe_layer_stack (one lax.scan over stacked blocks) follows the
    unrolled MoE LM's trajectory exactly given identical weights."""
    from paddle_tpu.models.moe import switch_transformer_lm
    vocab, seq, L = 32, 8, 2
    kw = dict(n_layer=L, n_head=2, d_model=16, d_inner=32,
              num_experts=4, top_k=2)
    rng = np.random.RandomState(9)
    words = rng.randint(1, vocab, (8, seq)).astype('int64')
    labels = np.roll(words, -1, axis=1)

    def build(scan):
        fluid.reset_default_programs()
        avg, _ = switch_transformer_lm(vocab, seq, scan_layers=scan,
                                       **kw)
        fluid.optimizer.SGD(learning_rate=0.3).minimize(avg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        return avg, exe

    su, ss = fluid.Scope(), fluid.Scope()
    with fluid.scope_guard(su):
        avg, exe = build(False)
        init = {n: np.asarray(su.find(n)) for n in su.keys()
                if su.find(n) is not None}
        base = [float(np.asarray(exe.run(
            feed={'word': words, 'label': labels},
            fetch_list=[avg])[0]).reshape(())) for _ in range(3)]
    with fluid.scope_guard(ss):
        avg, exe = build(True)
        # seed the scan scope with the unrolled init, then convert via
        # the production mapping (models.moe.stack_moe_trained_weights);
        # leftover per-layer names in the scope are simply unread
        from paddle_tpu.models.moe import stack_moe_trained_weights
        for name, val in init.items():
            ss.set(name, val)
        stacked = stack_moe_trained_weights(ss, L)
        assert stacked, 'no params were stacked'
        got = [float(np.asarray(exe.run(
            feed={'word': words, 'label': labels},
            fetch_list=[avg])[0]).reshape(())) for _ in range(3)]
    np.testing.assert_allclose(got, base, rtol=1e-4, atol=1e-5)


def test_moe_scan_layers_ep_mesh():
    """The stacked MoE LM trains on a dp2 x ep4 mesh, with the expert
    axis (axis 1 of the [L, E, ...] stacks) sharded over 'ep'."""
    from paddle_tpu.models.moe import switch_transformer_lm
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    vocab, seq = 32, 8
    avg, _ = switch_transformer_lm(vocab, seq, n_layer=2, n_head=2,
                                   d_model=16, d_inner=32,
                                   num_experts=4, scan_layers=True)
    fluid.default_main_program().random_seed = 7
    fluid.optimizer.Adam(learning_rate=3e-3).minimize(avg)
    mesh = make_mesh(dp=2, ep=4)
    prog = transpile(fluid.default_main_program(), mesh,
                     ParallelStrategy(data_parallel=True))
    spec = prog.var_shardings['moe_stack_1.w']
    assert tuple(spec)[:2] == (None, 'ep'), spec
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    words = rng.randint(1, vocab, (8, seq)).astype('int64')
    losses = [float(np.asarray(exe.run(
        feed={'word': words, 'label': np.roll(words, -1, axis=1)},
        fetch_list=[avg])[0]).reshape(())) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses


def _train_moe_pp(mesh=None, strategy=None, aux_weight=0.0, steps=3,
                  top_k=1):
    """Stacked MoE LM, capacity_factor high enough that nothing drops
    (pipelined routing is per-microbatch, so only the no-drop regime is
    bit-comparable to the full-batch scan)."""
    from paddle_tpu.models.moe import switch_transformer_lm
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    fluid.default_main_program().random_seed = 7
    cost, _ = switch_transformer_lm(
        vocab_size=64, seq_len=8, n_layer=2, n_head=2, d_model=16,
        d_inner=32, num_experts=4, capacity_factor=4.0, top_k=top_k,
        aux_weight=aux_weight, scan_layers=True)
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(cost)
    if mesh is not None:
        transpile(fluid.default_main_program(), mesh, strategy)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    words = rng.randint(1, 64, (8, 8)).astype('int64')
    feed = {'word': words, 'label': np.roll(words, -1, axis=1)}
    return [float(np.asarray(exe.run(
        feed=feed, fetch_list=[cost])[0]).reshape(()))
        for _ in range(steps)]


@pytest.mark.parametrize('top_k', [1, 2])
def test_moe_pipeline_ep_matches_single_device(top_k):
    """Program-path pipelining of the MoE stack (pp x ep): stage-sharded
    layers, expert weights still 'ep'-split inside the stage (GSPMD
    manages ep under the pp-manual shard_map), aux accumulated over
    valid ticks only. aux_weight=0 + no capacity drops -> trajectory
    equals single device — Switch top-1 AND GShard top-2 routing."""
    base = _train_moe_pp(top_k=top_k)
    pp_ep = _train_moe_pp(
        top_k=top_k,
        mesh=make_mesh(dp=1, pp=2, ep=4),
        strategy=ParallelStrategy(data_parallel=False,
                                  pipeline_parallel=True))
    np.testing.assert_allclose(pp_ep, base, rtol=2e-4, atol=1e-5)
    prog = fluid.default_main_program()
    spec = prog.var_shardings['moe_stack_1.w']
    assert tuple(spec)[:2] == ('pp', 'ep'), spec


def test_moe_pipeline_four_axis_matches_single_device():
    """pp x sp x ep (+ the causal ring nested inside the stage): the MoE
    stack's attention dispatches ring attention under pipelining while
    experts stay 'ep'-split — all in one program, trajectory equal to
    single device in the no-drop regime."""
    base = _train_moe_pp()
    four = _train_moe_pp(
        mesh=make_mesh(dp=1, pp=2, sp=2, ep=2),
        strategy=ParallelStrategy(data_parallel=False,
                                  sequence_parallel=True,
                                  pipeline_parallel=True,
                                  sp_vars=['word', 'label']))
    np.testing.assert_allclose(four, base, rtol=2e-4, atol=1e-5)


def test_moe_pipeline_with_aux_trains():
    """dp x pp x ep with the load-balancing aux on: the pipelined aux is
    the mean of per-microbatch means (documented semantic difference),
    so assert training health, not bit equality."""
    losses = _train_moe_pp(
        mesh=make_mesh(dp=2, pp=2, ep=2),
        strategy=ParallelStrategy(data_parallel=True,
                                  pipeline_parallel=True),
        aux_weight=1e-2, steps=4)
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
