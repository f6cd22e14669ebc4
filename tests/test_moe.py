"""MoE + expert parallelism tests.

Differential stance as everywhere (``train_ffns.py:386-391``): the
expert-parallel shard_map path must reproduce a dense per-shard oracle
exactly — routing, capacity drops, gate scaling, gradients, SGD — on the
fake 8-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.data import (batch_from_seed,
                                                   make_seed_schedule,
                                                   shard_seeds_strided)
from distributed_llm_code_samples_tpu.models import (MoEStackParams,
                                                     init_moe_stack)
from distributed_llm_code_samples_tpu.ops.moe import (dispatch_tensor,
                                                      dispatch_tensor_topk,
                                                      expert_capacity,
                                                      moe_layer,
                                                      moe_stack_fwd,
                                                      moe_stack_aux,
                                                      route_top1,
                                                      route_topk,
                                                      router_aux_loss)
from distributed_llm_code_samples_tpu.optim import sgd
from distributed_llm_code_samples_tpu.parallel import (EXPERT_AXIS,
                                                       make_mesh,
                                                       train_moe_dense,
                                                       train_moe_ep)

D, L, E, T = 16, 2, 8, 64  # d_model, layers, experts, tokens per shard


@pytest.fixture(scope="module")
def params():
    return init_moe_stack(jax.random.PRNGKey(0), D, L, E)


@pytest.fixture(scope="module")
def mesh_ep4():
    return make_mesh({EXPERT_AXIS: 4})


def test_dispatch_tensor_slots():
    idx = jnp.asarray([0, 1, 0, 0, 1])
    disp = dispatch_tensor(idx, n_experts=2, capacity=2)
    # token 0 -> e0 slot 0, token 2 -> e0 slot 1, token 3 dropped (overflow)
    assert disp[0, 0, 0] == 1 and disp[2, 0, 1] == 1
    assert disp[3].sum() == 0
    assert disp[1, 1, 0] == 1 and disp[4, 1, 1] == 1
    # every token occupies at most one slot
    assert float(disp.sum()) == 4.0


def test_route_top1_gate_is_prob():
    wg = jax.random.normal(jax.random.PRNGKey(1), (E, D))
    x = jax.random.normal(jax.random.PRNGKey(2), (T, D))
    idx, gate = route_top1(wg, x)
    probs = jax.nn.softmax(x @ wg.T, axis=-1)
    np.testing.assert_allclose(np.asarray(gate),
                               np.asarray(probs.max(axis=-1)), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.asarray(probs.argmax(axis=-1)))


def test_moe_layer_equals_manual_gather():
    """The einsum dispatch/combine equals a per-token gather-apply loop when
    nothing overflows."""
    wg = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (E, D))
    w1 = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (E, 4 * D, D))
    w2 = 0.02 * jax.random.normal(jax.random.PRNGKey(3), (E, D, 4 * D))
    x = jax.random.normal(jax.random.PRNGKey(4), (T, D))
    y = moe_layer(wg, w1, w2, x, capacity_factor=float(E))  # no drops
    idx, gate = route_top1(wg, x)
    for t in range(8):  # spot-check a few tokens
        e = int(idx[t])
        h = jnp.maximum(x[t] @ w1[e].T, 0.0)
        want = gate[t] * (h @ w2[e].T)
        np.testing.assert_allclose(np.asarray(y[t]), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_capacity_overflow_drops_to_zero():
    """All tokens to one expert with capacity 1: every later token emits 0
    from the raw layer (the stack's residual then passes it through)."""
    wg = jnp.zeros((E, D)).at[0].set(1.0)  # expert 0 wins for positive sums
    w1 = jnp.ones((E, 4 * D, D)) * 0.01
    w2 = jnp.ones((E, D, 4 * D)) * 0.01
    x = jnp.ones((8, D))
    y = moe_layer(wg, w1, w2, x, capacity_factor=1.0 / E)  # capacity == 1
    assert float(jnp.abs(y[0]).sum()) > 0
    np.testing.assert_array_equal(np.asarray(y[1:]),
                                  np.zeros_like(np.asarray(y[1:])))


def test_dropped_token_passes_through_stack_residual():
    """Switch drop semantics: a capacity-dropped token keeps
    its input activation through the stack's residual instead of zeroing
    for every remaining layer."""
    p = MoEStackParams(wg=jnp.zeros((1, E, D)).at[0, 0].set(1.0),
                       w1=jnp.ones((1, E, 4 * D, D)) * 0.01,
                       w2=jnp.ones((1, E, D, 4 * D)) * 0.01)
    x = jnp.ones((8, D))
    y = moe_stack_fwd(p, x, capacity_factor=1.0 / E)  # capacity == 1
    # token 0 got expert compute + residual; tokens 1.. are pure residual
    np.testing.assert_array_equal(np.asarray(y[1:]), np.asarray(x[1:]))
    assert float(jnp.abs(y[0] - x[0]).sum()) > 0


def test_route_topk_gates_and_distinctness():
    wg = jax.random.normal(jax.random.PRNGKey(1), (E, D))
    x = jax.random.normal(jax.random.PRNGKey(2), (T, D))
    idx, gates = route_topk(wg, x, k=2)
    assert idx.shape == (T, 2) and gates.shape == (T, 2)
    # the two choices are distinct experts; gates renormalize to 1
    assert int(jnp.sum(idx[:, 0] == idx[:, 1])) == 0
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    # rank-0 choice == top-1 choice
    idx1, _ = route_top1(wg, x)
    np.testing.assert_array_equal(np.asarray(idx[:, 0]), np.asarray(idx1))


def test_dispatch_topk_choice_major_priority():
    """With capacity 1, a token's rank-1 choice loses the slot to a LATER
    token's rank-0 choice (GShard choice-major ordering)."""
    idx = jnp.asarray([[0, 1],   # token 0: first choice e0, second e1
                       [1, 0]])  # token 1: first choice e1, second e0
    disp = dispatch_tensor_topk(idx, n_experts=2, capacity=1)
    assert disp.shape == (2, 2, 2, 1)
    # rank-0 choices claim both experts' single slots...
    assert disp[0, 0, 0, 0] == 1 and disp[0, 1, 1, 0] == 1
    # ...so both rank-1 choices drop
    assert float(disp[1].sum()) == 0


def test_moe_layer_top2_mixes_two_experts():
    """With ample capacity, top-2 output is the gate-weighted sum of both
    chosen experts' FFNs."""
    wg = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (E, D))
    w1 = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (E, 4 * D, D))
    w2 = 0.02 * jax.random.normal(jax.random.PRNGKey(3), (E, D, 4 * D))
    x = jax.random.normal(jax.random.PRNGKey(4), (16, D))
    y = moe_layer(wg, w1, w2, x, capacity_factor=float(E), k=2)
    idx, gates = route_topk(wg, x, k=2)
    for t in range(4):
        want = jnp.zeros((D,))
        for c in range(2):
            e = int(idx[t, c])
            h = jnp.maximum(x[t] @ w1[e].T, 0.0)
            want = want + gates[t, c] * (h @ w2[e].T)
        np.testing.assert_allclose(np.asarray(y[t]), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def test_router_aux_loss_uniform_vs_collapsed():
    """Aux loss is ~1 at uniform routing and E at full collapse — the
    Switch load-balancing objective."""
    x = jax.random.normal(jax.random.PRNGKey(5), (512, D))
    uniform = float(router_aux_loss(jnp.zeros((E, D)), x))
    np.testing.assert_allclose(uniform, 1.0, rtol=0.2)
    # positive inputs + a one-sided router => every token picks expert 0
    x_pos = jnp.abs(x) + 0.1
    collapsed = float(router_aux_loss(
        jnp.zeros((E, D)).at[0].set(50.0), x_pos))
    np.testing.assert_allclose(collapsed, E, rtol=1e-3)
    # differentiable, nonzero gradient toward balance
    g = jax.grad(lambda w: router_aux_loss(w, x))(
        jnp.zeros((E, D)).at[0].set(1.0))
    assert float(jnp.abs(g).sum()) > 0
    # stack form: one term per layer
    p = init_moe_stack(jax.random.PRNGKey(0), D, L, E)
    aux = float(moe_stack_aux(p, x))
    assert aux > 0


def test_moe_grads_flow_to_router():
    """The gate path gives the router a nonzero hand-composable gradient."""
    p = init_moe_stack(jax.random.PRNGKey(0), D, 1, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (32, D))
    g = jax.grad(lambda p: moe_stack_fwd(p, x).sum())(p)
    assert float(jnp.abs(g.wg).sum()) > 0
    assert float(jnp.abs(g.w1).sum()) > 0


def _oracle_step(params, seed_row, t_local, lr, capacity_factor=2.0, k=1,
                 aux_coef=0.0):
    """Dense per-shard oracle for one EP step: each shard's tokens routed
    independently (grouped dispatch: per-shard share of the global
    capacity), router grads summed across shards (SUM semantics), expert
    grads summed by token ownership."""
    def f(p):
        ys = []
        for r in range(seed_row.shape[0]):
            x_r, _ = batch_from_seed(seed_row[r], t_local, D, jnp.float32)
            ys.append(moe_stack_fwd(p, x_r, capacity_factor, k))
        return jnp.stack(ys)

    _, vjp = jax.vjp(f, params)
    dl = jnp.stack([batch_from_seed(seed_row[r], t_local, D, jnp.float32)[1]
                    for r in range(seed_row.shape[0])])
    grads = vjp(dl)[0]
    if aux_coef:
        def aux_f(p):
            total = 0.0
            for r in range(seed_row.shape[0]):
                x_r, _ = batch_from_seed(seed_row[r], t_local, D,
                                         jnp.float32)
                total = total + moe_stack_aux(p, x_r, capacity_factor, k)
            return total
        g_aux = jax.grad(aux_f)(params)
        grads = jax.tree_util.tree_map(
            lambda g, a: g + aux_coef * a.astype(g.dtype), grads, g_aux)
    return sgd(params, grads, lr)


def test_ep_matches_dense_oracle(params, mesh_ep4):
    """train_moe_ep == dense per-shard oracle over 8 global steps on a
    4-shard expert mesh (the analogue of the reference's DDP==FSDP check)."""
    n = 4
    seeds = make_seed_schedule(2 * n, random_seed=9)
    tokens = n * T
    out = train_moe_ep(params, seeds, tokens, D, mesh_ep4, lr=0.1)

    oracle = params
    for row in np.asarray(shard_seeds_strided(seeds, n)):
        oracle = _oracle_step(oracle, jnp.asarray(row), T, lr=0.1)

    np.testing.assert_allclose(np.asarray(out.wg), np.asarray(oracle.wg),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.w1), np.asarray(oracle.w1),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.w2), np.asarray(oracle.w2),
                               rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("k,aux_coef", [(2, 0.0), (1, 0.01), (2, 0.01)])
def test_ep_top2_and_aux_match_dense_oracle(params, mesh_ep4, k, aux_coef):
    """Top-2 routing and the load-balancing aux term preserve the EP ==
    dense-oracle equality (per-shard oracle, same grouped capacity)."""
    n = 4
    seeds = make_seed_schedule(n, random_seed=11)
    out = train_moe_ep(params, seeds, n * T, D, mesh_ep4, lr=0.1, k=k,
                       aux_coef=aux_coef)
    oracle = params
    for row in np.asarray(shard_seeds_strided(seeds, n)):
        oracle = _oracle_step(oracle, jnp.asarray(row), T, lr=0.1, k=k,
                              aux_coef=aux_coef)
    for field in MoEStackParams._fields:
        np.testing.assert_allclose(np.asarray(getattr(out, field)),
                                   np.asarray(getattr(oracle, field)),
                                   rtol=1e-3, atol=1e-5, err_msg=field)


def test_ep_overflow_pressure_matches_oracle(params, mesh_ep4):
    """Under real capacity pressure (factor 0.25: ~8 candidates per 2
    slots per expert per shard) EP's grouped drops equal the per-shard
    oracle's — the capacity semantics are shared, not just the no-drop
    regime."""
    n = 4
    # sanity: this factor actually drops at this shape
    wg, x = params.wg[0], batch_from_seed(jnp.int32(3), T, D,
                                          jnp.float32)[0]
    idx, _ = route_top1(wg, x)
    disp = dispatch_tensor(idx, E, expert_capacity(T, E, 0.25))
    assert float(disp.sum()) < T, "no pressure — test would be vacuous"

    seeds = make_seed_schedule(n, random_seed=13)
    out = train_moe_ep(params, seeds, n * T, D, mesh_ep4, lr=0.1,
                       capacity_factor=0.25)
    oracle = params
    for row in np.asarray(shard_seeds_strided(seeds, n)):
        oracle = _oracle_step(oracle, jnp.asarray(row), T, lr=0.1,
                              capacity_factor=0.25)
    for field in MoEStackParams._fields:
        np.testing.assert_allclose(np.asarray(getattr(out, field)),
                                   np.asarray(getattr(oracle, field)),
                                   rtol=1e-3, atol=1e-5, err_msg=field)


def test_ep_validates_divisibility(params, mesh_ep4):
    seeds = make_seed_schedule(4, random_seed=1)
    with pytest.raises(ValueError, match="divisible"):
        train_moe_ep(params._replace(w1=params.w1[:, :6], w2=params.w2[:, :6],
                                     wg=params.wg[:, :6]),
                     seeds, 4 * T, D, mesh_ep4)
    with pytest.raises(ValueError, match="divisible"):
        train_moe_ep(params, seeds, 4 * T + 2, D, mesh_ep4)


@pytest.mark.parametrize("k,aux_coef", [(1, 0.0), (2, 0.01)])
def test_train_moe_dense_is_user_facing_ep_oracle(params, mesh_ep4, k,
                                                  aux_coef):
    """The package's own dense trainer (``train_moe_dense(n_groups=n)``)
    reproduces the EP run — the oracle behind the CLI's --method 9 check,
    independent of this file's hand-rolled ``_oracle_step``."""
    n = 4
    seeds = make_seed_schedule(2 * n, random_seed=13)
    ep = train_moe_ep(params, seeds, n * T, D, mesh_ep4, lr=0.1, k=k,
                      aux_coef=aux_coef)
    dense = train_moe_dense(params, seeds, n * T, D, lr=0.1, k=k,
                            aux_coef=aux_coef, n_groups=n)
    for f in MoEStackParams._fields:
        np.testing.assert_allclose(np.asarray(getattr(ep, f)),
                                   np.asarray(getattr(dense, f)),
                                   rtol=1e-4, atol=1e-5)


def test_train_moe_dense_global_capacity_differs_from_grouped(params):
    """n_groups=1 (global capacity, one routing group) is a *different*
    semantics from the grouped EP emulation — the distinction
    ``parallel/expert.py`` documents. Under overflow pressure they must
    diverge; losing that divergence means the grouping is dead code."""
    seeds = make_seed_schedule(4, random_seed=3)
    kwargs = dict(lr=0.1, capacity_factor=0.25)  # force drops
    dense1 = train_moe_dense(params, seeds, 4 * T, D, n_groups=1, **kwargs)
    dense4 = train_moe_dense(params, seeds, 4 * T, D, n_groups=4, **kwargs)
    assert not np.allclose(np.asarray(dense1.w1), np.asarray(dense4.w1),
                           rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("k,aux_coef,cf", [(1, 0.0, 2.0), (2, 0.01, 2.0),
                                           (1, 0.0, 0.5)])
def test_ep_composes_with_data_parallel(params, k, aux_coef, cf):
    """2-D data x expert mesh: dp DDP-style replicas of the EP group,
    seeds strided over the flat dp x n grid, grads psum'd over data. ==
    the grouped dense oracle with per-EP-group capacities
    (capacity_groups=n), including under overflow pressure (cf=0.5)."""
    dp, n = 2, 4
    seeds = make_seed_schedule(2 * dp * n, random_seed=9)
    tokens = n * T  # per EP group per step
    mesh = make_mesh({"data": dp, EXPERT_AXIS: n})
    got = train_moe_ep(params, seeds, tokens, D, mesh, lr=0.1, k=k,
                       aux_coef=aux_coef, capacity_factor=cf)
    want = train_moe_dense(params, seeds, tokens * dp, D, lr=0.1, k=k,
                           aux_coef=aux_coef, capacity_factor=cf,
                           n_groups=dp * n, capacity_groups=n)
    for f in MoEStackParams._fields:
        np.testing.assert_allclose(np.asarray(getattr(got, f)),
                                   np.asarray(getattr(want, f)),
                                   rtol=2e-4, atol=1e-5, err_msg=f)


@pytest.mark.parametrize("k,cf", [(1, 2.0), (2, 2.0), (1, 0.25), (2, 0.5)])
def test_scatter_dispatch_matches_dense(k, cf):
    """moe_layer_scatter == moe_layer to float tolerance: same routing,
    same capacity drops (including heavy-overflow regimes), same GShard
    choice-major priority — only the token movement differs (O(T*d)
    scatter/gather vs O(T*E*C*d) one-hot einsums). Gradients too: the
    scatter path's vjp must produce the same wg/w1/w2/x cotangents."""
    from distributed_llm_code_samples_tpu.ops.moe import moe_layer_scatter
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    wg = jax.random.normal(key[0], (E, D))
    w1 = 0.1 * jax.random.normal(key[1], (E, 4 * D, D))
    w2 = 0.1 * jax.random.normal(key[2], (E, D, 4 * D))
    x = jax.random.normal(key[3], (T, D))
    dense = moe_layer(wg, w1, w2, x, capacity_factor=cf, k=k)
    scat = moe_layer_scatter(wg, w1, w2, x, capacity_factor=cf, k=k)
    np.testing.assert_allclose(np.asarray(scat), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)

    def loss_dense(args):
        return jnp.sum(jnp.sin(moe_layer(*args, capacity_factor=cf, k=k)))

    def loss_scat(args):
        return jnp.sum(jnp.sin(
            moe_layer_scatter(*args, capacity_factor=cf, k=k)))

    gd = jax.grad(loss_dense)((wg, w1, w2, x))
    gs = jax.grad(loss_scat)((wg, w1, w2, x))
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6)


def test_scatter_dispatch_through_stack(params):
    """The stack walk (residual + aux loss) is dispatch-agnostic."""
    from distributed_llm_code_samples_tpu.ops.moe import moe_stack_fwd_aux
    x, _ = batch_from_seed(jnp.int32(5), T, D)
    yd, auxd = moe_stack_fwd_aux(params, x, k=2)
    ys, auxs = moe_stack_fwd_aux(params, x, k=2, dispatch="scatter")
    np.testing.assert_allclose(np.asarray(ys), np.asarray(yd),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(auxs), float(auxd), rtol=1e-6)
    with pytest.raises(ValueError, match="dispatch"):
        moe_stack_fwd_aux(params, x, dispatch="magic")


@pytest.mark.parametrize("k,cf", [(1, 2.0), (2, 2.0), (1, 0.25), (2, 0.5)])
def test_gather_dispatch_matches_dense(k, cf):
    """moe_layer_gather == moe_layer to float tolerance: same routing,
    same capacity drops (including heavy-overflow regimes), same GShard
    choice-major priority — the movement is gather-only in BOTH
    directions (the custom VJPs replace autodiff's scatter transposes
    with inverse-permutation gathers). Gradients checked against the
    dense path's, which test_moe_grads_flow_to_router pins to the
    framework's hand-VJP stance."""
    from distributed_llm_code_samples_tpu.ops.moe import moe_layer_gather
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    wg = jax.random.normal(key[0], (E, D))
    w1 = 0.1 * jax.random.normal(key[1], (E, 4 * D, D))
    w2 = 0.1 * jax.random.normal(key[2], (E, D, 4 * D))
    x = jax.random.normal(key[3], (T, D))
    dense = moe_layer(wg, w1, w2, x, capacity_factor=cf, k=k)
    gath = moe_layer_gather(wg, w1, w2, x, capacity_factor=cf, k=k)
    np.testing.assert_allclose(np.asarray(gath), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)

    def loss_dense(args):
        return jnp.sum(jnp.sin(moe_layer(*args, capacity_factor=cf, k=k)))

    def loss_gath(args):
        return jnp.sum(jnp.sin(
            moe_layer_gather(*args, capacity_factor=cf, k=k)))

    gd = jax.grad(loss_dense)((wg, w1, w2, x))
    gg = jax.grad(loss_gath)((wg, w1, w2, x))
    for a, b in zip(gg, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6)


def test_gather_dispatch_through_stack(params):
    """The stack walk accepts dispatch="gather" (residual + aux
    unchanged)."""
    from distributed_llm_code_samples_tpu.ops.moe import moe_stack_fwd_aux
    x, _ = batch_from_seed(jnp.int32(5), T, D)
    yd, auxd = moe_stack_fwd_aux(params, x, k=2)
    yg, auxg = moe_stack_fwd_aux(params, x, k=2, dispatch="gather")
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yd),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(auxg), float(auxd), rtol=1e-6)


def test_ep_gather_dispatch_matches_dense(params, mesh_ep4):
    """EP with gather dispatch == EP with dense dispatch, final params,
    including router grads through the aux loss — the a2a pair and the
    rest of the step are shared with the other dispatch forms."""
    seeds = make_seed_schedule(8, random_seed=23)
    dense = train_moe_ep(params, seeds, 4 * T, D, mesh_ep4, lr=0.1, k=2,
                         aux_coef=0.01)
    gath = train_moe_ep(params, seeds, 4 * T, D, mesh_ep4, lr=0.1, k=2,
                        aux_coef=0.01, dispatch="gather")
    for a, b in zip(jax.tree_util.tree_leaves(gath),
                    jax.tree_util.tree_leaves(dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_ep_scatter_dispatch_matches_dense(params, mesh_ep4):
    """EP with scatter dispatch == EP with dense dispatch == the grouped
    dense oracle: the movement form changes nothing about routing,
    grouped capacity, drops, or gradients — the all_to_all pair and the
    rest of the step are shared."""
    seeds = make_seed_schedule(8, random_seed=21)
    dense = train_moe_ep(params, seeds, 4 * T, D, mesh_ep4, lr=0.1, k=2,
                         aux_coef=0.01)
    scat = train_moe_ep(params, seeds, 4 * T, D, mesh_ep4, lr=0.1, k=2,
                        aux_coef=0.01, dispatch="scatter")
    for a, b in zip(jax.tree_util.tree_leaves(scat),
                    jax.tree_util.tree_leaves(dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="dispatch"):
        train_moe_ep(params, seeds, 4 * T, D, mesh_ep4, lr=0.1,
                     dispatch="magic")
