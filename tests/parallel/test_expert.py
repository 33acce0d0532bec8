"""Expert-parallel MoE vs the single-device routed oracle.

The all_to_all dispatch is a pure re-layout of the oracle's per-shard
routing: forward outputs, aux losses, and training trajectories must match
on the 8 virtual CPU devices (conftest).
"""

import numpy as np
import optax
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from elephas_tpu.parallel.expert import (
    MoEFeedForward,
    build_ep_train_step,
    build_mesh_ep,
)


def _mse(y, y_pred):
    return jnp.sum((y - y_pred) ** 2, axis=-1)


def _tokens(n=64, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


@pytest.mark.parametrize("dp,ep,k", [(1, 8, 1), (1, 8, 2), (2, 4, 2)])
def test_forward_matches_oracle(dp, ep, k):
    mesh = build_mesh_ep(data=dp, expert=ep)
    model = MoEFeedForward(d_model=8, d_ff=16, n_experts=8, k=k,
                           capacity_factor=1.5)
    params = model.init(seed=1)
    x = _tokens(n=64, d=8)

    # oracle: per data group, per-source-shard dispatch
    outs, auxes = [], []
    for blk in np.split(x, dp, axis=0):
        y, aux = model.apply_reference(params, jnp.asarray(blk), ep=ep)
        outs.append(np.asarray(y))
        auxes.append(float(aux))
    want = np.concatenate(outs, axis=0)

    sharded = model.shard_params(mesh, params)
    token_spec = P(("data", "expert"))

    def impl(p, xb):
        yb, aux = model.apply(p, xb)
        return yb, aux[None]  # aux replicated within each expert group

    fwd = jax.jit(
        shard_map(
            impl, mesh=mesh,
            in_specs=(model.specs(), token_spec),
            out_specs=(token_spec, P("data")),
            check_vma=False,
        )
    )
    xd = jax.device_put(x, NamedSharding(mesh, token_spec))
    got, aux_got = fwd(sharded, xd)
    got = np.asarray(got)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(
        np.asarray(aux_got), auxes, rtol=3e-5, atol=3e-5
    )


def test_capacity_drops_tokens():
    """A tiny capacity factor must drop tokens (combine weight 0 ⇒ the MoE
    contribution vanishes) rather than corrupt neighbors."""
    model = MoEFeedForward(d_model=4, d_ff=8, n_experts=2, k=1,
                           capacity_factor=0.1)
    params = model.init(seed=0)
    x = jnp.asarray(_tokens(n=32, d=4, seed=3))
    y, _ = model.apply_reference(params, x)
    # capacity = ceil(0.1 * 1 * 32 / 2) = 2 slots/expert ⇒ ≤4 nonzero rows
    nonzero = np.sum(np.any(np.abs(np.asarray(y)) > 0, axis=-1))
    assert nonzero <= 4


@pytest.mark.parametrize("dp,ep,routing", [(2, 4, "token_choice"),
                                           (2, 4, "expert_choice")])
def test_train_step_matches_oracle(dp, ep, routing):
    mesh = build_mesh_ep(data=dp, expert=ep)
    model = MoEFeedForward(d_model=8, d_ff=16, n_experts=8, k=2,
                           capacity_factor=2.0, routing=routing)
    optimizer = optax.adam(1e-2)
    aux_w = 1e-2
    params = model.init(seed=2)
    rng = np.random.default_rng(5)
    x = _tokens(n=64, d=8, seed=5)
    y = rng.normal(size=(64, 8)).astype(np.float32)

    def oracle_loss(p):
        total, aux_sum = 0.0, 0.0
        for xb, yb in zip(np.split(x, dp), np.split(y, dp)):
            h, aux = model.apply_reference(p, jnp.asarray(xb), ep=ep)
            total = total + jnp.sum(_mse(jnp.asarray(yb), jnp.asarray(xb) + h))
            aux_sum = aux_sum + aux
        return total / x.shape[0] + aux_w * aux_sum / dp

    o_state = optimizer.init(params)
    o_params = {k: jnp.asarray(v) for k, v in params.items()}
    for _ in range(3):
        grads = jax.grad(oracle_loss)(o_params)
        updates, o_state = optimizer.update(grads, o_state, o_params)
        o_params = jax.tree_util.tree_map(jnp.add, o_params, updates)

    step, opt_init = build_ep_train_step(
        model, mesh, optimizer, _mse, aux_weight=aux_w
    )
    sharded = model.shard_params(mesh, params)
    state = opt_init(sharded)
    token_spec = P(("data", "expert"))
    xd = jax.device_put(x, NamedSharding(mesh, token_spec))
    yd = jax.device_put(y, NamedSharding(mesh, token_spec))
    for _ in range(3):
        sharded, state, loss = step(sharded, state, xd, yd)

    got = model.gather_params(sharded)
    for k, v in o_params.items():
        np.testing.assert_allclose(
            got[k], np.asarray(v), rtol=5e-4, atol=5e-5, err_msg=k
        )


@pytest.mark.parametrize("dp,ep", [(1, 8), (2, 4)])
def test_expert_choice_forward_matches_oracle(dp, ep):
    mesh = build_mesh_ep(data=dp, expert=ep)
    model = MoEFeedForward(d_model=8, d_ff=16, n_experts=8, k=2,
                           capacity_factor=1.0, routing="expert_choice")
    params = model.init(seed=1)
    x = _tokens(n=64, d=8)

    outs = []
    for blk in np.split(x, dp, axis=0):
        y, aux = model.apply_reference(params, jnp.asarray(blk), ep=ep)
        assert float(aux) == 0.0  # balanced by construction, no aux
        outs.append(np.asarray(y))
    want = np.concatenate(outs, axis=0)

    sharded = model.shard_params(mesh, params)
    token_spec = P(("data", "expert"))
    fwd = jax.jit(
        shard_map(
            lambda p, xb: model.apply(p, xb)[0], mesh=mesh,
            in_specs=(model.specs(), token_spec), out_specs=token_spec,
            check_vma=False,
        )
    )
    xd = jax.device_put(x, NamedSharding(mesh, token_spec))
    got = np.asarray(fwd(sharded, xd))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_expert_choice_trains():
    """Dropless routing must train through build_ep_train_step unchanged."""
    mesh = build_mesh_ep(data=2, expert=4)
    model = MoEFeedForward(d_model=8, d_ff=16, n_experts=8, k=2,
                           routing="expert_choice")
    step, opt_init = build_ep_train_step(model, mesh, optax.adam(1e-2), _mse)
    params = model.shard_params(mesh, model.init(seed=2))
    state = opt_init(params)
    rng = np.random.default_rng(5)
    x = _tokens(n=64, d=8, seed=5)
    y = rng.normal(size=(64, 8)).astype(np.float32)
    token_spec = P(("data", "expert"))
    xd = jax.device_put(x, NamedSharding(mesh, token_spec))
    yd = jax.device_put(y, NamedSharding(mesh, token_spec))
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, xd, yd)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


@pytest.mark.parametrize(
    "activation,bias,cf,k,ep",
    [
        ("relu", True, 1.5, 2, 1),
        ("swiglu", False, 2.0, 2, 1),   # Mixtral expert shape
        ("swiglu", False, 0.25, 2, 1),  # capacity binds: drops must match
        ("gelu", True, 1.0, 3, 4),      # multi-group per-shard quotas
        ("gelu", True, 1.25, 1, 2),     # one expert a token
        ("relu", False, 1.0, 3, 4),     # three a token, no biases
    ],
)
def test_slots_matches_onehot_oracle(activation, bias, cf, k, ep):
    """The index-form (gather) slot executor must reproduce the one-hot
    dispatch oracle exactly — same keeps, drops, combine weights, aux."""
    model = MoEFeedForward(d_model=8, d_ff=16, n_experts=8, k=k,
                           capacity_factor=cf, activation=activation,
                           bias=bias)
    params = model.init(seed=4)
    x = jnp.asarray(_tokens(n=64, d=8, seed=7))
    want, aux_want = model.apply_reference(params, x, ep=ep)
    got, aux_got = model.apply_slots(params, x, ep=ep)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux_got), float(aux_want), rtol=1e-5)


def test_slots_gradients_match_onehot():
    model = MoEFeedForward(d_model=8, d_ff=16, n_experts=4, k=2,
                           capacity_factor=1.25)
    params = {k: jnp.asarray(v) for k, v in model.init(seed=9).items()}
    x = jnp.asarray(_tokens(n=32, d=8, seed=11))
    y = jnp.asarray(_tokens(n=32, d=8, seed=12))

    def loss(p, fn):
        h, aux = fn(p, x)
        return jnp.mean(_mse(y, x + h)) + 1e-2 * aux

    g_ref = jax.grad(lambda p: loss(p, model.apply_reference))(params)
    g_slt = jax.grad(lambda p: loss(p, model.apply_slots))(params)
    for k_ in params:
        np.testing.assert_allclose(
            np.asarray(g_slt[k_]), np.asarray(g_ref[k_]),
            rtol=2e-5, atol=2e-6, err_msg=k_)


def test_validation():
    with pytest.raises(ValueError):
        MoEFeedForward(d_model=4, d_ff=8, n_experts=1, k=2)
    with pytest.raises(ValueError, match="routing"):
        MoEFeedForward(d_model=4, d_ff=8, n_experts=4, routing="soft")
    mesh = build_mesh_ep(data=1, expert=8)
    model = MoEFeedForward(d_model=4, d_ff=8, n_experts=6, k=1)
    with pytest.raises(ValueError, match="not divisible"):
        build_ep_train_step(model, mesh, optax.sgd(0.1), _mse)
