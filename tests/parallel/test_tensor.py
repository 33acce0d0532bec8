"""Tensor-parallel layers/trainer vs the single-device dense oracle.

The 8 virtual CPU devices (conftest) are folded into 2-D meshes; every
configuration must reproduce the math of the unsharded MLP bit-closely:
column/row sharding + psum is a pure re-layout of the same contractions.
"""

import numpy as np
import optax
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp

from elephas_tpu.parallel.tensor import (
    TensorParallelMLP,
    build_mesh2d,
    build_tp_train_step,
    opt_state_specs,
)


from tests._helpers import softmax_xent as _softmax_xent  # noqa: E402


@pytest.mark.parametrize("dp,tp", [(1, 8), (2, 4), (4, 2), (8, 1)])
def test_forward_matches_dense(dp, tp):
    mesh = build_mesh2d(data=dp, model=tp)
    model = TensorParallelMLP([12, 32, 16, 24, 6], tp=tp)
    params = model.init(seed=3)
    x = np.random.default_rng(0).normal(size=(16, 12)).astype(np.float32)

    want = np.asarray(model.apply_reference(params, x))

    sharded = model.shard_params(mesh, params)
    from jax.sharding import NamedSharding, PartitionSpec as P

    fwd = jax.jit(
        shard_map(
            model.apply, mesh=mesh,
            in_specs=(model.specs(), P("data")), out_specs=P("data"),
            check_vma=False,
        )
    )
    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    got = np.asarray(fwd(sharded, xd))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dp,tp,opt_name", [(2, 4, "adam"), (4, 2, "sgd")])
def test_train_step_matches_dense(dp, tp, opt_name):
    mesh = build_mesh2d(data=dp, model=tp)
    model = TensorParallelMLP([10, 16, 8, 16, 4], tp=tp)
    optimizer = optax.adam(1e-2) if opt_name == "adam" else optax.sgd(0.1)
    params = model.init(seed=1)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(32, 10)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=32)]

    # dense oracle: plain jax on full params
    def oracle_loss(p):
        return jnp.mean(_softmax_xent(y, model.apply_reference(p, x)))

    o_state = optimizer.init(params)
    o_params = params
    o_losses = []
    for _ in range(3):
        loss, grads = jax.value_and_grad(oracle_loss)(o_params)
        updates, o_state = optimizer.update(grads, o_state, o_params)
        o_params = jax.tree_util.tree_map(jnp.add, o_params, updates)
        o_losses.append(float(loss))

    # tp trainer
    step, opt_init = build_tp_train_step(model, mesh, optimizer, _softmax_xent)
    sharded = model.shard_params(mesh, params)
    state = opt_init(sharded)
    from jax.sharding import NamedSharding, PartitionSpec as P

    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    yd = jax.device_put(y, NamedSharding(mesh, P("data")))
    losses = []
    for _ in range(3):
        sharded, state, loss = step(sharded, state, xd, yd)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, o_losses, rtol=1e-4, atol=1e-5)
    got = model.gather_params(sharded)
    for k, v in model.gather_params({k: v for k, v in o_params.items()}).items():
        np.testing.assert_allclose(got[k], v, rtol=2e-4, atol=2e-5)


def test_opt_state_specs_structure():
    from jax.sharding import PartitionSpec as P

    model = TensorParallelMLP([8, 16, 4], tp=2)
    specs = model.specs()
    params = model.init()
    tree = opt_state_specs(optax.adam(1e-3), params, specs)
    leaves = jax.tree_util.tree_leaves(
        tree, is_leaf=lambda s: isinstance(s, P)
    )
    # adam: count (replicated) + mu/nu mirroring the 4 params each
    assert sum(1 for s in leaves if s == P()) >= 1
    assert sum(1 for s in leaves if s == P(None, "model")) == 2  # w0 in mu,nu
    assert sum(1 for s in leaves if s == P("model", None)) == 2  # w1 in mu,nu


def test_dims_validation():
    with pytest.raises(ValueError):
        TensorParallelMLP([8, 16], tp=2)  # single layer (even dims len)
    with pytest.raises(ValueError):
        TensorParallelMLP([8, 15, 4], tp=2)  # hidden not divisible
